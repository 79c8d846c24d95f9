"""The least time the chip could take for a batch's field
multiplications (`benchmarks/rooflines/`, against the int8 peak of
`benchmarks/peaks.json`) over the device time the staged programs took
for it.  The batch is the mean traced dispatch, from the ledger."""

from benchmarks.harness import work
from benchmarks.layer_metrics import _kernels


def read(ctx):
    secs = _kernels.busy_seconds_per_batch(ctx)
    ledger = ctx["traced_ledger"]
    if secs is None:
        return None
    n = len(ledger)
    lanes = sum(r["lanes"] for r in ledger) / n
    rows = sum(r["rows"] for r in ledger) / n
    fresh = sum(r["h2c"]["cache_misses"] for r in ledger) / n
    muls = work.fp_muls(ctx["table"], lanes, rows, fresh)
    return 100.0 * work.least_seconds(ctx["table"], ctx["peak"],
                                      muls) / secs
