"""The least time the cell's chips could take TOGETHER for the traced
dispatches' field multiplications (`benchmarks/rooflines/`, the table
the one-chip cells use: the same counted work whatever implements it,
from the records' lanes, rows and fresh messages; against the int8 peak
of `benchmarks/peaks.json` times the cell's chips) over the device time
from the first module's start to the last module's end on any chip.
Time in which three chips wait for the first one's hashing is in the
denominator: that is the point."""

from benchmarks.harness import profile, work
from benchmarks.layer_metrics import _mesh


def read(ctx):
    found = _mesh.traced(ctx)
    if found is None:
        return None
    first, last = profile.traced_span(found[0])
    if last <= first:
        return None
    ledger = ctx["traced_ledger"]
    muls = sum(work.fp_muls(ctx["table"], r["lanes"], r["rows"],
                            r["h2c"]["cache_misses"]) for r in ledger)
    least = work.least_seconds(ctx["table"], ctx["peak"], muls)
    return 100.0 * least / ctx["cell"]["chips"] / (last - first)
