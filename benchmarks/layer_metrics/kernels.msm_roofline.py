"""The least time the chip could take for the field multiplications of
a batch's scalar multiplications and row folds (per lane, from
`benchmarks/rooflines/`: one 64-bit coefficient on the key in G1, one
on the signature in G2, one addition of each into its row; against the
int8 peak of `benchmarks/peaks.json`) over the device time of the MSM's
modules.  The lanes are the mean traced dispatch's, from the ledger;
the count comes from the table, never from the traced graph."""

from benchmarks.harness import work
from benchmarks.layer_metrics import _msm

PER_LANE = ("scalar_mul_g1_64bit", "scalar_mul_g2_64bit", "fold_into_row")


def read(ctx):
    secs = _msm.seconds_per_batch(ctx)
    ledger = ctx["traced_ledger"]
    if secs is None:
        return None
    lanes = sum(r["lanes"] for r in ledger) / len(ledger)
    per_lane = ctx["table"]["per_lane"]
    muls = lanes * sum(per_lane[name]["fp_mul"] for name in PER_LANE)
    return 100.0 * work.least_seconds(ctx["table"], ctx["peak"],
                                      muls) / secs
