"""The least time the chip could take for the traced dispatches' key
sums (the roofline table's `per_key` term: one G1 addition for each
live key beyond its lane's first, the live keys and lanes from the
traced dispatches' ledger records; against the int8 peak of
`benchmarks/peaks.json`) over the device time of `stage_prepare`, which
holds the sum.  None where the configuration's table has no key term
or the trace no `stage_prepare`."""

from benchmarks.harness import work
from benchmarks.layer_metrics import _keys


def read(ctx):
    per_key = ctx["table"].get("per_key")
    got = _keys.prepare_seconds(ctx)
    if per_key is None or got is None:
        return None
    secs, _dispatches = got
    ledger = ctx["traced_ledger"]
    beyond = sum(r.get("keys", 0) - r.get("lanes", 0) for r in ledger)
    if beyond <= 0:
        return None
    muls = beyond * sum(v["fp_mul"] for v in per_key.values())
    return 100.0 * work.least_seconds(ctx["table"], ctx["peak"],
                                      muls) / secs
