"""Shared by the key-axis readers: device seconds of `stage_prepare`
(the masked tree sum of each lane's keys, then the signature's
decompression and subgroup check) over the dispatches a traced run put
under the profiler."""

from benchmarks.harness import profile


def prepare_seconds(ctx):
    """(total seconds of `stage_prepare` in the trace, traced
    dispatches), or None where there is nothing to read."""
    reduced, dispatches = ctx["reduced"], len(ctx["traced_ledger"])
    if reduced is None or not dispatches:
        return None
    secs = profile.module_seconds(reduced["trace"]).get("stage_prepare")
    return (secs, dispatches) if secs else None
