"""Shared by the MSM readers: device seconds per traced dispatch of the
modules that multiply every lane's key and signature by its random
coefficient and fold the lanes into their message rows.  On the ladder
path those are `stage_scalars` and `stage_group`, on the bucketed path
the one module `stage_scalars_pippenger`: the same work under either
name, so a change of path moves the number and not what it measures."""

from benchmarks.harness import profile


def seconds_per_batch(ctx):
    reduced, dispatches = ctx["reduced"], len(ctx["traced_ledger"])
    if reduced is None or not dispatches:
        return None
    secs = sum(s for stage, s
               in profile.module_seconds(reduced["trace"]).items()
               if stage.startswith("stage_scalars")
               or stage == "stage_group")
    return secs / dispatches if secs else None
