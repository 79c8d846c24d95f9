"""Shared by the kernel readers: device seconds of the staged programs
per dispatch, over the dispatches a traced run put under the profiler
(whole ones: the profiler starts before they are queued and stops
after their verdicts)."""

from benchmarks.harness import profile


def busy_seconds_per_batch(ctx):
    reduced, dispatches = ctx["reduced"], len(ctx["traced_ledger"])
    if reduced is None or not dispatches:
        return None
    staged = sum(secs for stage, secs
                 in profile.module_seconds(reduced["trace"]).items()
                 if stage.startswith("stage_"))
    return staged / dispatches if staged else None
