"""Device time of all `stage_*` modules in the profiler trace over the
dispatches traced."""

from benchmarks.layer_metrics import _kernels


def read(ctx):
    secs = _kernels.busy_seconds_per_batch(ctx)
    return None if secs is None else secs * 1e3
