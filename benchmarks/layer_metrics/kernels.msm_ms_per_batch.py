"""Device time of the `stage_scalars*` and `stage_group` modules in the
profiler trace over the dispatches traced: the multi-scalar
multiplication, whichever path ran it."""

from benchmarks.layer_metrics import _msm


def read(ctx):
    secs = _msm.seconds_per_batch(ctx)
    return None if secs is None else secs * 1e3
