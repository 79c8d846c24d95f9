"""Device time of the `stage_prepare` module in the profiler trace over
the dispatches traced: at 512 keys a lane, mostly the key sum."""

from benchmarks.layer_metrics import _keys


def read(ctx):
    got = _keys.prepare_seconds(ctx)
    if got is None:
        return None
    secs, dispatches = got
    return secs / dispatches * 1e3
