"""Median per dispatch of the window of the `lock_wait` phase: how long
the breaker's dispatch thread stood at the serving pair's device-entry
lock, behind the other worker's whole dispatch."""

from benchmarks.layer_metrics import _phases


def read(ctx):
    return _phases.median_ms(ctx["window_ledger"], ("lock_wait",))
