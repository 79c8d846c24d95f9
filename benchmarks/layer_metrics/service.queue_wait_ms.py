"""Median `queue_wait` stage of the window's tasks (enqueue to the
drain that took the task), from the program's stage samples."""

import statistics


def read(ctx):
    win = ctx["window"]
    waits = [secs for t0, secs in ctx["sampler"].queue_wait
             if win.t_open <= t0 <= win.t_close]
    if not waits:
        return None
    return statistics.median(waits) * 1e3
