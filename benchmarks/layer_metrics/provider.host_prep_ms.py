"""Median per dispatch of the `host_prep` stage (wire parse and key
lookup, then array packing), from the program's stage samples."""

import statistics


def read(ctx):
    win = ctx["window"]
    per_dispatch = [secs for t0, secs in ctx["sampler"].host_prep.items()
                    if win.t_open <= t0 <= win.t_close]
    if not per_dispatch:
        return None
    return statistics.median(per_dispatch) * 1e3
