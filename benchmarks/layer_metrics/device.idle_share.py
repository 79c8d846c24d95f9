"""The share of the measured window in which no staged program ran on
the device: 1 - (device seconds of one dispatch, from the profiler
trace of the traced dispatches) x (dispatches answered inside the
window) / (the window's seconds).

The profiler cannot be stopped while the window's dispatches go on
(PERF.md), so the trace holds whole dispatches queued once the window
has drained.  What a staged program costs the device does not depend
on what the host does meanwhile; how many dispatches the window fitted
does.  So a change that overlaps one dispatch's host work with
another's device work moves this number, which one isolated
dispatch's own idle share (`busy_s` / `window_s` under `device`) cannot
show."""


def read(ctx):
    reduced, traced = ctx["reduced"], ctx["traced_ledger"]
    win, ledger = ctx["window"], ctx["window_ledger"]
    if reduced is None or not reduced["busy_s"] or not traced or not ledger:
        return None
    busy_per_dispatch = reduced["busy_s"] / len(traced)
    lanes = sum(r["lanes"] for r in ledger) / len(ledger)
    dispatches = len(win.in_window()) / lanes
    return 100.0 * (1.0 - busy_per_dispatch * dispatches / win.seconds)
