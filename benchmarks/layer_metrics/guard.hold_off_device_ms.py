"""Median per dispatch of the window of the device-entry lock's hold
less the device seconds of the programs launched inside it, each as
long as on the traced dispatches' module line: the part of a hold in
which the lock was held and none of its programs ran.  A program takes
the same device time in every dispatch of a shape, so the hold's
edges and launches on the host's clock are all the window needs.  A
launch outside the hold is not the hold's; a dispatch with a program
the traced ones never ran is left out.  A program whose records carry
no `launches` (the parent of the PR that brought them) gives nothing
to read."""

import statistics

from benchmarks.layer_metrics import _launches


def read(ctx):
    seconds = _launches.program_seconds(ctx)
    if seconds is None:
        return None
    off = []
    for rec in _launches.launched(ctx["window_ledger"]):
        lock = rec.get("lock") or {}
        if not {"acquired", "released"} <= set(lock):
            continue
        lo, hi = lock["acquired"], lock["released"]
        inside = [launch[0] for launch in rec["launches"]
                  if lo <= launch[1] <= hi]
        if all(program in seconds for program in inside):
            off.append(hi - lo - sum(seconds[p] for p in inside))
    return statistics.median(off) * 1e3 if off else None
