"""Median over consecutive dispatches of the window of the time in
which nobody held the device-entry lock: one dispatch's `lock.released`
to the next one's `lock.acquired`.  With a worker always waiting at the
lock this is the hand-over alone; anything more is time in which the
device had no host working for it."""

import statistics


def read(ctx):
    locks = sorted((rec["lock"]["acquired"], rec["lock"]["released"])
                   for rec in ctx["window_ledger"]
                   if {"acquired", "released"} <= set(rec.get("lock") or ()))
    idle = [nxt[0] - prev[1] for prev, nxt in zip(locks, locks[1:])]
    if not idle:
        return None
    return statistics.median(idle) * 1e3
