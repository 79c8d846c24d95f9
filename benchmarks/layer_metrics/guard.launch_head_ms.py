"""Median per dispatch of the window of the `launch_head` phase (its
pieces summed where the H(m) arena's plan splits it): how long the
breaker's dispatch thread held the device-entry lock before its first
program call, on the provider's bookkeeping and ledger record.  A
program whose records carry no such phase (the parent of the PR that
brought it) gives nothing to read."""

from benchmarks.layer_metrics import _phases


def read(ctx):
    headed = [rec for rec in ctx["window_ledger"]
              if any(name == "launch_head"
                     for name, _t0, _secs in rec.get("phases") or ())]
    return _phases.median_ms(headed, ("launch_head",))
