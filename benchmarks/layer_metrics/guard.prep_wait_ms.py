"""Median per dispatch of the window of the `prep_wait` phase: how long
the breaker's dispatch thread stood waiting for the guarded provider's
turn to pack, behind the other worker's host half (host halves run one
at a time).  A program whose records carry no such phase (the parent
of the PR that brought it) gives nothing to read."""

from benchmarks.layer_metrics import _phases


def read(ctx):
    waited = [rec for rec in ctx["window_ledger"]
              if any(name == "prep_wait"
                     for name, _t0, _secs in rec.get("phases") or ())]
    return _phases.median_ms(waited, ("prep_wait",))
