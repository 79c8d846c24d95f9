"""Median per dispatch of the window of `return_hop` + `settle`: from
the end of the device sync, back through the two threads to the event
loop, to the last future of the batch settled."""

from benchmarks.layer_metrics import _phases


def read(ctx):
    return _phases.median_ms(ctx["window_ledger"],
                             ("return_hop", "settle"))
