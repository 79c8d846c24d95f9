"""Device ms a traced dispatch in which exactly ONE chip ran a module
and the others ran nothing: `stage_h2c` over a fresh message, the
arena's scatter and gather and the row gather, all on the mesh's first
chip.  Counted from the chips' busy intervals, not from module names,
so whatever else runs on one chip alone is in it."""

import numpy as np

from benchmarks.layer_metrics import _mesh


def read(ctx):
    found = _mesh.traced(ctx)
    if found is None:
        return None
    trace, dispatches = found
    edges, steps = [], []
    for starts, ends in _mesh.chip_intervals(trace):
        edges += [starts, ends]
        steps += [np.ones(len(starts)), -np.ones(len(ends))]
    edges, steps = np.concatenate(edges), np.concatenate(steps)
    if not len(edges):
        return None
    # ends before starts at one instant: a chip that hands over to
    # another at the same tick is never counted as two
    order = np.lexsort((steps, edges))
    edges, busy = edges[order], np.cumsum(steps[order])
    alone = np.diff(edges)[busy[:-1] == 1].sum()
    return float(alone) / dispatches * 1e3
