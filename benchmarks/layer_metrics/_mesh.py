"""Shared by the readers of a mesh dispatch's trace: a dispatch on
several chips is three parts on the profiler's module line.  Programs
that run on ONE chip while the others stand idle (`stage_h2c` over a
fresh message, the arena's scatter and gather, the gather of the H(m)
rows into the shard layout); then the shards' programs on every chip
(`mesh_prepare`, `mesh_scalars`, `mesh_group`, `mesh_miller`: the
single-chip stages on the shard's own lanes and rows, no
communication); then `mesh_exchange` on every chip (one all_gather of
the chips' Fq12 partial products, their product, the final
exponentiation).  A program without those modules (the parent of the
PR that brought them, or a one-chip run) gives the readers nothing to
read."""

from benchmarks.harness import profile

PREFIX = "mesh_"
EXCHANGE = "mesh_exchange"


def is_shard_program(module):
    return module.startswith(PREFIX) and module != EXCHANGE


def is_exchange(module):
    return module == EXCHANGE


def _module(name):
    return profile.stage_of(name).removeprefix("jit_")


def traced(ctx):
    """(trace, traced dispatches) or None where a reader has nothing
    to read: no trace, no traced dispatch, fewer than two chips, none
    of the mesh's modules."""
    reduced, dispatches = ctx["reduced"], len(ctx["traced_ledger"])
    if reduced is None or not dispatches:
        return None
    trace = reduced["trace"]
    if len(trace["devices"]) < 2 or not any(
            _module(name).startswith(PREFIX)
            for lines in trace["devices"].values()
            for name, _s, _d in lines.get(profile.MODULE_LINE, ())):
        return None
    return trace, dispatches


def slowest_chip_ms(ctx, wanted):
    """Device ms a traced dispatch of the modules that `wanted` picks,
    on the chip where they took longest."""
    found = traced(ctx)
    if found is None:
        return None
    trace, dispatches = found
    per_chip = [sum(secs for name, _s, secs
                    in lines.get(profile.MODULE_LINE, ())
                    if wanted(_module(name)))
                for lines in trace["devices"].values()]
    if not any(per_chip):
        return None
    return max(per_chip) / dispatches * 1e3


def chip_intervals(trace):
    """Per chip, the merged intervals in which a module ran:
    [(starts, ends)]."""
    out = []
    for lines in trace["devices"].values():
        mods = lines.get(profile.MODULE_LINE) or ()
        out.append(profile.union([(s, s + d) for _n, s, d in mods]))
    return out
