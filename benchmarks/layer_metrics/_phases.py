"""Shared by the readers of a dispatch's phases: the dispatch ledger's
records carry `phases` ([name, t_mono, seconds], tiling the dispatch
from the service's hand-over to its last settled future), `lock`
({acquired, released}, t_mono) and, per first dispatch of a shape,
`compile.programs` (one load record per AOT program).  A program
without them (the parent of the PR that brought them) gives the
readers nothing to read."""

import statistics


def median_ms(ledger, names):
    """Median per dispatch of the seconds inside the named phases, in
    ms, over the records that carry phases; None without any."""
    per_dispatch = [sum(secs for name, _t0, secs in rec["phases"]
                        if name in names)
                    for rec in ledger if rec.get("phases")]
    if not per_dispatch:
        return None
    return statistics.median(per_dispatch) * 1e3


def setup_load_records(ctx):
    """The load records of every program resolved before the window
    opened: the ones inside set-up's first dispatches and the ones
    outside any dispatch (`pk_validate`, loaded by the supervisor's
    probe), from the AOT store's own list."""
    from teku_tpu.infra import aotstore
    records = getattr(aotstore, "load_records", None)
    if records is None or ctx.get("window") is None:
        return []
    return [r for r in records() if r["t_mono"] < ctx["window"].t_open]
