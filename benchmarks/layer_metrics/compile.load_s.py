"""Seconds of set-up inside AOT loads, cache loads and compiles: the
supervisor's probe (provider built, `pk_validate` loaded or compiled
and run) and the enqueue of every first dispatch of a shape in set-up,
which the dispatch ledger stamps with its outcome."""


def read(ctx):
    firsts = [r["compile"]["enqueue_s"] for r in ctx["setup_ledger"]
              if (r.get("compile") or {}).get("outcome")
              in ("compile", "cache_load", "aot_load")]
    return ctx["probe_s"] + sum(firsts)
