"""Share of the window's H(m) lookups that the device arena served:
the sum of `h2c.cache_hits` over the sum of `cache_hits` +
`cache_misses` of the window's dispatch-ledger records.  The program
looks a message up once a dispatch, however many Miller rows its
committee was split over, so this counts messages.  Records without an
`h2c` block, or a window without a lookup, give nothing to read."""


def read(ctx):
    hits = lookups = 0
    for rec in ctx["window_ledger"]:
        h2c = rec.get("h2c") or {}
        hits += h2c.get("cache_hits", 0)
        lookups += h2c.get("cache_hits", 0) + h2c.get("cache_misses", 0)
    if not lookups:
        return None
    return 100.0 * hits / lookups
