"""Dead lanes and dead rows over padded lanes and rows, from the
window's dispatch-ledger records."""


def read(ctx):
    real = padded = 0
    for rec in ctx["window_ledger"]:
        for stage in ("lane", "h2c"):
            w = (rec.get("waste") or {}).get(stage) or {}
            real += w.get("real", 0)
            padded += w.get("padded", 0)
    if padded <= 0:
        return None
    return 100.0 * (padded - real) / padded
