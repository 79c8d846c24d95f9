"""Dead key slots over dispatched key slots (padded lanes x the batch's
key bucket), from the window's dispatch-ledger records' `waste.key`."""


def read(ctx):
    real = padded = 0
    for rec in ctx["window_ledger"]:
        w = (rec.get("waste") or {}).get("key") or {}
        real += w.get("real", 0)
        padded += w.get("padded", 0)
    if padded <= 0:
        return None
    return 100.0 * (padded - real) / padded
