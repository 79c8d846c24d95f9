"""Share of the window's guarded dispatches that the oracle answered,
from `bls_verify_requests_total{backend}`."""


def read(ctx):
    before, after = ctx["before"].served, ctx["after"].served
    oracle = after["oracle"] - before["oracle"]
    device = after["device"] - before["device"]
    if oracle + device <= 0:
        return None
    return 100.0 * oracle / (oracle + device)
