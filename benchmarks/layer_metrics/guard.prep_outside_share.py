"""Share of the window's dispatches whose host prep ran wholly before
the device-entry lock was taken (the ledger record's `prep` is
`outside_lock`): the other worker's dispatch ran on the chip meanwhile.
A dispatch that validated a public key or looked up the H(m) arena
under the lock says `under_lock`, with its reason.  A program whose
records carry no `prep` field (the parent of the PR that brought it)
gives nothing to read."""


def read(ctx):
    where = [rec["prep"] for rec in ctx["window_ledger"] if "prep" in rec]
    if not where:
        return None
    return 100.0 * where.count("outside_lock") / len(where)
