"""Median per dispatch of the host seconds spent looking up a dispatch's
public keys and filling its key matrices (the record's `key_pack_s`),
from the window's dispatch-ledger records, in ms.  None where no record
carries the field."""

import statistics


def read(ctx):
    secs = [rec["key_pack_s"] for rec in ctx["window_ledger"]
            if rec.get("key_pack_s") is not None]
    if not secs:
        return None
    return statistics.median(secs) * 1e3
