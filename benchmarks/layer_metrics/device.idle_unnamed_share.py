"""Of the traced dispatch's idle gaps of 0.1 ms or more (the device's
own line, `harness/profile.py`), the share of their seconds that no
phase of the traced dispatches' ledger records covers by half or more.
The phases are put on the trace's clock by the offset the harness found
for its own spans."""

from benchmarks.harness import profile


def read(ctx):
    reduced = ctx["reduced"]
    if reduced is None:
        return None
    spans = [(name, t0 + reduced["offset"], secs)
             for rec in ctx["traced_ledger"]
             for name, t0, secs in rec.get("phases") or ()]
    if not spans:
        return None
    gaps = dict(profile.idle_gaps(reduced["trace"], reduced["lo"],
                                  reduced["hi"], spans, top=1 << 20))
    gaps.pop("between_modules", None)       # the gaps under 0.1 ms
    total = sum(gaps.values())
    if not total:
        return None
    unnamed = (gaps.get("between_spans", 0.0)
               + gaps.get("waiting_for_tasks", 0.0))
    return 100.0 * unnamed / total
