"""Seconds of set-up spent reading AOT-store entries from disk and
deserializing them into loaded programs, summed over the load records
of every program resolved before the window opened."""

from benchmarks.layer_metrics import _phases


def read(ctx):
    records = _phases.setup_load_records(ctx)
    if not records:
        return None
    return sum(r["read_s"] + r["deserialize_s"] for r in records)
