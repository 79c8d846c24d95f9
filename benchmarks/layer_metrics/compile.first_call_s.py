"""Seconds of set-up spent in the first call of each resolved program,
waited for (`block_until_ready`): the runtime's own load of the program
onto the device, and the wait for the programs ahead of it."""

from benchmarks.layer_metrics import _phases


def read(ctx):
    records = _phases.setup_load_records(ctx)
    if not records:
        return None
    return sum(r["first_call_s"] or 0.0 for r in records)
