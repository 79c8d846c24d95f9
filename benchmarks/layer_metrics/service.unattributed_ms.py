"""Median over the window's tasks of the time inside a task's trace
that no span covers: the `unattributed` nodes, at every level, of the
program's own gap-free span tree (`timeline.span_tree`) of the trace."""

import statistics


def unattributed_ms(node):
    return sum(child["dur_ms"] if child["phase"] == "unattributed"
               else unattributed_ms(child) for child in node["children"])


def read(ctx):
    from teku_tpu.infra import timeline
    win = ctx["window"]
    if win is None:
        return None
    per_task = [unattributed_ms(timeline.span_tree(a.trace.to_dict()))
                for a in win.answers
                if a.trace is not None and a.trace.complete]
    if not per_task:
        return None
    return statistics.median(per_task)
