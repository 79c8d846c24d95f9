"""Shared by the readers of a dispatch's launches.  A ledger record
carries `launches` ([program, t_mono, seconds] a program call, in
launch order, `program` being its module's name on the profiler's line
less `jit_` and the run id) and, among its phases, `launch_head` (the
held lock's host work up to the first call).  A program whose records
carry no `launches` (the parent of the PR that brought them) gives the
readers nothing to read."""

from benchmarks.harness import profile


def program_of(module):
    """A module of the profiler's line by its launches' name."""
    return profile.stage_of(module).removeprefix("jit_")


def first_chip(reduced):
    """The first chip's module line: [module, start_s, seconds] on the
    trace's clock."""
    lines = next(iter(reduced["trace"]["devices"].values()), {})
    return lines.get(profile.MODULE_LINE, ())


def launched(ledger):
    return [rec for rec in ledger if rec.get("launches")]


def phase(rec, name):
    """(t_mono, seconds) of the record's last `name` phase, or None."""
    return next(((t0, secs) for got, t0, secs in reversed(rec["phases"])
                 if got == name), None)


def program_seconds(ctx):
    """{program: device seconds of one call} over the traced
    dispatches' launches and the first chip's module line (on a mesh
    the chip that runs the one-chip programs too, so its line is the
    dispatch's critical path); None with nothing to read."""
    reduced, traced = ctx["reduced"], launched(ctx["traced_ledger"])
    if reduced is None or not traced:
        return None
    calls = {}
    for rec in traced:
        for launch in rec["launches"]:
            calls[launch[0]] = calls.get(launch[0], 0) + 1
    total = {}
    for module, _start, secs in first_chip(reduced):
        program = program_of(module)
        if program in calls:
            total[program] = total.get(program, 0.0) + secs
    return {program: secs / calls[program]
            for program, secs in total.items()} or None


def traced_idle_ms(ctx, name):
    """Mean over the traced dispatches of the seconds of their `name`
    phase in which no module ran on the first chip, in ms: the phase is
    put on the trace's clock by the offset the harness found for its
    own spans, and a module's part outside the phase is not counted, so
    the reading lies between 0 and the phase's length."""
    reduced = ctx["reduced"]
    spans = [phase(rec, name) for rec in launched(ctx["traced_ledger"])]
    spans = [span for span in spans if span is not None]
    if reduced is None or not spans:
        return None
    chip = {"devices": {"first": {profile.MODULE_LINE: first_chip(reduced)}}}
    idle = []
    for t0, secs in spans:
        lo = t0 + reduced["offset"]
        idle.append(secs - profile.busy_seconds(chip, lo, lo + secs))
    return sum(idle) / len(idle) * 1e3
