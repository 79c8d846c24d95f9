"""Seconds from the supervisor's start to READY (supervisor snapshot)."""


def read(ctx):
    return ctx["ready_s"]
