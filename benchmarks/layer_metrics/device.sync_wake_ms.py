"""Mean over the traced dispatches of the seconds of `device_sync` (the
last program call's return to the verdict in hand) in which no module
ran on the first chip: every program is out by then, so this is the
host learning that the device had finished, the wait for the
interpreter lock included, read off the trace's module line.  The
traced dispatch runs alone; the window's holds show in
`guard.hold_off_device_ms`."""

from benchmarks.layer_metrics import _launches


def read(ctx):
    return _launches.traced_idle_ms(ctx, "device_sync")
