"""Mean over the traced dispatches of the seconds of `device_enqueue`
(the first program call to the last call's return) in which no module
ran on the first chip: the device waiting for its programs to be
launched, read off the trace's module line.  The traced dispatch runs
alone, with no other worker packing beside it; the window's holds,
whose launches that packing slows, show in
`guard.hold_off_device_ms`."""

from benchmarks.layer_metrics import _launches


def read(ctx):
    return _launches.traced_idle_ms(ctx, "device_enqueue")
