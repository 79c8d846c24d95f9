"""`guard.launch_head_ms` in an open-loop cell, where it moves the
latency and not the rate: the same reader under a name of its own."""

from benchmarks.harness.cell import load_reader

read = load_reader("guard.launch_head_ms")
