"""`guard.prep_wait_ms` in an open-loop cell, where it moves the latency and
not the rate: the same reader under a name of its own.  An open loop's
partial drains are where two host halves meet in steady state."""

from benchmarks.harness.cell import load_reader

read = load_reader("guard.prep_wait_ms")
