"""The benchmark's entry point.

    python3 benchmarks/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

run from the root of a checkout, on the machine that holds the chip.
It finds the cell in `BENCHMARK.json`, its configuration and traffic
mix by name under `benchmarks/`, runs one measured window through the
served verify path and prints one JSON object as its last line
(`benchmarks/README.md`).  It exits with another code than 0, and
prints no result, when JAX finds no TPU, or in a directory without the
program.
"""

import time

T_START = time.monotonic()      # set-up is counted from here

import argparse      # noqa: E402
import asyncio       # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

EXIT_NO_DEVICE = 3
EXIT_NO_PROGRAM = 4
EXIT_BOOT = 5


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, seams=None) -> int:
    """One run.  `seams` (`cell.Seams`) is the tests' way in: another
    table of cells, a fault planted under the timed path, no look for a
    chip.  The benchmark's own runs pass none."""
    args = parse(argv)
    from benchmarks.harness import boot, cell
    seams = seams or cell.Seams()
    bench = seams.bench
    if bench is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
            bench = json.load(fh)
    try:
        import teku_tpu  # noqa: F401  the system under test
    except ImportError as exc:
        print(f"no program in this directory: {exc}", file=sys.stderr)
        return EXIT_NO_PROGRAM
    try:
        return asyncio.run(cell.run(bench, args, T_START, seams))
    except cell.NoDevice as exc:
        print(f"no device: {exc}", file=sys.stderr)
        return EXIT_NO_DEVICE
    except boot.BootError as exc:
        print(f"set-up failed: {exc}", file=sys.stderr)
        return EXIT_BOOT


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the program (an abandoned dispatch, the
    # profiler) must not hold the exit
    os._exit(code)
