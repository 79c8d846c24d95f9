"""Drives one run of the block cell's structure at a size a CPU test
run can hold: `drive.py`'s seams, with the one cell
`tiny-blocks.saturate-blocks` standing where
`mainnet-electra-blocks.saturate` stands in `BENCHMARK.json`.

    python3 benchmarks/tests/drive_blocks.py <trace 0|1> <seed> [tamper]
        [--ledger-out file]
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

SECONDS = 3
REAL = "mainnet-electra-blocks.saturate"
CELL = "tiny-blocks.saturate-blocks"


def tiny_bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"] = [
        {"name": "tiny-blocks", "source": "benchmarks/tests",
         "file": "benchmarks/tests/data/tiny-blocks.json",
         "reduced": [], "why": "a configuration's structure at test size"}]
    bench["workloads"] = [
        {"name": CELL, "config": "tiny-blocks",
         "traffic": "tiny-saturate-blocks", "chips": 1,
         "why": "test size"}]
    # the cell reports what the real one does, under its test name
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [CELL if w == REAL else w
                                   for w in metric["workloads"]]
    return bench


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("trace")
    ap.add_argument("seed")
    ap.add_argument("tamper", nargs="?")
    ap.add_argument("--ledger-out")
    opts = ap.parse_args()
    from benchmarks import run
    from benchmarks.harness import cell as harness_cell, tamper
    code = run.main(
        ["--workload", CELL, "--seed", opts.seed, "--seconds",
         str(SECONDS), "--trace", opts.trace],
        harness_cell.Seams(
            bench=tiny_bench(), traffic_dir=os.path.join(HERE, "data"),
            tamper=tamper.BY_NAME[opts.tamper] if opts.tamper else None,
            look_for_chip=False, ledger_out=opts.ledger_out))
    sys.stdout.flush()
    os._exit(code)
