"""Drives one run of the four-chip cell's structure at a size a CPU
test run can hold, on four virtual devices: `drive.py`'s seams, with
the one cell `tiny-gossip-mesh4.saturate` standing where
`mainnet-subnet-gossip.mesh4` stands in `BENCHMARK.json`.

    python3 benchmarks/tests/drive_mesh4.py <trace 0|1> <seed> [tamper]
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

SECONDS = 3
REAL = "mainnet-subnet-gossip.mesh4"
CELL = "tiny-gossip-mesh4.saturate"


def tiny_bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"] = [
        {"name": "tiny-gossip-mesh4", "source": "benchmarks/tests",
         "file": "benchmarks/tests/data/tiny-gossip-mesh4.json",
         "reduced": [], "why": "a configuration's structure at test size"}]
    bench["workloads"] = [
        {"name": CELL, "config": "tiny-gossip-mesh4",
         "traffic": "tiny-saturate-mesh4", "chips": 4, "why": "test size"}]
    # the cell reports what the real one does, under its test name
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [CELL if w == REAL else w
                                   for w in metric["workloads"]]
    return bench


if __name__ == "__main__":
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4").strip()
    from benchmarks import run
    from benchmarks.harness import cell as harness_cell, tamper
    trace, seed = sys.argv[1:3]
    fault = tamper.BY_NAME[sys.argv[3]] if len(sys.argv) > 3 else None
    code = run.main(
        ["--workload", CELL, "--seed", seed, "--seconds", str(SECONDS),
         "--trace", trace],
        harness_cell.Seams(bench=tiny_bench(), tamper=fault,
                           traffic_dir=os.path.join(HERE, "data"),
                           look_for_chip=False))
    sys.stdout.flush()
    os._exit(code)
