"""Drives one run of the harness at a size a CPU test run can hold,
without the harness's look for a chip, optionally with a fault planted
under the timed path.  The tests start it as a process of its own (the
program's facade and supervisor are process-global).

    python3 benchmarks/tests/drive.py <cell> <trace 0|1> <seed> [tamper]
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

SECONDS = 3


def tiny_bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"] = [
        {"name": name, "source": "benchmarks/tests",
         "file": f"benchmarks/tests/data/{name}.json", "reduced": [],
         "why": "a configuration's structure at test size"}
        for name in ("tiny-gossip", "tiny-unique")]
    bench["workloads"] = [
        {"name": f"{config}.{mix}", "config": config,
         "traffic": f"tiny-{mix}", "chips": 1, "why": "test size"}
        for config, mix in (("tiny-gossip", "saturate"),
                            ("tiny-unique", "saturate"),
                            ("tiny-unique", "poisson"))]
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            names = [w.replace("backfill-unique", "tiny-unique")
                     for w in metric["workloads"]]
            # the grouped configuration reports what a backlog cell does
            if "tiny-unique.saturate" in names:
                names.append("tiny-gossip.saturate")
            metric["workloads"] = names
    return bench


if __name__ == "__main__":
    from benchmarks import run
    from benchmarks.harness import cell as harness_cell, tamper
    cell, trace, seed = sys.argv[1:4]
    fault = tamper.BY_NAME[sys.argv[4]] if len(sys.argv) > 4 else None
    code = run.main(
        ["--workload", cell, "--seed", seed, "--seconds", str(SECONDS),
         "--trace", trace],
        harness_cell.Seams(bench=tiny_bench(), tamper=fault,
                           traffic_dir=os.path.join(HERE, "data"),
                           look_for_chip=False))
    sys.stdout.flush()
    os._exit(code)
