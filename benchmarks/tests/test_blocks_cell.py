"""The block cell: it is in the benchmark with its files, and a whole
run of its structure (`data/tiny-blocks.json`: a task is one block's
signature set, one block a drain), at a size a CPU holds, is correct,
dispatches one block a lane a triple, and is found out by the
control."""

import json
import os
import subprocess
import sys

from benchmarks.harness import cell
from benchmarks.tests import drive_blocks

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
SEED = "3000000019"


def _drive(tmp_path, *argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               TEKU_TPU_AOT_STORE_DIR=str(tmp_path / "aot"))
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "drive_blocks.py"), *argv],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


def test_the_cell_is_in_the_benchmark_with_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry = cell.find_cell(bench, drive_blocks.REAL)
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == ("mainnet-electra-blocks", "saturate-blocks", 1)
    conf = next(c for c in bench["configs"]
                if c["name"] == entry["config"])
    with open(os.path.join(ROOT, conf["file"])) as fh:
        config = json.load(fh)
    assert conf["source"] == config["source"] and len(conf["source"]) <= 200
    assert conf["reduced"] == config["reduced"] == ["signer_set"]
    for metric in bench["per_layer"]:
        if drive_blocks.REAL in metric.get("workloads", ()):
            assert callable(cell.load_reader(metric["name"]))
    assert drive_blocks.REAL in next(
        m for m in bench["end_to_end"]
        if m["name"] == "sigs_per_s")["workloads"]


def test_blocks_run_whole(tmp_path):
    path = tmp_path / "ledger.json"
    out, err = _drive(tmp_path, "1", SEED, "--ledger-out", str(path))
    assert out["correct"] is True, err[-3000:]
    assert all(c["value"] == 0 for c in out["checks"].values())
    with open(path) as fh:
        records = json.load(fh)
    # one block a drain: seven lanes at the block's key bucket
    assert {r["shape"] for r in records} == {"16x32"}
    assert {r["lanes"] for r in records} == {7}
    assert all(r["key_pack_s"] > 0 for r in records)
    # a traced run reports the layers; on a CPU no device plane, so no
    # kernels.* reader finds anything
    assert out["metrics"]["provider.key_pack_ms"]["value"] > 0
    assert out["metrics"]["provider.key_pad_waste"]["value"] > 0
    probe = next(ln for ln in err.splitlines() if "] probe: " in ln)
    assert probe.split()[-1] == "False"


def test_the_control_is_found_out(tmp_path):
    out, err = _drive(tmp_path, "0", "3000000020", "always_true")
    assert out["correct"] is False, err[-3000:]
    assert out["checks"]["wrong_verdicts"]["value"] >= 1
