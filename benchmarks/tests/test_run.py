"""The rest of a run, without the look for a chip, at a size a CPU test
run can hold: a sound run is correct and prints exactly the contract's
keys; the control and each planted fault come out as not correct; a
run on anything but a TPU, or without the program, prints no result."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.harness import traffic
from benchmarks.tests import drive

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    return env


def _drive(tmp_path, *argv):
    # a store of its own: what XLA:CPU serializes does not always load
    # again on the machine that made it, and the program counts that as
    # an AOT-store error, which the guarantee forbids
    env = dict(_env(), TEKU_TPU_AOT_STORE_DIR=str(tmp_path / "aot"))
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "drive.py"), *argv],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


def _seed_with_forged_in(second_half: bool) -> int:
    with open(os.path.join(HERE, "data", "tiny-unique.json")) as fh:
        cfg = json.load(fh)
    with open(os.path.join(HERE, "data", "tiny-saturate.json")) as fh:
        mix = json.load(fh)
    for seed in range(3_000_000_000, 3_000_000_100):
        at = traffic.plan(cfg, mix, seed, drive.SECONDS).meta["forged_at"]
        if (at >= 4) == second_half:
            return seed
    raise AssertionError("no such seed")


@pytest.mark.parametrize("cell", ["tiny-gossip.saturate",
                                  "tiny-unique.saturate",
                                  "tiny-unique.poisson"])
def test_a_sound_run_is_correct(cell, tmp_path):
    mix = cell.split(".")[1]
    out, err = _drive(tmp_path, cell, "0", "3000000019")
    assert out["correct"] is True, err[-3000:]
    assert set(out) - {"checks"} == KEYS
    assert list(out)[-1] == "checks"
    assert out["failed"] == 0 and out["attempted"] > 8
    # a rate where the service is offered all it can take, the tails
    # where it is offered a fixed rate below that
    want = {"setup_s"} | ({"verify_p50_ms", "verify_p95_ms"}
                          if mix == "poisson" else {"sigs_per_s"})
    assert set(out["metrics"]) == want
    assert all(set(m) == {"value", "unit"} and m["value"] > 0
               for m in out["metrics"].values())
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    # every number compared stands beside its limit, last on stderr too
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())
    assert err.strip().splitlines()[-1] == "correct: True"


def test_a_traced_run_reports_the_layers(tmp_path):
    out, err = _drive(tmp_path, "tiny-unique.poisson", "1", "3000000021")
    assert out["correct"] is True, err[-3000:]
    names = set(out["metrics"])
    assert {"bringup.ready_s", "compile.load_s",
            "service.batch_fill.open_loop", "service.queue_wait_ms",
            "provider.host_prep_ms.open_loop"} == names
    # no device plane in a CPU trace: the device readers find nothing to
    # read and are left out, never reported as 0



@pytest.mark.parametrize("fault,number", [
    ("always_true", "wrong_verdicts"),       # the control
    ("inverted", "wrong_verdicts"),          # an answer altered
    ("half_batch", "undispatched_tasks"),    # half of the batch left out
    ("oracle_serves", "oracle_dispatches"),  # not the device's verdicts
])
def test_a_broken_timed_path_is_not_correct(fault, number, tmp_path):
    seed = _seed_with_forged_in(second_half=True)
    out, err = _drive(tmp_path, "tiny-unique.saturate", "0", str(seed),
                      fault)
    assert out["correct"] is False, err[-3000:]
    check = out["checks"][number]
    assert check["value"] > check["limit"]


def test_no_result_on_anything_but_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "backfill-unique.saturate", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=ROOT, env=_env(), timeout=600)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no device" in proc.stderr


def test_no_result_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "backfill-unique.saturate", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, cwd=tmp_path, env=_env(),
        timeout=600)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
