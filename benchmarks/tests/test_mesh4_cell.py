"""The four-chip cell: its configuration is the one-chip gossip
configuration but for the mesh, so it plans the very same tasks; it is
in the benchmark with its files; and a whole run of its structure, at a
size a CPU holds and on four virtual devices, is correct, sharded in
every dispatch, and found out by the control."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import cell, traffic
from benchmarks.tests import drive_mesh4

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NAME = "mainnet-subnet-gossip.mesh4"
SEED = 3_000_000_019


def _load(kind, name):
    with open(os.path.join(BENCH, kind, f"{name}.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_the_cell_is_in_the_benchmark_with_its_files(bench):
    entry = cell.find_cell(bench, NAME)
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == ("mainnet-subnet-gossip-mesh4", "saturate-mesh4", 4)
    conf = next(c for c in bench["configs"]
                if c["name"] == entry["config"])
    config = _load("configs", entry["config"])
    assert conf["file"] == f"benchmarks/configs/{entry['config']}.json"
    assert conf["source"] == config["source"] and len(conf["source"]) <= 200
    assert conf["reduced"] == config["reduced"]
    # of four cells one may ask for four chips
    assert [w["chips"] for w in bench["workloads"]].count(4) == 1
    # every metric that names the cell has a reader, and the cell
    # reports an end-to-end metric beside setup_s
    for metric in bench["per_layer"]:
        if NAME in metric.get("workloads", ()):
            assert callable(cell.load_reader(metric["name"]))
    assert NAME in next(m for m in bench["end_to_end"]
                        if m["name"] == "sigs_per_s")["workloads"]


def test_the_configuration_is_the_one_chip_one_but_for_the_mesh():
    one, four = (_load("configs", "mainnet-subnet-gossip"),
                 _load("configs", "mainnet-subnet-gossip-mesh4"))
    env = dict(four["knobs"]["env"])
    assert env.pop("TEKU_TPU_MESH") == 4
    assert env == one["knobs"]["env"]
    assert {k: v for k, v in four["knobs"].items() if k != "env"} \
        == {k: v for k, v in one["knobs"].items() if k != "env"}
    for key in ("kmax", "traffic_shape", "signer_set", "min_bucket",
                "h2c_min_bucket", "subnets", "reduced", "assumed",
                "roofline"):
        assert four[key] == one[key], key
    # the one-chip file's four guarantees word for word, and the mesh's
    assert {k: v for k, v in four["guarantees"].items() if k != "mesh"} \
        == one["guarantees"]
    assert "four chips" in four["guarantees"]["mesh"]
    assert "msm_path" not in four["full_batch_shape"]
    assert four["full_batch_shape"]["per_shard"] == {
        "shards": 4, "lanes": 64, "rows": 4, "live_rows": [2, 3],
        "group_bucket": 32}


def test_the_traffic_is_saturates_with_a_deeper_pool():
    one, four = _load("traffic", "saturate"), _load("traffic",
                                                     "saturate-mesh4")
    assert {k: v for k, v in four.items()
            if k not in ("what", "pool_tasks_per_s")} \
        == {k: v for k, v in one.items()
            if k not in ("what", "pool_tasks_per_s")}
    # twice what the cell sustained, in whole service batches
    assert four["pool_tasks_per_s"] == 2000
    assert traffic.pool_size(four, 30, 250) == 61_000


@pytest.mark.parametrize("seed", [SEED, 2_147_483_659])
def test_it_plans_the_one_chip_cells_very_tasks(seed):
    one = traffic.plan(_load("configs", "mainnet-subnet-gossip"),
                       _load("traffic", "saturate"), seed, 30)
    four = traffic.plan(_load("configs", "mainnet-subnet-gossip-mesh4"),
                        _load("traffic", "saturate-mesh4"), seed, 30)
    for field in ("warm", "probe", "traced", "meta", "signers",
                  "arrivals", "backlog", "topup"):
        assert getattr(one, field) == getattr(four, field), field
    # the deeper pool is a longer run of the same stream
    assert len(one.pool) == 31_000 and len(four.pool) == 61_000
    assert four.pool[:len(one.pool)] == one.pool


def _drive(tmp_path, *argv):
    # four virtual devices and a store of its own (test_run.py)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               TEKU_TPU_AOT_STORE_DIR=str(tmp_path / "aot"))
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "drive_mesh4.py"), *argv],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=1500)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


def test_a_traced_rehearsal_on_four_virtual_devices_is_correct(tmp_path):
    out, err = _drive(tmp_path, "1", str(SEED))
    assert out["correct"] is True, err[-3000:]
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())
    assert out["device"]["count"] == 4
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    # every window dispatch ran sharded over the four devices
    assert metrics["mesh.dispatch_share"] == 100.0
    assert metrics["mesh.shard_imbalance"] >= 0.0
    # no device plane in a CPU trace: the device readers find nothing
    # to read and are left out, never reported as 0
    assert not {"mesh.shard_ms_per_batch", "mesh.exchange_ms_per_batch",
                "mesh.single_chip_ms_per_batch",
                "mesh.chip_busy_min_share",
                "kernels.mesh_verify_roofline"} & set(metrics)
    for name in ("service.batch_fill", "guard.oracle_share",
                 "provider.pad_waste", "provider.arena_hit_share",
                 "guard.prep_outside_share"):
        assert name in metrics, name
    assert metrics["guard.oracle_share"] == 0.0


def test_the_control_is_found_out_on_the_mesh(tmp_path):
    out, err = _drive(tmp_path, "0", str(SEED), "always_true")
    assert out["correct"] is False, err[-3000:]
    assert out["checks"]["wrong_verdicts"]["value"] >= 1
    assert set(out["metrics"]) == {"sigs_per_s", "setup_s"}
