"""The planner on the `mainnet-subnet-gossip` configuration: one
subnet's stream as the configuration states it, and every drain of the
shape its `full_batch_shape` names, by the program's own bucket rule."""

import json
import os

import pytest

from benchmarks.harness import cell, traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2_147_483_659


def _load(kind, name):
    with open(os.path.join(BENCH, kind, f"{name}.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def cfg():
    return _load("configs", "mainnet-subnet-gossip")


@pytest.fixture(scope="module")
def plan(cfg):
    return traffic.plan(cfg, _load("traffic", "saturate"), SEED, 30)


def _groups(specs):
    groups = {}
    for s in specs:
        groups[s.message] = groups.get(s.message, 0) + 1
    return list(groups.values())


def _first_task(pool):
    """Index of each message's first task in the stream."""
    first = {}
    for i, s in enumerate(pool):
        first.setdefault(s.message, i)
    return first


def _drains(plan, cfg):
    batch = cfg["knobs"]["service"]["max_batch"]
    assert len(plan.pool) % batch == 0
    return [plan.pool[lo:lo + batch]
            for lo in range(0, len(plan.pool), batch)]


def test_the_cell_is_in_the_benchmark_with_its_files():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry = cell.find_cell(bench, "mainnet-subnet-gossip.saturate")
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == ("mainnet-subnet-gossip", "saturate", 1)
    conf = next(c for c in bench["configs"]
                if c["name"] == entry["config"])
    assert conf["file"] == "benchmarks/configs/mainnet-subnet-gossip.json"
    cfg = _load("configs", "mainnet-subnet-gossip")
    assert conf["reduced"] == cfg["reduced"] == list(cfg["reduced_why"])
    assert cfg["guarantees"] == _load("configs",
                                      "backfill-unique")["guarantees"]
    # the arena at the program's default: no knob of the file sets it
    assert "TEKU_TPU_H2C_CACHE_CAP" not in cfg["knobs"]["env"]
    for m in bench["per_layer"]:
        if m["name"] in ("provider.arena_hit_share",
                         "kernels.msm_ms_per_batch",
                         "kernels.msm_roofline"):
            assert m["workloads"] == [entry["name"]]
            assert m["moves"] == "sigs_per_s"
            cell.load_reader(m["name"])


def test_messages_come_in_runs_of_464_and_nothing_repeats(plan, cfg):
    assert cfg["traffic_shape"]["tasks_per_message"] == 464
    assert cfg["signer_set"] > 464
    assert len(plan.pool) == 31_000
    sizes = _groups(plan.pool)
    assert set(sizes[:-1]) == {464} and sizes[-1] == 31_000 % 464
    # consecutive: a message's tasks are one run of the stream
    assert sorted(_first_task(plan.pool).values()) == list(range(0, 31_000, 464))
    everything = (plan.pool + plan.probe + plan.traced
                  + [s for w in plan.warm for s in w])
    # no (signer, message) twice: the service would coalesce the twins
    assert len({(s.signer, s.message) for s in everything}) \
        == len(everything)
    assert all(len(s.message) == 32 for s in everything)
    assert [s.forged for s in plan.probe].count(True) == 1


def test_every_drain_is_one_or_two_messages_in_8_or_9_rows(plan, cfg,
                                                           monkeypatch):
    from teku_tpu.ops import msm, shapeset
    # the rule as the chip sees it: `auto` looks at the device
    monkeypatch.setattr(msm, "_device_is_tpu", lambda: True)
    want = cfg["full_batch_shape"]
    env, knobs = cfg["knobs"]["env"], cfg["knobs"]
    first = _first_task(plan.pool)
    fresh = lookups = 0
    seen = set()
    drains = _drains(plan, cfg)
    for d, specs in enumerate(drains):
        groups = _groups(specs)
        got = shapeset.batch_plan(
            groups, min_bucket=knobs["min_bucket"],
            h2c_min_bucket=env["TEKU_TPU_H2C_MIN_BUCKET"],
            group_cap=env["TEKU_TPU_H2C_GROUP_CAP"])
        assert (got["lanes"], got["padded"], got["group_bucket"],
                got["u_total"], got["msm_path"], got["h2c_bucket"]) == (
            want["lanes"], want["padded_lanes"], want["group_bucket"],
            want["unique_bucket"], want["msm_path"],
            want["h2c_miss_bucket"])
        assert got["rows"] in want["rows"]
        assert got["messages"] in want["messages"]
        # one message: 7 rows of 32 and one of 26; two: one row more,
        # unless the cut falls so that both tails fit their last rows
        assert got["rows"] >= 8 + (got["messages"] == 2) - 1
        seen.add((got["messages"], got["rows"]))
        lookups += len(groups)
        # a message is fresh in the drain that holds its first task
        new = sum(1 for m in {s.message for s in specs}
                  if d * len(specs) <= first[m] < (d + 1) * len(specs))
        assert new in (0, 1)
        fresh += new
    assert seen >= {(1, 8), (2, 9)}
    # about 54 % of drains meet one fresh message, 1.54 look-ups a drain
    assert fresh / len(drains) == pytest.approx(250 / 464, abs=0.01)
    assert lookups / len(drains) == pytest.approx(1 + 250 / 464, abs=0.02)


def test_setup_warms_every_shape_the_window_dispatches(plan, cfg):
    knobs = cfg["knobs"]
    warmed = {cell.shape_signature(w, knobs, arena_warm=False)
              for w in plan.warm}
    assert len(warmed) == 1, "both warm batches: one fresh message"
    (_single, shape, u_hm, g_bucket, path, h2c_bucket), = warmed
    assert (shape, u_hm, g_bucket, h2c_bucket) == ("256x1", 16, 32, 16)
    for specs in _drains(plan, cfg):
        # a drain that meets a fresh message runs what the warm batches
        # ran; one that meets none runs the same less stage_h2c
        assert cell.shape_signature(specs, knobs, arena_warm=False) \
            in warmed
        hit = cell.shape_signature(specs, knobs, arena_warm=True)
        assert hit[:5] == (False, shape, u_hm, g_bucket, path)
        assert hit[5] == 0
    # the traced dispatch is a fresh-message drain
    assert _groups(plan.traced) == [250]
    assert cell.shape_signature(plan.traced, knobs, arena_warm=False) \
        in warmed
