"""The three readers that the subnet-gossip cell brings give known
numbers on a recorded ledger (hit and miss records, by message) and a
recorded trace (the bucketed MSM path; the ladder path from the trace
the other readers' tests use), and nothing where there is nothing to
read."""

import json
import os

import pytest

from benchmarks.harness import cell, work

HERE = os.path.dirname(os.path.abspath(__file__))


def _data(name):
    with open(os.path.join(HERE, "data", name)) as fh:
        return json.load(fh)


@pytest.fixture()
def gossip():
    return _data("recorded_gossip.json")


def _read(name, **ctx):
    base = {"window": None, "window_ledger": [], "traced_ledger": [],
            "setup_ledger": [], "reduced": None,
            "table": work.load_table("bls_verify"),
            "peak": work.load_peak("TPU v5 lite")}
    return cell.load_reader(name)({**base, **ctx})


def _reduced(trace):
    return {"trace": trace}


def test_arena_hit_share_counts_messages_over_the_window(gossip):
    # look-ups 1 + 2 + 1 + 1 = 5, hits 0 + 1 + 1 + 1 = 3; the record
    # without an h2c block is skipped
    assert _read("provider.arena_hit_share",
                 window_ledger=gossip["window_ledger"]) \
        == pytest.approx(60.0)
    fresh_only = gossip["window_ledger"][:1]
    assert _read("provider.arena_hit_share",
                 window_ledger=fresh_only) == 0.0


@pytest.mark.parametrize("ledger", [
    [], [{"seq": 1, "lanes": 250}],
    [{"seq": 1, "h2c": {"cache_hits": 0, "cache_misses": 0,
                        "dispatch_bucket": 0}}]])
def test_arena_hit_share_without_a_lookup_is_nothing(ledger):
    assert _read("provider.arena_hit_share", window_ledger=ledger) is None


def test_msm_time_is_the_bucketed_module_per_traced_dispatch(gossip):
    got = _read("kernels.msm_ms_per_batch",
                reduced=_reduced(gossip["trace"]),
                traced_ledger=gossip["traced_ledger"])
    assert got == pytest.approx(250.0)


def test_msm_time_reads_the_same_work_on_the_ladder_path():
    # recorded_trace.json: two dispatches, each stage_scalars 47 ms and
    # stage_group 40 ms (the work stage_scalars_pippenger does in one)
    recorded = _data("recorded_trace.json")
    two = [{"lanes": 250}, {"lanes": 250}]
    assert _read("kernels.msm_ms_per_batch",
                 reduced=_reduced(recorded["trace"]), traced_ledger=two) \
        == pytest.approx(87.0)
    assert _read("kernels.msm_roofline",
                 reduced=_reduced(recorded["trace"]), traced_ledger=two) \
        == pytest.approx(0.0282213214, rel=1e-6)


def test_msm_roofline_counts_from_the_table(gossip):
    # 250 lanes x (800 + 1952 + 40) multiplications x 6912 x 2 int8
    # operations against 393e12 a second: 24.55 us, over 250 ms
    table = work.load_table("bls_verify")
    per_lane = sum(table["per_lane"][k]["fp_mul"] for k in (
        "scalar_mul_g1_64bit", "scalar_mul_g2_64bit", "fold_into_row"))
    assert per_lane == 2792
    got = _read("kernels.msm_roofline",
                reduced=_reduced(gossip["trace"]),
                traced_ledger=gossip["traced_ledger"])
    assert got == pytest.approx(0.0098210198, rel=1e-6)
    assert got < 100.0


@pytest.mark.parametrize("name", ["kernels.msm_ms_per_batch",
                                  "kernels.msm_roofline"])
def test_msm_readers_give_nothing_without_their_modules(gossip, name):
    assert _read(name) is None                      # an untraced parent
    assert _read(name, reduced=_reduced(gossip["trace"])) is None
    bare = {"devices": {"/device:TPU:0": {"XLA Modules": [
        ["jit_stage_h2c(1)", 1.0, 0.1], ["jit__gather(2)", 1.1, 0.001]]}},
        "sync_s": None}
    assert _read(name, reduced=_reduced(bare),
                 traced_ledger=gossip["traced_ledger"]) is None
