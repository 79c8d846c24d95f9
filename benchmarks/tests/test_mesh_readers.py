"""The readers that the four-chip cell brings give known numbers on a
recorded trace of four device planes and on recorded ledger records
with mesh blocks, and nothing where there is nothing to read: a
one-chip trace, a trace without the two mesh modules (the parent's
program), an untraced run, records without a mesh plan."""

import json
import os

import pytest

from benchmarks.harness import cell, work

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = {"name": "mainnet-subnet-gossip.mesh4", "chips": 4}
TRACE_READERS = ("mesh.shard_ms_per_batch", "mesh.exchange_ms_per_batch",
                 "mesh.single_chip_ms_per_batch",
                 "mesh.chip_busy_min_share", "kernels.mesh_verify_roofline")


def _data(name):
    with open(os.path.join(HERE, "data", name)) as fh:
        return json.load(fh)


@pytest.fixture()
def mesh4():
    return _data("recorded_mesh4.json")


def _read(name, **ctx):
    base = {"cell": CELL, "window": None, "window_ledger": [],
            "traced_ledger": [], "setup_ledger": [], "reduced": None,
            "table": work.load_table("bls_verify"),
            "peak": work.load_peak("TPU v5 lite")}
    return cell.load_reader(name)({**base, **ctx})


def _traced(rec):
    return {"reduced": {"trace": rec["trace"], "lo": rec["lo"],
                        "hi": rec["hi"]},
            "traced_ledger": rec["traced_ledger"]}


def test_the_sharded_programs_read_their_slowest_chip(mesh4):
    # 40 + 47 + 41 + 22 ms on the first chip
    assert _read("mesh.shard_ms_per_batch", **_traced(mesh4)) \
        == pytest.approx(150.0)
    # the chip whose shard took 147 ms waits for the one that took 150
    assert _read("mesh.exchange_ms_per_batch", **_traced(mesh4)) \
        == pytest.approx(61.0)


def test_single_chip_time_is_where_one_chip_alone_is_busy(mesh4):
    # stage_h2c 130 ms and three 0.1 ms programs on the first chip
    assert _read("mesh.single_chip_ms_per_batch", **_traced(mesh4)) \
        == pytest.approx(130.3)


def test_the_three_parts_add_up_to_the_dispatch(mesh4):
    parts = sum(_read(name, **_traced(mesh4)) for name in (
        "mesh.single_chip_ms_per_batch", "mesh.shard_ms_per_batch",
        "mesh.exchange_ms_per_batch"))
    # first start 1.000 s, last end 1.340 s
    assert parts == pytest.approx(340.0, rel=0.05)


def test_the_least_busy_chip_over_the_traced_window(mesh4):
    # chips 1 to 3 run 208 ms of the 360 ms window, the first 338.3
    assert _read("mesh.chip_busy_min_share", **_traced(mesh4)) \
        == pytest.approx(100.0 * 0.208 / 0.36)


def test_mesh_roofline_counts_the_table_against_four_chips(mesh4):
    table = work.load_table("bls_verify")
    muls = work.fp_muls(table, 250, 8, 1)
    assert muls == 250 * 6692 + 8 * 7064 + 8000 + 16300
    one_chip = work.least_seconds(table, work.load_peak("TPU v5 lite"),
                                  muls)
    got = _read("kernels.mesh_verify_roofline", **_traced(mesh4))
    assert got == pytest.approx(100.0 * one_chip / 4 / 0.340)
    assert 0.004 < got < 0.005


def test_two_traced_dispatches_halve_the_per_batch_numbers(mesh4):
    ctx = _traced(mesh4)
    ctx["traced_ledger"] = ctx["traced_ledger"] * 2
    assert _read("mesh.shard_ms_per_batch", **ctx) == pytest.approx(75.0)
    assert _read("mesh.single_chip_ms_per_batch", **ctx) \
        == pytest.approx(65.15)


@pytest.mark.parametrize("name", TRACE_READERS)
def test_trace_readers_give_nothing_without_a_mesh_trace(mesh4, name):
    assert _read(name) is None                       # an untraced run
    assert _read(name, reduced={"trace": mesh4["trace"], "lo": 0.99,
                                "hi": 1.35}) is None   # no traced record
    one = {"devices": {"/device:TPU:0":
                       mesh4["trace"]["devices"]["/device:TPU:0"]},
           "sync_s": None}
    assert _read(name, reduced={"trace": one, "lo": 0.99, "hi": 1.35},
                 traced_ledger=mesh4["traced_ledger"]) is None
    # the parent's program: four planes, one module under another name
    parent = {"devices": {
        f"/device:TPU:{i}": {"XLA Modules": [
            ["jit_shard_fn(7)", 1.0, 0.3]]} for i in range(4)},
        "sync_s": None}
    assert _read(name, reduced={"trace": parent, "lo": 0.99, "hi": 1.35},
                 traced_ledger=mesh4["traced_ledger"]) is None


def test_shard_imbalance_is_the_mean_makespan_over_one(mesh4):
    # 64 / 62.5 = 1.024 three times and 128 / 125 = 1.024 once; the
    # record without a mesh block is skipped
    assert _read("mesh.shard_imbalance",
                 window_ledger=mesh4["window_ledger"]) \
        == pytest.approx(2.4)


def test_dispatch_share_sees_a_mesh_that_shrank(mesh4):
    assert _read("mesh.dispatch_share",
                 window_ledger=mesh4["window_ledger"]) \
        == pytest.approx(75.0)
    assert _read("mesh.dispatch_share",
                 window_ledger=mesh4["window_ledger"][:3]) == 100.0
    # a one-chip provider's records say devices 0: none ran on four
    assert _read("mesh.dispatch_share", window_ledger=[
        {"seq": 1, "mesh": {"devices": 0, "epoch": 0}}]) == 0.0


@pytest.mark.parametrize("name", ["mesh.shard_imbalance",
                                  "mesh.dispatch_share"])
def test_counter_readers_give_nothing_without_mesh_blocks(name):
    assert _read(name) is None
    assert _read(name, window_ledger=[{"seq": 1, "lanes": 250}]) is None


def test_shard_imbalance_skips_one_chip_records():
    assert _read("mesh.shard_imbalance", window_ledger=[
        {"seq": 1, "mesh": {"devices": 0, "epoch": 0}}]) is None
