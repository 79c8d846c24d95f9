"""The three readers that `mainnet-aggregates.saturate` brings, on a
slice recorded from a chip run of that cell (`data/recorded_aggregates
.json`: window and traced ledger records cut to the fields the readers
read, and the traced dispatch's module line), and nothing where there is
nothing to read."""

import json
import os

import pytest

from benchmarks.harness import cell, profile, work

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "mainnet-aggregates.saturate"
NEW = ("kernels.prepare_ms_per_batch", "kernels.key_sum_roofline",
       "provider.key_pad_waste")


@pytest.fixture()
def rec():
    with open(os.path.join(HERE, "data", "recorded_aggregates.json")) as fh:
        return json.load(fh)


def _read(name, table="bls_verify_keys", **ctx):
    base = {"window": None, "window_ledger": [], "traced_ledger": [],
            "setup_ledger": [], "reduced": None,
            "table": work.load_table(table),
            "peak": work.load_peak("TPU v5 lite")}
    return cell.load_reader(name)({**base, **ctx})


def _prepare_s(rec):
    return sum(secs for lines in rec["trace"]["devices"].values()
               for name, _t0, secs in lines["XLA Modules"]
               if profile.stage_of(name) == "stage_prepare")


def test_prepare_time_is_the_module_per_traced_dispatch(rec):
    got = _read("kernels.prepare_ms_per_batch",
                reduced={"trace": rec["trace"]},
                traced_ledger=rec["traced_ledger"])
    want = _prepare_s(rec) / len(rec["traced_ledger"]) * 1e3
    assert got == pytest.approx(want) == pytest.approx(621.049412)
    # the key sum does most of a dispatch's device time
    busy = _read("kernels.busy_ms_per_batch",
                 reduced={"trace": rec["trace"]},
                 traced_ledger=rec["traced_ledger"])
    assert got > busy / 2


def test_key_sum_roofline_counts_live_keys_beyond_each_lanes_first(rec):
    ledger = rec["traced_ledger"]
    beyond = sum(r["keys"] - r["lanes"] for r in ledger)
    table = work.load_table("bls_verify_keys")
    assert table["per_key"]["key_sum"]["fp_mul"] == 16     # 11M + 5S
    least = work.least_seconds(table, work.load_peak("TPU v5 lite"),
                               beyond * 16)
    got = _read("kernels.key_sum_roofline",
                reduced={"trace": rec["trace"]}, traced_ledger=ledger)
    assert got == pytest.approx(100.0 * least / _prepare_s(rec))
    # 37,002 live keys beyond their lanes' first: ~20.8 us at the int8
    # peak against 621 ms of `stage_prepare`
    assert beyond == 37_002
    assert got == pytest.approx(0.003352, rel=1e-3)


def test_key_pad_waste_reads_the_records_key_slots(rec):
    ledger = rec["window_ledger"]
    real = sum(r["waste"]["key"]["real"] for r in ledger)
    padded = sum(r["waste"]["key"]["padded"] for r in ledger)
    got = _read("provider.key_pad_waste", window_ledger=ledger)
    assert got == pytest.approx(100.0 * (padded - real) / padded)
    # 252 lanes of 1, 1 and 400-488 keys in 256 x 512 slots
    assert got == pytest.approx(71.4284, abs=1e-4)


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_is_nothing(name, rec):
    # no trace, no traced dispatch, no window records
    assert _read(name) is None
    if name != "provider.key_pad_waste":
        assert _read(name, traced_ledger=rec["traced_ledger"]) is None
        # a table with no key term (the one-key cells') gives no key-sum
        # roofline; the module's time still reads
        assert (_read(name, table="bls_verify",
                      reduced={"trace": rec["trace"]},
                      traced_ledger=rec["traced_ledger"]) is None) \
            == (name == "kernels.key_sum_roofline")
    # records from before PR 36 carry no `waste.key`
    assert _read("provider.key_pad_waste",
                 window_ledger=[{"seq": 1, "waste": {"lane": {
                     "real": 250, "padded": 256}}}]) is None


def test_the_cell_lists_each_new_metric():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "sigs_per_s"
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", f"{name}.py"))
    cfg = next(c for c in bench["configs"]
               if c["name"] == "mainnet-aggregates")
    with open(os.path.join(ROOT, cfg["file"])) as fh:
        assert json.load(fh)["roofline"] == "bls_verify_keys"
    # the one-key table's roofline is not reported where it has no key
    # term
    assert CELL not in by_name["kernels.staged_verify_roofline"][
        "workloads"]
