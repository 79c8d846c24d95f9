"""`guard.prep_wait_ms` (and its open-loop name) on a recorded ledger:
the median per dispatch of the `prep_wait` phase over the records that
carry it, and nothing on the records of a program without the phase
(the parent of the PR that brought it), whose line leaves the metric
out.  Pure arithmetic, no device."""

import json
import os

import pytest

from benchmarks.harness import cell

HERE = os.path.dirname(os.path.abspath(__file__))
NAMES = ("guard.prep_wait_ms", "guard.prep_wait_ms.open_loop")


@pytest.fixture()
def recorded():
    with open(os.path.join(HERE, "data", "recorded_prep_wait.json")) as fh:
        return json.load(fh)


def _read(name, ledger):
    return cell.load_reader(name)({"window": None, "window_ledger": ledger})


@pytest.mark.parametrize("name", NAMES)
def test_median_of_the_records_that_carry_the_phase(recorded, name):
    ledger = recorded["window_ledger"]
    # 0, 110, 0.03 and 0.02 ms; the record of a provider without a
    # host half and the one without phases are skipped
    assert _read(name, ledger) == pytest.approx(0.025)
    # the burst's pair alone: the first found the turn free, the
    # second waited out its packing
    assert _read(name, ledger[:2]) == pytest.approx(55.0)
    assert _read(name, ledger[1:2]) == pytest.approx(110.0)


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_on_a_program_without_the_phase(recorded, name):
    parent = recorded["parent_window_ledger"]
    assert all(rec["phases"] for rec in parent)
    assert _read(name, parent) is None
    assert _read(name, recorded["window_ledger"][4:]) is None
    assert _read(name, []) is None


def test_the_other_phase_readers_read_the_same_with_the_phase_in(recorded):
    """`prep_wait` takes nothing from the phases the accepted readers
    sum: the record with the 110 ms wait reads `host_prep` as packing
    alone, and `lock_wait` as the wait for the device alone."""
    ledger = recorded["window_ledger"][:4]
    assert _read("guard.lock_wait_ms", ledger) \
        == pytest.approx((0.87448 + 0.87697) / 2 * 1e3)
    idle = _read("guard.lock_idle_ms", ledger)
    assert idle == pytest.approx(0.5)


def test_both_metrics_are_declared_with_their_cells():
    bench = cell.load_json(cell.ROOT, "BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    lock_idle = by_name["guard.lock_idle_ms"]
    rate, latency = (by_name[name] for name in NAMES)
    assert rate["workloads"] == lock_idle["workloads"]
    assert (rate["moves"], latency["moves"]) == ("sigs_per_s",
                                                 "verify_p50_ms")
    assert latency["workloads"] == ["backfill-unique.poisson"]
    for metric in (rate, latency):
        assert (metric["layer"], metric["source"], metric["unit"],
                metric["better"]) == ("guarded provider", "program_span",
                                      "ms", "lower")
    assert [m["name"] for m in bench["per_layer"]][-2:] == list(NAMES)
