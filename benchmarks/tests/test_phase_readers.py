"""The readers of a dispatch's phases and of the AOT store's load
records give known numbers on recorded ledger records and a recorded
trace (pure arithmetic, no device), and nothing where their input is
absent: the parent of the PR that brought the phases has none."""

import json
import os
import types

import pytest

from benchmarks.harness import cell

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture()
def recorded():
    with open(os.path.join(HERE, "data", "recorded_phases.json")) as fh:
        return json.load(fh)


def _read(name, **ctx):
    base = {"window": None, "window_ledger": [], "traced_ledger": [],
            "setup_ledger": [], "reduced": None}
    return cell.load_reader(name)({**base, **ctx})


def test_lock_wait_and_settle_are_medians_per_dispatch(recorded):
    ledger = recorded["window_ledger"]
    # 0, 500.5 and 501.5 ms; the record without phases is skipped
    assert _read("guard.lock_wait_ms", window_ledger=ledger) \
        == pytest.approx(500.5)
    # return_hop + settle: 6, 8 and 8 ms
    assert _read("service.settle_ms", window_ledger=ledger) \
        == pytest.approx(8.0)


def test_lock_idle_is_release_to_the_next_acquire(recorded):
    # 10.521 -> 10.5215 and 11.0415 -> 11.0425: 0.5 and 1.0 ms
    assert _read("guard.lock_idle_ms",
                 window_ledger=recorded["window_ledger"]) \
        == pytest.approx(0.75)
    one = recorded["window_ledger"][:1]
    assert _read("guard.lock_idle_ms", window_ledger=one) is None


def _task(t0, total, spans):
    from teku_tpu.infra import tracing
    trace = tracing.Trace("bench_task", {})
    trace.t_start, trace._end = t0, t0 + total
    for stage, s0, secs in spans:
        trace.add_stage(stage, secs, t0=s0)
    return types.SimpleNamespace(trace=trace)


def test_unattributed_is_what_no_span_covers_at_any_level(recorded):
    phases = recorded["window_ledger"][1]["phases"]
    dispatch = ("dispatch", 10.01999, 11.0436 - 10.01999)
    named = [("queue_wait", 9.8, 0.2195), ("assembly", 10.0195, 0.00049),
             dispatch] + [tuple(p) for p in phases]
    # the parent's spans: the hop and the wait at the lock have no
    # name, the two host_prep spans leave a hole, the way back has none
    bare = [("queue_wait", 9.8, 0.2195), ("assembly", 10.0195, 0.00049),
            dispatch, ("host_prep", 10.5215, 0.02),
            ("host_prep", 10.5435, 0.028),
            ("device_enqueue", 10.5715, 0.01),
            ("device_sync", 10.5815, 0.459)]
    total = 11.0535 - 9.8
    win = types.SimpleNamespace(answers=[
        _task(9.8, total, named), _task(9.8, total, named),
        _task(9.8, total, bare),
        types.SimpleNamespace(trace=None)])
    # named: only the tail after `settle` (11.0485 -> 11.0535: the done
    # callbacks); the median of (5, 5, bare) ms
    assert _read("service.unattributed_ms", window=win) \
        == pytest.approx(5.0, abs=0.01)
    win.answers = win.answers[2:]
    # bare: 501.51 ms before host_prep, 2 ms between its two spans, 3.1
    # after device_sync inside `dispatch`, and 9.9 ms after it
    assert _read("service.unattributed_ms", window=win) \
        == pytest.approx(501.51 + 2.0 + 3.1 + 9.9, abs=0.01)


def test_unnamed_idle_share_of_a_recorded_trace(recorded):
    with open(os.path.join(HERE, "data", "recorded_trace.json")) as fh:
        trace = json.load(fh)["trace"]
    # the first batch alone: idle 50 ms before its first module (45 of
    # them under host_prep), 30 ms after its last (2 under return_hop,
    # 20 under settle, 8 under nothing)
    reduced = {"trace": trace, "lo": 99.95, "hi": 100.5,
               "offset": recorded["traced_offset"]}
    share = _read("device.idle_unnamed_share", reduced=reduced,
                  traced_ledger=recorded["traced_ledger"])
    assert share == pytest.approx(0.0)
    # without the settle phase the tail is nobody's by half
    cut = [dict(recorded["traced_ledger"][0])]
    cut[0]["phases"] = cut[0]["phases"][:-1]
    share = _read("device.idle_unnamed_share", reduced=reduced,
                  traced_ledger=cut)
    assert share == pytest.approx(100.0 * 0.030 / 0.080)


def test_setup_load_records_split_by_cost(recorded, monkeypatch):
    from teku_tpu.infra import aotstore
    monkeypatch.setattr(aotstore, "load_records",
                        lambda since=0.0: recorded["load_records"],
                        raising=False)
    win = types.SimpleNamespace(t_open=30.0)    # the last one is later
    assert _read("compile.aot_deserialize_s", window=win) \
        == pytest.approx(0.01 + 0.5 + 0.25 + 7.25)
    assert _read("compile.first_call_s", window=win) \
        == pytest.approx(1.5 + 4.0 + 3.0)


def test_readers_find_nothing_on_a_program_without_phases(monkeypatch):
    from teku_tpu.infra import aotstore
    bare = [{"seq": 1, "lanes": 250}]
    for name in ("guard.lock_wait_ms", "service.settle_ms",
                 "guard.lock_idle_ms"):
        assert _read(name, window_ledger=bare) is None
    assert _read("service.unattributed_ms") is None
    assert _read("device.idle_unnamed_share", traced_ledger=bare) is None
    assert _read("device.idle_unnamed_share", traced_ledger=bare,
                 reduced={"trace": {"devices": {}}, "lo": 0.0, "hi": 1.0,
                          "offset": 0.0}) is None
    monkeypatch.delattr(aotstore, "load_records")
    win = types.SimpleNamespace(t_open=30.0, answers=[])
    for name in ("compile.aot_deserialize_s", "compile.first_call_s"):
        assert _read(name, window=win) is None
        assert _read(name) is None
