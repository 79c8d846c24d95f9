"""The trace reduction gives known numbers on a small recorded trace:
two all-unique batches' module line as the chip gave it (PR 25),
re-timed onto round numbers."""

import json
import os

import pytest

from benchmarks.harness import profile, work

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture()
def recorded():
    with open(os.path.join(HERE, "data", "recorded_trace.json")) as fh:
        return json.load(fh)


def test_module_time_per_stage(recorded):
    secs = profile.module_seconds(recorded["trace"])
    assert secs["stage_h2c"] == pytest.approx(2 * 0.177)
    assert secs["stage_finish"] == pytest.approx(2 * 0.114)
    assert secs["stage_scalars"] == pytest.approx(2 * 0.047)
    assert secs["jit_gather"] == pytest.approx(0.002)
    staged = sum(v for k, v in secs.items() if k.startswith("stage_"))
    assert staged / 2 == pytest.approx(0.469)
    assert profile.top_modules(recorded["trace"], 1)[0][0] == "stage_h2c"


def test_stage_names():
    assert profile.stage_of("jit_stage_h2c(124856)") == "stage_h2c"
    assert profile.stage_of("jit_stage_scalars_pippenger(1)") \
        == "stage_scalars_pippenger"
    assert profile.stage_of("jit__pk_validate_kernel(7)") \
        == "_pk_validate_kernel"
    assert profile.stage_of("jit_scatter(99)") == "jit_scatter"


def test_busy_is_the_union_of_module_intervals(recorded):
    lo, hi = recorded["window"]
    # 2 x (0.469 + 0.001) busy; 0.05 before, 0.03 between, 0.01 after
    busy = profile.busy_seconds(recorded["trace"], lo, hi)
    assert busy == pytest.approx(0.940)
    assert hi - lo - busy == pytest.approx(0.090)
    # clipped to a window that cuts the first module in half
    assert profile.busy_seconds(recorded["trace"], 100.0885, 100.177) \
        == pytest.approx(0.0885)
    starts, ends = profile.union([(0, 2), (1, 3), (5, 6)])
    assert starts.tolist() == [0, 5] and ends.tolist() == [3, 6]


def test_gaps_are_named_by_the_host_span_they_fall_in(recorded):
    lo, hi = recorded["window"]
    gaps = dict(profile.idle_gaps(recorded["trace"], lo, hi,
                                  recorded["host_spans"]))
    assert gaps["host_prep"] == pytest.approx(0.030)
    # before the first batch and after the last no span was open
    assert gaps["waiting_for_tasks"] == pytest.approx(0.060)


def test_a_reader_that_finds_no_trace_returns_nothing():
    from benchmarks.harness import cell
    for name in ("device.idle_share", "kernels.busy_ms_per_batch",
                 "kernels.staged_verify_roofline"):
        assert cell.load_reader(name)({"reduced": None, "window": None,
                                       "window_ledger": [],
                                       "traced_ledger": []}) is None


def test_work_and_peaks():
    table = work.load_table("bls_verify")
    peak = work.load_peak("TPU v5 lite")
    lane = sum(v["fp_mul"] for v in table["per_lane"].values())
    assert lane == 2660 + 1240 + 800 + 1952 + 40
    gossip = work.fp_muls(table, lanes=250, rows=8, fresh_messages=2)
    unique = work.fp_muls(table, lanes=250, rows=250, fresh_messages=250)
    assert gossip == 250 * lane + 8 * 7064 + 2 * 8000 + 16300
    assert unique - gossip == 242 * 7064 + 248 * 8000
    # 6,912 multiply-adds a multiplication, two operations each
    assert work.least_seconds(table, peak, 1e6) \
        == pytest.approx(1e6 * 13824 / 393e12)
    with pytest.raises(KeyError):
        work.load_peak("cpu")


def test_kernel_time_is_per_traced_dispatch(recorded):
    from benchmarks.harness import cell
    ctx = {"reduced": {"trace": recorded["trace"]},
           "traced_ledger": [{}, {}]}
    assert cell.load_reader("kernels.busy_ms_per_batch")(ctx) \
        == pytest.approx(469.0)
    ctx["traced_ledger"] = []
    assert cell.load_reader("kernels.busy_ms_per_batch")(ctx) is None


def test_idle_share_is_the_windows_own(recorded):
    """One dispatch's device seconds from the trace, times the
    dispatches the window fitted, over the window's seconds: a window
    that fits more dispatches of the same device cost is less idle."""
    from benchmarks.harness import cell, window
    lo, hi = recorded["window"]
    read = cell.load_reader("device.idle_share")

    def ctx(dispatches):
        done = [window.Answer(due=0.0, done=1.0, verdict=True)
                for _ in range(250 * dispatches)]
        late = [window.Answer(due=0.0, done=31.0, verdict=True)] * 250
        return {"reduced": {"busy_s": profile.busy_seconds(
                    recorded["trace"], lo, hi)},
                "traced_ledger": [{}, {}],
                "window_ledger": [{"lanes": 250}] * (dispatches + 1),
                "window": window.WindowResult(t_open=0.0, t_close=30.0,
                                              answers=done + late)}
    # 0.470 s of device time a dispatch
    assert read(ctx(58)) == pytest.approx(100 * (1 - 0.470 * 58 / 30))
    assert read(ctx(62)) == pytest.approx(100 * (1 - 0.470 * 62 / 30))
    assert read(ctx(62)) < read(ctx(58))
