"""The readers of a dispatch's launches and of its `launch_head` phase
on recorded ledger records and a recorded module line: a traced
dispatch of the gossip cell on the chip, whose module line gives the
device's idle inside its phases; a fresh and a hit drain of known
launches against its module seconds; and the parent's records (no
`launches`, no `launch_head`), on which every reader finds nothing.
Pure arithmetic, no device."""

import copy
import json
import os

import pytest

from benchmarks.harness import cell

HERE = os.path.dirname(os.path.abspath(__file__))
NAMES = ("guard.launch_head_ms", "guard.launch_head_ms.open_loop",
         "guard.hold_off_device_ms", "device.launch_late_ms",
         "device.sync_wake_ms")
PK = "_pk_validate_kernel"


@pytest.fixture()
def recorded():
    with open(os.path.join(HERE, "data", "recorded_launches.json")) as fh:
        return json.load(fh)


def _ctx(recorded, window=None, traced=None, parent=False):
    prefix = "parent_" if parent else ""
    return {"window": None,
            "window_ledger": (recorded[prefix + "window_ledger"]
                              if window is None else window),
            "traced_ledger": (recorded[prefix + "traced_ledger"]
                              if traced is None else traced),
            "reduced": {"trace": recorded["trace"],
                        "offset": recorded["offset"]}}


def _read(name, ctx):
    return cell.load_reader(name)(ctx)


def _launches():
    import importlib.util
    path = os.path.join(os.path.dirname(HERE), "layer_metrics",
                        "_launches.py")
    spec = importlib.util.spec_from_file_location("launches_helper", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _line(recorded):
    return recorded["trace"]["devices"]["/device:TPU:0"]["XLA Modules"]


def test_programs_take_their_traced_seconds(recorded):
    seconds = _launches().program_seconds(_ctx(recorded))
    assert seconds == pytest.approx({
        "stage_h2c": 0.056432637, "_scatter": 3.7745e-05,
        "_gather": 1.7881e-05, "stage_prepare": 0.04073208,
        "stage_scalars": 0.047162503, "stage_group": 0.008429567,
        "stage_miller": 0.028716186, "stage_finish": 0.02341772})


def test_a_program_takes_its_seconds_on_the_first_chip(recorded):
    """On a mesh the other chips' intervals hold their wait for the
    first chip's one-chip programs (PERF.md section 5), so only the
    first chip's line counts."""
    trace = copy.deepcopy(recorded["trace"])
    line = trace["devices"]["/device:TPU:0"]["XLA Modules"]
    trace["devices"]["/device:TPU:1"] = {"XLA Modules": [
        [line[3][0], line[3][1], line[3][2] + 0.1]]}
    ctx = dict(_ctx(recorded), reduced={"trace": trace,
                                        "offset": recorded["offset"]})
    seconds = _launches().program_seconds(ctx)
    assert seconds["stage_prepare"] == pytest.approx(0.04073208)


def test_hold_off_device_is_the_hold_less_its_programs_seconds(recorded):
    """Fresh drain: held 217.746 ms, its eight programs 204.946; hit
    drain: held 158.176, six programs 148.476.  The third drain runs a
    program the traced one never ran and is left out."""
    ctx = _ctx(recorded)
    fresh, hit, unseen = ctx["window_ledger"]
    assert _read("guard.hold_off_device_ms", ctx) == pytest.approx(
        (12.8 + 9.7) / 2, abs=1e-6)
    assert _read("guard.hold_off_device_ms", _ctx(
        recorded, window=[fresh, unseen])) == pytest.approx(12.8, abs=1e-6)
    assert _read("guard.hold_off_device_ms", _ctx(
        recorded, window=[unseen])) is None


def test_a_key_validated_under_the_lock_is_the_holds(recorded):
    """A `pk_miss` dispatch validates its keys before `launch_head`:
    under the lock that launch is the hold's and its seconds count
    off; before the lock it is not the hold's; with no traced seconds
    for it, the dispatch is left out."""
    ctx = _ctx(recorded)
    fresh, hit, _unseen = ctx["window_ledger"]
    acquired = fresh["lock"]["acquired"]

    def with_key(at):
        return dict(fresh, launches=[[PK, at, 0.0004]] + fresh["launches"])

    under, before = with_key(acquired + 0.0011), with_key(acquired - 0.002)
    assert _read("guard.hold_off_device_ms", _ctx(
        recorded, window=[before])) == pytest.approx(12.8, abs=1e-6)
    assert _read("guard.hold_off_device_ms", _ctx(
        recorded, window=[under, hit])) == pytest.approx(9.7, abs=1e-6)
    # the traced dispatch validated a key too: 2 ms on the chip, done
    # 1 ms before its hold
    (traced,) = ctx["traced_ledger"]
    t0 = traced["lock"]["acquired"] - 0.003
    traced = dict(traced, launches=[[PK, t0, 0.0003]] + traced["launches"])
    trace = copy.deepcopy(recorded["trace"])
    modules = trace["devices"]["/device:TPU:0"]["XLA Modules"]
    modules.insert(0, [f"jit_{PK}(1)", t0 + recorded["offset"], 0.002])
    keyed = dict(_ctx(recorded, window=[under], traced=[traced]),
                 reduced={"trace": trace, "offset": recorded["offset"]})
    assert _read("guard.hold_off_device_ms", keyed) == pytest.approx(
        12.8 - 2.0, abs=1e-6)
    # the traced idle readers read the phases, not the first launch
    for name in ("device.launch_late_ms", "device.sync_wake_ms"):
        assert _read(name, keyed) == pytest.approx(
            _read(name, ctx), abs=1e-9)


def test_the_traced_split_is_the_module_lines_idle(recorded):
    """`device_enqueue` ran 8.566 ms and `stage_h2c` began 1.355 ms
    into it and outlasted it; `device_sync` holds the seven gaps
    between modules (0.065 ms) and the 1.260 ms from the last module's
    end to the sync's end."""
    ctx = _ctx(recorded)
    (rec,) = ctx["traced_ledger"]
    offset = recorded["offset"]
    line = _line(recorded)
    enqueue = next(p for p in rec["phases"] if p[0] == "device_enqueue")
    sync = next(p for p in rec["phases"] if p[0] == "device_sync")
    late = line[0][1] - (enqueue[1] + offset)
    gaps = sum(b[1] - (a[1] + a[2]) for a, b in zip(line, line[1:]))
    wake = gaps + sync[1] + sync[2] + offset - (line[-1][1] + line[-1][2])
    assert late * 1e3 == pytest.approx(1.355, abs=1e-3)
    assert wake * 1e3 == pytest.approx(1.326, abs=1e-3)
    assert _read("device.launch_late_ms", ctx) == pytest.approx(
        late * 1e3, abs=1e-6)
    assert _read("device.sync_wake_ms", ctx) == pytest.approx(
        wake * 1e3, abs=1e-6)
    # with the hold's head (0.103 ms), the arena's plan (0.035) and the
    # tail (0.197) the split covers the hold less its device seconds
    head = sum(p[2] for p in rec["phases"] if p[0] == "launch_head")
    plan = rec["phases"][5][2]
    tail = rec["lock"]["released"] - (sync[1] + sync[2])
    busy = sum(d for _m, _s, d in line)
    held = rec["lock"]["released"] - rec["lock"]["acquired"]
    assert head + plan + late + wake + tail == pytest.approx(
        held - busy, abs=1e-9)


def test_the_traced_idle_lies_inside_its_phase(recorded):
    """A phase the device was busy all through reads 0, one it never
    ran in reads the phase's length: never below 0, never above."""
    ctx = _ctx(recorded)
    (rec,) = ctx["traced_ledger"]
    h2c_start = _line(recorded)[0][1] - recorded["offset"]
    phases = [[name, h2c_start + 0.001 if name == "device_enqueue" else t0,
               secs] for name, t0, secs in rec["phases"]]
    busy = dict(ctx, traced_ledger=[dict(rec, phases=phases)])
    assert _read("device.launch_late_ms", busy) == pytest.approx(
        0.0, abs=1e-9)
    phases = [[name, t0 - 10.0 if name == "device_sync" else t0, secs]
              for name, t0, secs in rec["phases"]]
    idle = dict(ctx, traced_ledger=[dict(rec, phases=phases)])
    assert _read("device.sync_wake_ms", idle) == pytest.approx(
        rec["phases"][8][2] * 1e3)


@pytest.mark.parametrize("name", NAMES[:2])
def test_launch_head_is_the_median_of_its_pieces_summed(recorded, name):
    # 1.5 + 0.5, 2.0 + 0.6 and 2.0 + 0.6 ms
    assert _read(name, _ctx(recorded)) == pytest.approx(2.6, abs=1e-6)
    assert _read(name, _ctx(recorded, window=recorded["window_ledger"][:2])) \
        == pytest.approx(2.3, abs=1e-6)


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_on_the_parents_records(recorded, name):
    parent = _ctx(recorded, parent=True)
    assert all(rec["phases"] and rec["lock"]
               for rec in parent["window_ledger"] + parent["traced_ledger"])
    assert _read(name, parent) is None
    assert _read(name, dict(parent, window_ledger=[], traced_ledger=[],
                            reduced=None)) is None


def test_the_five_metrics_are_declared_with_their_cells():
    bench = cell.load_json(cell.ROOT, "BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    # one after the other, wherever later metrics are appended
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NAMES[0])
    assert names[first:first + len(NAMES)] == list(NAMES)
    saturate = by_name["guard.prep_wait_ms"]["workloads"]
    one_chip = ["backfill-unique.saturate", "mainnet-subnet-gossip.saturate"]
    want = {
        "guard.launch_head_ms": ("program_span", "guarded provider",
                                 "sigs_per_s", saturate),
        "guard.launch_head_ms.open_loop": (
            "program_span", "guarded provider", "verify_p50_ms",
            ["backfill-unique.poisson"]),
        "guard.hold_off_device_ms": ("program_span", "guarded provider",
                                     "sigs_per_s", saturate),
        "device.launch_late_ms": ("device_trace", "device", "sigs_per_s",
                                  one_chip),
        "device.sync_wake_ms": ("device_trace", "device", "sigs_per_s",
                                one_chip),
    }
    for name, (source, layer, moves, workloads) in want.items():
        metric = by_name[name]
        assert (metric["source"], metric["layer"], metric["moves"],
                metric["workloads"], metric["unit"], metric["better"]) \
            == (source, layer, moves, workloads, "ms", "lower")
