"""A dispatch's whole life in the program's own spans: the phase marks
of `infra/tracing.py` tile a dispatch from the service's hand-over to
its last settled future, across the event loop, the `to_thread` worker
and the breaker's dispatch thread, and land once in the stage
histogram, once in the batch's traces and once in the dispatch's ledger
record.

The device here is a fake that does what `ops/provider.py` does around
a dispatch (a host half that marks `host_prep`, a device half that
opens the ledger record and hands the real `_DispatchHandle` its
verdict arrays) and sleeps where the provider works, so the service,
the facade, the guard, the breaker, the handle and the ledger are the
real ones."""

import asyncio
import subprocess
import sys
import time

import numpy as np
import pytest

from teku_tpu.crypto import bls
from teku_tpu.crypto.bls import loader
from teku_tpu.crypto.bls.spi import PreparedDispatch
from teku_tpu.infra import clock, dispatchledger, timeline, tracing
from teku_tpu.infra.metrics import GLOBAL_REGISTRY, MetricsRegistry
from teku_tpu.infra.supervisor import CircuitBreaker
from teku_tpu.services.signatures import (
    AggregatingSignatureVerificationService)

SERVED = ["thread_hop", "prep_wait", "host_prep", "lock_wait",
          "launch_head", "device_enqueue", "device_sync", "return_hop",
          "settle"]
PK = b"\xa0" + bytes(47)


class _Prepared(PreparedDispatch):
    __slots__ = ("triples",)


# the device half's two programs: each is a launch of the dispatch
def stage_fake_first(seconds):
    time.sleep(seconds)


def stage_fake_second(seconds):
    time.sleep(seconds)


PROGRAMS = ["stage_fake_first", "stage_fake_second"]


class _Late:
    """Verdict lanes handed over `seconds` after the sync asks for them,
    as a real handle's sync waits out programs still on the device."""

    def __init__(self, lanes, seconds):
        self.lanes = lanes
        self.seconds = seconds

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.seconds)
        return self.lanes


class PhasedDevice:
    """Packs for `hold_s / 2` in its host half and launches two
    programs of `hold_s / 2` each in its device half (under the guard's
    lock), after its bookkeeping as the provider's `_launch` does it;
    its sync then waits `sync_s`.  A task whose message starts with
    b"bad" makes its batch false."""

    name = "phased-fake"

    def __init__(self, hold_s: float = 0.0, sync_s: float = 0.0):
        self.hold_s = hold_s
        self.sync_s = sync_s

    def prepare_dispatch(self, op, *args):
        tracing.current_marks().mark("host_prep")
        time.sleep(self.hold_s / 2)
        triples = args[0] if op == "batch_verify" else [args]
        prepared = _Prepared()
        prepared.triples = triples
        return prepared

    def launch_dispatch(self, prepared):
        from teku_tpu.ops.provider import _DispatchHandle
        triples = prepared.triples
        n = len(triples)
        marks = tracing.current_marks()
        marks.mark("launch_head")
        traces = tracing.current_traces()
        rec = dispatchledger.open_record(
            trace_ids=[t.trace_id for t in traces], shape=f"{n}x1",
            lanes=n, prep="outside_lock")
        for program in (stage_fake_first, stage_fake_second):
            tracing.launched(program.__name__, program, self.hold_s / 2)
        rec["compile"] = {"outcome": "cache_hit", "enqueue_s": 0.0}
        ok = not any(msg.startswith(b"bad") for _pks, msg, _sig in triples)
        lanes = np.ones(n, dtype=bool)
        return _DispatchHandle(
            np.bool_(ok), _Late(lanes, self.sync_s) if self.sync_s else lanes,
            n, traces,
            shape=f"{n}x1", path="vpu", t_enq_end=time.perf_counter(),
            rec=rec, marks=marks)

    def _run(self, op, *args):
        with tracing.dispatch_marks("host_prep"):
            return self.launch_dispatch(
                self.prepare_dispatch(op, *args)).result()

    def batch_verify(self, triples):
        return self._run("batch_verify", triples)

    def fast_aggregate_verify(self, pks, msg, sig):
        return self._run("fast_aggregate_verify", pks, msg, sig)


class AsyncPhasedDevice(PhasedDevice):
    """The async seam too, as the raw provider has it."""

    def begin_batch_verify(self, triples):
        handle = self.launch_dispatch(
            self.prepare_dispatch("batch_verify", triples))
        tracing.current_marks().mark("return_hop")
        return handle


@pytest.fixture(autouse=True)
def _reset():
    tracing.set_enabled(True)
    yield
    tracing.set_enabled(True)
    bls.reset_implementation()


def _guarded(device):
    breaker = CircuitBreaker(failure_threshold=2, deadline_s=5.0,
                             cooldown_s=0.2, name="phases",
                             registry=MetricsRegistry())
    return loader.GuardedBls12381(device, breaker,
                                  registry=MetricsRegistry())


def _serve(impl, messages, traced=True, **service_kw):
    """Queue one task a message in ONE event-loop turn; returns the
    verdicts, the tasks' traces and the ledger records of the run."""
    seq0 = dispatchledger.LEDGER.recorded_total
    traces = []

    async def main():
        bls.set_implementation(impl)
        svc = AggregatingSignatureVerificationService(
            registry=MetricsRegistry(), name="phases_svc", **service_kw)
        await svc.start()
        futs = []
        for msg in messages:
            tr = tracing.new_trace("phase_task") if traced else None
            traces.append(tr)
            with tracing.attach([tr]):
                futs.append(svc.verify([PK], msg, b"sig"))
        verdicts = await asyncio.gather(*futs)
        # a task's trace ends with its verdict: the service's stop and
        # the loop's teardown are not part of it (they read as a hole
        # after `settle`, 5 ms and more on a loaded machine)
        for tr in traces:
            tracing.finish(tr)
        await svc.stop()
        return verdicts

    verdicts = asyncio.run(main())
    records = [r for r in dispatchledger.LEDGER.snapshot()
               if r["seq"] > seq0]
    return verdicts, traces, records


def _assert_tiles(phases):
    """Consecutive, no gap, no overlap (to the µs the record rounds
    to)."""
    for (_n0, t0, secs), (_n1, t1, _s1) in zip(phases, phases[1:]):
        assert t0 + secs == pytest.approx(t1, abs=2.5e-6)
    assert all(secs >= 0 for _n, _t, secs in phases)


def _stage_counts():
    hist = GLOBAL_REGISTRY.labeled_histogram(
        "verify_stage_duration_seconds", labelnames=("stage",))
    return {s: hist.labels(stage=s).snapshot()[2] for s in SERVED}


# --------------------------------------------------------------------------
# (a) the served path: service -> guarded facade -> device
# --------------------------------------------------------------------------

def test_served_dispatch_phases_tile_in_order():
    before = _stage_counts()
    verdicts, traces, records = _serve(
        _guarded(PhasedDevice(0.02)), [b"m0", b"m1", b"m2"],
        num_workers=1)
    assert verdicts == [True] * 3
    assert len(records) == 1
    rec = records[0]
    assert [name for name, _t, _s in rec["phases"]] == SERVED
    _assert_tiles(rec["phases"])
    # the lock's edges lie inside the dispatch, around the device's work
    # alone: taken once the host half is done, at `lock_wait`'s end
    by_name = {name: (t0, secs) for name, t0, secs in rec["phases"]}
    acquired, released = rec["lock"]["acquired"], rec["lock"]["released"]
    assert sum(by_name["host_prep"]) <= by_name["lock_wait"][0] + 2.5e-6
    assert by_name["lock_wait"][0] <= acquired \
        <= by_name["device_enqueue"][0] + 1e-3
    assert sum(by_name["device_sync"]) <= released + 2.5e-6
    assert released - acquired >= 0.02
    assert rec["parent_seq"] is None
    # once through record_stage: one histogram sample a phase a
    # dispatch, and every trace of the batch holds the span at its t0
    after = _stage_counts()
    assert {s: after[s] - before[s] for s in SERVED} \
        == {s: 1 for s in SERVED}
    for tr in traces:
        spans = {stage: (t0, secs) for stage, t0, secs in tr.spans}
        for name, t0, secs in rec["phases"]:
            assert spans[name][0] == pytest.approx(t0, abs=1e-6)
            assert spans[name][1] == pytest.approx(secs, abs=1e-6)


def test_served_dispatch_stamps_each_launch_inside_device_enqueue():
    """Every program call of the device half is a launch of the record:
    in launch order, by the program's name, the first one at
    `device_enqueue`'s start and the last one ended inside it."""
    hold = 0.04
    _v, _t, (rec,) = _serve(_guarded(PhasedDevice(hold)), [b"m0", b"m1"],
                            num_workers=1)
    launches = rec["launches"]
    assert [program for program, _t0, _s in launches] == PROGRAMS
    by_name = {name: (t0, secs) for name, t0, secs in rec["phases"]}
    enq0, enq_s = by_name["device_enqueue"]
    assert launches[0][1] == enq0
    for (_p, t0, secs), (_q, t1, _s) in zip(launches, launches[1:]):
        assert t0 + secs <= t1 + 2.5e-6
    for _p, t0, secs in launches:
        assert enq0 <= t0 and t0 + secs <= enq0 + enq_s + 2.5e-6
        assert hold / 2 <= secs + 2.5e-6 < hold / 2 + 0.05


def test_launch_head_runs_from_the_lock_to_the_first_launch():
    """The hold begins with `launch_head`, at the very instant stamped
    as the lock's edge, and the first launch ends it: `lock_wait` is
    the wait alone."""
    hold = 0.1
    _v, _t, records = _serve(
        _guarded(PhasedDevice(hold)), [b"a0", b"a1", b"b0", b"b1"],
        num_workers=2, max_batch_size=2)
    assert len(records) == 2
    for rec in records:
        by_name = {name: (t0, secs) for name, t0, secs in rec["phases"]}
        head0, head_s = by_name["launch_head"]
        assert head0 == rec["lock"]["acquired"]
        assert sum(by_name["lock_wait"]) == pytest.approx(head0,
                                                          abs=2.5e-6)
        assert head0 + head_s == pytest.approx(rec["launches"][0][1],
                                               abs=2.5e-6)
        # the record and the bookkeeping: none of the programs' sleep
        assert head_s < hold / 2


def test_phases_with_the_launches_in_still_tile_to_the_rounding():
    """Many dispatches of two workers: every seam between consecutive
    phases is within the record's rounding (three values to the µs, so
    1.5 µs at worst)."""
    _v, _t, records = _serve(
        _guarded(PhasedDevice(0.004)), [b"m%d" % i for i in range(12)],
        num_workers=2, max_batch_size=2)
    assert len(records) == 6
    seams = [abs(t0 + secs - t1)
             for rec in records
             for (_n0, t0, secs), (_n1, t1, _s1)
             in zip(rec["phases"], rec["phases"][1:])]
    assert max(seams) <= 1.5e-6 + 1e-9
    for rec in records:
        assert [n for n, _t, _s in rec["phases"]] == SERVED
        assert [p for p, _t0, _s in rec["launches"]] == PROGRAMS


def test_span_tree_names_the_whole_dispatch():
    """`timeline.span_tree`, unchanged, shows the phases: `dispatch`
    holds the hop, the wait, the device's stages and the way back with
    no `unattributed` hole between them, and `settle` follows it."""
    _v, traces, _r = _serve(_guarded(PhasedDevice(0.02)), [b"m0", b"m1"],
                            num_workers=1)
    tree = timeline.span_tree(traces[0].to_dict())
    top = [c["phase"] for c in tree["children"]
           if c["phase"] != "unattributed"]
    assert top == ["queue_wait", "assembly", "dispatch", "settle"]
    dispatch = next(c for c in tree["children"]
                    if c["phase"] == "dispatch")
    assert [c["phase"] for c in dispatch["children"]
            if c["phase"] != "unattributed"] == SERVED[:-1]
    # what no phase covers is a preempted thread at a seam, if anything
    holes = [c["dur_ms"] for node in (tree, dispatch)
             for c in node["children"] if c["phase"] == "unattributed"]
    assert sum(holes) < 5.0


def test_second_worker_waits_out_the_first_ones_hold():
    """Two workers, two batches at once: they pack one after the
    other (`prep_wait`), the second once the first has launched, then
    the second one's `lock_wait` runs from its host half's end to the
    first one's release of the lock, while the first one's sync waits
    for the device."""
    hold = 0.12
    verdicts, _t, records = _serve(
        _guarded(PhasedDevice(0.02, sync_s=hold)),
        [b"a0", b"a1", b"b0", b"b1"], num_workers=2, max_batch_size=2)
    assert verdicts == [True] * 4
    assert len(records) == 2
    first, second = sorted(records, key=lambda r: r["lock"]["acquired"])
    for rec in records:
        assert [n for n, _t, _s in rec["phases"]] == SERVED
        _assert_tiles(rec["phases"])
    by_name = [dict((n, (t, s)) for n, t, s in r["phases"])
               for r in (first, second)]
    waits = [phases["lock_wait"][1] for phases in by_name]
    held = first["lock"]["released"] - first["lock"]["acquired"]
    assert held >= hold
    assert waits[0] < 0.03
    # the second one's host half ended, and its wait began, while the
    # first one still held the lock
    assert first["lock"]["acquired"] < by_name[1]["lock_wait"][0] \
        < first["lock"]["released"]
    assert waits[1] == pytest.approx(
        first["lock"]["released"] - by_name[1]["lock_wait"][0], abs=0.03)
    # nobody held the lock between the two for longer than a hand-over
    assert 0 <= second["lock"]["acquired"] - first["lock"]["released"] \
        < 0.03


class RecordingDevice(PhasedDevice):
    """Notes when each host half began and ended, on the marks'
    clock."""

    def __init__(self, hold_s):
        super().__init__(hold_s)
        self.preps = []

    def prepare_dispatch(self, op, *args):
        t_in = clock.mono()
        prepared = super().prepare_dispatch(op, *args)
        self.preps.append((t_in, clock.mono()))
        return prepared


def test_two_workers_pack_in_turn_and_say_how_long_they_waited():
    """Two workers drain a burst together: the host halves never
    overlap, the second one's `prep_wait` is the first one's packing
    and launches, its own `host_prep` is its packing alone, and both
    records still tile first mark to last."""
    hold = 0.4                       # a host half sleeps 0.2 s
    device = RecordingDevice(hold)
    verdicts, _t, records = _serve(
        _guarded(device), [b"a0", b"a1", b"b0", b"b1"],
        num_workers=2, max_batch_size=2)
    assert verdicts == [True] * 4 and len(records) == 2
    (in0, out0), (in1, out1) = sorted(device.preps)
    assert out0 <= in1                      # one at a time
    by_name = []
    for rec in records:
        assert [n for n, _t, _s in rec["phases"]] == SERVED
        _assert_tiles(rec["phases"])
        by_name.append({n: (t, s) for n, t, s in rec["phases"]})
    first, second = sorted(by_name, key=lambda ph: ph["host_prep"][0])
    # the first found the turn free; the second waited out the first's
    # packing and its launches, which end where its sync begins (both
    # began within the hand-over of each other)
    assert first["prep_wait"][1] < 0.05
    assert second["prep_wait"][1] == pytest.approx(
        sum(first["device_enqueue"]) - second["prep_wait"][0], abs=0.01)
    assert second["prep_wait"][1] > hold / 2 + hold - 0.1
    # `host_prep` is packing alone: the sleep, not the sleep twice over
    for phases in (first, second):
        assert hold / 2 <= phases["host_prep"][1] < hold / 2 + 0.1
    # the wait for the chip begins only after the turn is given back
    assert sum(second["host_prep"]) <= second["lock_wait"][0] + 2.5e-6


def test_span_tree_holds_prep_wait_and_no_new_hole():
    """A task served behind another worker's packing: its span tree
    names the wait (`prep_wait`, a child of `dispatch`, of the length
    the ledger's phase has), and what no span covers stays what it
    was: seams, not the wait."""
    hold = 0.2
    _v, traces, records = _serve(
        _guarded(PhasedDevice(hold)), [b"a0", b"a1", b"b0", b"b1"],
        num_workers=2, max_batch_size=2)
    waited = max(records, key=lambda r: dict(
        (n, s) for n, _t, s in r["phases"])["prep_wait"])
    wait_s = dict((n, s) for n, _t, s in waited["phases"])["prep_wait"]
    assert wait_s > hold / 2 - 0.08
    trace = next(t for t in traces if t.trace_id in waited["trace_ids"])
    tree = timeline.span_tree(trace.to_dict())
    dispatch = next(c for c in tree["children"]
                    if c["phase"] == "dispatch")
    named = [c for c in dispatch["children"]
             if c["phase"] != "unattributed"]
    assert [c["phase"] for c in named] == SERVED[:-1]
    node = next(c for c in named if c["phase"] == "prep_wait")
    assert node["dur_ms"] == pytest.approx(wait_s * 1e3, abs=0.01)
    holes = [c["dur_ms"] for n in (tree, dispatch)
             for c in n["children"] if c["phase"] == "unattributed"]
    assert sum(holes) < 5.0


def test_lock_is_taken_after_host_prep_never_over_it():
    """Whatever the interleaving of two workers' dispatches: no
    dispatch holds the device-entry lock during its `host_prep`, and
    the record says where its prep ran."""
    _v, _t, records = _serve(
        _guarded(PhasedDevice(0.04)), [b"m%d" % i for i in range(8)],
        num_workers=2, max_batch_size=2)
    assert len(records) == 4
    for rec in records:
        for name, t0, secs in rec["phases"]:
            if name == "host_prep":
                assert t0 + secs <= rec["lock"]["acquired"] + 2.5e-6
        assert rec["prep"] == "outside_lock"
    # the holds do not overlap: the lock still serialises the device
    holds = sorted((r["lock"]["acquired"], r["lock"]["released"])
                   for r in records)
    for (_a0, r0), (a1, _r1) in zip(holds, holds[1:]):
        assert r0 <= a1 + 2.5e-6


def test_profiler_annotations_cover_the_single_thread_phases(monkeypatch):
    entered, exited = [], []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __exit__(self, *exc):
            exited.append(self.name)

    def annotation(name):
        entered.append(name)
        return Ann(name)

    monkeypatch.setattr(tracing, "_annotation", annotation)
    _serve(_guarded(PhasedDevice()), [b"m0"], num_workers=1)
    want = ["prep_wait", "host_prep", "lock_wait", "launch_head",
            "device_enqueue", "device_sync", "settle"]
    assert entered == want and exited == want


def test_tracing_imports_and_marks_without_jax():
    code = (
        "import sys\n"
        "from teku_tpu.infra import tracing\n"
        "m = tracing.new_marks()\n"
        "m.mark('host_prep'); m.mark('lock_wait'); m.close()\n"
        "assert [p[0] for p in m.phases] == ['host_prep', 'lock_wait']\n"
        "assert 'jax' not in sys.modules\n")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_real_annotation_is_a_profiler_trace_annotation():
    import jax
    ann = tracing._annotation("host_prep")
    assert isinstance(ann, jax.profiler.TraceAnnotation)
    ann.__exit__(None, None, None)


# --------------------------------------------------------------------------
# (b) tracing off leaves nothing behind
# --------------------------------------------------------------------------

def test_disabled_tracing_leaves_no_marks_no_phases():
    tracing.set_enabled(False)
    assert tracing.new_marks() is tracing.current_marks()
    assert not tracing.current_marks()
    assert tracing.span("lock_wait") is tracing.span("settle")
    with tracing.dispatch_marks("host_prep") as marks:
        assert marks is tracing.new_marks()
        assert tracing.current_marks() is marks
    before = _stage_counts()
    verdicts, _t, records = _serve(
        _guarded(PhasedDevice()), [b"m0", b"m1"], traced=False,
        num_workers=1)
    assert verdicts == [True, True]
    assert len(records) == 1
    assert not {"phases", "lock", "parent_seq"} & set(records[0])
    assert records[0]["verdict"] is True
    assert _stage_counts() == before


def test_disabled_tracing_stamps_no_launch():
    """With tracing off a launch is the program call alone: no marks to
    stamp, no `launches` on the record."""
    tracing.set_enabled(False)
    calls = []
    assert tracing.launched("stage_fake_first", calls.append, 7) is None
    assert calls == [7]
    assert tracing.current_marks().launches == ()
    _v, _t, (rec,) = _serve(_guarded(PhasedDevice(0.01)), [b"m0"],
                            traced=False, num_workers=1)
    assert "launches" not in rec and rec["verdict"] is True


# --------------------------------------------------------------------------
# (c) a failed batch's bisection points at its parent
# --------------------------------------------------------------------------

def test_bisect_dispatches_carry_parent_seq():
    verdicts, _t, records = _serve(
        _guarded(PhasedDevice()), [b"g0", b"g1", b"g2", b"bad"],
        num_workers=1, split_threshold=2)
    assert verdicts == [True, True, True, False]
    # the whole, its two halves, the bad half's two single tasks
    lanes = [r["lanes"] for r in records]
    assert lanes == [4, 2, 2, 1, 1]
    whole, good_half, bad_half, single_good, single_bad = records
    assert whole["parent_seq"] is None
    assert good_half["parent_seq"] == bad_half["parent_seq"] \
        == whole["seq"]
    assert single_good["parent_seq"] == single_bad["parent_seq"] \
        == bad_half["seq"]
    # a failed batch settles nothing: its marks close where they stand
    for rec in (whole, bad_half):
        assert rec["verdict"] is False
        assert [n for n, _t, _s in rec["phases"]] == SERVED
        _assert_tiles(rec["phases"])
    # a single task (the facade sends it through
    # `_guarded("fast_aggregate_verify")`) gets the same phases, and a
    # false single IS settled
    for rec in (single_good, single_bad):
        assert [n for n, _t, _s in rec["phases"]] == SERVED
        _assert_tiles(rec["phases"])


# --------------------------------------------------------------------------
# the async seam, and a direct caller
# --------------------------------------------------------------------------

def test_async_seam_marks_both_hand_overs():
    """On the raw provider's async seam a dispatch crosses to a thread
    twice (begin, then the sync); `device_sync` stays the blocking wait
    alone."""
    verdicts, _t, records = _serve(
        AsyncPhasedDevice(0.02), [b"m0", b"m1"], num_workers=1,
        overlap=True)
    assert verdicts == [True, True]
    assert len(records) == 1
    names = [n for n, _t, _s in records[0]["phases"]]
    assert names == ["thread_hop", "host_prep", "launch_head",
                     "device_enqueue", "return_hop", "thread_hop",
                     "device_sync", "return_hop", "settle"]
    _assert_tiles(records[0]["phases"])
    assert records[0]["lock"] == {}
    sync = dict((n, s) for n, _t, s in records[0]["phases"])["device_sync"]
    assert sync < 0.01


def test_direct_caller_keeps_the_providers_phases_only():
    """No service (a warm-up, block import): the provider's own scope
    opens and closes the marks."""
    seq0 = dispatchledger.LEDGER.recorded_total
    with tracing.trace("direct") as tr:
        assert PhasedDevice().batch_verify([([PK], b"m", b"sig")]) is True
    assert not tracing.current_marks()
    rec = [r for r in dispatchledger.LEDGER.snapshot()
           if r["seq"] > seq0][-1]
    names = [n for n, _t, _s in rec["phases"]]
    assert names == ["host_prep", "launch_head", "device_enqueue",
                     "device_sync", "return_hop"]
    _assert_tiles(rec["phases"])
    assert rec["lock"] == {} and rec["parent_seq"] is None
    assert [s for s, _d in tr.stages] == names


def test_a_raising_dispatch_leaves_the_phases_it_reached():
    """An unguarded provider that raises fails the batch's futures; the
    traces still say how far the dispatch came."""
    class Raising(PhasedDevice):
        def batch_verify(self, triples):
            tracing.current_marks().mark("host_prep")
            raise RuntimeError("device fault")

    traces = []

    async def main():
        bls.set_implementation(Raising())
        svc = AggregatingSignatureVerificationService(
            num_workers=1, registry=MetricsRegistry(), name="raise_svc")
        await svc.start()
        futs = []
        for msg in (b"m0", b"m1"):
            traces.append(tracing.new_trace("phase_task"))
            with tracing.attach(traces[-1:]):
                futs.append(svc.verify([PK], msg, b"sig"))
        out = await asyncio.gather(*futs, return_exceptions=True)
        await svc.stop()
        return out

    out = asyncio.run(main())
    assert all(isinstance(e, RuntimeError) for e in out)
    for tr in traces:
        assert [s for s, _d in tr.stages][-3:] == ["dispatch",
                                                   "thread_hop",
                                                   "host_prep"]


def test_oracle_fallback_is_a_phase_of_the_dispatch():
    """Breaker open: the oracle serves, and the dispatch's life is
    still named end to end (no device record to complete)."""
    guarded = _guarded(PhasedDevice())
    guarded.breaker.record_failure()
    guarded.breaker.record_failure()
    assert guarded.serving == "oracle"

    class Oracle:
        def batch_verify(self, triples):
            return True

    guarded.oracle = Oracle()
    verdicts, traces, records = _serve(guarded, [b"m0", b"m1"],
                                       num_workers=1)
    assert verdicts == [True, True] and records == []
    stages = [s for s, _d in traces[0].stages]
    assert stages[:2] == ["queue_wait", "assembly"]
    assert set(stages[2:]) == {"thread_hop", "oracle_execute", "dispatch",
                               "settle"}


# --------------------------------------------------------------------------
# (e) vocabulary
# --------------------------------------------------------------------------

def test_prep_wait_is_a_stage_of_the_histogram_and_an_annotation():
    """The phase is in the closed stage vocabulary, in the
    single-thread set (it begins and ends on the breaker's dispatch
    thread) and, once a dispatch was served, a label of
    `verify_stage_duration_seconds`; every label of that family is a
    declared stage."""
    assert "prep_wait" in tracing.STAGES
    assert tracing.STAGES.index("thread_hop") \
        < tracing.STAGES.index("prep_wait") \
        < tracing.STAGES.index("host_prep") \
        < tracing.STAGES.index("lock_wait")
    assert "prep_wait" in tracing._ANNOTATED
    _serve(_guarded(PhasedDevice()), [b"m0"], num_workers=1)
    hist = GLOBAL_REGISTRY.labeled_histogram(
        "verify_stage_duration_seconds", labelnames=("stage",))
    labels = {key[0] for key, _child in hist._items()}
    assert "prep_wait" in labels
    assert labels <= set(tracing.STAGES), labels - set(tracing.STAGES)


def test_launch_head_is_a_stage_of_the_histogram_and_an_annotation():
    """`launch_head` begins and ends on the breaker's dispatch thread:
    one more stage of the closed vocabulary, between `lock_wait` and
    `device_enqueue`, one more annotation, and a label of
    `verify_stage_duration_seconds` once a dispatch was served."""
    assert tracing.STAGES.index("lock_wait") \
        < tracing.STAGES.index("launch_head") \
        < tracing.STAGES.index("device_enqueue")
    assert "launch_head" in tracing._ANNOTATED
    before = _stage_counts()["launch_head"]
    _serve(_guarded(PhasedDevice()), [b"m0"], num_workers=1)
    assert _stage_counts()["launch_head"] == before + 1
    assert len(tracing.STAGES) == 15


def test_new_stage_names_are_declared_and_no_timeline_phase_is_new():
    assert set(SERVED) | {"oracle_execute", "dispatch", "complete",
                          "queue_wait", "assembly",
                          "warmup"} == set(tracing.STAGES)
    assert tracing._ANNOTATED <= set(tracing.STAGES)
    # hops cross threads: never a profiler annotation
    assert not {"thread_hop", "return_hop"} & tracing._ANNOTATED
    # the marks emit through tracing alone: a served dispatch puts on
    # the timeline's ring what it did before, the device's busy
    # interval and the queue's, and none of the new names
    since = timeline.RING.mark()
    _serve(_guarded(PhasedDevice()), [b"m0", b"m1"], num_workers=1)
    emitted = {(e["track"], e["phase"])
               for e in timeline.RING.snapshot(since_seq=since)}
    assert emitted == {("device", "busy"), ("worker", "queue_nonempty")}
    assert not (set(SERVED) - {"host_prep"}) & set(timeline.PHASES)
