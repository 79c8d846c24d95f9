"""Scenario driver: replay a traffic model against the REAL pipeline.

Like ``services/overload_sim.py`` (whose virtual-clock technique this
extends), the control plane under test is PRODUCTION CODE, unmodified:
the real ``AggregatingSignatureVerificationService`` (priority queue,
coalescing, bisect, flush deadlines) and the real
``AdmissionController`` (adaptive batching, brownout) with the real
``CapacityTelemetry`` — all on one injected virtual clock, so a
scenario replays deterministically in milliseconds of wall time.

What stands in for hardware is the DEVICE MODEL, and it is
dedup-AWARE: a dispatch costs
``overhead + padded_unique_messages * h2c_cost + padded_lanes *
lane_cost`` virtual seconds — the cost model PERF.md measured for the
unique-message pipeline — so committee-duplicated traffic is genuinely
cheaper per lane than a dup-collapse flood, and the capacity model
sees exactly the shape-dependent latency it sees in production.  A
triple whose signature carries ``INVALID_SIG_PREFIX`` fails its whole
batch, which forces the service's real bisect path.  Blob-batch events
dispatch through ``crypto/kzg.py``'s REAL facade with a model backend
installed, so the ``source="kzg"`` arrival accounting and the guarded
fallback seams are the production code paths.

Per-scenario evidence (the ``cli loadgen`` report and bench's
``mainnet`` phase): sigs/sec, per-class p50/p99 and shed counts,
dedup ratio, coalesced/bisect counts, and every brownout transition.

CHAOS scenarios (``Scenario.mesh_devices`` + a ``chaos`` schedule)
route the model through the REAL supervisor machinery —
``GuardedBls12381`` + breaker + ``parallel/selfheal.MeshHealer`` over
a model mesh — and arm timed device-keyed ``bls.mesh_shard`` faults
mid-run, so eject/reshape/readmit runs under traffic and the report
carries the full recovery evidence (``rep["chaos"]``).

VIRTUAL-CLOCK DISCIPLINE: the driver advances the clock ONLY while
the service is quiescent at the thread boundary
(``svc.inflight_dispatches == 0``).  Advancing while a dispatch
crossed into ``asyncio.to_thread`` charged GIL-scheduling wall time
to virtual latency — on a 1-core box each thread handoff costs a
~5 ms GIL switch interval of driver spinning, which at 20 ms of
virtual time per spin inflated the r10/r11 block-import p50 to
~3.6 s.  With the gate, virtual latency is queue wait + modeled
device time on any host.
"""

import asyncio
import time
from typing import (Callable, Dict, List, Optional, Sequence, Tuple,
                    Union)

from ..crypto import bls, kzg
from ..crypto.bls.loader import GuardedBls12381
from ..infra import capacity as capacity_mod
from ..infra import faults, flightrecorder, timeline
from ..infra.metrics import GLOBAL_REGISTRY, MetricsRegistry
from ..infra.supervisor import CircuitBreaker
from ..parallel import selfheal
from ..services.admission import AdmissionController, VerifyClass
from ..services.overload_sim import VirtualClock, _next_pow2
from ..services.signatures import (AggregatingSignatureVerificationService,
                                   ServiceCapacityExceededError)
from . import model as model_mod
from . import scenarios as scenarios_mod
from .model import INVALID_SIG_PREFIX, generate_events
from .scenarios import Scenario

# process-global loadgen evidence (closed label vocabularies: scenario
# names from the registry, kinds from the model, classes from the enum)
_M_EVENTS = GLOBAL_REGISTRY.labeled_counter(
    "loadgen_events_total",
    "traffic-model events replayed, by scenario and event kind",
    labelnames=("scenario", "kind"))
_M_SHEDS = GLOBAL_REGISTRY.labeled_counter(
    "loadgen_sheds_total",
    "loadgen submissions shed by the service, by scenario and class",
    labelnames=("scenario", "class"))
_M_DEDUP = GLOBAL_REGISTRY.labeled_gauge(
    "loadgen_dedup_ratio",
    "measured lane-duplication ratio of the last run per scenario "
    "(1 - unique messages / lanes at the device)",
    labelnames=("scenario",))


class DedupAwareDevice:
    """Model BLS implementation on the virtual clock with the
    unique-message cost model; verdicts honor the invalid-signature
    marker so failed batches exercise the real bisect recursion."""

    def __init__(self, clock: VirtualClock,
                 telemetry: capacity_mod.CapacityTelemetry,
                 lane_sigs_per_sec: float = 3000.0,
                 h2c_msgs_per_sec: float = 1500.0,
                 overhead_s: float = 0.002, min_pad: int = 8,
                 completed_at: Optional[Dict[tuple, float]] = None):
        self.clock = clock
        self.telemetry = telemetry
        self.lane_s = 1.0 / lane_sigs_per_sec
        self.h2c_s = 1.0 / h2c_msgs_per_sec
        self.overhead_s = overhead_s
        self.min_pad = min_pad
        self.dispatches = 0
        self.lanes_total = 0
        self.unique_total = 0
        # shareable across backends: the chaos scenario swaps model
        # backends mid-run (eject/reshape) and the latency stamps must
        # land in ONE dict the driver's callbacks read
        self.completed_at: Dict[tuple, float] = (
            completed_at if completed_at is not None else {})

    def batch_verify(self, triples) -> bool:
        n = len(triples)
        uniques = len({msg for _pks, msg, _sig in triples})
        padded = max(_next_pow2(n), self.min_pad)
        padded_u = max(_next_pow2(uniques), 1)
        dt = (self.overhead_s + padded_u * self.h2c_s
              + padded * self.lane_s)
        t0 = self.clock()
        self.clock.advance(dt)
        self.telemetry.record_dispatch(f"{padded}x1", "sim", n, t0,
                                       self.clock())
        self.dispatches += 1
        self.lanes_total += n
        self.unique_total += uniques
        ok = True
        now = self.clock()
        for _pks, msg, sig in triples:
            self.completed_at[(msg, sig)] = now
            if sig.startswith(INVALID_SIG_PREFIX):
                ok = False
        return ok

    def fast_aggregate_verify(self, pks, msg, sig) -> bool:
        return self.batch_verify([(pks, msg, sig)])

    def dedup_ratio(self) -> float:
        if not self.lanes_total:
            return 0.0
        return 1.0 - self.unique_total / self.lanes_total


class MeshModelDevice(DedupAwareDevice):
    """Model MESH: the dedup-aware cost model scaled by the live
    device subset (losing a chip costs 1/N of throughput), with every
    dispatch passing the REAL ``bls.mesh_shard`` fault site keyed by
    the live device names — the production seam the chaos schedule
    arms, so a keyed wedge fails the collective exactly while the
    sick device is in the live set and stops once it is ejected."""

    def __init__(self, clock: VirtualClock,
                 telemetry: capacity_mod.CapacityTelemetry,
                 live: Sequence[int], total: int,
                 lane_sigs_per_sec: float, h2c_msgs_per_sec: float,
                 completed_at: Optional[Dict[tuple, float]] = None):
        frac = len(live) / max(total, 1)
        super().__init__(clock, telemetry,
                         lane_sigs_per_sec=lane_sigs_per_sec * frac,
                         h2c_msgs_per_sec=h2c_msgs_per_sec * frac,
                         completed_at=completed_at)
        self.live_names = tuple(f"vdev{i}" for i in live)
        self.mesh_info = {"devices": list(self.live_names),
                          "n_devices": len(live), "axis": "dp"}

    def batch_verify(self, triples) -> bool:
        faults.check(selfheal.FAULT_SITE, keys=self.live_names)
        return super().batch_verify(triples)


def _settle(done: Callable[[], bool], what: str,
            timeout_s: float = 5.0) -> None:
    """Wait, in REAL time and with the virtual clock standing, for the
    healer's threads to finish a step the model counts as instant.
    The healer runs on the wall clock (its probes are a fault-site
    check each, its reprobe interval 50 ms) while the traffic runs on
    the virtual one; left to race, the number of dispatches that meet
    the wedged mesh, and the virtual instant the mesh grows back, go
    by how fast this machine schedules those threads, and every count
    of the report moves with the load on the box."""
    deadline = time.monotonic() + timeout_s
    while not done():
        if time.monotonic() > deadline:
            raise RuntimeError(f"loadgen chaos: {what} did not finish "
                               f"within {timeout_s:.0f}s of wall time")
        time.sleep(0.001)


class _FallbackOracle(DedupAwareDevice):
    """The oracle a failed mesh dispatch falls to mid-heal.  Its
    modeled cost is twenty times the mesh's, a heal sweep's a few
    fault-site checks: the sweep is over when the oracle answers, so
    exactly the dispatches the fault schedule fails pay the cliff."""

    healer = None

    def batch_verify(self, triples) -> bool:
        ok = super().batch_verify(triples)
        if self.healer is not None:
            _settle(lambda: not self.healer.healing, "the heal sweep")
        return ok


class ModelKzgBackend:
    """Stand-in KZG device: one virtual-time dispatch per blob batch,
    fed through the REAL ``crypto/kzg.py`` facade so its arrival
    accounting and guarded-fallback seams are exercised."""

    name = "loadgen-model"

    def __init__(self, clock: VirtualClock,
                 telemetry: capacity_mod.CapacityTelemetry,
                 blob_s: float = 0.004, overhead_s: float = 0.002):
        self.clock = clock
        self.telemetry = telemetry
        self.blob_s = blob_s
        self.overhead_s = overhead_s
        self.batches = 0
        self.blobs = 0

    def verify_blob_kzg_proof_batch(self, blobs, commitments, proofs,
                                    setup) -> bool:
        n = len(blobs)
        t0 = self.clock()
        self.clock.advance(self.overhead_s + n * self.blob_s)
        self.telemetry.record_dispatch(f"kzg{_next_pow2(n)}", "sim",
                                       n, t0, self.clock())
        self.batches += 1
        self.blobs += n
        return True


def _percentiles(lats: List[float]) -> Tuple[float, float]:
    if not lats:
        return 0.0, 0.0
    ordered = sorted(lats)

    def pct(q):
        return ordered[min(len(ordered) - 1,
                           int(q * len(ordered)))] * 1e3
    return round(pct(0.50), 3), round(pct(0.99), 3)


async def _run_scenario(scenario: Scenario, seed: int, slots: int,
                        validators: Optional[int]) -> dict:
    model = scenario.model
    if validators is not None:
        model = model.with_overrides(validators=validators)
    events = generate_events(model, seed=seed, slots=slots)
    stats = model_mod.stream_stats(events)

    clock = VirtualClock()
    registry = MetricsRegistry()
    recorder = flightrecorder.FlightRecorder(capacity=2048,
                                             registry=registry)
    telemetry = capacity_mod.CapacityTelemetry(
        registry=registry, window_s=2.5, clock=clock, recorder=recorder)
    # dedup-aware device scaled so the scenario's offered rate is a
    # meaningful fraction of capacity (storms overload, steady holds)
    base_lane = scenario.capacity_sigs_per_sec * 2
    base_h2c = scenario.capacity_sigs_per_sec
    completed_at: Dict[tuple, float] = {}
    backends: List[DedupAwareDevice] = []
    guarded = healer = breaker = None
    if scenario.mesh_devices:
        # chaos wiring: the model mesh behind the REAL supervisor
        # machinery — GuardedBls12381 (oracle-model fallback, breaker)
        # + parallel/selfheal.MeshHealer — so a timed bls.mesh_shard
        # wedge exercises production eject/reshape/readmit, measured
        # under traffic
        total = scenario.mesh_devices

        def make_backend(live):
            if not live:
                return None
            be = MeshModelDevice(clock, telemetry, live, total,
                                 base_lane, base_h2c,
                                 completed_at=completed_at)
            backends.append(be)
            return be

        device = make_backend(tuple(range(total)))
        # the last-resort cliff a wedged dispatch falls to mid-heal:
        # same verdict rule, oracle (~CPU) speed — the very cliff
        # self-healing exists to avoid paying for the whole mesh
        oracle = _FallbackOracle(
            clock, telemetry, lane_sigs_per_sec=base_lane / 20,
            h2c_msgs_per_sec=base_h2c / 20, completed_at=completed_at)
        breaker = CircuitBreaker(
            failure_threshold=6, deadline_s=5.0, cooldown_s=0.5,
            name="loadgen_mesh", registry=registry)
        guarded = GuardedBls12381(device, breaker, oracle=oracle,
                                  registry=registry)

        def heal_install(be, live, epoch):
            if be is None:
                return        # zero healthy: oracle stays last resort
            guarded.swap_device(be)
            # production wiring parity (loader.make_mesh_healer): the
            # reshaped backend is known-good, so serving resumes now
            breaker.record_success()

        healer = selfheal.MeshHealer(
            [f"vdev{i}" for i in range(total)],
            probe=lambda i: faults.check(selfheal.FAULT_SITE,
                                         keys=(f"vdev{i}",)),
            make_backend=make_backend, install=heal_install,
            trip_threshold=1, probe_deadline_s=1.0, reprobe_s=0.05,
            registry=registry, recorder=recorder)
        guarded.healer = oracle.healer = healer
        impl = guarded
    else:
        device = DedupAwareDevice(
            clock, telemetry, lane_sigs_per_sec=base_lane,
            h2c_msgs_per_sec=base_h2c, completed_at=completed_at)
        backends.append(device)
        impl = device
    kzg_backend = ModelKzgBackend(clock, telemetry)
    controller = AdmissionController(
        telemetry=telemetry, min_bucket=8, max_batch=256,
        slo_p50_s=0.1, tick_s=0.02, hold_ticks=25, clock=clock,
        registry=registry, recorder=recorder,
        name=f"loadgen_{scenario.name}")
    svc = AggregatingSignatureVerificationService(
        num_workers=1, queue_capacity=4000, max_batch_size=256,
        registry=registry, name="loadgen", overlap=False,
        controller=controller, telemetry=telemetry, recorder=recorder,
        clock=clock)

    submitted: Dict[str, int] = {c.label: 0 for c in VerifyClass}
    sheds: Dict[str, int] = {c.label: 0 for c in VerifyClass}
    pending: List[tuple] = []      # (event, future)
    by_class: Dict[str, List[float]] = {}
    kzg_setup = kzg.TrustedSetup(g1_lagrange=None,
                                 g2_monomial=[None, None])

    def observe_latency(fut, key, t_sub, cls_label):
        """Resolution-time latency capture: reading the device stamp
        when THIS future settles, not after the whole run — a later
        re-delivery of the same triple re-dispatches and would
        overwrite the stamp, inflating every earlier submission."""
        def _cb(f):
            if f.cancelled() or f.exception() is not None:
                return
            done_at = completed_at.get(key)
            if done_at is not None:
                by_class.setdefault(cls_label, []).append(
                    done_at - t_sub)
        fut.add_done_callback(_cb)

    t_start = clock()
    horizon = t_start + slots * model_mod.SECONDS_PER_SLOT
    # the PER-DISPATCH real-time bound: virtual progress is gated on
    # service quiescence below, so a genuinely wedged dispatch must
    # fail the harness by wall clock, not hang it.  PROGRESS-BASED —
    # reset whenever the service goes quiescent — so a long healthy
    # run (many slots, slow box) can never trip it cumulatively
    wall_stall_s = 120.0
    wall_deadline = time.monotonic() + wall_stall_s
    chaos = sorted(scenario.chaos, key=lambda c: c.t)
    chaos_idx = 0
    chaos_log: List[dict] = []

    def fire_chaos():
        """Arm/clear the schedule's faults as virtual time reaches
        them — the timed bls.mesh_shard wedge mid-steady-state."""
        nonlocal chaos_idx
        while chaos_idx < len(chaos) \
                and clock() - t_start >= chaos[chaos_idx].t:
            ce = chaos[chaos_idx]
            chaos_idx += 1
            if ce.action == "wedge":
                faults.inject(selfheal.FAULT_SITE, faults.Raise(
                    RuntimeError(f"chaos: vdev{ce.device} wedged"),
                    times=ce.times, key=f"vdev{ce.device}"))
            else:
                faults.clear(selfheal.FAULT_SITE)
                # re-admission is one reprobe interval away: instant
                # on the scenario's scale, so the mesh is whole again
                # before virtual time moves on
                _settle(lambda: not healer.ledger.ejected()
                        and len(healer.live_devices)
                        == scenario.mesh_devices, "the mesh's regrowth")
            chaos_log.append({"t": round(clock() - t_start, 3),
                              "action": ce.action,
                              "device": ce.device})

    async def park_for_dispatch():
        """A dispatch is crossing the thread boundary: hold the
        VIRTUAL clock and park in a real sleep so the executor thread
        gets the GIL immediately.  Spinning sleep(0) here while
        advancing the clock was the r10/r11 block-import p50
        inflation: on a 1-core box the driver keeps the GIL for the
        full switch interval (~5 ms) per thread handoff, and every
        spin charged idle_tick VIRTUAL seconds to whatever was in
        flight — ~3.6 s p50 from pure scheduler wall time.  Holding
        the clock makes virtual latency what the model says it is
        (queue wait + modeled device time), on any core count."""
        if time.monotonic() > wall_deadline:
            raise RuntimeError(
                f"loadgen made no dispatch progress for "
                f"{wall_stall_s:.0f}s of wall time (wedged executor "
                "thread?)")
        await asyncio.sleep(0.0005)

    def note_progress():
        nonlocal wall_deadline
        wall_deadline = time.monotonic() + wall_stall_s

    bls.set_implementation(impl)
    kzg_prev_backend = kzg.get_backend()
    kzg.set_backend(kzg_backend)
    telemetry_prev = capacity_mod.swap_default(telemetry)
    # causal-timeline window: ring events are stamped on the REAL
    # monotonic clock even while scenario time is virtual, so the
    # attribution below reads real-wall overlap (model backends emit
    # no device-busy events — those metrics honestly come back
    # None/zero, the skip-if-missing contract)
    ring_mark = timeline.RING.mark()
    t_real0 = time.perf_counter()
    try:
        await svc.start()
        idx = 0
        idle_tick = 0.02
        while True:
            fire_chaos()
            if idx < len(events):
                ev = events[idx]
                t_ev = t_start + ev.t
                if clock() < t_ev:
                    if svc.inflight_dispatches:
                        await park_for_dispatch()
                        continue
                    note_progress()
                    # advance to the next arrival (bounded tick so the
                    # controller and flush deadlines stay live)
                    clock.advance(min(t_ev - clock(), idle_tick))
                    await asyncio.sleep(0)
                    continue
                idx += 1
                _M_EVENTS.labels(scenario=scenario.name,
                                 kind=ev.kind).inc()
                if ev.kind == "blob_batch":
                    # through the REAL kzg facade: arrival accounting
                    # (source="kzg") + the installed model backend
                    kzg.verify_blob_kzg_proof_batch(
                        [b"blob"] * ev.blobs, [b"c"] * ev.blobs,
                        [b"p"] * ev.blobs, kzg_setup)
                    continue
                submitted[ev.cls.label] += 1
                t_sub = clock()
                try:
                    if len(ev.triples) == 1:
                        pks, msg, sig = ev.triples[0]
                        fut = svc.verify(pks, msg, sig, cls=ev.cls,
                                         source=ev.source)
                        key = (msg, sig)
                    else:
                        fut = svc.verify_multi(list(ev.triples),
                                               cls=ev.cls,
                                               source=ev.source)
                        key = (ev.triples[0][1], ev.triples[0][2])
                except ServiceCapacityExceededError:
                    sheds[ev.cls.label] += 1
                    _M_SHEDS.labels(scenario=scenario.name,
                                    **{"class": ev.cls.label}).inc()
                    continue
                observe_latency(fut, key, t_sub, ev.cls.label)
                pending.append((ev, fut))
                await asyncio.sleep(0)
                continue
            # stream exhausted: drain the queue in virtual time (the
            # horizon guard bounds the drain — a wedged future must
            # fail the run loudly, not hang the harness)
            if svc._queue.qsize() == 0 and all(
                    f.done() for _, f in pending):
                break
            if clock() >= horizon + 120:
                raise RuntimeError(
                    "loadgen drain did not settle within the virtual "
                    "horizon (wedged task?)")
            if svc.inflight_dispatches:
                await park_for_dispatch()
                continue
            note_progress()
            clock.advance(idle_tick)
            await asyncio.sleep(0)

        # throughput window ends when the load drains — the brownout
        # cool-down below advances the clock further and must not
        # dilute sigs/sec on exactly the scenarios that browned out
        duration = clock() - t_start
        completed = 0
        failed_verdicts = 0
        for ev, fut in pending:
            try:
                ok = await fut
            except ServiceCapacityExceededError:
                sheds[ev.cls.label] += 1
                _M_SHEDS.labels(scenario=scenario.name,
                                **{"class": ev.cls.label}).inc()
                continue
            if ok:
                completed += len(ev.triples)
            else:
                failed_verdicts += 1
        # cool down through the brownout exit hysteresis so the report
        # shows the full enter→exit episode
        for _ in range(controller.hold_ticks + 20):
            if controller.brownout_level == 0:
                break
            clock.advance(max(telemetry.window_s / 4,
                              controller.tick_s))
            controller.tick()
        await svc.stop()
    finally:
        if scenario.chaos:
            faults.clear(selfheal.FAULT_SITE)
        if healer is not None:
            healer.close()
        capacity_mod.swap_default(telemetry_prev)
        kzg.set_backend(kzg_prev_backend)
        bls.reset_implementation()

    t_real1 = time.perf_counter()
    attribution = timeline.attribution(
        timeline.RING.snapshot(since_seq=ring_mark), t_real0, t_real1)

    # aggregate device evidence across every backend that served (the
    # chaos scenario swaps model backends on eject/readmit; counting
    # only the last would hide the wedge-window work)
    dev_dispatches = sum(b.dispatches for b in backends)
    dev_lanes = sum(b.lanes_total for b in backends)
    dev_unique = sum(b.unique_total for b in backends)
    dedup_ratio = (1.0 - dev_unique / dev_lanes) if dev_lanes else 0.0

    all_lats = [lat for ls in by_class.values() for lat in ls]
    p50, p99 = _percentiles(all_lats)
    per_class = {}
    for c in VerifyClass:
        ls = by_class.get(c.label, [])
        c50, c99 = _percentiles(ls)
        per_class[c.label] = {
            "submitted": submitted[c.label],
            "completed": len(ls),
            "shed": sheds[c.label],
            "p50_ms": c50, "p99_ms": c99}
    dispatch_counter = registry.metrics()["loadgen_dispatch_total"]
    dispatches = {kind: int(child.value) for (kind,), child
                  in dispatch_counter._items()}
    coalesced = int(
        registry.metrics()["loadgen_coalesced_total"].value)
    b_events = [e for e in recorder.snapshot()
                if e["kind"].startswith("brownout_")]
    _M_DEDUP.labels(scenario=scenario.name).set(round(dedup_ratio, 4))
    chaos_block = None
    if healer is not None:
        mesh_events = [e for e in recorder.snapshot()
                       if e["kind"].startswith("mesh_")]
        req = registry.metrics().get("bls_verify_requests_total")
        served = {}
        if req is not None:
            for (backend, reason), child in req._items():
                served[f"{backend}:{reason}"] = int(child.value)
        chaos_block = {
            "schedule": chaos_log,
            "mesh": healer.snapshot(),
            "ejects": sum(1 for e in mesh_events
                          if e["kind"] == "mesh_eject"),
            "readmits": sum(1 for e in mesh_events
                            if e["kind"] == "mesh_readmit"),
            "reshapes": dict(healer.reshapes),
            "recovery_s": healer.last_recovery_s,
            "recovered": not healer.ledger.ejected(),
            # no invalid signatures in this mix: every failed verdict
            # during device loss would be a WRONG verdict — the
            # zero-wrong-verdict chaos gate reads this
            "wrong_verdicts": failed_verdicts,
            "served": served,
            "events": [{k: e.get(k) for k in
                        ("kind", "device", "direction",
                         "from_devices", "to_devices", "epoch",
                         "trace_id")}
                       for e in mesh_events[:24]],
        }
    return {
        "scenario": scenario.name,
        "seed": seed,
        "slots": slots,
        "validators": model.validators,
        "committee_shaped": scenario.committee_shaped,
        "adversarial": scenario.adversarial,
        "classes_declared": list(scenario.classes),
        "stream": stats,
        "duration_s": round(duration, 3),
        "sigs_per_sec": round(completed / duration, 1) if duration
        else 0.0,
        "completed_triples": completed,
        "failed_verdicts": failed_verdicts,
        "p50_ms": p50, "p99_ms": p99,
        "by_class": per_class,
        "sheds": sheds,
        "shed_total": sum(sheds.values()),
        "dedup_ratio": round(dedup_ratio, 4),
        "coalesced": coalesced,
        "attribution": attribution,
        "dispatches": dispatches,
        "bisect_dispatches": dispatches.get("bisect", 0),
        "device": {"dispatches": dev_dispatches,
                   "lanes": dev_lanes,
                   "unique": dev_unique},
        **({"chaos": chaos_block} if chaos_block is not None else {}),
        "kzg": {"batches": kzg_backend.batches,
                "blobs": kzg_backend.blobs,
                "source_accounted": capacity_mod.SOURCE_KZG in
                telemetry.snapshot()["arrival_rate_per_second"]},
        "arrival_sources": sorted(
            telemetry.snapshot()["arrival_rate_per_second"]),
        "brownout": {
            "enters": sum(1 for e in b_events
                          if e["kind"] == "brownout_enter"
                          and e.get("from_level", 0) == 0),
            "exits": sum(1 for e in b_events
                         if e["kind"] == "brownout_exit"),
            "final_level": controller.brownout_level,
            "transitions": [
                {k: e.get(k) for k in ("kind", "level", "from_level",
                                       "utilization")}
                for e in b_events[:16]],
        },
    }


def run_scenario(scenario: Union[str, Scenario], seed: int = 1,
                 slots: int = 2,
                 validators: Optional[int] = None) -> dict:
    """One scenario end-to-end; returns the evidence dict."""
    if isinstance(scenario, str):
        scenario = scenarios_mod.get(scenario)
    return asyncio.run(_run_scenario(scenario, seed=seed, slots=slots,
                                     validators=validators))


def run_scenarios(names: Optional[Sequence[str]] = None, seed: int = 1,
                  slots: int = 2,
                  validators: Optional[int] = None) -> dict:
    """The sweep bench's ``mainnet`` phase embeds: every named (default
    all) scenario under the same seed, with a cross-scenario summary."""
    names = list(names or scenarios_mod.DEFAULT_SWEEP)
    out: dict = {"seed": seed, "slots": slots, "scenarios": {}}
    for name in names:
        out["scenarios"][name] = run_scenario(name, seed=seed,
                                              slots=slots,
                                              validators=validators)
    out["summary"] = summarize(out["scenarios"])
    return out


def summarize(scenarios: Dict[str, dict]) -> dict:
    """Cross-scenario acceptance view (what the bench gate reads)."""
    worst_block_import = 0
    worst_critical_p50 = 0.0
    dedup_floor = None
    for rep in scenarios.values():
        if not isinstance(rep, dict) or "by_class" not in rep:
            continue
        worst_block_import = max(
            worst_block_import,
            rep["sheds"].get("block_import", 0)
            + rep["sheds"].get("vip", 0))
        if not rep.get("adversarial"):
            # the critical-p50 bound holds on every PRODUCTION shape;
            # adversarial floods (deep bisect recursion) stress other
            # properties — their gate is sheds==0, not latency
            for cls in ("vip", "block_import"):
                worst_critical_p50 = max(
                    worst_critical_p50,
                    rep["by_class"][cls]["p50_ms"])
        if rep.get("committee_shaped"):
            d = rep.get("dedup_ratio", 0.0)
            dedup_floor = d if dedup_floor is None \
                else min(dedup_floor, d)
    return {
        "scenarios_run": len(scenarios),
        "block_import_sheds_worst": worst_block_import,
        "critical_p50_ms_worst": round(worst_critical_p50, 3),
        "committee_dedup_ratio_min": (round(dedup_floor, 4)
                                      if dedup_floor is not None
                                      else None),
    }
