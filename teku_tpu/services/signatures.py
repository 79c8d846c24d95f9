"""Aggregating signature verification service — the TPU batch scheduler.

Async front-end that converts bursty per-message verification requests
into device-sized batches, preserving the semantics of the reference's
gossip-side batcher (reference: ethereum/statetransition/src/main/java/
tech/pegasys/teku/statetransition/validation/signatures/
AggregatingSignatureVerificationService.java:41-262):

- bounded queue; overflow raises ServiceCapacityExceeded (:146-160);
- worker drain of queued tasks into ONE batch verify (:171-205) — here
  a single TPU dispatch via the provider, whose power-of-two padding
  keeps jit shapes static;
- on batch failure: single task fails; >= split_threshold bisects
  recursively; otherwise tasks verify individually (:213-226);
- multi-signature tasks stay atomic — a task's triples verify together
  or not at all (AsyncBatchBLSSignatureVerifier.java:24-60 grouping);
- queue-size gauge, batch/task counters, batch-size histogram (:76-98).

Overload resilience on top of the reference semantics (ROADMAP 3):

- PRIORITY CLASSES (``services/admission.py:VerifyClass``): the queue
  is per-class with STRICT-PRIORITY drain — VIP > BLOCK_IMPORT >
  SYNC_CRITICAL > GOSSIP > OPTIMISTIC.  A VIP task (single signature,
  e.g. a block's proposer sig) bypasses aggregation entirely and is
  dispatched alone.  Per-class depth/age metrics expose where a burst
  is queuing.
- ADAPTIVE BATCHING: when an ``AdmissionController`` is wired, each
  drain consults its ``BatchPlan`` — pow-2 bucket-aligned batch size
  picked from live depth + the per-shape device-latency model + the
  p50 burn rate, plus a flush deadline that lets workers hold a
  partial batch open ONLY when utilization says throughput is the
  constraint — replacing the fixed ``max_batch_size`` drain.
- SHED-BY-CLASS: queue overflow evicts a strictly-lower-priority
  sheddable task to admit a higher-class arrival (never the reverse);
  brownout (controller-declared, hysteretic) sheds OPTIMISTIC first,
  then GOSSIP by oldest deadline — BLOCK_IMPORT and VIP are never
  shed.  Every shed lands in the flight recorder with its class and
  the originating trace id, and in ``*_rejected_total{class=...}``.

Two dedup/overlap layers (PR 5):

- identical in-flight triples coalesce — gossip re-delivers the same
  (pks, msg, sig); duplicates ride the already-pending task and the
  verdict fans out to every waiter (``*_coalesced_total``).  A waiter
  of a HIGHER class promotes the shared task's effective class (and
  its queue position), so a VIP duplicate of a queued GOSSIP verify
  gets VIP treatment;
- async overlap — when the BLS implementation exposes the async begin
  seam (bls.begin_batch_verify), a worker host_preps + enqueues batch
  N+1 while batch N executes on device, synchronizing only at verdict
  read (``TEKU_TPU_ASYNC_OVERLAP=0`` disables).

Deliberate departure from the reference: its workers block up to 30 s
waiting to fill a batch, which is throughput-friendly but latency-naive;
here the flush deadline is CONTROLLED — zero (take whatever is queued)
while the node has headroom, nonzero only under measured pressure.
"""

import asyncio
import logging
import os
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ..crypto import bls
from ..infra import (capacity, dispatchledger, faults, flightrecorder,
                     timeline, tracing)
from ..infra.metrics import GLOBAL_REGISTRY, MetricsRegistry
from ..infra.env import env_bool, env_float
from .admission import (AdmissionController, BatchPlan, SHEDDABLE,
                        VerifyClass, class_deadline_s)

Triple = Tuple[Sequence[bytes], bytes, bytes]

_LOG = logging.getLogger(__name__)

# Overlap host_prep of batch N+1 with device execution of batch N: the
# worker begins (host_prep + async device enqueue) the next batch
# BEFORE synchronizing the previous one — JAX async dispatch keeps the
# device busy while the host packs arrays.  Engages only when the
# active BLS implementation exposes an async begin (the raw JAX
# provider; breaker-guarded backends stay sync — the breaker owns its
# dispatch deadline).  TEKU_TPU_ASYNC_OVERLAP=0 disables.
ENV_OVERLAP = "TEKU_TPU_ASYNC_OVERLAP"


def _overlap_default() -> bool:
    return env_bool(ENV_OVERLAP, True)


class ServiceCapacityExceededError(Exception):
    """Task shed — the caller treats it as load shedding (gossip
    IGNORE).  Raised at submission for rejected arrivals; set on the
    future for tasks evicted from the queue after admission."""


@dataclass(eq=False)   # identity eq: queue remove() wants THIS task,
class _Task:           # not a payload-equal twin, and field-wise eq
    triples: List[Triple]  # would byte-compare signatures per scan
    future: asyncio.Future = field(repr=False)
    # stamped at enqueue: queue-wait attribution + the caller's root
    # trace (the gossip validator's), so the worker can attribute its
    # stages to the trace that is awaiting this task's future
    t_enqueue: float = 0.0
    trace: Optional[tracing.Trace] = field(default=None, repr=False)
    # priority class + the enqueue-to-verdict deadline it implies
    # (monotonic): brownout sheds GOSSIP oldest-deadline-first
    cls: VerifyClass = VerifyClass.GOSSIP
    deadline: float = 0.0
    # in-flight dedup: gossip re-delivers the same (pks, msg, sig) —
    # identical pending triples coalesce onto ONE queued task, and the
    # verdict fans out to every waiter future
    key: Optional[tuple] = None
    waiters: List[asyncio.Future] = field(default_factory=list,
                                          repr=False)
    # class of each coalesced waiter, parallel to `waiters`: a
    # cancelled primary recomputes the effective class from survivors
    waiter_classes: List[VerifyClass] = field(default_factory=list)

    def settle(self, result: Optional[bool] = None,
               exc: Optional[BaseException] = None) -> None:
        """Resolve the primary future AND every coalesced waiter."""
        for fut in (self.future, *self.waiters):
            if fut.done():
                continue
            if exc is not None:
                fut.set_exception(exc)
            else:
                fut.set_result(result)


class _PriorityQueue:
    """Per-class bounded FIFO deques with strict-priority pop.

    Everything runs on the event loop (like the asyncio.Queue it
    replaces), so no locks.  Capacity bounds the TOTAL across classes
    — the reference's ArrayBlockingQueue.offer semantics per class
    would let a gossip storm starve the shared budget invisibly."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        self._qs: Dict[VerifyClass, deque] = {
            c: deque() for c in VerifyClass}
        self._size = 0
        self._triples = 0
        self._nonempty = asyncio.Event()
        # pulse on every put: flush-deadline waiters wake per arrival
        self._arrival = asyncio.Event()
        # timeline: start of the current queue-nonempty interval (the
        # wall-time denominator of overlap_efficiency); None while the
        # queue is empty or the timeline is disabled
        self._t_nonempty: Optional[float] = None

    def _note_size_change(self) -> None:
        """Close the queue-nonempty timeline interval when the queue
        drains (every decrement path funnels here)."""
        if self._size == 0 and self._t_nonempty is not None:
            t0 = self._t_nonempty
            self._t_nonempty = None
            timeline.interval("worker", "queue_nonempty",
                              time.perf_counter() - t0, t_mono=t0)

    def qsize(self) -> int:
        return self._size

    @property
    def triples(self) -> int:
        return self._triples

    def depth(self, cls: VerifyClass) -> int:
        return len(self._qs[cls])

    def oldest_deadline(self, cls: VerifyClass) -> Optional[float]:
        q = self._qs[cls]
        return min(t.deadline for t in q) if q else None

    def put_nowait(self, task: _Task) -> None:
        if self._size >= self.capacity:
            raise asyncio.QueueFull
        self._qs[task.cls].append(task)
        self._size += 1
        self._triples += len(task.triples)
        if self._t_nonempty is None and timeline.enabled():
            self._t_nonempty = time.perf_counter()
        self._nonempty.set()
        self._arrival.set()

    def best_class(self) -> Optional[VerifyClass]:
        """Highest-priority class with queued work (None = empty)."""
        for c in VerifyClass:
            if self._qs[c]:
                return c
        return None

    def get_nowait(self, prefer_non_vip: bool = False) -> _Task:
        """Strict-priority pop (VIP first).  ``prefer_non_vip`` is the
        anti-starvation guard: after a VIP-only dispatch the worker
        takes the best NON-VIP task when one is queued, so a steady
        VIP trickle cannot monopolize the device with tiny padded
        dispatches — a VIP then waits at most one bounded batch."""
        order = list(VerifyClass)
        if prefer_non_vip:
            order = order[1:] + order[:1]
        for c in order:
            q = self._qs[c]
            if q:
                return self._pop(q, 0)
        raise asyncio.QueueEmpty

    def pop_class(self, cls: VerifyClass) -> Optional[_Task]:
        q = self._qs[cls]
        return self._pop(q, 0) if q else None

    async def get(self, prefer_non_vip: bool = False) -> _Task:
        while True:
            try:
                return self.get_nowait(prefer_non_vip)
            except asyncio.QueueEmpty:
                self._nonempty.clear()
                await self._nonempty.wait()

    async def wait_arrival(self, timeout: float) -> None:
        self._arrival.clear()
        try:
            await asyncio.wait_for(self._arrival.wait(), timeout)
        except asyncio.TimeoutError:
            pass

    def _pop(self, q: deque, idx: int) -> _Task:
        if idx == 0:
            task = q.popleft()
        else:
            task = q[idx]
            del q[idx]
        self._size -= 1
        self._triples -= len(task.triples)
        self._note_size_change()
        return task

    def remove(self, task: _Task) -> bool:
        """Withdraw a specific queued task (promotion / shed)."""
        q = self._qs[task.cls]
        try:
            idx = q.index(task)
        except ValueError:
            return False
        self._pop(q, idx)
        return True

    def promote(self, task: _Task, cls: VerifyClass) -> None:
        """Raise a queued task's class (re-files it under the higher-
        priority deque; a task already in flight just re-labels)."""
        if self.remove(task):
            task.cls = cls
            self.put_nowait(task)
        else:
            task.cls = cls

    def evict_for(self, cls: VerifyClass) -> Optional[_Task]:
        """Pick a victim to admit a `cls` arrival on a full queue:
        the lowest-priority SHEDDABLE class strictly below the
        arrival, oldest deadline first.  None = the arrival itself is
        the least valuable thing here."""
        for victim_cls in SHEDDABLE:   # OPTIMISTIC, then GOSSIP
            if victim_cls <= cls:
                continue               # never evict peers or betters
            q = self._qs[victim_cls]
            if q:
                idx = min(range(len(q)), key=lambda i: q[i].deadline)
                return self._pop(q, idx)
        return None

    def drain_class(self, cls: VerifyClass) -> List[_Task]:
        q = self._qs[cls]
        victims = list(q)
        for t in victims:
            self._size -= 1
            self._triples -= len(t.triples)
        q.clear()
        self._note_size_change()
        return victims

    def _drop_many(self, cls: VerifyClass,
                   victims: List[_Task]) -> None:
        """Remove a victim set in ONE rebuild pass — per-victim
        remove() would rescan the deque per victim, O(victims x
        depth) on the event loop at peak overload."""
        if not victims:
            return
        victim_ids = {id(t) for t in victims}
        q = self._qs[cls]
        keep = [t for t in q if id(t) not in victim_ids]
        q.clear()
        q.extend(keep)
        for t in victims:
            self._size -= 1
            self._triples -= len(t.triples)
        self._note_size_change()

    def drain_expired(self, cls: VerifyClass, now: float
                      ) -> List[_Task]:
        """Shed every `cls` task whose deadline already passed:
        past-deadline work can no longer make its SLO, and verifying
        it spends device time the still-viable queue needs."""
        victims = [t for t in self._qs[cls] if t.deadline <= now]
        self._drop_many(cls, victims)
        return victims

    def drain_oldest(self, cls: VerifyClass, keep: int) -> List[_Task]:
        """Shed `cls` down to `keep` tasks, oldest deadline first."""
        q = self._qs[cls]
        excess = len(q) - keep
        if excess <= 0:
            return []
        victims = sorted(q, key=lambda t: t.deadline)[:excess]
        self._drop_many(cls, victims)
        return victims

    def drain_all(self) -> List[_Task]:
        out = []
        for c in VerifyClass:
            out.extend(self.drain_class(c))
        return out


class AggregatingSignatureVerificationService:
    """Queue/drain/dispatch batch verifier over the pluggable BLS SPI."""

    def __init__(self, num_workers: int = 2, queue_capacity: int = 15_000,
                 max_batch_size: int = 250, split_threshold: int = 25,
                 registry: MetricsRegistry = GLOBAL_REGISTRY,
                 name: str = "signature_verifications",
                 overlap: Optional[bool] = None,
                 controller: Optional[AdmissionController] = None,
                 default_class: VerifyClass = VerifyClass.GOSSIP,
                 telemetry: Optional[capacity.CapacityTelemetry]
                 = None,
                 recorder: Optional[flightrecorder.FlightRecorder]
                 = None,
                 clock: Callable[[], float] = time.monotonic):
        if num_workers < 1:
            raise ValueError("need at least one worker")
        # the capacity sink (arrivals/sheds/queue depth) and the shed
        # event sink: injectable so closed-loop simulations run on a
        # virtual clock without touching process-global state
        self._telemetry = telemetry or capacity.TELEMETRY
        self._recorder = recorder or flightrecorder.RECORDER
        # deadline clock: task deadlines (enqueue + class budget) and
        # the expiry checks against them run on this clock, so the
        # virtual-clock overload sim ages queues deterministically.
        # Worker-liveness stamps stay on real monotonic time — a
        # stalled worker is a wall-clock fact.
        self._clock = clock
        # flight-recorder flood guard: during a brownout every rejected
        # arrival is a shed; recording each one would wash the valuable
        # brownout-edge events out of the bounded ring.  Per
        # (class, reason) at most one event per cooldown window; the
        # next recorded event carries the suppressed count.
        self._shed_event_cooldown_s = env_float(
            "TEKU_TPU_SHED_EVENT_COOLDOWN_S", 1.0)
        self._shed_event_last: Dict[tuple, float] = {}
        self._shed_event_suppressed: Dict[tuple, int] = {}
        # REAL-TIME flush failsafe: the batch-fill hold runs on the
        # service clock (virtual in sims), with a wall-clock
        # termination bound so a stalled virtual clock can never hold
        # a worker forever.  Env-tunable (TEKU_TPU_FLUSH_FAILSAFE_MS;
        # 0 = the plan's own flush deadline, the legacy bound).  The
        # r10 investigation SUSPECTED this silent failsafe for a 3.6 s
        # loadgen block-import p50 on 1-core boxes — each firing is
        # now counted, flight-recorded, and stamped into the fired
        # batch's own ledger record (and that evidence shows the
        # loadgen inflation fires ZERO failsafes, ruling this path
        # out).
        # clamped: a negative typo'd value would read truthy and put
        # the wall deadline in the past, firing the failsafe on EVERY
        # fill hold (degrade-never-fail, like every env knob here)
        self._flush_failsafe_s = max(0.0, env_float(
            "TEKU_TPU_FLUSH_FAILSAFE_MS", 0.0) / 1e3)
        self._failsafe_event_last = 0.0
        self._m_flush_failsafe = registry.counter(
            f"{name}_flush_failsafe_total",
            "batch-fill holds terminated by the wall-clock failsafe "
            "instead of the service-clock flush deadline")
        self.num_workers = num_workers
        self._name = name
        self.overlap = _overlap_default() if overlap is None else overlap
        self.queue_capacity = queue_capacity
        self.max_batch_size = max_batch_size
        self.split_threshold = split_threshold
        # the feedback controller (None = fixed-policy legacy mode:
        # max_batch_size drain, overflow-only shedding, no brownout)
        self.controller = controller
        self.default_class = default_class
        # Genuinely bounded, like the reference's ArrayBlockingQueue.offer
        # (AggregatingSignatureVerificationService.java:146-160): put_nowait
        # on a full queue raises QueueFull -> shed-by-class or
        # capacity-exceeded, so concurrent producers cannot overshoot.
        self._queue = _PriorityQueue(queue_capacity)
        self._workers: List[asyncio.Task] = []
        self._started = False
        self._stopped = False
        self._m_queue = registry.gauge(
            f"{name}_queue_size", "pending verification tasks",
            supplier=lambda: self._queue.qsize())
        self._m_batches = registry.counter(
            f"{name}_batch_count_total", "batches dispatched")
        self._m_tasks = registry.counter(
            f"{name}_task_count_total", "tasks completed")
        # a task is one triple (`verify`) or several verified together
        # (`verify_multi`: the three checks of an aggregate-and-proof);
        # a dispatch's lanes are triples, so load by task undercounts
        self._m_triples = registry.counter(
            f"{name}_triple_count_total",
            "triples of the completed tasks")
        self._m_multi_tasks = registry.counter(
            f"{name}_multi_task_count_total",
            "completed tasks of more than one triple")
        self._m_batch_size = registry.histogram(
            f"{name}_batch_size", "signatures per dispatched batch",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512))
        # (batch LATENCY is `verify_stage_duration_seconds{stage=
        # "dispatch"}`, split by phase: infra/tracing.py)
        # first-try vs bisect-recursion dispatches: the failure path
        # amplifies one bad batch into O(log n) extra device calls, and
        # that amplification used to be invisible
        self._m_dispatches = registry.labeled_counter(
            f"{name}_dispatch_total",
            "batch dispatches by kind (first_try vs bisect recursion)",
            labelnames=("kind",))
        # shedding by CLASS: a node rejecting gossip under load while
        # protecting block import must be distinguishable from one
        # rejecting blindly (bounded cardinality: VerifyClass is a
        # closed enum)
        self._m_rejected = registry.labeled_counter(
            f"{name}_rejected_total",
            "tasks shed (queue overflow, preemption by a higher class, "
            "or brownout), by priority class",
            labelnames=("class",))
        # per-class queue observability: depth + age of the oldest
        # queued task — WHERE a burst is queuing, not just how much
        self._m_class_depth = registry.labeled_gauge(
            f"{name}_class_queue_depth",
            "pending tasks per priority class",
            labelnames=("class",))
        self._m_class_age = registry.labeled_gauge(
            f"{name}_class_oldest_wait_seconds",
            "how long the oldest queued task of each class has waited",
            labelnames=("class",))
        for c in VerifyClass:          # complete family from scrape 1
            self._m_class_depth.labels(**{"class": c.label}).set(0.0)
            self._m_class_age.labels(**{"class": c.label}).set(0.0)
        # gossip re-delivery dedup: each coalesced submission rode an
        # already-pending identical task instead of a fresh lane
        self._m_coalesced = registry.counter(
            f"{name}_coalesced_total",
            "duplicate in-flight submissions coalesced onto a pending "
            "identical task")
        # identical-triples key -> the pending task carrying it (entries
        # removed when the task settles; all on the event loop, no lock)
        self._pending: Dict[tuple, _Task] = {}
        # (queue saturation is served by health_snapshot() / the
        # readiness endpoint, not a supplier gauge: get_or_create would
        # pin the family to the FIRST service instance's closure)
        # worker liveness: monotonic stamp of the last time ANY worker
        # made progress (took or finished a batch) — queued work plus a
        # stale stamp is the signature of every worker wedged in a
        # dispatch, which no throughput counter can distinguish from
        # simple idleness
        self._last_worker_progress = time.monotonic()
        # dispatches currently crossing the thread boundary (inside an
        # asyncio.to_thread BLS call).  Event-loop-only mutation, no
        # lock.  Virtual-clock harnesses gate their clock advancement
        # on this: while a dispatch is in flight, spinning the event
        # loop (and the virtual clock) starves the executor thread of
        # the GIL on small hosts, charging wall scheduling time to the
        # task's VIRTUAL latency — the r10 3.6 s loadgen block-import
        # p50 on a 1-core box (see loadgen/driver.py)
        self._inflight_dispatches = 0

    # ------------------------------------------------------------------
    async def start(self) -> None:
        if self._started:
            return
        self._started = True
        for i in range(self.num_workers):
            self._workers.append(
                asyncio.create_task(self._worker(), name=f"sig-verify-{i}"))

    async def stop(self) -> None:
        self._stopped = True
        for w in self._workers:
            w.cancel()
        for w in self._workers:
            try:
                await w
            except asyncio.CancelledError:
                pass
        self._workers.clear()
        # Fail tasks still in the queue so callers never hang on shutdown.
        for task in self._queue.drain_all():
            for fut in (task.future, *task.waiters):
                if not fut.done():
                    fut.cancel()
        self._pending.clear()

    # ------------------------------------------------------------------
    def verify(self, public_keys: Sequence[bytes], message: bytes,
               signature: bytes,
               cls: Optional[VerifyClass] = None,
               source: Optional[str] = None
               ) -> "asyncio.Future[bool]":
        """Queue one fast-aggregate triple; resolves with the verdict."""
        return self.verify_multi([(public_keys, message, signature)],
                                 cls=cls, source=source)

    @staticmethod
    def _task_key(triples: Sequence[Triple]) -> tuple:
        return tuple((tuple(pks), msg, sig) for pks, msg, sig in triples)

    @property
    def inflight_dispatches(self) -> int:
        """Dispatches currently inside an ``asyncio.to_thread`` BLS
        call (enqueue or sync).  0 = the service is quiescent at the
        thread boundary — the virtual-clock harness gate."""
        return self._inflight_dispatches

    async def _dispatch_in_thread(self, fn, *args):
        """One BLS call on a worker thread, counted as in-flight for
        the whole thread round-trip."""
        self._inflight_dispatches += 1
        try:
            return await asyncio.to_thread(fn, *args)
        finally:
            self._inflight_dispatches -= 1

    def _current_plan(self) -> Optional[BatchPlan]:
        if self.controller is None:
            return None
        try:
            return self.controller.plan()
        except Exception:  # noqa: BLE001 - control must not kill verify
            _LOG.exception("admission controller plan() failed")
            return None

    def verify_multi(self, triples: Sequence[Triple],
                     cls: Optional[VerifyClass] = None,
                     source: Optional[str] = None
                     ) -> "asyncio.Future[bool]":
        """Queue several triples as ONE atomic task (e.g. the three
        signatures of a SignedAggregateAndProof verify together).

        ``source`` names the arrival's demand stream in the capacity
        model (default: this service's name) — the sync-committee verbs
        pass ``capacity.SOURCE_SYNC_COMMITTEE`` so their load is
        attributable separately from attestation gossip.

        Identical in-flight submissions coalesce: gossip re-delivers
        the same (pks, msg, sig), and re-verifying a triple that is
        already pending wastes a lane — the duplicate rides the pending
        task and its future resolves with the same verdict.  A waiter
        of a HIGHER class promotes the shared task."""
        if not self._started or self._stopped:
            raise RuntimeError("service not running")
        cls = self.default_class if cls is None else VerifyClass(cls)
        if cls is VerifyClass.VIP and len(triples) != 1:
            raise ValueError("the VIP lane is single-signature only")
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        key = self._task_key(triples)
        pending = self._pending.get(key)
        if pending is not None and not pending.future.cancelled():
            pending.waiters.append(fut)
            pending.waiter_classes.append(cls)
            if cls < pending.cls:
                # the shared lane inherits the most urgent waiter's
                # class — a VIP duplicate must not queue at GOSSIP
                self._queue.promote(pending, cls)
                pending.deadline = min(
                    pending.deadline,
                    self._clock() + class_deadline_s(cls))
            self._m_coalesced.inc()
            # timeline: the waiter's trace joins the pending task's
            # in-flight lane — the Perfetto export draws the async
            # arrow from this mark to the carrying dispatch
            timeline.instant(
                "worker", "coalesce",
                trace_id=(pending.trace.trace_id
                          if pending.trace is not None else ""),
                waiter_class=cls.label,
                waiters=len(pending.waiters))
            return fut
        # capacity input: demand is OFFERED load — a shed arrival is
        # still demand (counting only accepted work would read
        # utilization low during exactly the overload the brownout
        # controller exists to manage)
        self._telemetry.record_arrival(source or self._name,
                                       len(triples))
        plan = self._current_plan()
        if plan is not None and plan.sheds(cls):
            # brownout admission control: the controller already
            # declared this class shed — reject before it costs a slot
            self._count_shed(cls, len(triples), reason="brownout",
                             trace=tracing.current_trace())
            raise ServiceCapacityExceededError(
                f"brownout level {plan.brownout_level}: "
                f"{cls.label} shed")
        task = _Task(
            list(triples), fut, t_enqueue=time.perf_counter(),
            trace=tracing.current_trace(), key=key, cls=cls,
            deadline=self._clock() + class_deadline_s(cls))
        try:
            # `sigservice.enqueue` fault site: Overflow injection proves
            # the shed path (metrics + WARN) without a 15k-deep queue
            faults.check("sigservice.enqueue")
            self._queue.put_nowait(task)
        except asyncio.QueueFull:
            # shed-by-class: a full queue admits a higher-priority
            # arrival by evicting the least valuable queued task
            # (OPTIMISTIC first, then GOSSIP oldest-deadline; never
            # BLOCK_IMPORT/VIP) — only when the arrival outranks it
            victim = self._queue.evict_for(cls)
            if victim is not None:
                self._shed_task(victim, reason="preempted")
                self._queue.put_nowait(task)
            else:
                self._count_shed(cls, len(triples), reason="overflow",
                                 trace=task.trace)
                _LOG.warning(
                    "signature verification queue at capacity "
                    "(%d/%d pending) — shedding %s task (%d triples)",
                    self._queue.qsize(), self.queue_capacity,
                    cls.label, len(triples))
                raise ServiceCapacityExceededError(
                    f"queue at capacity ({self.queue_capacity})"
                ) from None
        self._pending[key] = task
        self._m_class_depth.labels(**{"class": cls.label}).set(
            self._queue.depth(cls))
        # the queue-depth time series the admin endpoint serves and
        # the admission controller sizes batches from — in TRIPLES
        # (lanes), the unit the batch plan and demand rate use, not
        # tasks (an aggregate task is 3 triples)
        self._telemetry.record_queue_depth(self._queue.triples)
        return fut

    # ------------------------------------------------------------------
    def _count_shed(self, cls: VerifyClass, triples: int, reason: str,
                    trace: Optional[tracing.Trace] = None) -> None:
        """Shared shed bookkeeping: class-labeled counter, capacity
        demand, and a flight-recorder event naming the class AND the
        originating trace id."""
        self._m_rejected.labels(**{"class": cls.label}).inc()
        self._telemetry.record_shed(triples)
        key = (cls.label, reason)
        now = time.monotonic()
        last = self._shed_event_last.get(key)
        if (last is not None
                and now - last < self._shed_event_cooldown_s):
            # ring flood guard: the counter above is the authoritative
            # shed count; the event stream keeps only the edges
            self._shed_event_suppressed[key] = (
                self._shed_event_suppressed.get(key, 0) + 1)
            return
        self._shed_event_last[key] = now
        suppressed = self._shed_event_suppressed.pop(key, 0)
        trace_id = trace.trace_id if trace is not None else None
        self._recorder.record(
            "queue_shed", trace_id=trace_id, service=self._name,
            reason=reason, queue_size=self._queue.qsize(),
            capacity=self.queue_capacity, triples=triples,
            suppressed_since_last=suppressed,
            **{"class": cls.label})

    def _shed_task(self, task: _Task, reason: str) -> None:
        """Shed an ALREADY-QUEUED task: fail its future (and every
        coalesced waiter) with the capacity error the callers already
        treat as load shedding."""
        self._drop_pending(task)
        self._count_shed(task.cls, len(task.triples), reason=reason,
                         trace=task.trace)
        self._m_class_depth.labels(**{"class": task.cls.label}).set(
            self._queue.depth(task.cls))
        task.settle(exc=ServiceCapacityExceededError(
            f"{task.cls.label} task shed ({reason})"))

    def _apply_brownout(self, plan: BatchPlan) -> int:
        """Trim the queue per the controller's brownout level: all
        queued OPTIMISTIC at level >= 1; GOSSIP down to two batches'
        worth, oldest deadline first, at level 2.  Returns sheds."""
        if plan.brownout_level < 1:
            return 0
        victims = self._queue.drain_class(VerifyClass.OPTIMISTIC)
        # deadline-aware: while browned out, a GOSSIP task that cannot
        # produce its verdict inside its deadline budget (its deadline
        # falls before now + one modeled device dispatch) is dead
        # weight at ANY level — verifying it spends the device time
        # the still-viable queue needs, and serving a seconds-stale
        # backlog is what turns a 2x overload transient into a blown
        # p50
        horizon = self._clock() + (plan.modeled_batch_s or 0.0)
        victims += self._queue.drain_expired(VerifyClass.GOSSIP,
                                             horizon)
        if plan.brownout_level >= 2:
            keep = max(1, plan.batch_size * 2)
            victims += self._queue.drain_oldest(VerifyClass.GOSSIP,
                                                keep)
        for t in victims:
            self._shed_task(t, reason="brownout")
        return len(victims)

    # ------------------------------------------------------------------
    def queue_snapshot(self) -> dict:
        """Per-class queue state (the admin endpoint body); also
        refreshes the per-class depth/age gauges."""
        now = self._clock()
        classes = {}
        for c in VerifyClass:
            depth = self._queue.depth(c)
            oldest = self._queue.oldest_deadline(c)
            # oldest wait = how far the oldest task is INTO its
            # deadline budget (>= 0; clamped — a promoted task keeps
            # its original, possibly tighter, deadline)
            age = 0.0
            if oldest is not None:
                age = max(0.0, class_deadline_s(c) - (oldest - now))
            classes[c.label] = {"depth": depth,
                                "oldest_wait_s": round(age, 4)}
            self._m_class_depth.labels(**{"class": c.label}).set(depth)
            self._m_class_age.labels(**{"class": c.label}).set(
                round(age, 4))
        return {"total": self._queue.qsize(),
                "triples": self._queue.triples,
                "capacity": self.queue_capacity,
                "classes": classes}

    def health_snapshot(self) -> dict:
        """Queue + worker liveness for `infra/health.py`'s check:
        `stalled_s` is nonzero only while tasks are QUEUED with no
        worker progress — an idle service never reads as stalled."""
        qsize = self._queue.qsize()
        stalled_s = 0.0
        if qsize > 0 and self._started and not self._stopped:
            stalled_s = max(
                0.0, time.monotonic() - self._last_worker_progress)
        return {"queue_size": qsize,
                "capacity": self.queue_capacity,
                "saturation": qsize / self.queue_capacity,
                "workers": len(self._workers),
                "stalled_s": stalled_s,
                "classes": self.queue_snapshot()["classes"],
                "brownout_level": (self.controller.brownout_level
                                   if self.controller else 0),
                # the derived capacity signals (arrival rate,
                # utilization, headroom, occupancy) the SLO engine, the
                # health check and the admission controller consume —
                # full per-shape detail lives on /teku/v1/admin/capacity
                "capacity_model": self._telemetry.summary()}

    # ------------------------------------------------------------------
    async def _worker(self) -> None:
        # At most ONE in-flight async dispatch per worker: batch N
        # executes on device while this loop assembles and host_preps
        # batch N+1 (bls.begin_batch_verify), then retires N.  The
        # overlap only defers the SYNC, so when the queue is empty the
        # in-flight batch retires immediately — no added latency.
        inflight: Optional[tuple] = None
        vip_streak = False      # last dispatch was VIP-only
        try:
            while not self._stopped:
                if inflight is not None:
                    try:
                        first = self._queue.get_nowait(vip_streak)
                    except asyncio.QueueEmpty:
                        prev, inflight = inflight, None
                        await self._retire(*prev)
                        continue
                else:
                    first = await self._queue.get(vip_streak)
                self._last_worker_progress = time.monotonic()
                plan = self._current_plan()
                if plan is not None:
                    self._apply_brownout(plan)
                    if plan.sheds(first.cls):
                        # admitted before the brownout edge: device
                        # time is the scarce resource now
                        self._shed_task(first, reason="brownout")
                        continue
                tasks, failsafe_fired = await self._take_batch(
                    first, plan)
                if not tasks:
                    continue
                vip_streak = all(t.cls is VerifyClass.VIP
                                 for t in tasks)
                try:
                    handle = marks = None
                    if self.overlap and bls.supports_async_verify():
                        handle, marks = await self._begin(
                            tasks, plan, failsafe_fired)
                    if handle is None:
                        # sync path: implementation has no async seam
                        if inflight is not None:
                            prev, inflight = inflight, None
                            await self._retire(*prev)
                        await self._verify_batch(
                            tasks, plan=plan,
                            flush_failsafe=failsafe_fired)
                    else:
                        prev, inflight = inflight, (tasks, handle, marks)
                        if prev is not None:
                            await self._retire(*prev)
                except asyncio.CancelledError:
                    raise
                except Exception as exc:  # provider/JAX runtime error
                    # The worker must survive (the reference at least
                    # logs worker death, doStart .finish(err ->
                    # LOG.error)); fail the affected futures so callers
                    # never await forever.
                    _LOG.exception("signature batch verification failed")
                    for t in tasks:
                        self._drop_pending(t)
                        t.settle(exc=exc)
                finally:
                    self._last_worker_progress = time.monotonic()
        finally:
            # shutdown/cancellation with a batch still in flight: never
            # leave its callers awaiting forever
            if inflight is not None:
                for t in inflight[0]:
                    self._drop_pending(t)
                    for fut in (t.future, *t.waiters):
                        if not fut.done():
                            fut.cancel()

    async def _take_batch(
            self, first: _Task,
            plan: Optional[BatchPlan]) -> Tuple[List[_Task], bool]:
        """Assemble one dispatch batch under the current plan: VIP
        bypasses aggregation (dispatched alone, immediately); other
        classes drain up to the plan's pow-2 batch size, optionally
        holding the batch open up to the flush deadline when the
        controller says throughput is the constraint.  Returns
        ``(tasks, failsafe_fired)`` — the flag rides with THIS batch
        into its ledger annotation (a shared instance flag would let
        one worker's firing stamp another worker's record)."""
        # recompute the effective class first: a cancelled VIP primary
        # with GOSSIP waiters must not hold the express lane
        live = self._drop_cancelled([first])
        if not live:
            return [], False
        first = live[0]
        budget = plan.batch_size if plan is not None \
            else self.max_batch_size
        if first.cls is VerifyClass.VIP:
            # bypass aggregation: no flush wait, no lower-class lanes
            # — but other QUEUED VIPs ride the same dispatch (one
            # padded shape serves them all; leaving them behind would
            # cost a full extra dispatch each)
            return self._drop_cancelled(
                self._assemble(first, budget, vip_only=True)), False
        failsafe_fired = False
        if plan is not None and plan.flush_deadline_s > 0:
            needed = budget - len(first.triples)
            # elapsed runs on the service clock (virtual in the sim, so
            # the hold window is deterministic while load flows and
            # arrivals pulse re-checks); the REAL-time deadline is the
            # termination failsafe — a virtual clock that stops
            # advancing (sim load window over) must not hold a worker
            # forever.  TEKU_TPU_FLUSH_FAILSAFE_MS tightens the wall
            # bound independently of the plan's (virtual) deadline.
            start = self._clock()
            failsafe_s = self._flush_failsafe_s \
                or plan.flush_deadline_s
            real_deadline = time.monotonic() + failsafe_s
            while self._queue.triples < needed:
                best = self._queue.best_class()
                if best is not None and best < first.cls:
                    # a more urgent class arrived mid-hold: stop
                    # gathering and dispatch NOW — a proposer
                    # signature must not wait out a gossip batch's
                    # fill window (it rides this immediate dispatch)
                    break
                remaining = (plan.flush_deadline_s
                             - (self._clock() - start))
                real_remaining = real_deadline - time.monotonic()
                if remaining <= 0:
                    break
                if real_remaining <= 0:
                    # the wall clock beat the service clock: the
                    # failsafe (not the flush policy) ended this hold
                    # — the silent 1-core latency source r10 chased
                    self._note_flush_failsafe(plan, failsafe_s,
                                              remaining)
                    failsafe_fired = True
                    break
                await self._queue.wait_arrival(
                    min(remaining, real_remaining))
        return (self._drop_cancelled(self._assemble(first, budget)),
                failsafe_fired)

    def _note_flush_failsafe(self, plan: BatchPlan, failsafe_s: float,
                             virtual_remaining_s: float) -> None:
        """Stamp a real-time flush-failsafe firing: counter always,
        flight-recorder event edge-throttled (a stalled virtual clock
        fires once per drain); the ledger flag rides _take_batch's
        return with the batch whose hold fired it."""
        self._m_flush_failsafe.inc()
        now = time.monotonic()
        if now - self._failsafe_event_last \
                >= self._shed_event_cooldown_s:
            self._failsafe_event_last = now
            self._recorder.record(
                "flush_failsafe", service=self._name,
                failsafe_ms=round(failsafe_s * 1e3, 3),
                flush_deadline_ms=round(
                    plan.flush_deadline_s * 1e3, 3),
                virtual_remaining_ms=round(
                    virtual_remaining_s * 1e3, 3),
                detail="wall clock beat the service clock during the "
                       "batch-fill hold (TEKU_TPU_FLUSH_FAILSAFE_MS)")

    def _assemble(self, first: _Task, budget_triples: int,
                  vip_only: bool = False) -> List[_Task]:
        """Drain up to the batch budget into one batch + stamp
        queue-wait/assembly attribution (strict priority: the pow-2
        plan size keeps the padded dispatch bucket-aligned).
        ``vip_only`` restricts the drain to the VIP deque (the express
        dispatch carries no lower-class lanes)."""
        t_first = time.perf_counter()
        tasks = [first]
        budget = budget_triples - len(first.triples)
        while budget > 0:
            if vip_only:
                nxt = self._queue.pop_class(VerifyClass.VIP)
                if nxt is None:
                    break
            else:
                try:
                    nxt = self._queue.get_nowait()
                except asyncio.QueueEmpty:
                    break
            tasks.append(nxt)
            budget -= len(nxt.triples)
        # drain-side depth sample (triples): the series shows both the
        # burst build-up (enqueue stamps) and the worker's drawdown
        self._telemetry.record_queue_depth(self._queue.triples)
        for c in VerifyClass:
            self._m_class_depth.labels(**{"class": c.label}).set(
                self._queue.depth(c))
        if tracing.enabled():
            # per-task attribution: each task experienced its own
            # queue-wait and the whole batch's assembly time
            assembly = time.perf_counter() - t_first
            for t in tasks:
                trs = (t.trace,) if t.trace is not None else ()
                # exact start offsets: queue_wait began at enqueue,
                # assembly at the drain — the timeline's span tree
                # tiles on these
                tracing.record_stage(
                    "queue_wait", t_first - t.t_enqueue, trs,
                    t0=t.t_enqueue)
                tracing.record_stage("assembly", assembly, trs,
                                     t0=t_first)
        return tasks

    def _dispatch_annotations(self, tasks: List[_Task],
                              plan: Optional[BatchPlan] = None,
                              flush_failsafe: bool = False) -> dict:
        """The admission context the dispatch-ledger record carries:
        the plan that GOVERNED this batch (the worker passes the plan
        it assembled under — re-fetching controller.plan() here could
        tick a brownout edge mid-flight and stamp a mode the batch was
        never admitted under), the batch's verify-class mix, and
        whether the real-time flush failsafe ended the fill hold.
        Bound via dispatchledger.annotate() so asyncio.to_thread
        carries it into the provider's _launch.  Bisect
        re-dispatches carry no governing plan and fall back to a
        passive last_plan() read (no tick side effects)."""
        mix: Dict[str, int] = {}
        for t in tasks:
            mix[t.cls.label] = mix.get(t.cls.label, 0) + 1
        ann: dict = {"classes": mix, "service": self._name}
        if plan is None and self.controller is not None:
            try:
                plan = self.controller.last_plan()
            except Exception:  # noqa: BLE001 - annotation must not kill
                plan = None
        if plan is not None:
            ann.update(plan_mode=plan.mode,
                       brownout_level=plan.brownout_level,
                       plan_batch_size=plan.batch_size,
                       flush_deadline_s=plan.flush_deadline_s)
        else:
            ann.update(plan_mode=None, brownout_level=0)
        if flush_failsafe:
            ann["flush_failsafe"] = True
        return ann

    async def _begin(self, tasks: List[_Task],
                     plan: Optional[BatchPlan] = None,
                     flush_failsafe: bool = False):
        """Async-dispatch a batch: host_prep + device enqueue on a
        worker thread.  Returns (handle, marks); handle is None when
        the active implementation has no async path."""
        triples = [tr for t in tasks for tr in t.triples]
        traces = [t.trace for t in tasks]
        marks = tracing.new_marks(traces)
        with tracing.attach(traces, marks), \
                dispatchledger.annotate(
                    **self._dispatch_annotations(
                        tasks, plan, flush_failsafe)):
            with tracing.span("dispatch"):
                marks.mark("thread_hop")
                handle = await self._dispatch_in_thread(
                    bls.begin_batch_verify, triples)
        if handle is None:
            return None, marks
        self._m_batches.inc()
        self._m_batch_size.observe(len(triples))
        self._m_dispatches.labels(kind="first_try").inc()
        return handle, marks

    async def _retire(self, tasks: List[_Task], handle, marks) -> None:
        """Synchronize an in-flight dispatch and settle its tasks
        (bisecting failures through the sync path)."""
        try:
            # the handle marks device_sync and the way back itself (it
            # captured the batch's marks at dispatch time)
            marks.mark("thread_hop")
            ok = await self._dispatch_in_thread(handle.result)
            marks.mark("settle")
        except asyncio.CancelledError:
            raise
        except Exception as exc:
            _LOG.exception("signature batch verification failed")
            for t in tasks:
                self._drop_pending(t)
                t.settle(exc=exc)
            marks.close()
            return
        await self._resolve_batch(tasks, ok, marks)

    def _drop_cancelled(self, tasks: List[_Task]) -> List[_Task]:
        """Filter cancelled tasks, releasing their pending-map entries.

        A cancelled PRIMARY with live coalesced waiters does not kill
        the task: the waiters' callers still want the verdict (only the
        original submitter bailed), so the first live waiter is
        promoted to primary — and the task's effective class becomes
        the most urgent SURVIVING waiter's class (a cancelled VIP
        primary must neither strand its GOSSIP waiters nor keep the
        express lane for them)."""
        live = []
        for t in tasks:
            if t.future.cancelled():
                survivors = [(f, c) for f, c in
                             zip(t.waiters, t.waiter_classes)
                             if not f.done()]
                if survivors:
                    t.future = survivors[0][0]
                    t.waiters = [f for f, _ in survivors[1:]]
                    t.waiter_classes = [c for _, c in survivors[1:]]
                    t.cls = min(c for _, c in survivors)
                    live.append(t)
                    continue
                self._drop_pending(t)
            else:
                live.append(t)
        return live

    async def _verify_batch(self, tasks: List[_Task],
                            first_try: bool = True,
                            plan: Optional[BatchPlan] = None,
                            flush_failsafe: bool = False,
                            parent_seq: Optional[int] = None) -> None:
        tasks = self._drop_cancelled(tasks)
        if not tasks:
            return
        triples = [tr for t in tasks for tr in t.triples]
        self._m_batches.inc()
        self._m_batch_size.observe(len(triples))
        self._m_dispatches.labels(
            kind="first_try" if first_try else "bisect").inc()
        # the dispatch runs with the whole batch's traces and its
        # phase marks bound to the context: asyncio.to_thread copies
        # it, so the guard's and the provider's phases attribute to
        # every trace and land in ONE ledger record.  `thread_hop`
        # opens inside the `dispatch` span, its parent in the span tree
        traces = [t.trace for t in tasks]
        marks = tracing.new_marks(traces, parent_seq)
        try:
            with tracing.attach(traces, marks), \
                    dispatchledger.annotate(
                        **self._dispatch_annotations(
                            tasks, plan, flush_failsafe)):
                with tracing.span("dispatch"):
                    marks.mark("thread_hop")
                    ok = await self._dispatch_in_thread(
                        bls.batch_verify, triples)
        except BaseException:
            # the dispatch that raised is the one to look at: its
            # traces get the phases it reached
            marks.close()
            raise
        # after the span has closed: `settle` is its sibling, never a
        # child that a late mark would nest under it
        marks.mark("settle")
        await self._resolve_batch(tasks, ok, marks)

    async def _resolve_batch(self, tasks: List[_Task], ok: bool,
                             marks) -> None:
        """Post-dispatch settlement: complete on success, bisect on
        failure (shared by the sync and the async-overlap paths).
        `marks` (the dispatch's, in `settle` by now) close with the
        last future settled; a failed batch settles nothing, and its
        bisection's dispatches have marks of their own that point at
        this one's ledger record."""
        settled = ok or len(tasks) == 1
        if settled:
            for t in tasks:
                self._complete(t, ok)
        marks.close()
        if settled:
            return
        parent = marks.seq
        if len(tasks) >= self.split_threshold:
            half = len(tasks) // 2
            await self._verify_batch(tasks[:half], first_try=False,
                                     parent_seq=parent)
            await self._verify_batch(tasks[half:], first_try=False,
                                     parent_seq=parent)
        else:
            for t in tasks:
                await self._verify_batch([t], first_try=False,
                                         parent_seq=parent)

    def _drop_pending(self, task: _Task) -> None:
        if task.key is not None and self._pending.get(task.key) is task:
            del self._pending[task.key]

    def _complete(self, task: _Task, result: bool) -> None:
        self._m_tasks.inc()
        self._m_triples.inc(len(task.triples))
        if len(task.triples) > 1:
            self._m_multi_tasks.inc()
        self._drop_pending(task)
        task.settle(result)
