"""Hot-path tracing: per-stage verify-latency attribution.

The north-star metric is attestation-gossip p50 verify latency, but an
end-to-end number cannot say WHERE a slow verify spent its time — the
asyncio queue, batch assembly, the hop to the dispatch thread, the wait
for the turn to pack, host-side limb packing, the wait at the guarded
provider's lock, a JAX recompile, the device, the hop back, or an
oracle fallback.  This module is the attribution layer (the
reference's analogue is the per-stage labelled timers its Besu
MetricsSystem hangs off the validation pipeline):

- ``span(stage, **labels)`` — a context-manager stopwatch usable from
  asyncio tasks AND worker threads (monotonic ``perf_counter``); on
  exit the duration lands in the per-stage latency histogram
  ``verify_stage_duration_seconds{stage=...}`` and in every trace
  attached to the current context;
- ``trace(name, **labels)`` — opens a ROOT span: creates a `Trace`,
  binds it to the current context (a `ContextVar`, so `asyncio.to_thread`
  carries it into worker threads for free), and on exit completes the
  trace: total duration → the ``complete`` stage histogram, the trace →
  the slow-trace ring (+ the optional sampler);
- ``new_trace``/``attach``/``finish`` — the unbundled form for flows
  whose root outlives one lexical scope (the batching service attaches
  a whole batch's traces around one device dispatch; the benchmark
  holds a trace open across submit→future-resolve);
- ``new_marks``/``current_marks``/``dispatch_marks`` — ONE dispatch's
  life as consecutive marks on ``clock.mono()``: a mark closes the
  phase before it and opens the next, so the phases tile the dispatch
  by construction across the three threads it passes through (event
  loop → ``to_thread`` worker → the breaker's dispatch thread and
  back).  Each closed phase goes once through ``record_stage`` (the
  stage histogram), once into the dispatch's ledger record
  (``phases``, shared by reference: ``infra/dispatchledger.py``
  ``open_record``) and, at ``close()``, in one pass into the batch's
  traces with its exact start; the phases that begin and end on one
  thread are also entered as ``jax.profiler.TraceAnnotation``, so a
  profiler capture holds them on the device trace's own clock;
- ``launched(program, fn, *args)`` — one program call, stamped into
  the dispatch's ``launches`` (two clock reads and an append; nothing
  where no dispatch is marked): the serving seam
  (``infra/aotstore.py``) and the H(m) arena call their programs
  through it;
- a bounded ring of the N slowest complete traces with their stage
  breakdowns, dumped by ``GET /teku/v1/admin/traces``.

Disabled mode (``--tracing off`` / ``set_enabled(False)``) compiles
spans to a shared no-op: ``span()``/``trace()`` return singletons whose
enter/exit do nothing, ``new_trace`` returns None, ``new_marks`` /
``current_marks`` return the shared no-op marks, and record calls
return immediately — no allocation, no lock, no histogram touch, no
annotation.
"""

import itertools
import os
import sys
import threading
import time
from contextlib import contextmanager
from contextvars import ContextVar
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from . import clock
from .env import env_int

from .metrics import GLOBAL_REGISTRY, LATENCY_BUCKETS_S

# The canonical hot-path stages.  Per task: `queue_wait` (enqueue →
# the drain that took it) and `assembly` (the drain), then `complete`,
# the root span's end-to-end total.  Per dispatch, in order, the
# phases that `DispatchMarks` tiles from the instant the service has
# its batch to the instant its last future is settled:
#   thread_hop      service hands over → the dispatch thread runs
#                   (`to_thread` pool wait, thread start)
#   prep_wait       blocked on the guarded provider's turn to pack:
#                   host halves run one at a time (two at once convoy
#                   on the interpreter lock), and a dispatch keeps the
#                   turn through `lock_wait` and its launches, so the
#                   wait holds the other worker's launches too; absent
#                   where the provider has no host half
#   host_prep       wire parse, key lookup, array packing: the
#                   provider's host half, packing alone, off the
#                   device-entry lock (a second, short one under the
#                   lock where a key had to be validated or the H(m)
#                   arena looked up)
#   lock_wait       blocked on the serving triple's device-entry lock,
#                   holding the turn
#   launch_head     the lock is held, no program called yet: the
#                   provider's bookkeeping and ledger record (a second
#                   piece after the H(m) arena's plan, under the lock)
#   device_enqueue  from the first program call: the async launches
#                   (plus XLA compile or program load on a first
#                   shape), each stamped in `DispatchMarks.launches`
#   device_sync     only the blocking wait at the handle's result()
#   return_hop      sync ended → the service runs again on the event loop
#   settle          the verified batch's futures resolved, to the last
# `dispatch` is the service's span around the whole thread round-trip
# (the parent of thread_hop .. return_hop in the span tree);
# `oracle_execute` is a guarded call the oracle served for the device;
# `warmup` is one warm profile's dispatch at bring-up, before the
# provider serves (`warmup()`), in no verification's trace.
STAGES = ("queue_wait", "assembly", "dispatch", "thread_hop",
          "prep_wait", "host_prep", "lock_wait", "launch_head",
          "device_enqueue", "device_sync", "return_hop", "settle",
          "oracle_execute", "complete", "warmup")

# phases that begin and end on ONE thread: entered as profiler
# annotations too (a hop crosses threads and cannot be)
_ANNOTATED = frozenset(("prep_wait", "host_prep", "lock_wait",
                        "launch_head", "device_enqueue", "device_sync",
                        "settle"))

_enabled = True

# Traces bound to the current execution context.  A tuple (not a single
# trace): one device dispatch serves a whole batch of root traces, and
# its host_prep/device_enqueue/device_sync spans must attribute to
# every one.
_CURRENT: ContextVar[Tuple["Trace", ...]] = ContextVar(
    "teku_tpu_traces", default=())

_STAGE_HIST = GLOBAL_REGISTRY.labeled_histogram(
    "verify_stage_duration_seconds",
    "per-stage latency attribution of the verification pipeline",
    labelnames=("stage",), buckets=LATENCY_BUCKETS_S)

# Called with every completed Trace (bench installs one to compute
# per-stage percentiles from raw samples instead of bucket edges).
_sampler: Optional[Callable[["Trace"], None]] = None


def set_enabled(on: bool) -> None:
    global _enabled
    _enabled = bool(on)


def enabled() -> bool:
    return _enabled


def set_sampler(fn: Optional[Callable[["Trace"], None]]) -> None:
    global _sampler
    _sampler = fn


# Process-unique trace ids: the ONE key logs, slow traces, and flight-
# recorder events correlate on.  pid-prefixed so ids from a devnet of
# subprocesses (or a bench child) stay distinguishable in merged logs.
_TRACE_SEQ = itertools.count(1)


def _next_trace_id() -> str:
    return f"{os.getpid():x}-{next(_TRACE_SEQ):06x}"


class Trace:
    """One verification's stage breakdown, root-span start to verdict.

    Thread-safe append: the enqueueing asyncio task, the service worker
    task, and the device-dispatch worker thread all contribute stages.
    """

    __slots__ = ("trace_id", "name", "labels", "t_start", "t_wall",
                 "_end", "stages", "spans", "_lock")

    def __init__(self, name: str, labels: Dict[str, str]):
        self.trace_id = _next_trace_id()
        self.name = name
        self.labels = labels
        # the shared clock-spine pair (infra/clock.py): t_wall and
        # t_start (mono) are ONE stamp, so this trace joins the
        # flight-recorder and dispatch-ledger rings on either axis
        self.t_wall, self.t_start = clock.now()
        self._end: Optional[float] = None
        self.stages: List[Tuple[str, float]] = []
        # (stage, t_mono_start, seconds): the stage intervals the
        # timeline's gap-free span tree is built from.  `stages` keeps
        # the historical (stage, seconds) pairs — consumers iterate it
        # as 2-tuples
        self.spans: List[Tuple[str, float, float]] = []
        self._lock = threading.Lock()

    def add_stage(self, stage: str, seconds: float,
                  t0: Optional[float] = None) -> None:
        if t0 is None:
            # recorded at stage end: derive the start offset
            t0 = time.perf_counter() - seconds
        with self._lock:
            self.stages.append((stage, seconds))
            self.spans.append((stage, t0, seconds))

    def add_spans(self, spans: Sequence[Sequence]) -> None:
        """Several `[stage, t_mono, seconds]` under ONE lock hold: a
        dispatch's phases reach each of its 250 traces in one pass."""
        with self._lock:
            for stage, t0, seconds in spans:
                self.stages.append((stage, seconds))
                self.spans.append((stage, t0, seconds))

    @property
    def complete(self) -> bool:
        return self._end is not None

    @property
    def total_s(self) -> float:
        end = self._end if self._end is not None else time.perf_counter()
        return end - self.t_start

    def to_dict(self) -> dict:
        with self._lock:
            spans = list(self.spans)
        return {"trace_id": self.trace_id,
                "name": self.name,
                "labels": dict(self.labels),
                "t_wall": round(self.t_wall, 3),
                "t_mono": round(self.t_start, 6),
                "total_ms": round(self.total_s * 1e3, 3),
                "stages": [{"stage": s, "ms": round(d * 1e3, 3),
                            "t_mono": round(t0, 6)}
                           for s, t0, d in spans]}


class _SlowTraceRing:
    """Bounded collection of the N slowest COMPLETE traces."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        self._traces: List[Trace] = []
        self._lock = threading.Lock()

    def offer(self, trace: Trace) -> None:
        if self.capacity <= 0:   # ring disabled, histograms still live
            return
        with self._lock:
            if len(self._traces) < self.capacity:
                self._traces.append(trace)
                self._traces.sort(key=lambda t: t.total_s, reverse=True)
                return
            if trace.total_s > self._traces[-1].total_s:
                self._traces[-1] = trace
                self._traces.sort(key=lambda t: t.total_s, reverse=True)

    def snapshot(self) -> List[Trace]:
        with self._lock:
            return list(self._traces)

    def clear(self) -> None:
        with self._lock:
            self._traces.clear()


_RING = _SlowTraceRing(
    env_int("TEKU_TPU_SLOW_TRACE_RING", 32, lo=1))


def slow_traces() -> List[dict]:
    """Slowest complete traces, slowest first, as JSON-able dicts."""
    return [t.to_dict() for t in _RING.snapshot()]


def clear_slow_traces() -> None:
    _RING.clear()


# --------------------------------------------------------------------------
# Recording primitives
# --------------------------------------------------------------------------

def record_stage(stage: str, seconds: float,
                 traces: Optional[Sequence[Trace]] = None,
                 t0: Optional[float] = None) -> None:
    """Attribute an already-measured duration: stage histogram + the
    given traces (default: the context's current traces).  ``t0`` is
    the stage's start on the mono axis (spans pass it exactly; when
    omitted the stage is assumed to end NOW)."""
    if not _enabled:
        return
    _STAGE_HIST.labels(stage=stage).observe(seconds)
    if t0 is None:
        t0 = time.perf_counter() - seconds
    for t in (traces if traces is not None else _CURRENT.get()):
        t.add_stage(stage, seconds, t0=t0)


class _Span:
    __slots__ = ("stage", "_traces", "_t0")

    def __init__(self, stage: str, traces: Optional[Sequence[Trace]]):
        self.stage = stage
        self._traces = traces

    def __enter__(self) -> "_Span":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        record_stage(self.stage, time.perf_counter() - self._t0,
                     self._traces, t0=self._t0)


class _NoopSpan:
    __slots__ = ()

    def __enter__(self):
        # None, not self: `with trace(...) as tr` callers test
        # `tr is None` to skip label stamping in disabled mode
        return None

    def __exit__(self, *exc) -> None:
        pass


_NOOP = _NoopSpan()


def span(stage: str, traces: Optional[Sequence[Trace]] = None):
    """Stopwatch context manager for one pipeline stage.  Records into
    the stage histogram and into `traces` (default: the context's
    current traces, empty tuple when none — histogram-only)."""
    if not _enabled:
        return _NOOP
    return _Span(stage, traces)


# --------------------------------------------------------------------------
# Dispatch marks: one dispatch's life, gap-free
# --------------------------------------------------------------------------

def _annotation(name: str, **meta):
    """An entered `jax.profiler.TraceAnnotation` (`meta` its
    arguments), or None in a process that never imported JAX (no
    device, no profiler: this module must import without it)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    ann = jax.profiler.TraceAnnotation(name, **meta)
    ann.__enter__()
    return ann


class DispatchMarks:
    """One dispatch's phases as consecutive marks on `clock.mono()`.

    `mark(name)` closes the open phase AT the instant it opens `name`,
    so the phases tile [first mark, close] with no gap and no overlap
    whichever thread makes the mark.  No lock: a dispatch's marks come
    one after another (the hand-overs between its threads order them);
    only a timed-out dispatch's orphaned thread can interleave, and
    its record is a fault's best effort.

    `launches` stamps each program call of the dispatch, in launch
    order: `[program, t_mono, seconds]` (and, on a mesh, the chips it
    ran on), `program` being the name the profiler's module line gives
    it less `jit_` and the run id.  A call made while `launch_head` is
    open ends that phase and opens `device_enqueue`; the later ones
    change no phase.

    `phases`, `lock` and `launches` are handed to the dispatch's ledger
    record by reference (`dispatchledger.open_record`), so what the
    service marks after the provider has published the record
    completes it in place.
    A closed phase reaches the stage histogram at once and the batch's
    traces at `close()`, all phases in one pass over the traces: the
    only per-task work, and less of it than a span a phase.
    """

    __slots__ = ("traces", "phases", "lock", "launches", "parent_seq",
                 "record", "_open", "_t0", "_ann", "_copied")

    def __init__(self, traces: Tuple[Trace, ...],
                 parent_seq: Optional[int] = None):
        self.traces = traces
        self.phases: List[list] = []    # [name, t_mono, seconds]
        self.lock: Dict[str, float] = {}    # acquired / released
        self.launches: List[list] = []  # [program, t_mono, seconds]
        self.parent_seq = parent_seq
        self.record: Optional[dict] = None
        self._open: Optional[str] = None
        self._t0 = 0.0
        self._ann = None
        self._copied = 0        # phases the traces already hold

    def mark(self, name: Optional[str]) -> float:
        """Close the open phase now and open `name` (None: close
        only).  Marking the phase that is already open changes
        nothing.  Returns the instant, so the caller needs no clock
        read of its own."""
        now = clock.mono()
        if name == self._open:
            return now
        if self._open is not None:
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
                self._ann = None
            seconds = now - self._t0
            self.phases.append([self._open, round(self._t0, 6),
                                round(seconds, 6)])
            record_stage(self._open, seconds, (), t0=self._t0)
        self._open, self._t0 = name, now
        if name in _ANNOTATED:
            self._ann = _annotation(name)
        return now

    def close(self) -> None:
        """Close the open phase and hand the traces what they do not
        hold yet (a dispatch on the async seam closes once, at its
        end; closing again adds nothing)."""
        self.mark(None)
        fresh = self.phases[self._copied:]
        if fresh:
            self._copied = len(self.phases)
            for trace in self.traces:
                trace.add_spans(fresh)

    def stamp_lock(self, edge: str, at: Optional[float] = None) -> None:
        """`acquired` / `released` of the device-entry lock (`at`: an
        instant the caller already read, e.g. a mark's)."""
        self.lock[edge] = round(clock.mono() if at is None else at, 6)

    @property
    def seq(self) -> Optional[int]:
        """The ledger seq of the record these marks complete (None
        until the provider has published it, or when no device
        dispatch happened: breaker open, invalid wire input)."""
        return (self.record or {}).get("seq")


class _NoMarks:
    """The shared no-op: tracing disabled, or no dispatch marked in
    this context.  `mark` still returns the instant (callers use it as
    their one clock read)."""

    __slots__ = ()
    seq = None
    launches = ()

    def __bool__(self) -> bool:
        return False

    def mark(self, name: Optional[str]) -> float:
        return clock.mono()

    def close(self) -> None:
        pass

    def stamp_lock(self, edge: str, at: Optional[float] = None) -> None:
        pass


_NO_MARKS = _NoMarks()

# The marks of the dispatch this context belongs to.  Travels the way
# `_CURRENT` and `dispatchledger.annotate()` do: `asyncio.to_thread`
# and the breaker's `copy_context()` both carry it.
_MARKS: ContextVar = ContextVar("teku_tpu_dispatch_marks",
                                default=_NO_MARKS)


def new_marks(traces: Sequence[Optional[Trace]] = (),
              parent_seq: Optional[int] = None):
    """Marks for a dispatch whose life outlives one lexical scope (the
    batching service: bind with `attach(traces, marks)` around the
    hand-over, `mark` on the way, `close` after the last future is
    settled).  `parent_seq`: the ledger seq of the failed batch this
    dispatch bisects.  The shared no-op when tracing is disabled."""
    if not _enabled:
        return _NO_MARKS
    return DispatchMarks(tuple(t for t in traces if t is not None),
                         parent_seq)


def current_marks():
    """The context's dispatch marks (the shared no-op when none)."""
    return _MARKS.get()


def launched(program: str, fn: Callable, *args):
    """`fn(*args)`, a program call, stamped on the context's dispatch
    marks as a launch of `program`; with no marks (tracing disabled, no
    dispatch marked) the call alone."""
    marks = _MARKS.get()
    if not marks:
        return fn(*args)
    # a dispatch's first call ends the hold's head at its start
    t0 = (marks.mark("device_enqueue") if marks._open == "launch_head"
          else clock.mono())
    try:
        return fn(*args)
    finally:
        marks.launches.append([program, round(t0, 6),
                               round(clock.mono() - t0, 6)])


@contextmanager
def dispatch_marks(first: str):
    """The bundled form, for a layer that may or may not sit under a
    marked dispatch: inside one, move it on to `first` and leave its
    closing to the layer that opened it; otherwise open marks of this
    block's own (over the context's current traces), closed at its
    end — so a direct caller of the provider still gets the
    provider's phases."""
    marks = _MARKS.get()
    if marks or not _enabled:
        marks.mark(first)
        yield marks
        return
    marks = DispatchMarks(_CURRENT.get())
    marks.mark(first)
    token = _MARKS.set(marks)
    try:
        yield marks
    finally:
        _MARKS.reset(token)
        marks.close()


@contextmanager
def warmup(profile: str, shape: str):
    """One warm profile's dispatch at bring-up: the `warmup` stage of
    the stage histogram and a profiler annotation `warmup` carrying the
    profile's name and its `{lanes}x{kmax}` shape.  In no root trace:
    a warm-up is no verification's latency."""
    if not _enabled:
        yield
        return
    ann = _annotation("warmup", profile=profile, shape=shape)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        if ann is not None:
            ann.__exit__(None, None, None)
        record_stage("warmup", time.perf_counter() - t0, (), t0=t0)


# --------------------------------------------------------------------------
# Root traces
# --------------------------------------------------------------------------

def new_trace(name: str, **labels) -> Optional[Trace]:
    """Create a root trace WITHOUT binding it to the context (use
    `attach` around the calls that should pick it up, `finish` when the
    verdict lands).  None when tracing is disabled — every consumer of
    a trace handle tolerates None."""
    if not _enabled:
        return None
    return Trace(name, labels)


@contextmanager
def attach(traces: Sequence[Optional[Trace]], marks=None):
    """Bind `traces` (Nones filtered) as the context's current traces
    for the duration of the block, and `marks` (`new_marks`) as its
    dispatch marks.  `asyncio.to_thread` copies the context, so spans
    and marks inside a worker thread attribute correctly."""
    live = tuple(t for t in traces if t is not None)
    t_token = _CURRENT.set(live) if live else None
    m_token = _MARKS.set(marks) if marks else None
    try:
        yield
    finally:
        if m_token is not None:
            _MARKS.reset(m_token)
        if t_token is not None:
            _CURRENT.reset(t_token)


def current_traces() -> Tuple[Trace, ...]:
    """Every trace bound to the current context.  Async dispatch
    handles capture these at enqueue time so the device span can
    attribute at sync time, possibly under a different context."""
    return _CURRENT.get()


def current_trace() -> Optional[Trace]:
    """First trace bound to the current context (the enqueue hot path
    stamps this onto queued tasks), or None."""
    traces = _CURRENT.get()
    return traces[0] if traces else None


def current_trace_id() -> str:
    """Trace id of the context's current trace, or "" — the correlation
    key JSON log records and flight-recorder events carry."""
    traces = _CURRENT.get()
    return traces[0].trace_id if traces else ""


def finish(trace: Optional[Trace]) -> None:
    """Complete a root trace: total → the `complete` stage histogram,
    trace → slow ring + sampler.  No-op for None (disabled mode)."""
    if trace is None or trace.complete:
        return
    end = time.perf_counter()
    trace._end = end
    total = end - trace.t_start
    if _enabled:
        _STAGE_HIST.labels(stage="complete").observe(total)
        _RING.offer(trace)
    sampler = _sampler
    if sampler is not None:
        try:
            sampler(trace)
        except Exception:  # pragma: no cover - observer must not kill
            pass


class _RootSpan:
    __slots__ = ("trace", "_token")

    def __init__(self, trace: Trace):
        self.trace = trace

    def __enter__(self) -> Trace:
        self._token = _CURRENT.set((self.trace,))
        return self.trace

    def __exit__(self, *exc) -> None:
        _CURRENT.reset(self._token)
        finish(self.trace)


def trace(name: str, **labels):
    """Open a root span: the returned context manager yields the Trace,
    binds it as current, and finishes it on exit — one trace covers
    gossip-arrival → verdict."""
    if not _enabled:
        return _NOOP
    return _RootSpan(Trace(name, labels))
