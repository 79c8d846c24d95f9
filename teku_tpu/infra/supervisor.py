"""Backend supervisor: background bring-up, hot-swap, circuit breaker.

Device bring-up takes minutes (the first boot compiles the staged
verify programs), short serial probes can never win that race, and a
node that gives up on timeout serves the pure oracle forever without
saying so.  This module changes the shape of bring-up instead of its
timeout values:

- the node boots IMMEDIATELY on the pure oracle (correctness first);
- a supervised background task drives device bring-up with
  unbounded-but-observable patience — state machine
  ``COLD → PROBING → WARMING → READY → DEGRADED/TRIPPED``, each probe
  round an `infra/aio.py:retry_with_backoff` with exponential backoff
  and jitter, every attempt and transition metered;
- on READY the caller-supplied install hook hot-swaps the facade to the
  device provider atomically (one reference assignment; in-flight
  verifications keep the implementation they grabbed);
- after READY every device dispatch runs under a CircuitBreaker:
  per-dispatch deadline, consecutive-failure/timeout threshold trips
  back to the oracle (correctness never degrades — only latency), and
  half-open probing re-closes the circuit.

The reference's analogue is the hard preflight (Teku.java:74) plus
BlstLoader's graceful degradation — but the reference's blst loads in
milliseconds, so it never needed this machine.  A 25-minute bring-up
does.  The design follows outsourced-verification systems where the
fast path is assumed to fail and the system must degrade gracefully
rather than hang (2G2T, arXiv:2602.23464).
"""

import asyncio
import contextvars
import enum
import logging
import threading
import time
from typing import Callable, List, Optional, Tuple

from . import aotstore, compilecache, faults, flightrecorder
from .aio import retry_with_backoff
from .metrics import GLOBAL_REGISTRY, MetricsRegistry
from .service import Service

_LOG = logging.getLogger(__name__)


class BackendState(enum.Enum):
    COLD = "cold"            # oracle serving, bring-up not started
    PROBING = "probing"      # oracle serving, background probe running
    WARMING = "warming"      # probe succeeded, warmup compiles running
    READY = "ready"          # device provider installed and serving
    DEGRADED = "degraded"    # bring-up abandoned: oracle is permanent
    TRIPPED = "tripped"      # breaker open: oracle serving, will retry


class CircuitOpenError(RuntimeError):
    """Dispatch refused: the circuit is open (cooldown not elapsed)."""


class WarmupVetoError(RuntimeError):
    """Raised by a warmup hook to VETO installation: the backend came
    up but produced a wrong verdict on known input.  A device that
    cannot be trusted must never be hot-swapped in — correctness over
    speed, always — so the supervisor goes DEGRADED instead of READY.
    (Ordinary warmup exceptions — e.g. a compile hiccup — still
    install: the first real batch compiles lazily.)"""


class DispatchTimeoutError(RuntimeError):
    """A device dispatch overran its per-dispatch deadline."""


class CircuitBreaker:
    """Per-dispatch deadline + consecutive-failure trip + half-open.

    ``call(fn, *args)`` runs `fn` in a daemon worker thread and waits at
    most `deadline_s`: a wedged device runtime blocks inside C where no
    Python signal can reach it (bench round 3 lost 3×25 minutes to
    exactly that), so the only safe containment is to abandon the wait
    and let the orphaned thread die with the process.  `failure_threshold`
    consecutive failures/timeouts OPEN the circuit; after `cooldown_s`
    one probe call is allowed through (HALF_OPEN) and success re-closes
    it.  The cooldown doubles per consecutive trip up to `max_cooldown_s`
    so a persistently sick device is probed ever more rarely.

    Thread-safe: dispatch sites call from asyncio worker threads.  A
    fresh thread per guarded call is deliberate: it keeps
    abandon-on-timeout trivially correct, and its ~0.1 ms cost is noise
    next to a batched device dispatch (ms) or an oracle verification
    (tens of ms) — revisit only if per-call dispatches ever dominate.
    """

    CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"

    def __init__(self, failure_threshold: int = 3, deadline_s: float = 30.0,
                 cooldown_s: float = 30.0, max_cooldown_s: float = 600.0,
                 name: str = "bls_device",
                 registry: MetricsRegistry = GLOBAL_REGISTRY,
                 clock: Callable[[], float] = time.monotonic,
                 on_state_change: Optional[Callable[[str], None]] = None):
        self.name = name
        self.failure_threshold = failure_threshold
        self.deadline_s = deadline_s
        self.base_cooldown_s = cooldown_s
        self.max_cooldown_s = max_cooldown_s
        self._clock = clock
        self._lock = threading.Lock()
        self._state = self.CLOSED
        self._consecutive_failures = 0
        self._trips = 0
        self._open_until = 0.0
        self.on_state_change = on_state_change
        # when True (set by a supervisor that runs its own synthetic
        # reprobe), the half-open slot is reserved for probe=True calls
        # so live traffic never absorbs the deadline_s probe cost
        self.probe_reserved = False
        self._m_state = registry.state_gauge(
            f"{name}_circuit_state", "circuit breaker state",
            states=(self.CLOSED, self.OPEN, self.HALF_OPEN))
        self._m_state.set_state(self.CLOSED)
        self._m_trips = registry.counter(
            f"{name}_circuit_trips_total", "circuit open transitions")
        self._m_timeouts = registry.counter(
            f"{name}_dispatch_timeouts_total",
            "device dispatches that overran the deadline")
        self._m_failures = registry.counter(
            f"{name}_dispatch_failures_total",
            "device dispatches that raised")

    @property
    def state(self) -> str:
        return self._state

    def _set_state(self, new: str) -> None:
        if new == self._state:
            return
        self._state = new
        self._m_state.set_state(new)
        cb = self.on_state_change
        if cb is not None:
            try:
                cb(new)
            except Exception:  # pragma: no cover - observer must not kill
                _LOG.exception("breaker state observer failed")

    # ------------------------------------------------------------------
    def allow(self, probe: bool = False) -> bool:
        """Admission check: False while OPEN and cooling down; flips to
        HALF_OPEN (admitting ONE probe call) once the cooldown elapses.
        With `probe_reserved`, only probe=True callers may take the
        half-open slot."""
        with self._lock:
            if self._state == self.CLOSED:
                return True
            if self._state == self.OPEN:
                if self._clock() >= self._open_until and (
                        probe or not self.probe_reserved):
                    self._set_state(self.HALF_OPEN)
                    return True
                return False
            # HALF_OPEN: one probe already in flight; hold the rest back
            return False

    def record_success(self) -> None:
        reclosed = False
        with self._lock:
            self._consecutive_failures = 0
            if self._state != self.CLOSED:
                _LOG.info("circuit %s: probe succeeded, re-closing",
                          self._m_state.name)
                self._trips = 0
                reclosed = True
            self._set_state(self.CLOSED)
        if reclosed:
            flightrecorder.record("breaker_reclose", breaker=self.name)

    def record_failure(self, timeout: bool = False) -> None:
        (self._m_timeouts if timeout else self._m_failures).inc()
        with self._lock:
            self._consecutive_failures += 1
            should_trip = (
                self._state == self.HALF_OPEN
                or self._consecutive_failures >= self.failure_threshold)
            if should_trip:
                self._trips += 1
                self._m_trips.inc()
                cooldown = min(
                    self.base_cooldown_s * (2 ** (self._trips - 1)),
                    self.max_cooldown_s)
                self._open_until = self._clock() + cooldown
                newly_open = self._state != self.OPEN
                if newly_open:
                    _LOG.warning(
                        "circuit %s OPEN after %d consecutive "
                        "failures (cooldown %.1fs)", self._m_state.name,
                        self._consecutive_failures, cooldown)
                consecutive = self._consecutive_failures
                self._consecutive_failures = 0
                self._set_state(self.OPEN)
            else:
                return
        # outside the lock: the trip event (with the tripping verify's
        # trace id — dispatch runs under the caller's copied context)
        # and the automatic JSONL dump must not hold the breaker
        flightrecorder.record(
            "breaker_trip", breaker=self.name,
            consecutive_failures=consecutive,
            timeout=timeout, cooldown_s=round(cooldown, 1),
            reopened=not newly_open)
        flightrecorder.RECORDER.dump_throttled(
            f"breaker trip: {self.name}")

    # ------------------------------------------------------------------
    def call(self, fn: Callable, *args, probe: bool = False, **kwargs):
        """Run one guarded dispatch.  Raises CircuitOpenError without
        touching the device while the circuit is open; otherwise
        enforces the per-dispatch deadline and feeds the verdict back
        into the trip counters."""
        if not self.allow(probe=probe):
            raise CircuitOpenError(
                f"circuit open ({self._open_until - self._clock():.1f}s "
                "cooldown remaining)")
        box: dict = {}
        done = threading.Event()
        # carry the caller's context (tracing's current traces) into
        # the dispatch thread — a raw Thread drops contextvars, which
        # would detach device spans from the traces awaiting them
        ctx = contextvars.copy_context()

        def run():
            try:
                box["ok"] = ctx.run(fn, *args, **kwargs)
            except BaseException as exc:  # noqa: BLE001 - reported below
                box["err"] = exc
            finally:
                done.set()

        t = threading.Thread(target=run, daemon=True,
                             name="breaker-dispatch")
        t.start()
        if not done.wait(self.deadline_s):
            self.record_failure(timeout=True)
            raise DispatchTimeoutError(
                f"dispatch exceeded {self.deadline_s:.1f}s deadline "
                "(wedged device runtime?)")
        if "err" in box:
            self.record_failure()
            raise box["err"]
        self.record_success()
        return box["ok"]


class BackendSupervisor(Service):
    """Owns backend bring-up and the READY/TRIPPED lifecycle.

    Pluggable hooks keep this module accelerator-agnostic (and make the
    fault-injection tests hermetic):

    - ``probe()``   (thread context) build + prove the device provider;
      returns an opaque backend handle.  Raises on failure.  The
      ``backend.init`` fault site fires here.
    - ``warmup(backend)`` (thread context, optional) pre-compile the hot
      programs so the first real batch doesn't stall.
    - ``install(backend)`` hot-swap the facades to the device provider.
    - ``uninstall()`` (optional) restore the oracle on stop.

    The supervisor records every state transition with a timestamp in
    ``self.transitions`` — bench.py copies them into the heartbeat JSON
    so BENCH_*.json finally shows WHY a run served which backend.
    """

    _STATE_ORDER = (BackendState.COLD, BackendState.PROBING,
                    BackendState.WARMING, BackendState.READY,
                    BackendState.DEGRADED, BackendState.TRIPPED)

    def __init__(self, probe: Callable, install: Callable,
                 warmup: Optional[Callable] = None,
                 uninstall: Optional[Callable] = None,
                 reprobe: Optional[Callable] = None,
                 breaker: Optional[CircuitBreaker] = None,
                 name: str = "bls_backend",
                 registry: MetricsRegistry = GLOBAL_REGISTRY,
                 probe_attempts_per_round: int = 3,
                 probe_base_delay_s: float = 1.0,
                 round_delay_s: float = 5.0,
                 max_round_delay_s: float = 600.0,
                 max_rounds: Optional[int] = None,
                 warmup_deadline_s: float = 3600.0):
        super().__init__(name)
        self._probe = probe
        self._warmup = warmup
        self._install = install
        self._uninstall = uninstall
        # optional synthetic known-good device dispatch: when TRIPPED,
        # the supervisor drives half-open probing itself so no live
        # request is ever held hostage for a deadline_s probe
        self._reprobe = reprobe
        self._reprobe_task: Optional[asyncio.Task] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        if reprobe is not None and breaker is not None:
            # the supervisor owns half-open probing: reserve the slot
            # so live traffic is never held for a deadline_s probe
            breaker.probe_reserved = True
        self.breaker = breaker
        if breaker is not None:
            breaker.on_state_change = self._on_breaker_state
        self.probe_attempts_per_round = probe_attempts_per_round
        self.probe_base_delay_s = probe_base_delay_s
        self.round_delay_s = round_delay_s
        self.max_round_delay_s = max_round_delay_s
        self.max_rounds = max_rounds
        self.warmup_deadline_s = warmup_deadline_s
        self.backend = None
        self.backend_detail: str = ""
        # optional mesh self-description ({devices, n_devices, axis})
        # the loader's install hook stamps for multi-chip backends —
        # surfaced in snapshot() so readiness self-describes the mesh
        self.mesh: Optional[dict] = None
        # WARMING's compile-cache verdict ({"hits", "misses", "s"}):
        # a warm boot shows hits>0, misses==0 — the multi-minute
        # per-shape compiles were served from disk
        self.warmup_cache: dict = {}
        self.transitions: List[Tuple[str, float]] = []
        self._task: Optional[asyncio.Task] = None
        self._ready_event = asyncio.Event()
        self._m_state = registry.state_gauge(
            f"{name}_state", "backend supervisor state",
            states=tuple(s.value for s in self._STATE_ORDER))
        self._m_transitions = registry.counter(
            f"{name}_state_transitions_total",
            "supervisor state transitions")
        self._m_probe_failures = registry.counter(
            f"{name}_probe_failures_total", "failed bring-up probes")
        self._m_probe_seconds = registry.gauge(
            f"{name}_last_probe_seconds",
            "wall seconds of the last probe attempt")
        self.state_b = BackendState.COLD
        self._record(BackendState.COLD)

    # ------------------------------------------------------------------
    def _record(self, state: BackendState) -> None:
        self.state_b = state
        self.transitions.append((state.value, time.time()))
        self._m_state.set_state(state.value)
        self._m_transitions.inc()
        flightrecorder.record("backend_state", supervisor=self.name,
                              state=state.value,
                              detail=self.backend_detail)
        _LOG.info("backend supervisor %s: %s", self.name, state.value)

    def _on_breaker_state(self, breaker_state: str) -> None:
        """Breaker observer: OPEN ⇒ TRIPPED (oracle serving), re-CLOSED
        after READY ⇒ READY again.  Runs on whatever thread dispatched."""
        # edge-triggered: repeated HALF_OPEN→OPEN cycles of a
        # persistently sick device must not append duplicate 'tripped'
        # entries (transitions feed every heartbeat snapshot)
        if (breaker_state == CircuitBreaker.OPEN
                and self.state_b is BackendState.READY):
            self._record(BackendState.TRIPPED)
            if self._reprobe is not None and self._loop is not None:
                try:
                    self._loop.call_soon_threadsafe(
                        self._ensure_reprobe_task)
                except RuntimeError:  # pragma: no cover - shutdown
                    pass
        elif (breaker_state == CircuitBreaker.CLOSED
                and self.state_b is BackendState.TRIPPED):
            self._record(BackendState.READY)

    def _ensure_reprobe_task(self) -> None:
        if self._reprobe_task is None or self._reprobe_task.done():
            self._reprobe_task = asyncio.create_task(
                self._reprobe_loop(), name=f"{self.name}-reprobe")

    async def _reprobe_loop(self) -> None:
        """Half-open probing off the hot path: once the cooldown
        elapses, dispatch a synthetic known-good batch instead of
        letting a live verification absorb the deadline_s probe cost.
        Success re-closes the circuit (READY); failure re-opens it with
        the doubled cooldown and this loop waits again."""
        br = self.breaker
        while self.state_b is BackendState.TRIPPED:
            await asyncio.sleep(
                max(br._open_until - br._clock(), 0.2))
            if self.state_b is not BackendState.TRIPPED:
                break
            try:
                await self._in_daemon_thread(
                    lambda: br.call(self._reprobe, probe=True),
                    f"{self.name}-reprobe")
                _LOG.info("backend %s reprobe succeeded", self.name)
            except asyncio.CancelledError:
                raise
            except Exception as exc:
                _LOG.info("backend %s reprobe failed (%s: %s); circuit "
                          "stays open", self.name,
                          type(exc).__name__, exc)

    @property
    def backend_state(self) -> str:
        return self.state_b.value

    def snapshot(self) -> dict:
        """One JSON-able dict for heartbeats / the bench harness."""
        out = {"state": self.state_b.value,
               "detail": self.backend_detail,
               "transitions": [{"state": s, "t": round(t, 2)}
                               for s, t in self.transitions]}
        if self.breaker is not None:
            out["circuit"] = self.breaker.state
        if self.warmup_cache:
            out["warmup_cache"] = self.warmup_cache
        if self.mesh:
            out["mesh"] = self.mesh
        return out

    async def wait_ready(self, timeout_s: Optional[float] = None) -> bool:
        """Test/bench convenience: block until READY (or timeout)."""
        try:
            await asyncio.wait_for(self._ready_event.wait(), timeout_s)
            return True
        except asyncio.TimeoutError:
            return False

    # ------------------------------------------------------------------
    async def do_start(self) -> None:
        self._loop = asyncio.get_running_loop()
        self._task = asyncio.create_task(self._run(),
                                         name=f"{self.name}-supervisor")

    async def do_stop(self) -> None:
        for task_attr in ("_task", "_reprobe_task"):
            task = getattr(self, task_attr)
            if task is not None:
                task.cancel()
                try:
                    await task
                except asyncio.CancelledError:
                    pass
                setattr(self, task_attr, None)
        if self._uninstall is not None:
            try:
                self._uninstall()
            except Exception:  # pragma: no cover - best-effort restore
                _LOG.exception("backend uninstall failed")

    # ------------------------------------------------------------------
    @staticmethod
    async def _in_daemon_thread(fn: Callable, name: str):
        """Run `fn` in an explicit DAEMON thread (same containment as
        CircuitBreaker.call): asyncio.to_thread would use the default
        executor, whose non-daemon workers block process shutdown for
        as long as a wedged device init hangs — the exact ~25-minute
        wedge this module exists to contain."""
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()

        def deliver(outcome, value):
            if fut.cancelled():
                return
            if outcome == "ok":
                fut.set_result(value)
            else:
                fut.set_exception(value)

        def run():
            try:
                result = ("ok", fn())
            except BaseException as exc:  # noqa: BLE001 - delivered
                result = ("err", exc)
            try:
                loop.call_soon_threadsafe(deliver, *result)
            except RuntimeError:  # pragma: no cover - loop shut down
                pass              # mid-hang: nobody left to deliver to
        threading.Thread(target=run, daemon=True, name=name).start()
        return await fut

    async def _probe_once(self):
        def run():
            # `backend.init` fault site runs IN the probe thread so a
            # SlowRamp models a slow bring-up without blocking the loop
            faults.check("backend.init")
            return self._probe()

        t0 = time.monotonic()
        try:
            return await self._in_daemon_thread(
                run, f"{self.name}-probe")
        finally:
            self._m_probe_seconds.set(round(time.monotonic() - t0, 3))

    async def _run(self) -> None:
        self._record(BackendState.PROBING)
        rounds = 0
        delay = self.round_delay_s
        backend = None
        while True:
            try:
                # one bounded retry_with_backoff round; the OUTER loop is
                # the unbounded patience, each round observable via logs
                # and the probe-failure counter
                backend = await retry_with_backoff(
                    self._probe_once,
                    attempts=self.probe_attempts_per_round,
                    base_delay_s=self.probe_base_delay_s,
                    jitter=0.25, what=f"{self.name} probe",
                    giveup=lambda e: isinstance(
                        e, (ImportError, ModuleNotFoundError)))
                break
            except asyncio.CancelledError:
                raise
            except Exception as exc:
                rounds += 1
                self._m_probe_failures.inc()
                non_retryable = isinstance(
                    exc.__cause__, (ImportError, ModuleNotFoundError))
                if non_retryable or (self.max_rounds is not None
                                     and rounds >= self.max_rounds):
                    self.backend_detail = (
                        f"bring-up abandoned after {rounds} round(s): "
                        f"{exc.__cause__ or exc}")
                    _LOG.warning(
                        "backend %s DEGRADED (oracle is permanent): %s",
                        self.name, self.backend_detail)
                    self._record(BackendState.DEGRADED)
                    return
                _LOG.warning(
                    "backend %s probe round %d failed (%s); retrying "
                    "in %.1fs", self.name, rounds, exc.__cause__ or exc,
                    delay)
                await asyncio.sleep(delay)
                delay = min(delay * 2, self.max_round_delay_s)
        self._record(BackendState.WARMING)
        if self._warmup is not None:
            # WARMING pays the hot-program compiles off-path; the
            # persistent compile cache decides whether that costs
            # minutes (fresh compiles) or seconds (cache loads) —
            # report which, so a slow bring-up explains itself
            cache_before = compilecache.stats()
            aot_before = aotstore.stats()
            warm_t0 = time.monotonic()
            # an overrun or a raising warmup installs anyway, with its
            # compiles still pending: the snapshot says which happened
            finished = False
            # what the warmup says of itself (the loader's: the key
            # buckets it warmed), merged into the snapshot
            warmed = None
            try:
                # bounded: WARMING must not become the one phase that
                # can wedge forever (probing retries, READY has the
                # breaker).  On deadline the orphaned thread keeps
                # compiling and we install anyway — a still-wedged
                # device then trips the breaker, whose reprobe cycle
                # owns recovery from there
                warmed = await asyncio.wait_for(
                    self._in_daemon_thread(
                        lambda: self._warmup(backend),
                        f"{self.name}-warmup"),
                    self.warmup_deadline_s)
                finished = True
            except asyncio.TimeoutError:
                _LOG.warning(
                    "backend %s warmup exceeded %.0fs; installing "
                    "anyway (breaker owns a wedged device)",
                    self.name, self.warmup_deadline_s)
            except asyncio.CancelledError:
                raise
            except WarmupVetoError as exc:
                # the device executed but got a KNOWN answer wrong:
                # installing it would degrade correctness, not latency
                self.backend_detail = f"warmup veto: {exc}"
                _LOG.error("backend %s DEGRADED (untrusted device, "
                           "oracle is permanent): %s", self.name, exc)
                self._record(BackendState.DEGRADED)
                return
            except Exception:
                _LOG.exception("backend warmup failed; installing "
                               "anyway (first batch compiles lazily)")
            moved = compilecache.delta(cache_before)
            aot_moved = aotstore.delta(aot_before)
            self.warmup_cache = {
                "hits": moved["hits"], "misses": moved["misses"],
                # AOT-store loads skip XLA entirely; kernel_compiles
                # counts the backend compiles above the kernel-grade
                # threshold this warmup actually performed — the
                # "warm boot does zero fresh compiles" observable
                "aot_loads": aot_moved["loads"],
                "backend_compiles": moved["backend_compiles"],
                "kernel_compiles": moved["kernel_compiles"],
                "finished": finished,
                "s": round(time.monotonic() - warm_t0, 1)}
            if isinstance(warmed, dict):
                self.warmup_cache.update(warmed)
            flightrecorder.record("warmup_cache", supervisor=self.name,
                                  **self.warmup_cache)
            _LOG.info(
                "backend %s warmup in %.1fs: %d AOT load(s), %d "
                "compile-cache load(s), %d fresh compile(s) (%d "
                "kernel-grade)%s", self.name,
                self.warmup_cache["s"], aot_moved["loads"],
                moved["hits"], moved["misses"],
                moved["kernel_compiles"],
                "" if compilecache.cache_dir() else
                " (persistent cache not configured)")
        self.backend = backend
        self._install(backend)
        self._record(BackendState.READY)
        self._ready_event.set()
