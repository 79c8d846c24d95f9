"""Mesh self-healing: shard-level fault isolation, ejection, reshape.

PR 10 made the mesh the production verify backend but kept the
whole-backend failure semantics of PR 1: one wedged shard trips the
ENTIRE mesh breaker and every verify drops to the host oracle — a
cliff from N-chip device throughput to ~CPU-oracle speed, exactly when
a 1M-validator node can least afford it.  This module makes losing a
chip cost 1/N capacity instead of all of it (ACE Runtime, PAPERS.md:
sub-second cryptographic finality as a *runtime* property that
survives component failure):

- ``DeviceHealthLedger`` — breaker-style per-DEVICE health: every
  mesh dispatch failure is attributed to a device by an isolation
  probe sweep (a collective failure names no chip, so each live
  device answers a tiny deadline-bounded probe; the wedged one can't),
  and ``trip_threshold`` consecutive attributed failures eject it.
- ``MeshHealer`` — the eject → reshape → readmit machine.  On
  ejection it re-plans onto the largest surviving pow-2 device subset
  (``parallel.make_mesh(devices=...)`` + the same group-aligned
  planner), AOT-warms the shrunken sharded shape set OFF the gossip
  path (the loader's warmup machinery), and atomically swaps the
  serving provider: in-flight verifies either complete on the old
  plan or retry on the new one — zero wrong verdicts, zero dropped
  tasks (the PR 1 hot-swap invariant, applied mid-mesh).  A
  background reprobe (the supervisor's half-open-slot idea, extended
  to ejected devices) re-admits a recovered chip and the mesh grows
  back.  The oracle remains the LAST resort, when the mesh shrinks to
  zero healthy devices.

The whole cycle is measured as a recovery-time objective:
``bls_mesh_reshape_total{direction,devices}`` counts every reshape,
``bls_mesh_recovery_seconds`` is the last eject→serving recovery, and
``mesh_eject`` / ``mesh_reshape`` / ``mesh_readmit`` flight-recorder
events carry the triggering dispatch's trace id so the doctor can
name the dispatch that killed a chip.  bench.py's ``chaos`` phase and
the loadgen ``chaos_device_loss`` scenario drive this REAL machinery
(faults keyed by device index at the ``bls.mesh_shard`` site), and
tools/bench_diff.py gates recovery ≤ ``mesh_recovery_s_max`` with
zero wrong verdicts and zero protected-class sheds.

The healer is deliberately GENERIC over the backend world: production
wires jax devices + ``JaxBls12381(mesh=...)`` factories
(crypto/bls/loader.py), the loadgen chaos scenario wires model devices
on a virtual clock — same ledger, same reshape state machine, same
events, so the control plane under chaos test IS the production code.
"""

import logging
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ..infra import flightrecorder, timeline, tracing
from ..infra.env import env_float, env_int
from ..infra.metrics import GLOBAL_REGISTRY, MetricsRegistry
from ..infra.pow2 import floor_pow2 as _floor_pow2

_LOG = logging.getLogger(__name__)

# the keyed fault site shared by the collective dispatch (keys = the
# live device index set) and the per-device isolation probes (keys =
# one index) — see infra/faults.py
FAULT_SITE = "bls.mesh_shard"

# closed {direction} vocabulary of the reshape counter (linted)
DIRECTIONS = ("shrink", "grow")

# Shared readout for the supplier gauges: one process serves one mesh,
# so (like parallel._ACTIVE) the most recent healer activity is the
# truthful value even when tests construct several healers.
_STATE = {"recovery_s": 0.0, "ejected": 0, "live": 0, "configured": 0}

GLOBAL_REGISTRY.gauge(
    "bls_mesh_recovery_seconds",
    "wall seconds of the last completed mesh recovery (dispatch "
    "failure -> reshaped mesh serving); 0 = no recovery yet",
    supplier=lambda: float(_STATE["recovery_s"]))
GLOBAL_REGISTRY.gauge(
    "bls_mesh_ejected_devices",
    "devices currently ejected from the verify mesh by the "
    "self-healing ledger",
    supplier=lambda: float(_STATE["ejected"]))
# the reshape family registers at import (complete from scrape 1, and
# the exposition lint can assert its label contract without needing a
# healer built); per-healer instances get_or_create the same family
GLOBAL_REGISTRY.labeled_counter(
    "bls_mesh_reshape_total",
    "self-healing mesh reshapes by direction (shrink = device "
    "ejected, grow = device readmitted) and the NEW live device count",
    labelnames=("direction", "devices"))


class InstallVetoError(RuntimeError):
    """Raised by a reshape-warm hook to VETO installing the reshaped
    backend: the surviving subset executed but produced a wrong
    verdict on known input (the loader maps WarmupVetoError here).
    Correctness over capacity, always — the old pair keeps serving
    and its breaker owns containment."""


def trip_threshold_default() -> int:
    """Consecutive ATTRIBUTED failures (dispatch failure + failed
    isolation probe) before a device is ejected.  Default 1: an
    ejection already requires two independent pieces of evidence."""
    return max(1, env_int("TEKU_TPU_MESH_DEVICE_TRIP", 1))


def probe_deadline_default() -> float:
    return max(0.1, env_float("TEKU_TPU_MESH_PROBE_DEADLINE_S", 5.0))


def reprobe_interval_default() -> float:
    return max(0.05, env_float("TEKU_TPU_MESH_REPROBE_S", 15.0))


class DeviceHealthLedger:
    """Per-device breaker-style health accounting for one mesh.

    Devices are addressed by index into the CONFIGURED (boot-time)
    device list; ``live()``/``ejected()`` return indices in that
    original order so the reshape's "largest surviving pow-2 subset"
    is deterministic.  Thread-safe: failures arrive from breaker
    dispatch threads, probes from the heal thread, readmits from the
    reprobe thread."""

    LIVE, EJECTED = "live", "ejected"

    def __init__(self, device_names: Sequence[str],
                 trip_threshold: Optional[int] = None):
        self.device_names = [str(d) for d in device_names]
        self.trip_threshold = (trip_threshold
                               if trip_threshold is not None
                               else trip_threshold_default())
        self._lock = threading.Lock()
        n = len(self.device_names)
        self._state = [self.LIVE] * n
        self._consecutive = [0] * n
        self._failures = [0] * n
        self._ejects = [0] * n
        self._last_error = [""] * n

    def record_failure(self, idx: int, error: str = "") -> bool:
        """One attributed failure; True when it crossed the trip
        threshold (the caller should eject)."""
        with self._lock:
            self._consecutive[idx] += 1
            self._failures[idx] += 1
            self._last_error[idx] = str(error)[:200]
            return (self._state[idx] == self.LIVE
                    and self._consecutive[idx] >= self.trip_threshold)

    def record_success(self, idx: int) -> None:
        with self._lock:
            self._consecutive[idx] = 0

    def eject(self, idx: int, count: bool = True) -> bool:
        """``count=False`` is the readmit-ROLLBACK path (a grow
        reshape that failed to install): the device goes back to
        ejected without inflating its eject count — a failed install
        is not a new flap."""
        with self._lock:
            if self._state[idx] == self.EJECTED:
                return False
            self._state[idx] = self.EJECTED
            if count:
                self._ejects[idx] += 1
            return True

    def readmit(self, idx: int) -> bool:
        with self._lock:
            if self._state[idx] == self.LIVE:
                return False
            self._state[idx] = self.LIVE
            self._consecutive[idx] = 0
            return True

    def live(self) -> List[int]:
        with self._lock:
            return [i for i, s in enumerate(self._state)
                    if s == self.LIVE]

    def ejected(self) -> List[int]:
        with self._lock:
            return [i for i, s in enumerate(self._state)
                    if s == self.EJECTED]

    def eject_count(self, idx: int) -> int:
        with self._lock:
            return self._ejects[idx]

    def snapshot(self) -> dict:
        with self._lock:
            return {"devices": [
                {"index": i, "name": self.device_names[i],
                 "state": self._state[i],
                 "consecutive_failures": self._consecutive[i],
                 "failures_total": self._failures[i],
                 "ejects_total": self._ejects[i],
                 "last_error": self._last_error[i]}
                for i in range(len(self.device_names))],
                "trip_threshold": self.trip_threshold}


class MeshHealer:
    """Eject → reshape → readmit over a pluggable backend world.

    - ``probe(index)`` (thread context, deadline-bounded by the
      healer) proves device `index` executes; raises/hangs when sick.
      Production probes run a tiny computation placed on the device;
      both worlds consult ``faults.check(FAULT_SITE, keys=(index,))``
      so the chaos harness can wedge exactly one chip.
    - ``make_backend(live_indices)`` builds a provider for the pow-2
      live subset (len >= 2: a sharded mesh; len == 1: single-device;
      empty tuple -> return None, oracle is the last resort).
    - ``warm(backend, live_indices)`` (optional) AOT-compiles the new
      shape set OFF the serving path; exceptions install anyway (the
      first real batch compiles lazily — same rule as supervisor
      warmup).
    - ``install(backend, live_indices, epoch)`` atomically swaps the
      serving provider (``GuardedBls12381.swap_device``) and updates
      the readiness surfaces.  Called with ``backend=None`` when the
      mesh shrank to zero — the caller keeps the oracle serving.
    """

    def __init__(self, device_names: Sequence[str],
                 probe: Callable[[int], None],
                 make_backend: Callable[[Tuple[int, ...]], object],
                 install: Callable[[object, Tuple[int, ...], int], None],
                 warm: Optional[Callable] = None,
                 trip_threshold: Optional[int] = None,
                 probe_deadline_s: Optional[float] = None,
                 reprobe_s: Optional[float] = None,
                 min_mesh: int = 2,
                 name: str = "bls_mesh",
                 registry: MetricsRegistry = GLOBAL_REGISTRY,
                 recorder: Optional[flightrecorder.FlightRecorder]
                 = None):
        self.name = name
        self.probe = probe
        self.make_backend = make_backend
        self.install = install
        self.warm = warm
        self.min_mesh = min_mesh
        self.trip_threshold = (trip_threshold
                               if trip_threshold is not None
                               else trip_threshold_default())
        self.probe_deadline_s = (probe_deadline_s
                                 if probe_deadline_s is not None
                                 else probe_deadline_default())
        self.reprobe_s = (reprobe_s if reprobe_s is not None
                          else reprobe_interval_default())
        self.ledger = DeviceHealthLedger(device_names,
                                         self.trip_threshold)
        self.configured_n = len(self.ledger.device_names)
        self.epoch = 0
        self.last_recovery_s: Optional[float] = None
        self.reshapes = {d: 0 for d in DIRECTIONS}
        self._recorder = recorder or flightrecorder.RECORDER
        self._live: Tuple[int, ...] = tuple(
            range(self.configured_n))
        self._lock = threading.Lock()       # heal single-flight state
        self._reshape_lock = threading.Lock()
        self._healing = False
        # failure contexts queued while a heal is in flight
        self._pending: List[Tuple[str, bool, Optional[str]]] = []
        self._closed = False
        self._reprobe_thread: Optional[threading.Thread] = None
        self._m_reshape = registry.labeled_counter(
            "bls_mesh_reshape_total",
            "self-healing mesh reshapes by direction (shrink = device "
            "ejected, grow = device readmitted) and the NEW live "
            "device count",
            labelnames=("direction", "devices"))
        _STATE["configured"] = self.configured_n
        _STATE["live"] = self.configured_n
        _STATE["ejected"] = 0

    # ------------------------------------------------------------------
    @property
    def live_devices(self) -> Tuple[int, ...]:
        return self._live

    @property
    def healing(self) -> bool:
        """True while a heal sweep is in flight (the loadgen holds its
        virtual clock over one, as it does over a dispatch)."""
        with self._lock:
            return self._healing

    def close(self) -> None:
        self._closed = True

    def snapshot(self) -> dict:
        """JSON-able state for the supervisor's readiness snapshot."""
        return {"configured": self.configured_n,
                "live": len(self._live),
                "live_devices": [self.ledger.device_names[i]
                                 for i in self._live],
                "ejected": [self.ledger.device_names[i]
                            for i in self.ledger.ejected()],
                "epoch": self.epoch,
                "reshapes": dict(self.reshapes),
                "last_recovery_s": self.last_recovery_s,
                "trip_threshold": self.trip_threshold,
                "reprobe_s": self.reprobe_s}

    # ------------------------------------------------------------------
    def on_dispatch_failure(self, error: str = "",
                            timeout: bool = False,
                            trace_id: Optional[str] = None) -> None:
        """A mesh dispatch failed/overran: attribute it to a device in
        a background heal thread (single-flight; failures arriving
        mid-heal queue ONE follow-up sweep).  Never blocks or raises —
        it is called from the guarded dispatch's failure path, where
        the oracle is already serving the caller."""
        if self._closed:
            return
        if trace_id is None:
            trace_id = (tracing.current_trace_id()
                        or self._recorder.last_trace_id())
        with self._lock:
            if self._healing:
                # queue THIS failure's context: the follow-up sweep's
                # eject events must cite a dispatch that actually
                # failed during the heal, not replay the first one's
                self._pending.append((error, timeout, trace_id))
                return
            self._healing = True
        threading.Thread(
            target=self._heal_loop, args=(error, timeout, trace_id),
            daemon=True, name=f"{self.name}-heal").start()

    def _heal_loop(self, error, timeout, trace_id) -> None:
        try:
            while True:
                self._heal_once(error, timeout, trace_id)
                with self._lock:
                    if not self._pending:
                        self._healing = False
                        return
                    # the most recent failure's context drives the
                    # follow-up sweep (overlapping failures collapse
                    # to one sweep; its events cite the latest)
                    error, timeout, trace_id = self._pending[-1]
                    self._pending.clear()
        except Exception:  # pragma: no cover - heal must never crash
            _LOG.exception("mesh heal failed")
            with self._lock:
                self._healing = False

    def _heal_once(self, error, timeout, trace_id) -> None:
        t0 = time.monotonic()
        live = self.ledger.live()
        if not live:
            return
        verdicts = self._probe_devices(live)
        tripped = []
        for idx in live:
            err = verdicts.get(idx)
            if err is None:
                self.ledger.record_success(idx)
            elif self.ledger.record_failure(idx, err):
                tripped.append((idx, err))
        if not tripped:
            # unattributable collective failure (e.g. host-side): the
            # whole-backend breaker keeps owning it — defense in depth
            self._recorder.record(
                "mesh_heal_unattributed", trace_id=trace_id,
                healer=self.name, probed=len(live),
                dispatch_error=str(error)[:200],
                dispatch_timeout=timeout)
            return
        for idx, err in tripped:
            self.ledger.eject(idx)
            _STATE["ejected"] = len(self.ledger.ejected())
            _LOG.warning(
                "mesh device %s EJECTED (%s; dispatch failure: %s)",
                self.ledger.device_names[idx], err,
                error or ("deadline overrun" if timeout else "?"))
            self._recorder.record(
                "mesh_eject", trace_id=trace_id, healer=self.name,
                device=self.ledger.device_names[idx], index=idx,
                probe_error=err, dispatch_error=str(error)[:200],
                dispatch_timeout=timeout,
                eject_count=self.ledger.eject_count(idx))
            # mesh overlay track on the causal timeline
            timeline.instant(
                "mesh", "eject", trace_id=trace_id,
                device=self.ledger.device_names[idx])
        try:
            self._reshape("shrink", recovery_t0=t0, trace_id=trace_id)
        finally:
            # ejected devices must ALWAYS end up watched, even when
            # the reshape itself raised (make_backend/install
            # hiccup): the reprobe loop also RECONCILES the live set
            # on its next tick, so a failed shrink install is retried
            # instead of stranding the wedged full-width mesh
            self._ensure_reprobe()

    def _probe_devices(self, idxs: Sequence[int]) -> Dict[int, Optional[str]]:
        """Deadline-bounded isolation probes, all devices in parallel
        (a wedged device must cost ONE deadline, not one per chip).
        Returns {index: None (healthy) | error string}."""
        boxes: Dict[int, dict] = {i: {} for i in idxs}
        events: Dict[int, threading.Event] = {
            i: threading.Event() for i in idxs}

        def run(i):
            try:
                self.probe(i)
            except BaseException as exc:  # noqa: BLE001 - verdict
                boxes[i]["err"] = f"{type(exc).__name__}: {exc}"
            finally:
                events[i].set()

        for i in idxs:
            threading.Thread(target=run, args=(i,), daemon=True,
                             name=f"{self.name}-probe-{i}").start()
        deadline = time.monotonic() + self.probe_deadline_s
        out: Dict[int, Optional[str]] = {}
        for i in idxs:
            if not events[i].wait(max(deadline - time.monotonic(),
                                      0.001)):
                out[i] = (f"probe overran "
                          f"{self.probe_deadline_s:.1f}s deadline")
            else:
                out[i] = boxes[i].get("err")
        return out

    # ------------------------------------------------------------------
    def _desired_live(self) -> Tuple[int, ...]:
        """The live subset the mesh SHOULD be serving: the largest
        pow-2 prefix of the healthy devices (one chip single-device,
        zero = oracle).  ONE definition — the reshape targets it and
        the reprobe loop reconciles the installed set against it."""
        healthy = self.ledger.live()
        n = _floor_pow2(len(healthy)) if healthy else 0
        if n < self.min_mesh:
            # below the smallest shardable mesh: one healthy chip
            # still serves single-device; zero means the oracle is
            # the last resort (install(None) — caller keeps it)
            n = 1 if healthy else 0
        return tuple(healthy[:n])

    def _reshape(self, direction: str, recovery_t0: Optional[float]
                 = None, trace_id: Optional[str] = None) -> bool:
        """Re-plan onto the largest surviving pow-2 subset, AOT-warm
        it off-path, and atomically install.  Serialized: a shrink and
        a concurrent readmit-grow must not interleave installs.
        Returns True when the install happened (False = vetoed; the
        reprobe loop rolls a failed grow's readmits back)."""
        with self._reshape_lock:
            if self._closed:
                return False
            t0 = recovery_t0 if recovery_t0 is not None \
                else time.monotonic()
            old_n = len(self._live)
            live = self._desired_live()
            n = len(live)
            backend = self.make_backend(live) if n else None
            if backend is not None and self.warm is not None:
                try:
                    # AOT warm OFF the serving path: the shrunken
                    # sharded shape set compiles here, not inside a
                    # breaker-guarded live dispatch
                    self.warm(backend, live)
                except InstallVetoError as exc:
                    # wrong verdict on known input: never install —
                    # the old pair keeps serving under its breaker
                    _LOG.error(
                        "mesh reshape to %d device(s) VETOED "
                        "(untrusted verdicts): %s", n, exc)
                    self._recorder.record(
                        "mesh_reshape_vetoed", trace_id=trace_id,
                        healer=self.name, direction=direction,
                        to_devices=n, error=str(exc)[:200])
                    return False
                except Exception:
                    _LOG.exception(
                        "mesh reshape warmup failed; installing "
                        "anyway (first real batch compiles lazily)")
            if self._closed:
                # the owner closed the healer while the candidate was
                # warming (a multi-minute compile): installing now
                # would mutate global serving state — gauge, readiness
                # mesh, latency-series retirement — that the close was
                # supposed to fence off (e.g. after supervisor
                # uninstall, or bench's chaos phase handing the
                # process to later phases)
                _LOG.info("mesh healer closed mid-reshape; candidate "
                          "discarded")
                return False
            self.epoch += 1
            self.install(backend, live, self.epoch)
            self._live = live
            self.reshapes[direction] = \
                self.reshapes.get(direction, 0) + 1
            dt = time.monotonic() - t0
            self._m_reshape.labels(direction=direction,
                                   devices=str(n)).inc()
            _STATE["live"] = n
            _STATE["ejected"] = len(self.ledger.ejected())
            if direction == "shrink":
                self.last_recovery_s = round(dt, 3)
                _STATE["recovery_s"] = self.last_recovery_s
            _LOG.warning(
                "mesh reshaped (%s): %d -> %d device(s) of %d "
                "configured, epoch %d, %.3fs", direction, old_n, n,
                self.configured_n, self.epoch, dt)
            self._recorder.record(
                "mesh_reshape", trace_id=trace_id, healer=self.name,
                direction=direction, from_devices=old_n,
                to_devices=n, configured=self.configured_n,
                epoch=self.epoch, recovery_s=round(dt, 3))
            # mesh-heal interval on the causal timeline: the duration
            # rides alone (the healer's stopwatch is time.monotonic —
            # a different base than the spine's mono axis, so the
            # interval is placed by its END, never by subtracting
            # across clock bases)
            timeline.interval(
                "mesh", "reshape", dt, trace_id=trace_id,
                direction=direction, devices=n,
                epoch=self.epoch)
            return True

    # ------------------------------------------------------------------
    def _ensure_reprobe(self) -> None:
        with self._lock:
            t = self._reprobe_thread
            if t is not None and t.is_alive():
                return
            self._reprobe_thread = threading.Thread(
                target=self._reprobe_loop, daemon=True,
                name=f"{self.name}-reprobe")
            self._reprobe_thread.start()

    def _reprobe_loop(self) -> None:
        """The supervisor's background-reprobe idea extended to
        ejected devices: probe them on an interval; a recovered chip
        re-admits and the mesh grows back.  The loop also RECONCILES
        the installed live set against the desired one, so a reshape
        whose install previously failed or vetoed gets retried here
        instead of stranding the mesh.  The thread exits only when
        nothing is ejected AND the install matches — and decides that
        under the same lock ``_ensure_reprobe`` takes, so an eject
        landing between the check and the exit finds
        ``_reprobe_thread`` cleared and starts a fresh thread
        (TOCTOU)."""
        while not self._closed:
            time.sleep(self.reprobe_s)
            if self._closed:
                return
            with self._lock:
                if not self.ledger.ejected() \
                        and self._desired_live() == self._live:
                    self._reprobe_thread = None
                    return
            ejected = self.ledger.ejected()
            t0 = time.monotonic()
            readmitted = []
            if ejected:
                verdicts = self._probe_devices(ejected)
                for idx in ejected:
                    if verdicts.get(idx) is None:
                        self.ledger.readmit(idx)
                        readmitted.append(idx)
                        _LOG.info("mesh device %s READMITTED",
                                  self.ledger.device_names[idx])
                        self._recorder.record(
                            "mesh_readmit", healer=self.name,
                            device=self.ledger.device_names[idx],
                            index=idx)
            desired = self._desired_live()
            if readmitted or desired != self._live:
                direction = ("grow" if len(desired) >= len(self._live)
                             else "shrink")
                installed = False
                try:
                    installed = self._reshape(direction,
                                              recovery_t0=t0)
                except Exception:  # pragma: no cover - keep probing
                    _LOG.exception("mesh %s reshape failed",
                                   direction)
                if not installed and readmitted:
                    # the grow did NOT install (veto / transient
                    # failure): roll the readmits back so the
                    # shrunken-but-serving state stays truthful
                    # (ledger, gauges, recovered=...) and this loop
                    # RETRIES instead of exiting with the mesh
                    # silently stuck below width.  count=False — a
                    # failed install is not a new flap.
                    for idx in readmitted:
                        self.ledger.eject(idx, count=False)
                    _STATE["ejected"] = len(self.ledger.ejected())
