"""BLS backend selection and supervised bring-up.

The reference refuses to boot before its accelerated BLS is proven
loadable (reference: teku/src/main/java/tech/pegasys/teku/Teku.java:74
preflight calling BLS.getBlsImpl, and the setBlsImplementation seam at
infrastructure/bls/src/main/java/tech/pegasys/teku/bls/BLS.java:51-62;
graceful degradation lives in BlstLoader.java:34-51).  That shape works
when the backend loads in milliseconds.  This repo's accelerator does
not: the first boot compiles the staged verify programs, which takes
minutes, so a blocking preflight either hangs the node or strands it on
the pure oracle forever.

Two bring-up shapes live here:

- ``configure("jax"|"pure"|"auto")`` — the legacy blocking path: probe
  under a deadline, install or fall back.  Kept for tests, offline
  tools, and operators who explicitly want a hard preflight.
- ``make_supervisor()`` — the supervised path (`infra/supervisor.py`):
  the node boots immediately on the oracle, a background task drives
  bring-up with unbounded-but-observable patience, and on READY the
  facade hot-swaps to a breaker-guarded device provider.  ``auto`` on
  the CLI now means this.

``GuardedBls12381`` is the hot-swap target: every device dispatch runs
under the supervisor's CircuitBreaker (per-dispatch deadline,
consecutive-failure trip, half-open re-close), and any device failure
falls back to the pure oracle for THAT call — correctness never
degrades, only latency.
"""

import logging
import os
import threading
import time
from typing import List, Optional, Sequence, Tuple

from . import get_implementation, reset_implementation, set_implementation
from ...infra import aotstore, compilecache, faults, tracing
from ...infra.env import env_bool, env_float, env_int, env_str
from ...infra.metrics import GLOBAL_REGISTRY, MetricsRegistry
from ...infra.supervisor import (BackendSupervisor, CircuitBreaker,
                                 CircuitOpenError, DispatchTimeoutError,
                                 WarmupVetoError)
from .pure_impl import PureBls12381
from .spi import BLS12381, BatchSemiAggregate

_LOG = logging.getLogger(__name__)

# generator pubkey (secret key 1): a cheap known-good probe input
_PROBE_PK = bytes.fromhex(
    "97f1d3a73197d7942695638c4fa9ac0fc3688c4f9774b905a14e3a3f171bac58"
    "6c55e83ff97a1aeffb3af00adb22c6bb")

CHOICES = ("auto", "supervised", "jax", "pure")


class BlsLoadError(RuntimeError):
    """The requested BLS implementation could not be brought up."""


def _probe_jax(max_batch: int, min_bucket: int, mont_path=None,
               mesh=None):
    """Instantiate the device provider and prove the backend executes:
    one pubkey-validation dispatch (the small program; the five staged
    verify programs compile lazily on first real batch).

    `mont_path` installs the process-global mont_mul engine choice
    (vpu | mxu | auto, ops/mxu.py) BEFORE any kernel traces — the
    seam the CLI's `--mont-path` threads through.  `mesh` (off | auto |
    N, CLI `--mesh` / TEKU_TPU_MESH; None reads the env) resolves to the largest pow-2 device count
    available (teku_tpu/parallel.resolve_mesh_devices — an
    over-ambitious N demotes with one WARN, never fails bring-up) and
    constructs JaxBls12381(mesh=...) so production dispatches shard
    group-aligned across the chips.  The warmup batches downstream
    then compile the resolved shape set off the gossip path."""
    from ...ops import mxu
    from ...ops.provider import JaxBls12381

    if mont_path is not None:
        mxu.set_path(mont_path)
    if mesh is None:
        mesh = env_str("TEKU_TPU_MESH", "off")
    from ... import parallel
    mesh_obj = None
    n_mesh = parallel.resolve_mesh_devices(mesh)
    if n_mesh >= 2:
        mesh_obj = parallel.make_mesh(n_mesh)
    impl = JaxBls12381(max_batch=max_batch, min_bucket=min_bucket,
                       mesh=mesh_obj)
    if not impl.public_key_is_valid(_PROBE_PK):
        raise BlsLoadError("device probe rejected the generator pubkey")
    device = device_label()
    if impl.mesh_info:
        device = f"mesh[{impl.mesh_info['n_devices']}] {device}"
    return impl, device


def device_label() -> str:
    """The dispatch device WITH its platform, e.g. ``tpu TPU v5 lite
    (TPU_0(...))`` or ``cpu cpu (TFRT_CPU_0)``: the probe accepts any
    backend jax offers (CPU tests rely on it), so every line that names
    the "jax" provider says what it actually runs on."""
    import jax
    dev = jax.devices()[0]
    return f"{dev.platform} {dev.device_kind} ({dev})"


# --------------------------------------------------------------------------
# Guarded provider: the hot-swap target installed at READY
# --------------------------------------------------------------------------

# Atomically-swapped state registration for the static analyzer:
# `_serving` holds (provider, device-entry lock, packers' turn) as one
# tuple so a reader can never observe a half-swap — which is only true if
# every reader performs exactly ONE attribute load and destructures
# the snapshot.  `cli lint`'s torn-read checker enforces the
# single-read rule tree-wide for every attribute declared here (the
# two-read bug shipped twice during PR 12 review).
__swap_attrs__ = ("_serving",)


class _DeferredSemi(BatchSemiAggregate):
    """Raw triple held until complete_batch_verify, so the guarded
    provider can route the WHOLE batch to whichever backend the circuit
    allows at dispatch time (device-specific semis must not outlive a
    mid-flight trip)."""

    __slots__ = ("triple",)

    def __init__(self, triple):
        self.triple = triple


class GuardedBls12381(BLS12381):
    """Device provider under a circuit breaker with oracle fallback.

    Verification dispatches go to the device while the circuit is
    closed; a trip (consecutive failures / deadline overruns) routes
    them to the pure oracle until half-open probing re-closes the
    circuit.  Non-batch host ops (keys, signing, aggregation) go to the
    oracle directly — the device provider delegates them there anyway.
    """

    def __init__(self, device: BLS12381, breaker: CircuitBreaker,
                 oracle: Optional[BLS12381] = None,
                 registry: MetricsRegistry = GLOBAL_REGISTRY):
        self.breaker = breaker
        self.oracle = oracle or PureBls12381()
        # optional mesh self-healer (parallel/selfheal.MeshHealer):
        # dispatch failures are reported so shard-level fault
        # isolation can eject the sick device and reshape, instead of
        # the whole-backend breaker cliff being the only containment
        self.healer = None
        # degraded-mode visibility: every guarded dispatch labeled by
        # the backend that actually served it and why — a node quietly
        # paying oracle latency must show up on one PromQL ratio
        self._m_requests = registry.labeled_counter(
            "bls_verify_requests_total",
            "guarded BLS dispatches by serving backend and reason",
            labelnames=("backend", "reason"))
        # how a dispatch with a host half gave the packers' turn back:
        # after its launches (the served path), at once on a verdict
        # the host half could give, or on a raise before its launches
        # had returned; a closed set
        self._m_turn = registry.labeled_counter(
            "bls_prep_turn_total",
            "guarded dispatches with a host half, by how they gave the "
            "packers' turn back (launched|host_verdict|error)",
            labelnames=("released",))
        # (provider, device-entry lock, packers' turn) as ONE
        # atomically-swapped triple.
        #
        # The lock guards what ENTERS THE DEVICE, and nothing else: a
        # dispatch's launches and its sync, a `pk_validate` for a key
        # the cache lacked, the H(m) arena's slots (their state must
        # follow device order).  A provider's host half
        # (`prepare_dispatch`: parsing, cache lookups, array packing)
        # runs before the lock is taken, so one worker packs its batch
        # while the other one's runs on the chip; the provider's host
        # caches carry their own locks (`LimitedMap`).
        #
        # The turn orders the host's work in front of the device: a
        # dispatch takes it to pack, keeps it through `lock_wait` and
        # its launches, and gives it back once `launch_dispatch` has
        # returned its handle, before the sync.  Host halves are plain
        # Python and small numpy calls under the interpreter lock, so
        # two at once gain nothing and convoy (a 256 x 512 batch packed
        # in 1.14-1.45 s each where one alone takes 0.11 s; PERF.md
        # §6), and a packer beside a launcher stretches every launch
        # call that lets the interpreter lock go (the first ~31 ms of
        # a hold; PERF.md §5).  So the other worker packs while this
        # dispatch's programs run, then stands at the lock holding the
        # turn, so no packing runs beside its own launches.  Taking the
        # turn only at the lock would put the packing that has just
        # begun in front of the launcher.  The wait for the turn is
        # `prep_wait`, which holds the other worker's launches too.
        # Order: turn, then lock; nobody holding the lock waits for
        # the turn.
        #
        # A timed-out dispatch's orphaned thread may still be on the
        # device (e.g. finishing a cold compile): it keeps the lock for
        # as long, a later dispatch packs, then blocks there holding
        # the turn until the orphan drains, and the one after it waits
        # for the turn; the breaker deadline bounds wait + prep + wait
        # + device and accounts the overrun as a timeout, so a busy
        # device reads as a busy device.  An orphan gives the turn back
        # when its launches end, however they end, as a host half that
        # hangs does when it ends.  The mesh-reshape hot-swap replaces
        # the TRIPLE in one reference assignment: dispatches that
        # grabbed the old one prepare for, and complete on, the old
        # plan (their orphans keep the old lock and turn), new
        # dispatches take the new provider and turn immediately and
        # never queue behind a wedged orphan.
        self._serving = (device, threading.Lock(), threading.Lock())

    @property
    def device(self) -> BLS12381:
        return self._serving[0]

    @property
    def _device_lock(self) -> threading.Lock:
        return self._serving[1]

    def swap_device(self, new_device: BLS12381) -> None:
        """Atomic mid-mesh hot-swap (the reshape install hook): one
        reference assignment, same invariant as the PR-1 install swap
        — in-flight verifies complete on the provider, lock and turn
        they grabbed, new verifies take the reshaped provider."""
        self._serving = (new_device, threading.Lock(), threading.Lock())

    def _notify_healer(self, exc: BaseException, timeout: bool) -> None:
        healer = self.healer
        if healer is None:
            return
        try:
            healer.on_dispatch_failure(
                error=f"{type(exc).__name__}: {exc}", timeout=timeout)
        except Exception:  # pragma: no cover - healing must not kill
            _LOG.exception("mesh healer notification failed")

    @property
    def name(self) -> str:
        return self.device.name

    @property
    def serving(self) -> str:
        """Which backend the NEXT dispatch will use."""
        return ("oracle" if self.breaker.state == CircuitBreaker.OPEN
                else "device")

    # --- host ops: straight to the oracle ----------------------------
    def secret_key_to_public_key(self, secret: int) -> bytes:
        return self.oracle.secret_key_to_public_key(secret)

    def sign(self, secret: int, message: bytes) -> bytes:
        return self.oracle.sign(secret, message)

    def aggregate_public_keys(self, public_keys: Sequence[bytes]) -> bytes:
        return self.oracle.aggregate_public_keys(public_keys)

    def aggregate_signatures(self, signatures: Sequence[bytes]) -> bytes:
        return self.oracle.aggregate_signatures(signatures)

    def signature_is_valid(self, signature: bytes) -> bool:
        return self.oracle.signature_is_valid(signature)

    # --- guarded device dispatches ------------------------------------
    def _guarded(self, op: str, *args):
        # ONE read of the serving triple: the provider, its entry lock
        # and its turn stay consistent even when a reshape swaps
        # mid-call
        device, lock, turn = self._serving
        device_fn = getattr(device, op)
        # the provider's own split of the verb into a host half and a
        # device half; one that has none (the oracle family, a model)
        # has nothing to run ahead of the lock.  A verb replaced on the
        # instance (a fault harness wrapping `device.batch_verify`) is
        # called as it stands: the halves would go around it
        prepare = None
        if op not in getattr(device, "__dict__", ()):
            prepare = getattr(device, "prepare_dispatch", None)

        def whole():
            # runs on the breaker's dispatch thread: the hop to it ends
            # with the first mark here.  No host half, no turn: the
            # verb runs whole under the lock
            marks = tracing.current_marks()
            marks.mark("lock_wait")
            with lock:
                # the lock is ours: up to the provider's first program
                # call the hold is host work (`launch_head`), which
                # begins at the very instant stamped as the lock's edge
                marks.stamp_lock("acquired", marks.mark("launch_head"))
                try:
                    return device_fn(*args)
                finally:
                    marks.stamp_lock("released")

        def in_turn():
            # as `whole`, with the host half first, in its turn.  The
            # wait for the turn (`prep_wait`) is the other worker's
            # packing and launches; the host half runs off the
            # device-entry lock, and the turn stays ours through the
            # wait for that lock (`lock_wait`: the other worker's
            # sync) and our launches, so the other worker's next
            # packing runs beside our programs, not our launch calls
            marks = tracing.current_marks()
            held = True

            def give_back(released):
                nonlocal held
                if held:
                    held = False
                    self._m_turn.labels(released=released).inc()
                    turn.release()

            marks.mark("prep_wait")
            turn.acquire()
            try:
                marks.mark("host_prep")
                prepared = prepare(op, *args)
                if prepared.verdict is not None:
                    # known on the host (malformed wire, a cached
                    # key): nothing enters the device
                    give_back("host_verdict")
                    return prepared.verdict
                marks.mark("lock_wait")
                with lock:
                    marks.stamp_lock("acquired", marks.mark("launch_head"))
                    try:
                        handle = device.launch_dispatch(prepared)
                        give_back("launched")
                        return handle.result()
                    finally:
                        marks.stamp_lock("released")
            finally:
                give_back("error")

        locked = whole if prepare is None else in_turn

        try:
            result = self.breaker.call(locked)
            self._m_requests.labels(backend="device", reason="ok").inc()
            return result
        except CircuitOpenError:
            # expected while tripped: silent oracle service
            self._m_requests.labels(backend="oracle",
                                    reason="breaker_open").inc()
        except DispatchTimeoutError as exc:
            self._m_requests.labels(backend="oracle",
                                    reason="fallback").inc()
            _LOG.warning("device %s overran deadline (%s); serving "
                         "this call from the oracle", op, exc)
            self._notify_healer(exc, timeout=True)
        except Exception as exc:  # noqa: BLE001 - any device fault
            self._m_requests.labels(backend="oracle",
                                    reason="fallback").inc()
            _LOG.warning("device %s failed (%s: %s); serving this "
                         "call from the oracle", op,
                         type(exc).__name__, exc)
            self._notify_healer(exc, timeout=False)
        # the oracle serving a device's call IS the degraded-mode cost:
        # a separate stage so traces show where the p50 went (a phase
        # of the dispatch where the service marks one)
        with tracing.dispatch_marks("oracle_execute"):
            return getattr(self.oracle, op)(*args)

    def public_key_is_valid(self, public_key: bytes) -> bool:
        return self._guarded("public_key_is_valid", public_key)

    def verify(self, public_key: bytes, message: bytes,
               signature: bytes) -> bool:
        return self._guarded("verify", public_key, message, signature)

    def fast_aggregate_verify(self, public_keys: Sequence[bytes],
                              message: bytes, signature: bytes) -> bool:
        return self._guarded("fast_aggregate_verify", public_keys,
                             message, signature)

    def aggregate_verify(self, public_keys: Sequence[bytes],
                         messages: Sequence[bytes],
                         signature: bytes) -> bool:
        return self._guarded("aggregate_verify", public_keys, messages,
                             signature)

    def batch_verify(
        self, triples: Sequence[Tuple[Sequence[bytes], bytes, bytes]],
    ) -> bool:
        return self._guarded("batch_verify", triples)

    # prepare/complete defer routing to complete-time: a device semi
    # prepared before a trip must not reach the oracle's completer
    def prepare_batch_verify(self, triple) -> Optional[BatchSemiAggregate]:
        return _DeferredSemi(triple)

    def complete_batch_verify(
        self, semi_aggregates: Sequence[Optional[BatchSemiAggregate]]
    ) -> bool:
        if any(sa is None for sa in semi_aggregates):
            return False
        # semis prepared BEFORE the hot-swap (by the oracle, the only
        # other installable facade impl) complete on the oracle — an
        # in-flight prepare/complete pair must finish on the
        # implementation family it started with, never crash
        deferred = [sa for sa in semi_aggregates
                    if isinstance(sa, _DeferredSemi)]
        foreign = [sa for sa in semi_aggregates
                   if not isinstance(sa, _DeferredSemi)]
        ok = True
        if deferred:
            ok = self.batch_verify([sa.triple for sa in deferred])
        if foreign:
            ok = self.oracle.complete_batch_verify(foreign) and ok
        return ok


# every warm dispatch, by profile (`shapeset.warmup_profiles`' names: x1,
# x<max_batch>, x<max_batch>dup8, aggregate, aggregate_forged; five a
# process) and its key bucket
_M_WARMUP = GLOBAL_REGISTRY.labeled_counter(
    "bls_warmup_dispatches_total",
    "warm-up dispatches at bring-up and reshape, by warm profile and "
    "key bucket (kmax)",
    labelnames=("profile", "kmax"))

# the aggregate profile's signers: secret keys 1..16 (1 is the probe's
# key), so the keys the provider resolves fit the probe's own
# pubkey-validation bucket
_WARM_SIGNERS = 16


def _warm_dispatch(impl, name: str, kmax: int, batch, want: bool) -> None:
    """One warm profile's dispatch, as a `warmup` span; a verdict other
    than `want` vetoes the device."""
    from ...ops import shapeset
    shape = shapeset.shape_label(
        shapeset.lane_bucket(len(batch), getattr(impl, "min_bucket", 1)),
        kmax)
    with tracing.warmup(name, shape):
        got = impl.batch_verify(batch)
    _M_WARMUP.labels(profile=name, kmax=str(kmax)).inc()
    if got is not want:
        # a wrong verdict on a known answer is a device we must never
        # install
        raise WarmupVetoError(
            f"warmup batch ({name}, {shape}) read {got}, want {want}")


def _aggregate_batches(oracle, tasks: int, kmax: int):
    """The aggregate profiles' two drains: `tasks`
    SignedAggregateAndProof-shaped tasks, each a selection proof (one
    key, a message every task shares), the aggregator's own signature
    (one key, its own message) and an aggregate of `kmax` keys (its
    committee's message), signed by the oracle (an aggregate of keys
    k_1..k_K over m is [sum k_i] H(m)); then the same tasks with fresh
    messages of their own and the last aggregate signed by other keys,
    which has to read False."""
    from .constants import R
    sks = list(range(1, _WARM_SIGNERS + 1))
    pks = [oracle.secret_key_to_public_key(sk) for sk in sks]
    shared = b"teku-tpu warmup selection"
    first, later = [], []
    for i in range(tasks):
        sk, pk = sks[i % _WARM_SIGNERS], pks[i % _WARM_SIGNERS]
        group = b"teku-tpu warmup aggregate %d" % i
        signers = [(i + q) % _WARM_SIGNERS for q in range(kmax)]
        agg_sk = sum(sks[j] for j in signers) % R
        # a well-formed signature under other keys: only the pairing
        # can tell
        forged_sk = agg_sk % (R - 1) + 1 if i == tasks - 1 else agg_sk
        selection = ([pk], shared, oracle.sign(sk, shared))
        for out, own, a_sk in (
                (first, b"teku-tpu warmup envelope %d" % i, agg_sk),
                (later, b"teku-tpu warmup envelope %d'" % i, forged_sk)):
            out += [selection, ([pk], own, oracle.sign(sk, own)),
                    ([pks[j] for j in signers], group,
                     oracle.sign(a_sk, group))]
    return first, later


def _warmup_batches(impl, max_batch: int,
                    key_bucket: Optional[int] = None) -> List[int]:
    """Compile the verify pipeline OFF the gossip path (the first real
    batch used to pay a multi-minute staged compile in the hot path),
    one dispatch a profile of ``shapeset.warmup_profiles``: the
    min_bucket pad, the primary bucket all-unique and duplicated and,
    given the node's `key_bucket`, the aggregate-and-proof drain at
    that many keys a lane (a slot's first, then a later one with a
    forged aggregate).  Other (pow-2 × kmax) shapes still compile
    lazily — a cold compile that overruns the breaker deadline serves
    that call from the oracle while the orphaned dispatch thread
    finishes populating the jit cache, so the shape warms itself.
    Shared by supervisor WARMING and the mesh self-healer's reshape
    warm (the shrunken sharded shape set must compile off-path too).
    Raises WarmupVetoError on a wrong verdict — a device that gets a
    KNOWN answer wrong must never serve.  Returns the key buckets
    warmed."""
    from ...ops import shapeset
    oracle = PureBls12381()
    msg = b"teku-tpu warmup"
    unique = [([_PROBE_PK], m, oracle.sign(1, m))
              for m in (b"teku-tpu warmup %d" % i
                        for i in range(max_batch))]
    profiles = shapeset.warmup_profiles(max_batch, key_bucket)
    later = None        # `aggregate_forged`'s drain, made beside the first
    for name, lane_groups, _missing, kmax in profiles:
        if name == "x1":
            _warm_dispatch(impl, name, kmax,
                           [([_PROBE_PK], msg, oracle.sign(1, msg))], True)
        elif name == "aggregate":
            first, later = _aggregate_batches(oracle, lane_groups[0], kmax)
            _warm_dispatch(impl, name, kmax, first, True)
        elif name == "aggregate_forged":
            _warm_dispatch(impl, name, kmax, later, False)
        elif lane_groups[0] == 1:
            # the primary bucket with DISTINCT messages: the
            # dedup-aware pipeline specializes on the unique-message
            # bucket, and all-unique (fresh gossip, dup factor 1) is
            # the worst-case shape — warm that first
            _warm_dispatch(impl, name, kmax, unique, True)
        else:
            # committee-duplicated (dup factor 8, the common gossip
            # mix): the grouped pipeline specializes on the (unique,
            # group) bucket pair, and the first REAL committee batch
            # must not pay that compile inside a breaker-guarded live
            # dispatch
            dup = lane_groups[0]
            _warm_dispatch(impl, name, kmax,
                           [unique[i // dup] for i in range(max_batch)],
                           True)
    return sorted({kmax for *_, kmax in profiles})


# --------------------------------------------------------------------------
# Mesh self-healing wiring (parallel/selfheal.MeshHealer, jax world)
# --------------------------------------------------------------------------

def make_mesh_healer(guarded: GuardedBls12381,
                     breaker: Optional[CircuitBreaker] = None, *,
                     max_batch: int = 256, min_bucket: int = 16,
                     supervisor=None,
                     registry: MetricsRegistry = GLOBAL_REGISTRY,
                     warm: bool = True,
                     key_bucket: Optional[int] = None,
                     **healer_kw):
    """Wire shard-level fault isolation around a mesh-backed guarded
    provider: per-device health ledger, eject + reshape onto the
    largest surviving pow-2 subset, AOT warm of the shrunken shape
    set, atomic ``swap_device`` install, background readmit.

    The reshape warm covers the node's `key_bucket` as the boot's does.
    Returns the ``MeshHealer`` (also assigned to ``guarded.healer``),
    or None when the serving provider is not mesh-backed or
    ``TEKU_TPU_MESH_SELF_HEAL=0`` opts out."""
    import numpy as _np

    from ...infra import capacity
    from ... import parallel
    from ...parallel import selfheal

    impl = guarded.device
    sharded = getattr(impl, "_sharded", None)
    if sharded is None or not env_bool("TEKU_TPU_MESH_SELF_HEAL", True):
        return None
    mesh_devices = list(_np.ravel(sharded.mesh.devices))
    names = [str(d) for d in mesh_devices]

    def probe(idx: int) -> None:
        # the keyed fault site first (keys are device NAMES, the same
        # vocabulary the collective dispatch passes): the chaos
        # harness wedges exactly one chip by key, and only that
        # chip's probe may fail here
        faults.check(selfheal.FAULT_SITE, keys=(names[idx],))
        import jax
        import jax.numpy as jnp
        # a tiny computation PLACED on the device proves its runtime
        # executes and answers; the reshape warm below proves the
        # full verify pipeline on the surviving collective
        x = jax.device_put(_np.arange(8, dtype=_np.int32),
                           mesh_devices[idx])
        if int(jnp.sum(x)) != 28:
            raise BlsLoadError(
                f"device {names[idx]} probe computed garbage")

    def make_backend(live):
        from ...ops.provider import JaxBls12381
        if len(live) >= 2:
            # advertise=False: this is a CANDIDATE — the gauge and
            # readiness keep describing the SERVING mesh until the
            # install hook swaps (a vetoed warm must leave them
            # untouched)
            mesh_obj = parallel.make_mesh(
                devices=[mesh_devices[i] for i in live],
                advertise=False)
            return JaxBls12381(max_batch=max_batch,
                               min_bucket=min_bucket, mesh=mesh_obj)
        # one healthy chip left: single-device dispatch
        return JaxBls12381(max_batch=max_batch, min_bucket=min_bucket)

    def heal_warm(new_impl, live):
        if not warm:
            return
        # bounded reshape warm: recovery time is the objective, so the
        # warm batch is a knob (default a fraction of the service
        # bucket; the persistent compile cache usually turns this into
        # disk loads).  A wrong verdict VETOES the install.
        wb = max(1, env_int("TEKU_TPU_MESH_WARM_BATCH",
                            min(max_batch, 64)))
        cc_before = compilecache.stats()
        aot_before = aotstore.stats()
        t0 = time.monotonic()
        try:
            _warmup_batches(new_impl, wb, key_bucket)
        except WarmupVetoError as exc:
            raise selfheal.InstallVetoError(str(exc)) from exc
        moved = compilecache.delta(cc_before)
        aot_moved = aotstore.delta(aot_before)
        # the reshape-under-fire observable: recovery warm must be
        # load-not-compile (AOT store / disk cache), never a fresh
        # multi-minute XLA compile while the backlog deepens
        _LOG.info(
            "reshape warm (x%d) in %.1fs: %d AOT load(s), %d "
            "compile-cache load(s), %d fresh compile(s) (%d "
            "kernel-grade)", wb, time.monotonic() - t0,
            aot_moved["loads"], moved["hits"], moved["misses"],
            moved["kernel_compiles"])

    healer_box: list = []

    def heal_install(backend, live, epoch):
        if backend is None:
            # mesh shrank to ZERO healthy devices: the oracle is the
            # last resort — keep the old guarded pair; its breaker
            # trips on the next failure and owns recovery from there.
            # The gauge must agree with the readiness snapshot below:
            # no serving mesh to advertise
            parallel.reset_active_mesh()
            _LOG.error(
                "mesh shrank to zero healthy devices; oracle is the "
                "last resort (backend breaker owns recovery)")
        else:
            backend.mesh_epoch = epoch
            guarded.swap_device(backend)
            # the INSTALLED topology is now the serving truth: publish
            # it (candidate meshes were built with advertise=False)
            mesh_info = getattr(backend, "mesh_info", None)
            if mesh_info:
                parallel.advertise_mesh(mesh_info["devices"],
                                        mesh_info.get("axis")
                                        or parallel.DEFAULT_AXIS)
            else:
                parallel.reset_active_mesh()
            try:
                # the admission planner's batch sizing must model the
                # LIVE topology: retire latency series recorded under
                # the old mesh size so plans shrink with the mesh
                capacity.TELEMETRY.latency.retire_mesh_shapes(
                    len(live) if len(live) >= 2 else 0)
            except Exception:  # pragma: no cover - advisory
                _LOG.exception("latency-series retirement failed")
            if breaker is not None:
                # the reshape warm just verified known-good signatures
                # on the new backend: close the circuit so serving
                # resumes immediately instead of waiting out a cooldown
                breaker.record_success()
        if supervisor is not None:
            mesh_desc = (getattr(backend, "mesh_info", None)
                         if backend is not None else None)
            if mesh_desc is None and backend is not None:
                mesh_desc = {"devices": [names[i] for i in live],
                             "n_devices": len(live), "axis": None}
            sup_mesh = dict(mesh_desc
                            or {"devices": [], "n_devices": 0,
                                "axis": None})
            if healer_box:
                # the FULL healer snapshot, same schema the initial
                # install publishes — with live/epoch overridden from
                # the hook args (the healer updates its installed-live
                # field only after this hook returns)
                snap = healer_box[0].snapshot()
                snap["live"] = len(live)
                snap["live_devices"] = [names[i] for i in live]
                snap["epoch"] = epoch
                sup_mesh["self_heal"] = snap
            supervisor.mesh = sup_mesh

    healer = selfheal.MeshHealer(
        names, probe=probe, make_backend=make_backend,
        install=heal_install, warm=heal_warm,
        registry=registry, **healer_kw)
    healer_box.append(healer)
    guarded.healer = healer
    return healer


# --------------------------------------------------------------------------
# Supervised bring-up (the CLI's `auto`)
# --------------------------------------------------------------------------

def make_supervisor(*, max_batch: int = 256, min_bucket: int = 16,
                    name: str = "bls_backend",
                    breaker_name: str = "bls_device",
                    registry: MetricsRegistry = GLOBAL_REGISTRY,
                    breaker: Optional[CircuitBreaker] = None,
                    warm: bool = True, mont_path: Optional[str] = None,
                    mesh: Optional[str] = None,
                    key_bucket: Optional[int] = None,
                    **supervisor_kw) -> BackendSupervisor:
    """Build the production BackendSupervisor: boot-on-oracle now,
    background JAX bring-up, breaker-guarded hot-swap at READY for both
    BLS (`set_implementation`) and KZG (`crypto/kzg.py:set_backend`).

    `key_bucket` is the `kmax` bucket of the largest aggregate the
    node's network sends (`shapeset.aggregate_key_bucket`, which `cli
    node` derives from its preset and state): WARMING dispatches the
    aggregate profile at it, so the breaker never meets that bucket's
    cold compile; None warms one key a lane only.

    The node owns the returned service's lifecycle
    (`node/node.py:do_start`); nothing here blocks.
    """
    def _make_breaker(bname: str) -> CircuitBreaker:
        return CircuitBreaker(
            name=bname, registry=registry,
            failure_threshold=env_int("TEKU_TPU_BREAKER_THRESHOLD", 3,
                                      lo=1),
            deadline_s=env_float("TEKU_TPU_DISPATCH_DEADLINE_S", 30.0,
                                 lo=0.1),
            cooldown_s=env_float("TEKU_TPU_BREAKER_COOLDOWN_S", 30.0,
                                 lo=0.1))

    if breaker is None:
        # `bls_device_*` metric series, per the README/PERF.md contract
        breaker = _make_breaker(breaker_name)
    # the KZG family gets its OWN breaker: with a shared one, healthy
    # KZG dispatches would keep resetting the BLS consecutive-failure
    # count (and vice versa), so a device wedged in only one program
    # family would never trip.  No supervisor reprobe on this one: it
    # half-opens on live KZG traffic, bounded by its own deadline
    kzg_breaker = _make_breaker("kzg_device")
    supervisor_box: list = []
    installed: dict = {}

    def probe():
        return _probe_jax(max_batch, min_bucket, mont_path=mont_path,
                          mesh=mesh)

    def warmup(backend):
        if not warm:
            return None
        impl, _ = backend
        # the readiness snapshot's `warmup_cache` names them
        return {"key_buckets": _warmup_batches(impl, max_batch,
                                               key_bucket)}

    def install(backend):
        impl, device = backend
        guarded = GuardedBls12381(impl, breaker)
        installed["guarded"] = guarded
        set_implementation(guarded)
        try:
            from .. import kzg as kzg_facade
            from ...ops.kzg import JaxKzg
            kzg_facade.set_backend(
                GuardedKzgBackend(JaxKzg(), kzg_breaker))
        except Exception as exc:  # pragma: no cover - defensive
            _LOG.warning("device KZG backend unavailable: %s", exc)
        if supervisor_box:
            supervisor_box[0].backend_detail = device
            # the readiness snapshot must self-describe the mesh (which
            # devices, how many, which axis) — multi-chip runs and
            # multi-node operators read it from /teku/v1/admin/readiness
            supervisor_box[0].mesh = getattr(impl, "mesh_info", None)
        if getattr(impl, "mesh_info", None):
            # shard-level fault isolation: a wedged chip costs 1/N
            # capacity (eject + reshape + readmit), not the whole-mesh
            # breaker cliff.  Failure here degrades to the PR-10
            # semantics (one breaker per backend), never blocks install
            try:
                healer = make_mesh_healer(
                    guarded, breaker, max_batch=max_batch,
                    min_bucket=min_bucket, registry=registry,
                    key_bucket=key_bucket,
                    supervisor=(supervisor_box[0] if supervisor_box
                                else None))
                if healer is not None:
                    installed["healer"] = healer
                    if supervisor_box:
                        sup_mesh = dict(impl.mesh_info)
                        sup_mesh["self_heal"] = healer.snapshot()
                        supervisor_box[0].mesh = sup_mesh
            except Exception:  # pragma: no cover - defensive
                _LOG.exception("mesh self-healing unavailable; the "
                               "whole-mesh breaker remains the only "
                               "containment")
        _LOG.info("BLS implementation hot-swapped: %s on %s "
                  "(breaker deadline %.1fs)", impl.name, device,
                  breaker.deadline_s)

    def uninstall():
        reset_implementation()
        _reset_kzg_backend()
        healer = installed.pop("healer", None)
        if healer is not None:
            healer.close()
        if supervisor_box:
            # no installed backend => no serving mesh: the name-
            # prefixed gauge and readiness snapshot must not keep
            # advertising a mesh the oracle is serving for
            supervisor_box[0].mesh = None

    def reprobe():
        # synthetic known-good dispatch for supervisor-driven half-open
        # probing: live traffic never pays the deadline_s probe cost.
        # Raises (keeping the circuit open) on failure OR wrong verdict
        guarded = installed.get("guarded")
        if guarded is None:
            raise BlsLoadError("no device backend installed")
        oracle = PureBls12381()
        msg = b"teku-tpu reprobe"
        sig = oracle.sign(1, msg)
        # ONE read of the serving triple — two property reads could
        # straddle a reshape swap and dispatch on the new provider
        # while holding the OLD lock.  The verb runs whole under the
        # lock, with no host half ahead of it, so it takes no turn
        device, lock, _turn = guarded._serving
        with lock:                     # same orphan-thread rule
            ok = device.batch_verify([([_PROBE_PK], msg, sig)])
        if not ok:
            raise BlsLoadError("reprobe batch did not verify")

    sup = BackendSupervisor(
        probe=probe, warmup=warmup, install=install, uninstall=uninstall,
        reprobe=reprobe, breaker=breaker, name=name, registry=registry,
        **supervisor_kw)
    supervisor_box.append(sup)
    # supervisor-name-prefixed mesh gauge (multi-node devnets keep the
    # series distinct, like the admission controller's families): the
    # device count of the mesh THIS supervisor's backend dispatches
    # over — 0 until a mesh backend installs
    registry.gauge(
        f"{name}_mesh_devices",
        "device count of this supervisor's installed verify mesh "
        "(0 = single-device or not yet installed)",
        supplier=lambda: float((sup.mesh or {}).get("n_devices", 0)))
    return sup


class GuardedKzgBackend:
    """Breaker-guarded device KZG backend: any device fault surfaces as
    `kzg.BackendUnavailable`, which the facade treats as 'fall through
    to the host path' — a tripped device must cost latency, never a
    wrong DA verdict."""

    def __init__(self, inner, breaker: CircuitBreaker):
        self.inner = inner
        self.breaker = breaker
        self.name = f"guarded({getattr(inner, 'name', 'device')})"
        self._device_lock = threading.Lock()   # same orphan-thread rule
                                               # as GuardedBls12381
        self._m_requests = GLOBAL_REGISTRY.labeled_counter(
            "kzg_verify_requests_total",
            "guarded KZG dispatches by serving backend and reason",
            labelnames=("backend", "reason"))

    def _call(self, op: str, *args):
        from .. import kzg as kzg_facade
        fn = getattr(self.inner, op)

        def run():
            # KzgError is a VERDICT on the input, not device sickness:
            # capture it so the breaker records the dispatch as healthy
            # instead of tripping on malformed blobs.  The fault site
            # fires INSIDE the guarded call so injected hangs meet the
            # deadline and injected raises feed the trip counters
            try:
                with self._device_lock:
                    faults.check("kzg.dispatch")
                    return ("ok", fn(*args))
            except kzg_facade.KzgError as exc:
                return ("kzg", exc)

        try:
            kind, value = self.breaker.call(run)
        except CircuitOpenError as exc:
            self._m_requests.labels(backend="oracle",
                                    reason="breaker_open").inc()
            raise kzg_facade.BackendUnavailable(str(exc)) from exc
        except DispatchTimeoutError as exc:
            self._m_requests.labels(backend="oracle",
                                    reason="fallback").inc()
            raise kzg_facade.BackendUnavailable(str(exc)) from exc
        except Exception as exc:  # noqa: BLE001 - any device fault
            self._m_requests.labels(backend="oracle",
                                    reason="fallback").inc()
            _LOG.warning("device KZG %s failed (%s: %s); host path "
                         "serves this call", op, type(exc).__name__, exc)
            raise kzg_facade.BackendUnavailable(str(exc)) from exc
        # KzgError verdicts executed on the device: still backend=device
        self._m_requests.labels(backend="device", reason="ok").inc()
        if kind == "kzg":
            raise value
        return value

    def g1_lincomb(self, setup, scalars):
        return self._call("g1_lincomb", setup, scalars)

    def verify_blob_kzg_proof(self, blob, commitment, proof, setup):
        return self._call("verify_blob_kzg_proof", blob, commitment,
                          proof, setup)

    def verify_blob_kzg_proof_batch(self, blobs, commitments, proofs,
                                    setup):
        return self._call("verify_blob_kzg_proof_batch", blobs,
                          commitments, proofs, setup)


# --------------------------------------------------------------------------
# Legacy blocking configure (tests, offline tools, explicit preflight)
# --------------------------------------------------------------------------

def configure(choice: str = "auto", *, max_batch: int = 256,
              min_bucket: int = 16,
              probe_timeout_s: Optional[float] = None,
              mont_path: Optional[str] = None,
              mesh: Optional[str] = None) -> str:
    """Install the BLS provider for this process; returns its name.

    auto: try the JAX/TPU provider under a deadline, fall back to the
          pure oracle with a loud warning on any failure.  (The CLI's
          `auto` uses make_supervisor() instead — this blocking form
          remains for tests and synchronous tools.)
    jax:  require the JAX/TPU provider; raise BlsLoadError on failure.
    pure: install the oracle (also the explicit opt-out for tests).
    supervised: install the oracle now; the caller is expected to run
          a make_supervisor() service for background bring-up.
    """
    if choice not in CHOICES:
        raise ValueError(f"unknown bls impl {choice!r} (use one of "
                         f"{'/'.join(CHOICES)})")
    if choice in ("pure", "supervised"):
        reset_implementation()
        _reset_kzg_backend()
        return "pure"
    if probe_timeout_s is None:
        probe_timeout_s = env_float("TEKU_TPU_BLS_PROBE_TIMEOUT_S",
                                    120.0, lo=1.0)

    result: dict = {}

    def run():
        try:
            result["ok"] = _probe_jax(max_batch, min_bucket,
                                      mont_path=mont_path, mesh=mesh)
        except BaseException as exc:  # noqa: BLE001 - report any failure
            result["err"] = exc

    t = threading.Thread(target=run, daemon=True,
                         name="bls-loader-probe")
    t.start()
    t.join(probe_timeout_s)
    if t.is_alive():
        err: BaseException = BlsLoadError(
            f"backend probe exceeded {probe_timeout_s:.0f}s")
    else:
        err = result.get("err")
    if err is None:
        impl, device = result["ok"]
        set_implementation(impl)
        # KZG rides the same kernel base: install the device backend
        # alongside (the reference's initKzg moment,
        # BeaconChainController.java:557-572)
        try:
            from .. import kzg as kzg_facade
            from ...ops.kzg import JaxKzg
            kzg_facade.set_backend(JaxKzg())
        except Exception as exc:  # pragma: no cover - defensive
            _LOG.warning("device KZG backend unavailable: %s", exc)
        _LOG.info("BLS implementation: %s on %s", impl.name, device)
        return impl.name
    if choice == "jax":
        raise BlsLoadError(f"--bls-impl jax: {err}") from (
            err if isinstance(err, Exception) else None)
    _LOG.warning(
        "BLS accelerator unavailable (%s: %s) — FALLING BACK to the "
        "pure-Python oracle; node-side signature verification will be "
        "slow", type(err).__name__, err)
    reset_implementation()
    _reset_kzg_backend()
    return "pure"


def _reset_kzg_backend() -> None:
    try:
        from .. import kzg as kzg_facade
        kzg_facade.set_backend(None)
    except Exception:  # pragma: no cover - import-order edge
        pass


def current_name() -> str:
    impl = get_implementation()
    return getattr(impl, "name", type(impl).__name__)
