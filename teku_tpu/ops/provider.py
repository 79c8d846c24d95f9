"""JaxBls12381 — the TPU-backed BLS provider behind the node's SPI.

Plugs the batched verification kernel (teku_tpu/ops/verify.py) into the
same provider seam the reference exposes for blst (reference:
infrastructure/bls/src/main/java/tech/pegasys/teku/bls/impl/BLS12381.java:
34-157, installed via bls/BLS.java:51-62 setBlsImplementation).  The
pure-Python oracle remains the host-side fallback and supplies the rare
non-batch operations (key generation, signing), mirroring how the
reference keeps BlstLoader's graceful-degradation path.

Host/device split:
- host (`prepare_dispatch`): wire-format parsing (flag bits, x < P),
  SHA-256 message expansion, pubkey cache lookups, random multipliers —
  all marshaling vectorized with numpy (no per-lane Python bigint work
  on the hot path), and NOTHING of it launches a device program or
  reads one back: the guarded provider runs this half before it takes
  its device-entry lock, while another dispatch owns the chip;
- device (`launch_dispatch`): pubkey decompression + subgroup checks
  for cache misses (one batched dispatch), the H(m) arena's lookups,
  insert and gather, and the whole verification pipeline — per-lane
  multi-key aggregation, hash-to-G2, scalar muls, Miller loops, final
  exponentiation — as a chain of staged jitted programs per padded
  batch-shape bucket.

DEDUP-AWARE: hash-to-G2 (the largest per-lane stage) runs over each
batch's UNIQUE messages, backed by a bounded device-resident H(m)
point cache (ops/h2c_cache.py — steady-state committee gossip pays h2c
once per distinct AttestationData, a fully-warm batch dispatches no
h2c at all), and the Miller loops fold to unique width via pairing
bilinearity (ops/verify.py:stage_group).  begin_batch_verify is the
unguarded provider's async seam (both halves now, the sync later).

MESH: constructed with mesh=..., dispatches shard GROUP-ALIGNED
across the chips (teku_tpu/parallel.GroupShardedVerifier): whole
message-group rows per shard, lanes permuted to follow their rows, so
the dedup pipeline (unique-message h2c, grouped Miller rows)
survives the mesh: hash-to-curve, the arena and the gather of the H(m)
rows into the shard layout run on the mesh's first chip, the stage
functions on every chip's own lanes and rows; one all_gather of
per-device Fq12 partials crosses the ICI and the verdict contract is
unchanged (lane_ok un-permutes at the sync point).

Batch sizes (and the per-lane key-count axis) are padded to powers of
two so the jit cache stays small and shapes stay static (XLA recompiles
nothing after warm-up).
"""

import hashlib
import os
import secrets
import threading
import time
import types
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..crypto.bls import hash_to_curve as OH
from ..infra import (capacity, compilecache, dispatchledger, faults,
                     timeline, tracing)
from ..infra.collections import LimitedMap
from ..infra.env import env_int
from ..infra.metrics import GLOBAL_REGISTRY
from ..crypto.bls.constants import P, R
from ..crypto.bls.pure_impl import PureBls12381
from ..crypto.bls.spi import (BLS12381, BatchSemiAggregate,
                              PreparedDispatch, ResolvedHandle)
from . import h2c_cache as HC
from . import limbs as fp
from . import mxu
from . import points as PT
from . import verify as V

# jax is imported by now (via ops/__init__): install the compile-cache
# hit/miss listener so dispatch outcomes below can be classified
compilecache.ensure_instrumented()

_G1_INF = bytes([0xC0] + [0] * 47)
_G2_INF = bytes([0xC0] + [0] * 95)

# Process-level dispatch observability (module-level because the staged
# verify jits in ops/verify.py are shared across provider instances).
# First dispatch of a (padded, kmax) bucket shape is the one that pays
# the XLA work — `compile` when it was a fresh compile, `cache_load`
# when the persistent compile cache served it from disk, `aot_load`
# when the serialized-executable store (infra/aotstore.py) skipped
# XLA entirely; everything after hits the in-memory jit cache
# (`cache_hit`).  `path` is the active mont_mul engine (vpu | mxu,
# ops/mxu.py).
_SEEN_SHAPES: set = set()
_SEEN_LOCK = threading.Lock()
_M_JIT = GLOBAL_REGISTRY.labeled_counter(
    "bls_jit_dispatch_total",
    "verify dispatches by padded bucket shape (lanes x keys), "
    "jit-cache outcome (compile|cache_load|aot_load|cache_hit) and "
    "mont_mul path (vpu|mxu)",
    labelnames=("shape", "outcome", "path"))

# Dedup-aware h2c observability: hash-to-curve runs over each batch's
# UNIQUE messages (committee traffic signs the same AttestationData
# many times), so the lanes/unique gap is realized h2c savings and the
# dispatch counter proves a warm H(m) cache skips h2c entirely.
_M_H2C_LANES = GLOBAL_REGISTRY.counter(
    "bls_h2c_lanes_total",
    "real lanes entering unique-message h2c dedup")
_M_H2C_UNIQUE = GLOBAL_REGISTRY.counter(
    "bls_h2c_unique_total",
    "unique messages after dedup (h2c work actually owed)")
_M_H2C_DISPATCH = GLOBAL_REGISTRY.counter(
    "bls_h2c_dispatch_total",
    "hash-to-curve device dispatches (0 growth = H(m) cache warm)")

# Mesh observability: sharded dispatches labeled by device count (a
# closed pow-2 vocabulary — the resolver only ever yields pow-2 mesh
# sizes, linted in test_metrics_exposition); the companion
# bls_mesh_devices gauge lives in teku_tpu/parallel.
_M_MESH_DISPATCH = GLOBAL_REGISTRY.labeled_counter(
    "bls_mesh_dispatch_total",
    "verify dispatches served by the group-aligned sharded mesh "
    "kernel, by mesh device count",
    labelnames=("devices",))


# Where a dispatch's host prep ran: all of it in the host half, which
# the guarded provider runs BEFORE it takes the device-entry lock (the
# other worker's dispatch runs on the chip meanwhile), or part of it in
# the device half, under that lock, and why: a public key the cache
# lacked (`pk_validate` enters the device) or the H(m) arena (slot
# state follows device order).  The ledger record carries the same
# (`prep`, `prep_reason`).
_M_PREP = GLOBAL_REGISTRY.labeled_counter(
    "bls_dispatch_prep_total",
    "verify dispatches by where their host prep ran "
    "(outside_lock|under_lock) and why it stayed under the "
    "device-entry lock (none|pk_miss|arena|pk_miss+arena)",
    labelnames=("prep", "reason"))


# The key axis: every lane is padded to the batch's key bucket (`kmax`,
# a power of two: a closed vocabulary, linted in
# test_metrics_exposition), and the masked tree sum of `stage_prepare`
# runs over every slot, filled or not.  filled / dispatched is the
# share of that sum which added a real key; the ledger record carries
# the same as `waste.key` and `keys`.
_M_KEY_SLOTS_FILLED = GLOBAL_REGISTRY.labeled_counter(
    "bls_key_slots_filled_total",
    "public-key slots of verify dispatches that held a live key, by "
    "key bucket (keys a lane, padded to a power of two)",
    labelnames=("kmax",))
_M_KEY_SLOTS = GLOBAL_REGISTRY.labeled_counter(
    "bls_key_slots_dispatched_total",
    "public-key slots verify dispatches put on the device (padded "
    "lanes x key bucket), by key bucket",
    labelnames=("kmax",))

# A lane wider than any valid signature (`max_keys_per_lane`) is refused
# as malformed, a False verdict with no dispatch: counted, so that a
# refusal is never silent.
_M_KEY_CAP = GLOBAL_REGISTRY.counter(
    "bls_key_cap_refusals_total",
    "verify lanes refused without a dispatch because they held more "
    "public keys than the preset's widest valid signature "
    "(MAX_VALIDATORS_PER_COMMITTEE x MAX_COMMITTEES_PER_SLOT)")


def _dedup_ratio() -> float:
    # read unique BEFORE lanes (writers inc lanes first): a dispatch
    # landing between the reads skews the ratio high, never negative
    uniq = _M_H2C_UNIQUE.value
    lanes = _M_H2C_LANES.value
    return (lanes - uniq) / lanes if lanes else 0.0


# duplication factor observable: 0.875 means 8 lanes/unique message —
# the fraction of h2c work the dedup pipeline did NOT have to do
GLOBAL_REGISTRY.gauge(
    "bls_h2c_dedup_ratio",
    "fraction of lanes whose H(m) was served by dedup instead of h2c",
    supplier=_dedup_ratio)

# the host-side wire caches share the H(m) arena's eviction family
_EVICT_PK = HC.evictions_counter("pk")
_EVICT_U = HC.evictions_counter("u")


# pow-2 padding trades jit-cache size for dead lanes; the dead
# fraction is a direct throughput observable (0.3 means 30% of device
# work verified nothing).  The gauge moved to the dispatch ledger
# (infra/dispatchledger.py) as bls_dispatch_padding_waste_ratio{stage}
# — SPLIT by stage bucket (lane vs unique-h2c row), fed from the same
# per-dispatch counts the records below carry.


# one shared definition of the padding rule (infra/pow2.py) — the
# admission planner and mesh shard planner pad with the same function
from ..infra.pow2 import next_pow2 as _next_pow2  # noqa: E402
# the bucket POLICY (floors, group split, shape labels) lives in
# ops/shapeset.py so `cli precompile` enumerates the exact programs
# this module dispatches — provider has no private copy of any rule
# (drift is structurally impossible; tests/test_shapeset.py pins it)
from . import shapeset as SS  # noqa: E402
from ..infra import aotstore  # noqa: E402


def bytes_to_limbs_np(b: np.ndarray) -> np.ndarray:
    """Vectorized big-endian byte matrix (N, nbytes) -> limb matrix
    (N, L), replacing per-lane Python bigint conversion on the dispatch
    hot path."""
    le = b[:, ::-1].astype(np.uint64)          # little-endian bytes
    n, nb = le.shape
    out = np.zeros((n, fp.L), dtype=np.int64)
    for i in range(fp.L):
        bit0 = fp.W * i
        byte0, shift = divmod(bit0, 8)
        acc = np.zeros(n, dtype=np.uint64)
        for k in range(5):                     # 26 + 7 bits span <= 5 bytes
            idx = byte0 + k
            if idx < nb:
                acc |= le[:, idx] << np.uint64(8 * k)
        out[:, i] = ((acc >> np.uint64(shift))
                     & np.uint64(fp.MASK)).astype(np.int64)
    return out


class _Semi(BatchSemiAggregate):
    """Parsed, host-validated triple awaiting the device dispatch."""

    __slots__ = ("pk_limbs", "message", "sig_x_bytes", "sig_large",
                 "sig_inf", "key_s")

    def __init__(self, pk_limbs, message, sig_x_bytes, sig_large, sig_inf,
                 key_s: float = 0.0):
        self.pk_limbs = pk_limbs     # list of (x_mont, y_mont) np (L,)
        self.message = message
        self.sig_x_bytes = sig_x_bytes  # (2, 48) BE bytes of (x1, x0)
        self.sig_large = sig_large
        self.sig_inf = sig_inf
        self.key_s = key_s           # host seconds of the key lookup


class _DispatchHandle:
    """An in-flight batch dispatch.

    The device work was enqueued via JAX async dispatch when this was
    created (the `device_enqueue` phase runs from the first program
    call to this handle's `result()`: the launch calls, each stamped
    in the marks' `launches`, plus any XLA compile or program load a
    first shape pays);
    result() forces the verdict arrays (the only host/device sync
    point) — callers may do arbitrary host work (e.g. host_prep of the
    NEXT batch) between the two.  result() records ONLY the blocking
    wait as `device_sync`, so under async overlap the span no longer
    absorbs host-prep time spent between enqueue and sync (the old
    combined `device_execute` span's documented caveat), and feeds the
    capacity model's per-shape device-latency/occupancy accounting
    with the overlap-corrected interval.  The traces bound at dispatch
    time are captured so both spans attribute to the right
    verifications even when result() runs under a different context.
    """

    __slots__ = ("_ok", "_lane_ok", "_n", "_traces", "_done",
                 "_verdict", "_shape", "_path", "_t_enq_end",
                 "_lane_sel", "_rec", "_recorded", "_marks")

    def __init__(self, ok, lane_ok, n, traces, shape, path, t_enq_end,
                 lane_sel=None, rec=None, marks=None):
        self._ok = ok
        self._lane_ok = lane_ok
        self._n = n
        self._traces = traces
        self._shape = shape
        self._path = path
        self._t_enq_end = t_enq_end
        # mesh dispatches PERMUTE lanes into group-aligned shard
        # blocks: lane_sel maps original lane i -> its slot in the
        # dispatched layout, so the verdict reads the right lanes
        self._lane_sel = lane_sel
        # the open dispatch-ledger record _launch assembled:
        # result() completes it (sync duration, overlap-corrected
        # device time, verdict) and publishes it into the ring
        self._rec = rec
        # the dispatch's phase marks, captured like the traces: the
        # sync may run under another context
        self._marks = (marks if marks is not None
                       else tracing.current_marks())
        self._done = False
        self._recorded = False
        self._verdict = False

    def result(self) -> bool:
        """Synchronize and return the batch verdict (idempotent)."""
        if self._done:
            return self._verdict
        t_sync0 = self._marks.mark("device_sync")
        synced = False
        try:
            # np.asarray forces the device round-trip: this wait (and
            # nothing else) is the device_sync stage
            lane_ok = np.asarray(self._lane_ok)
            real = (lane_ok[self._lane_sel]
                    if self._lane_sel is not None
                    else lane_ok[:self._n])
            verdict = bool(np.asarray(self._ok)) and bool(real.all())
            synced = True
        finally:
            # the sync has ended: what follows, to the service's next
            # step on the event loop, is the way back
            t_end = self._marks.mark("return_hop")
            # the timeline's device-busy interval: enqueue-end →
            # sync-end, the numerator of overlap_efficiency (a raising
            # sync still occupied the device until it raised)
            timeline.interval(
                "device", "busy", t_end - self._t_enq_end,
                t_mono=self._t_enq_end,
                trace_id=(self._traces[0].trace_id if self._traces
                          else ""),
                shape=self._shape)
            if not synced and self._rec is not None:
                # a raising sync is still a decision worth its ledger
                # entry — the doctor wants to see the dispatch that
                # wedged, with its full decision context
                self._rec["device"] = {
                    "sync_s": round(t_end - t_sync0, 6),
                    "sync_error": True}
                self._rec["verdict"] = None
                if not self._recorded:
                    dispatchledger.record(self._rec)
                    self._recorded = True
        # true device time = enqueue-end → sync-end, clamped by the
        # tracker so overlapped dispatches never double-count.  Only a
        # SUCCESSFUL sync counts its lanes: a raising dispatch gets
        # bisected and re-dispatched, and crediting its lanes here
        # would inflate sustainable capacity during exactly the fault
        # incidents the capacity endpoint is meant to diagnose.
        busy = capacity.record_dispatch(self._shape, self._path,
                                        self._n, self._t_enq_end,
                                        t_end)
        self._done = True
        self._verdict = faults.transform("bls.dispatch", verdict)
        if self._rec is not None:
            self._rec["device"] = {
                "sync_s": round(t_end - t_sync0, 6),
                "busy_s": round(busy, 6)}
            self._rec["verdict"] = self._verdict
            # a retry after a raising sync already published this dict
            # into the ring: the in-place update above is enough — a
            # second record() would double-count its waste/decision
            # metrics and give one trace id two ring entries
            if not self._recorded:
                dispatchledger.record(self._rec)
                self._recorded = True
        return self._verdict


class _Packed(types.SimpleNamespace):
    """One dispatch's host half (`JaxBls12381._pack`): the launches'
    arguments as numpy arrays, the shape decisions (`kmax`, `padded`,
    rows and buckets, the mesh `plan`) and what the ledger
    record says of them.  Nothing in it has touched the device.
    H(m) is resolved once a distinct MESSAGE: `digests` is None with
    the arena off or bypassed (`draws` is then the padded h2c input
    over the messages), else the messages' SHA-256 digests beside their
    `draws`; `row_msg` maps each Miller row of the row bucket `u_hm`
    to its message."""

    def fill_keys(self, entries: dict) -> bool:
        """Pack the keys the host half's cache lookup missed, now that
        the device has validated them; False when one is not a valid
        key (the batch is then false without a dispatch)."""
        for p, j, pk in self.pk_pending:
            entry = entries[pk]
            if entry[0] != "ok":
                return False
            self.pk_xs[p, j], self.pk_ys[p, j] = entry[1], entry[2]
        self.pk_pending = ()
        return True


class _Prepared(PreparedDispatch):
    """`prepare_dispatch`'s result: a verdict the host half already
    knows (malformed input, an empty batch, a cached key), or the
    packed dispatches (one, unless the batch exceeds `max_batch`) with
    `pk_miss`, the keys the cache lacked ({pk: parsed wire}); `key`
    names the one key a `public_key_is_valid` asks about."""

    __slots__ = ("packed", "pk_miss", "key")

    def __init__(self, verdict: Optional[bool] = None, packed=(),
                 pk_miss: Optional[dict] = None,
                 key: Optional[bytes] = None):
        super().__init__(verdict)
        self.packed = packed
        self.pk_miss = pk_miss or {}
        self.key = key


def _as_triples(op: str, args: tuple):
    """A verify verb's arguments as `(triples, randomize)`: every verb
    is a batch of (public keys, message, signature) lanes, random
    multipliers for `batch_verify` alone.  `(None, False)` when the
    arguments cannot verify."""
    if op == "batch_verify":
        (triples,) = args
        return triples, True
    if op == "verify":
        public_key, message, signature = args
        return [([public_key], message, signature)], False
    if op == "fast_aggregate_verify":
        return [args], False
    if op == "aggregate_verify":
        public_keys, messages, signature = args
        if not public_keys or len(public_keys) != len(messages):
            return None, False
        # prod_i e(pk_i, H(m_i)) == e(g1, sig): the r=1 batch with the
        # signature attached to lane 0 and infinity signatures elsewhere.
        return [([pk], msg, signature if i == 0 else _G2_INF)
                for i, (pk, msg) in enumerate(zip(public_keys,
                                                  messages))], False
    raise ValueError(f"not a verify verb: {op}")


def _parse_g2_wire(sig: bytes):
    """Host wire checks for a compressed G2 signature.

    Returns (x_bytes (2, 48), large, is_inf) or None when malformed.
    On-curve and subgroup membership are checked on device."""
    if len(sig) != 96 or not sig[0] & 0x80:
        return None
    if sig[0] & 0x40:
        if any(sig[1:]) or (sig[0] & 0x3F):
            return None
        return (np.zeros((2, 48), dtype=np.uint8), False, True)
    x1 = int.from_bytes(bytes([sig[0] & 0x1F]) + sig[1:48], "big")
    x0 = int.from_bytes(sig[48:96], "big")
    if x0 >= P or x1 >= P:
        return None
    xb = np.frombuffer(sig, dtype=np.uint8).reshape(2, 48).copy()
    xb[0, 0] &= 0x1F
    return (xb, bool(sig[0] & 0x20), False)


def _parse_g1_wire(pk: bytes):
    """Host wire checks for a compressed G1 pubkey; same contract."""
    if len(pk) != 48 or not pk[0] & 0x80:
        return None
    if pk[0] & 0x40:
        if any(pk[1:]) or (pk[0] & 0x3F):
            return None
        return (0, False, True)
    x = int.from_bytes(bytes([pk[0] & 0x1F]) + pk[1:], "big")
    if x >= P:
        return None
    return (x, bool(pk[0] & 0x20), False)


class JaxBls12381(BLS12381):
    """TPU provider: batched pairing verification as single dispatches."""

    name = "jax-tpu"

    def __init__(self, max_batch: int = 4096,
                 max_keys_per_lane: int = SS.MAX_KEYS_PER_LANE,
                 min_bucket: int = 4, mesh=None):
        self._pure = PureBls12381()
        self.max_batch = max_batch
        # optional multi-chip dispatch: GROUP-ALIGNED sharding over the
        # mesh's dp axis — every shard owns whole message-group rows,
        # so the dedup pipeline (unique-message Miller grouping)
        # survives the mesh; partial products ride one all_gather
        # (teku_tpu/parallel.GroupShardedVerifier)
        self._sharded = None
        self.mesh_info = None
        if mesh is not None:
            from ..parallel import GroupShardedVerifier
            self._sharded = GroupShardedVerifier(mesh,
                                                 min_bucket=min_bucket)
            min_bucket = self._sharded.min_bucket
            self.mesh_info = self._sharded.describe()
        # the widest valid signature: an Electra attestation over a
        # whole slot's committees (`shapeset.MAX_KEYS_PER_LANE`, 131,072
        # on the mainnet preset); a wider lane is refused, and counted
        self.max_keys_per_lane = max_keys_per_lane
        # tiny batches pad up to one shared bucket: a couple of masked
        # lanes cost microseconds on device, a fresh XLA compile costs
        # minutes — fewer distinct shapes is strictly better
        self.min_bucket = min_bucket
        # pk bytes -> ("ok", x_mont (L,), y_mont (L,)) | ("bad",).
        # Bounded LRU, NOT a clear-at-bound dict: a wholesale clear
        # dumps every warm validator key at once and the next gossip
        # batches pay a re-validation storm; LRU evicts one cold entry
        # per insert and the shared eviction counter makes churn visible.
        self._pk_cache: LimitedMap = LimitedMap(
            200_000, on_evict=lambda _k, _v: _EVICT_PK.inc())
        self._u_cache: LimitedMap = LimitedMap(
            100_000, on_evict=lambda _k, _v: _EVICT_U.inc())
        # device-resident H(m) point cache: steady-state gossip pays
        # hash-to-curve once per distinct AttestationData
        self._h2c_cache = HC.H2cPointCache()
        # h2c dispatches pad the unique bucket to a pow-2 with this
        # floor so the h2c program keeps very few distinct shapes
        self._h2c_min_bucket = env_int("TEKU_TPU_H2C_MIN_BUCKET", 8,
                                       lo=1)
        # stage_group materializes a (U, G) lane matrix: cap G and
        # split oversized committees across rows (a message may own
        # several Miller rows — same verdict, bounded memory)
        self._group_cap = env_int("TEKU_TPU_H2C_GROUP_CAP", 32, lo=1)
        # staged dispatch: small programs instead of one monolith whose
        # TPU compile is unbounded (ops/verify.py staged_jits); h2c
        # runs separately over unique messages (see _launch)
        self._pk_validate_jit = aotstore.wrap(
            f"pk_validate:{mxu.resolve()}",
            jax.jit(self._pk_validate_kernel))
        # observability: proof that node traffic actually reaches the
        # device path (mirrors the reference's signature_verifications_*
        # counters at AggregatingSignatureVerificationService.java:76-98)
        self.dispatch_count = 0
        self.lanes_dispatched = 0
        # h2c dispatches this provider issued: the warm-cache tests
        # assert a fully-warm batch leaves this untouched
        self.h2c_dispatch_count = 0
        # reshape generation stamp (parallel/selfheal.MeshHealer sets
        # it on the provider it installs): dispatch-ledger records and
        # doctor findings name WHICH live device set served a dispatch
        # across eject/readmit cycles
        self.mesh_epoch = 0
        # the mont_mul engine resolved when this provider was built —
        # jitted programs KEEP the engine they were traced with, so
        # the dispatch metric labels with this, not a re-resolution
        # (a mid-process set_path() affects only not-yet-traced shapes)
        self.mont_path = mxu.resolve()

    # ------------------------------------------------------------------
    # Host-side SPI ops delegated to the oracle (rare, non-batch paths)
    # ------------------------------------------------------------------
    def secret_key_to_public_key(self, secret: int) -> bytes:
        return self._pure.secret_key_to_public_key(secret)

    def sign(self, secret: int, message: bytes) -> bytes:
        return self._pure.sign(secret, message)

    def aggregate_public_keys(self, public_keys: Sequence[bytes]) -> bytes:
        return self._pure.aggregate_public_keys(public_keys)

    def aggregate_signatures(self, signatures: Sequence[bytes]) -> bytes:
        return self._pure.aggregate_signatures(signatures)

    def signature_is_valid(self, signature: bytes) -> bool:
        return self._pure.signature_is_valid(signature)

    # ------------------------------------------------------------------
    # Pubkey cache with batched device validation
    # ------------------------------------------------------------------
    @staticmethod
    def _pk_validate_kernel(x_plain, large):
        ok, pt = PT.g1_recover_y(x_plain, large)
        ok = ok & PT.g1_in_subgroup(pt)
        # Z == 1 by construction: (X, Y) are already the affine coords
        return ok, fp.compress(pt[0]), fp.compress(pt[1])

    def _lookup_pks(self, all_pks: Sequence[bytes], resolved: dict,
                    miss: dict) -> None:
        """The host half of pubkey resolution: every requested key
        goes into `resolved` ({pk: entry}, from the cache or rejected
        on its wire form) or into `miss` ({pk: parsed wire}, for
        `_validate_pks`).  Touches no device."""
        for pk in all_pks:
            if pk in resolved or pk in miss:
                continue
            entry = self._pk_cache.get(pk)   # refreshes LRU recency
            if entry is not None:
                resolved[pk] = entry
                continue
            wire = _parse_g1_wire(pk)
            if wire is None or wire[2]:   # malformed or infinity
                resolved[pk] = ("bad",)
                self._pk_cache.put(pk, ("bad",))
            else:
                miss[pk] = wire

    def _validate_pks(self, miss: dict) -> dict:
        """The device half: ONE `pk_validate` dispatch over the keys of
        `miss` the cache still lacks (another dispatch may have
        validated them since the lookup), cache-filling; returns
        {pk: entry} for every key of `miss`."""
        resolved = {}
        todo = []
        for pk, wire in miss.items():
            entry = self._pk_cache.get(pk)
            if entry is not None:
                resolved[pk] = entry
            else:
                todo.append((pk, wire))
        if not todo:
            return resolved
        # floor of 16 keeps the validation program at very few distinct
        # shapes (same compile-cost argument as the verify min_bucket)
        n = SS.pk_validate_bucket(len(todo))
        xs = np.zeros((n, fp.L), dtype=np.int64)
        large = np.zeros(n, dtype=bool)
        for i, (_, (x, lg, _inf)) in enumerate(todo):
            xs[i] = fp.int_to_limbs(x)
            large[i] = lg
        ok, gx, gy = self._pk_validate_jit(xs, large)
        ok = np.asarray(ok)
        gx, gy = np.asarray(gx), np.asarray(gy)
        for i, (pk, _) in enumerate(todo):
            entry = ("ok", gx[i], gy[i]) if ok[i] else ("bad",)
            resolved[pk] = entry
            self._pk_cache.put(pk, entry)
        return resolved

    def _resolve_pks(self, all_pks: Sequence[bytes]) -> dict:
        """Resolve every requested pubkey (cache-filling, one device
        dispatch for the misses) and return {pk: entry}.

        The cache is a bounded LRU (pubkey bytes can be
        attacker-influenced, so an unbounded cache — including "bad"
        entries — is a slow memory-growth vector); eviction is one cold
        entry at a time, counted in bls_cache_evictions_total{cache="pk"}.
        Callers MUST read entries from the returned snapshot, never
        re-read the shared cache afterwards: at the bound, this batch's
        own inserts (or a concurrent worker's) may evict an entry
        resolved here, and a valid signature must not verify False
        because its pubkey went cold."""
        resolved, miss = {}, {}
        self._lookup_pks(all_pks, resolved, miss)
        if miss:
            resolved.update(self._validate_pks(miss))
        return resolved

    def public_key_is_valid(self, public_key: bytes) -> bool:
        return self._resolve_pks([public_key])[public_key][0] == "ok"

    # ------------------------------------------------------------------
    # Message hashing (host SHA-256 -> field draws, cached)
    # ------------------------------------------------------------------
    def _u_draws(self, message: bytes):
        hit = self._u_cache.get(message)
        if hit is None:
            (a, b), (c, d) = OH.hash_to_field_fq2(message, 2)
            hit = (fp.int_to_mont(a), fp.int_to_mont(b),
                   fp.int_to_mont(c), fp.int_to_mont(d))
            self._u_cache.put(message, hit)
        return hit

    # ------------------------------------------------------------------
    # Verification API — everything lands in the batched kernel, in two
    # halves: `prepare_dispatch` (host only) and `launch_dispatch`
    # (whatever enters the device).  An unguarded caller runs them back
    # to back; the guarded provider (crypto/bls/loader.py) takes its
    # device-entry lock between the two, so one worker packs its batch
    # while the other one's runs on the chip.
    # ------------------------------------------------------------------
    def _host_semi(self, triple: Tuple[Sequence[bytes], bytes, bytes],
                   miss: dict) -> Optional[_Semi]:
        """`prepare_batch_verify` from the pubkey cache alone: a key
        the cache lacks stays in the semi as its BYTES, its parsed wire
        goes into `miss`, and the device half resolves both."""
        public_keys, message, signature = triple
        if not public_keys:
            return None
        if len(public_keys) > self.max_keys_per_lane:
            _M_KEY_CAP.inc()
            return None
        t0 = time.perf_counter()
        resolved: dict = {}
        self._lookup_pks(public_keys, resolved, miss)
        points = []
        for pk in public_keys:
            entry = resolved.get(pk)
            if entry is None:
                points.append(pk)
            elif entry[0] != "ok":
                return None
            else:
                points.append((entry[1], entry[2]))
        key_s = time.perf_counter() - t0
        sig = _parse_g2_wire(signature)
        if sig is None:
            return None
        return _Semi(points, message, *sig, key_s=key_s)

    def prepare_batch_verify(
        self, triple: Tuple[Sequence[bytes], bytes, bytes]
    ) -> Optional[BatchSemiAggregate]:
        miss: dict = {}
        semi = self._host_semi(triple, miss)
        if semi is None or not miss:
            return semi
        entries = self._validate_pks(miss)
        for j, pt in enumerate(semi.pk_limbs):
            if isinstance(pt, bytes):
                entry = entries[pt]
                if entry[0] != "ok":
                    return None
                semi.pk_limbs[j] = (entry[1], entry[2])
        return semi

    def prepare_dispatch(self, op: str, *args) -> "_Prepared":
        """The HOST half of the verb `op`: wire parse, pubkey-cache
        lookups, array packing, the random multipliers' bits or digits,
        message digests and hash-to-field draws.  It launches nothing
        on the device and reads nothing back from it, so it may run
        while another dispatch owns the device.  What a batch needs of
        the device before its launches (validating a key the cache
        lacks, the H(m) arena's slots) is left to `launch_dispatch`,
        decided by what the input shows."""
        tracing.current_marks().mark("host_prep")
        if op == "public_key_is_valid":
            (public_key,) = args
            resolved: dict = {}
            miss: dict = {}
            self._lookup_pks([public_key], resolved, miss)
            if miss:
                return _Prepared(pk_miss=miss, key=public_key)
            return _Prepared(verdict=resolved[public_key][0] == "ok")
        triples, randomize = _as_triples(op, args)
        if triples is None:
            return _Prepared(verdict=False)
        miss = {}
        semis = [self._host_semi(t, miss) for t in triples]
        return self._prepare_semis(semis, miss, randomize)

    def _prepare_semis(self, semis: Sequence[Optional[_Semi]],
                       miss: dict, randomize: bool) -> "_Prepared":
        if any(s is None for s in semis):
            return _Prepared(verdict=False)
        if not semis:
            return _Prepared(verdict=True)
        # oversized batches split; all chunks must pass
        return _Prepared(
            packed=[self._pack(semis[i:i + self.max_batch], randomize)
                    for i in range(0, len(semis), self.max_batch)],
            pk_miss=miss)

    def launch_dispatch(self, prepared: "_Prepared"):
        """The DEVICE half: finishes what `prepare_dispatch` had to
        leave (under the guard's lock, because it enters the device or
        must follow device order), opens the ledger record, launches
        the staged programs and returns the handle whose `result()`
        syncs."""
        if prepared.verdict is not None:
            return ResolvedHandle(prepared.verdict)
        t_prep0 = None
        if prepared.pk_miss:
            # a key the cache lacked: `pk_validate` runs on the device
            t_prep0 = tracing.current_marks().mark("host_prep")
            entries = self._validate_pks(prepared.pk_miss)
            if prepared.key is not None:
                return ResolvedHandle(entries[prepared.key][0] == "ok")
            if not all(p.fill_keys(entries) for p in prepared.packed):
                return ResolvedHandle(False)
        if len(prepared.packed) == 1:
            return self._launch(prepared.packed[0], t_prep0)
        return ResolvedHandle(all(
            self._launch(p, t_prep0 if i == 0 else None).result()
            for i, p in enumerate(prepared.packed)))

    def _run(self, op: str, *args) -> bool:
        # a direct caller: both halves back to back, under the
        # service's marks when it marks one, else this block's own
        with tracing.dispatch_marks("host_prep"):
            return self.launch_dispatch(
                self.prepare_dispatch(op, *args)).result()

    def complete_batch_verify(
        self, semi_aggregates: Sequence[Optional[BatchSemiAggregate]]
    ) -> bool:
        with tracing.dispatch_marks("host_prep"):
            return self.launch_dispatch(self._prepare_semis(
                list(semi_aggregates), {}, randomize=True)).result()

    def batch_verify(
        self, triples: Sequence[Tuple[Sequence[bytes], bytes, bytes]],
    ) -> bool:
        return self._run("batch_verify", triples)

    def verify(self, public_key: bytes, message: bytes,
               signature: bytes) -> bool:
        return self._run("verify", public_key, message, signature)

    def fast_aggregate_verify(self, public_keys: Sequence[bytes],
                              message: bytes, signature: bytes) -> bool:
        # the service's single-task dispatches (a bisection's last
        # steps) come this way: the same phases as a batch
        return self._run("fast_aggregate_verify", public_keys, message,
                         signature)

    def aggregate_verify(self, public_keys: Sequence[bytes],
                         messages: Sequence[bytes], signature: bytes) -> bool:
        return self._run("aggregate_verify", public_keys, messages,
                         signature)

    # ------------------------------------------------------------------
    # Dedup-aware dispatch: h2c over unique messages + async handle
    # ------------------------------------------------------------------
    def begin_batch_verify(self, triples: Sequence[
            Tuple[Sequence[bytes], bytes, bytes]]):
        """Async-overlap entry: host_prep + device enqueue NOW (JAX
        async dispatch), verdict at handle.result().  The batching
        service uses this to overlap host_prep of batch N+1 with
        device execution of batch N.  Returns None for oversized
        batches
        (callers fall back to the splitting sync path)."""
        if len(triples) > self.max_batch:
            return None
        # the caller's marks (the service's `_begin`): nothing is
        # scoped here, the handle outlives this call
        handle = self.launch_dispatch(
            self.prepare_dispatch("batch_verify", triples))
        # in flight: the caller gets its thread back and syncs later
        tracing.current_marks().mark("return_hop")
        return handle

    @staticmethod
    def _pad_draws(draws: Sequence[tuple], bucket: int):
        """Per-message hash_to_field draws as the h2c stage's padded
        (bucket, L) arrays."""
        u0c0 = np.zeros((bucket, fp.L), dtype=np.int64)
        u0c1 = np.zeros((bucket, fp.L), dtype=np.int64)
        u1c0 = np.zeros((bucket, fp.L), dtype=np.int64)
        u1c1 = np.zeros((bucket, fp.L), dtype=np.int64)
        for j, d in enumerate(draws):
            u0c0[j], u0c1[j], u1c0[j], u1c1[j] = d
        return (u0c0, u0c1), (u1c0, u1c1)

    def _uniq_draws(self, msgs: List[bytes], bucket: int):
        """Host hash_to_field draws for `msgs`, padded to `bucket`."""
        return self._pad_draws([self._u_draws(m) for m in msgs], bucket)

    def _h2c_dispatch(self, draws):
        """ONE hash-to-curve device dispatch over precomputed draws."""
        u0, u1 = draws
        self.h2c_dispatch_count += 1
        _M_H2C_DISPATCH.inc()
        return V.staged_jits()["h2c"](u0, u1)

    def _hm_host(self, uniq_msgs: List[bytes]):
        """Host half of H(m) resolution, once a MESSAGE: `(digests,
        draws)` over the batch's distinct messages, however many Miller
        rows each of them owns.

        With the arena off, or bypassed because the batch carries more
        distinct messages than the whole arena holds (inserting more
        points than capacity would recycle slots assigned earlier in
        the same call and serve the wrong point), `digests` is None and
        `draws` the padded h2c input at the messages' bucket.
        Otherwise the SHA-256 digests and the per-message draws, for
        `_hm_arena_plan`: which of them need an h2c dispatch is slot
        state, known only in device order."""
        cache = self._h2c_cache
        if not cache.enabled or len(uniq_msgs) > cache.capacity:
            return None, self._uniq_draws(
                uniq_msgs,
                SS.unique_bucket(len(uniq_msgs), self._h2c_min_bucket))
        return ([hashlib.sha256(m).digest() for m in uniq_msgs],
                [self._u_draws(m) for m in uniq_msgs])

    def _hm_arena_plan(self, digests, draws):
        """The arena's lookups, one a distinct message, in device order
        (the caller holds the device-entry lock where there is one): a
        slot read here must still hold its point when this dispatch's
        gather runs, and only the dispatches launched before it may
        recycle slots.  Returns `(slots, missing, digests, miss_draws)`
        by message; `miss_draws` is the h2c input over the missing
        messages at their miss bucket, None when every message hit."""
        slots = np.zeros(len(digests), dtype=np.int64)
        missing = []
        for j, dg in enumerate(digests):
            slot = self._h2c_cache.lookup(dg)
            if slot is None:
                missing.append(j)
            else:
                slots[j] = slot
        miss_draws = None
        if missing:
            mb = SS.h2c_miss_bucket(len(missing),
                                    self._h2c_min_bucket)
            miss_draws = self._pad_draws([draws[j] for j in missing],
                                         mb)
        return slots, missing, digests, miss_draws

    def _hm_device(self, plan, pack: "_Packed"):
        """Device half of H(m) resolution for a deduped batch: the
        H(m) tree with one entry a Miller ROW, from points resolved
        once a MESSAGE (`pack.row_msg` maps each row of the row bucket
        to its message).

        Arena hits cost one gather; misses pay ONE h2c dispatch over
        the missing-message bucket and land in the arena by one
        scatter at that bucket; a fully-warm batch performs ZERO h2c
        dispatches.  With the arena off or bypassed the distinct
        messages are hashed and, where a committee was split over
        several rows, gathered to them.  Padding rows (>= the row
        count) carry arbitrary points — group_present masks them
        downstream."""
        slots, missing, digests, draws = plan
        if slots is None:   # cache disabled/bypassed: plain unique h2c
            hm_msgs = self._h2c_dispatch(draws)
            if pack.n_rows == pack.n_unique:    # a row a message
                return hm_msgs
            return V.staged_jits()["gather"](
                hm_msgs, jnp.asarray(pack.row_msg))
        if missing:
            hm_bucket = self._h2c_dispatch(draws)
            new_slots = self._h2c_cache.insert(
                [digests[j] for j in missing], hm_bucket)
            slots[np.asarray(missing)] = new_slots
        return self._h2c_cache.gather(slots[pack.row_msg])

    def _pack(self, semis: Sequence[_Semi],
              randomize: bool) -> "_Packed":
        """One dispatch's host half: numpy and plain Python alone."""
        n = len(semis)
        t_hp0 = tracing.current_marks().mark("host_prep")
        kmax = SS.kmax_bucket(max(len(s.pk_limbs) for s in semis))
        # unique-message index + per-message lane groups: h2c AND
        # the Miller loops run at unique width (stage_group folds a
        # message's lanes into one pairing input via bilinearity)
        uniq_index: dict = {}
        uniq_msgs: List[bytes] = []
        groups: List[List[int]] = []
        for i, s in enumerate(semis):
            u = uniq_index.get(s.message)
            if u is None:
                u = uniq_index[s.message] = len(uniq_msgs)
                uniq_msgs.append(s.message)
                groups.append([])
            groups[u].append(i)
        # split committees larger than the group cap across rows:
        # G stays bounded (the grouped gather materializes a
        # (U, G) lane matrix) and a split message simply owns
        # several Miller rows backed by the SAME H(m) point
        rows: List[Tuple[int, List[int]]] = SS.group_rows(
            groups, self._group_cap)
        g_bucket = SS.group_bucket(rows)
        # canonical row bucket: the width of the H(m) tree the Miller
        # loops read.  Computed from the batch alone — IDENTICAL for
        # single-device and mesh dispatch of the same batch, so
        # the dedup counters and h2c dispatch count cannot depend
        # on the mesh (pinned in tests/test_mesh_grouped.py)
        u_hm = SS.unique_bucket(len(rows), self._h2c_min_bucket)
        # row -> message: H(m) is resolved once a message, and the
        # rows of a split committee share its point through this index
        # (padding rows read message 0 — masked)
        row_msg = np.zeros(u_hm, dtype=np.int32)
        row_msg[:len(rows)] = [u for u, _ in rows]
        if self._sharded is not None:
            # group-aligned shard layout: whole rows per shard,
            # lanes permuted into each shard's contiguous block
            plan = self._sharded.plan(
                rows, n, min_rows_total=self._h2c_min_bucket)
            padded = plan.padded
            u_total = plan.rows_total
            lane_pos = plan.lane_pos
        else:
            plan = None
            padded = SS.lane_bucket(n, self.min_bucket)
            u_total = u_hm
            lane_pos = None
        pk_xs = np.zeros((padded, kmax, fp.L), dtype=np.int64)
        pk_ys = np.zeros((padded, kmax, fp.L), dtype=np.int64)
        pk_present = np.zeros((padded, kmax), dtype=bool)
        sig_bytes = np.zeros((padded, 2, 48), dtype=np.uint8)
        s_large = np.zeros(padded, dtype=bool)
        s_inf = np.zeros(padded, dtype=bool)
        lane_valid = np.zeros(padded, dtype=bool)
        # (lane, key slot, pk bytes) of the keys the cache lacked: the
        # device half validates them and `fill_keys` packs them
        pk_pending = []
        t_keys = time.perf_counter()
        for i, s in enumerate(semis):
            p = i if lane_pos is None else int(lane_pos[i])
            for j, pt in enumerate(s.pk_limbs):
                pk_present[p, j] = True
                if isinstance(pt, bytes):
                    pk_pending.append((p, j, pt))
                else:
                    pk_xs[p, j], pk_ys[p, j] = pt
            sig_bytes[p] = s.sig_x_bytes
            s_large[p] = s.sig_large
            s_inf[p] = s.sig_inf
            lane_valid[p] = True
        # the key lookups (in `_host_semi`) and this fill: the host's
        # part of the key axis
        key_pack_s = (time.perf_counter() - t_keys
                      + sum(s.key_s for s in semis))
        group_idx = np.zeros((u_total, g_bucket), dtype=np.int32)
        group_present = np.zeros((u_total, g_bucket), dtype=bool)
        row_gather = None
        if plan is None:
            for r, (_, g) in enumerate(rows):
                group_idx[r, :len(g)] = g
                group_present[r, :len(g)] = True
        else:
            # group_idx carries SHARD-LOCAL lane indices (under
            # shard_map each shard sees only its own lane block);
            # row_gather scatters the canonical H(m) rows into the
            # shard layout (padding rows gather slot 0 — masked)
            row_gather = np.zeros(u_total, dtype=np.int32)
            for pos, r in enumerate(plan.row_layout):
                if r < 0:
                    continue
                g = rows[r][1]
                base = ((pos // plan.rows_per_shard)
                        * plan.lanes_per_shard)
                group_idx[pos, :len(g)] = \
                    lane_pos[np.asarray(g)] - base
                group_present[pos, :len(g)] = True
                row_gather[pos] = r
        sx1 = bytes_to_limbs_np(sig_bytes[:, 0])
        sx0 = bytes_to_limbs_np(sig_bytes[:, 1])
        if randomize:
            # one os-entropy draw for the whole batch (the
            # reference uses SecureRandom per multiplier,
            # BlstBLS12381.java:191-195); zero multipliers are
            # nudged to 1 (2^-64 bias, negligible)
            raw = np.frombuffer(secrets.token_bytes(8 * padded),
                                dtype=np.uint64).copy()
            raw[raw == 0] = 1
        else:
            raw = np.ones(padded, dtype=np.uint64)
        r_bits = PT.scalar_bits_np(raw)
        digests, draws = self._hm_host(uniq_msgs)
        # the timeline's host-prep interval: the serial host-side term
        # host_prep_serial_share is computed from (subtracting any
        # overlap with device-busy intervals)
        timeline.interval(
            "worker", "host_prep", time.perf_counter() - t_hp0,
            t_mono=t_hp0, trace_id=tracing.current_trace_id())
        return _Packed(
            n=n, randomize=randomize, kmax=kmax, key_pack_s=key_pack_s,
            n_unique=len(uniq_msgs), n_rows=len(rows),
            g_bucket=g_bucket, u_hm=u_hm, plan=plan, padded=padded,
            u_total=u_total, lane_pos=lane_pos, pk_xs=pk_xs,
            pk_ys=pk_ys, pk_present=pk_present, pk_pending=pk_pending,
            sx=(sx0, sx1), s_large=s_large, s_inf=s_inf,
            lane_valid=lane_valid, group_idx=group_idx,
            group_present=group_present, row_gather=row_gather,
            row_msg=row_msg, r_bits=r_bits,
            digests=digests, draws=draws)

    def _launch(self, pack: "_Packed",
                t_prep0: Optional[float] = None) -> "_DispatchHandle":
        """One packed dispatch onto the device.  `t_prep0`: when the
        device half's own prep began (a key validated under the lock),
        None when the host half had done it all."""
        # `bls.dispatch` fault site: the supervisor/breaker tests prove
        # hang/exception containment at the REAL device-dispatch seam
        faults.check("bls.dispatch")
        n = pack.n
        self.dispatch_count += 1
        self.lanes_dispatched += n
        # the dispatch's marks: the service's when it marks one, else
        # the scope `_run` opened
        marks = tracing.current_marks()
        # where this dispatch's prep ran, for the ledger and /metrics:
        # all of it in the host half (which the guard runs before it
        # takes the device-entry lock), or part of it here, and why
        reasons = ["pk_miss"] if t_prep0 is not None else []
        if pack.digests is None:
            hm_plan = (None, None, None, pack.draws)
        else:
            reasons.append("arena")
            if t_prep0 is None:
                t_prep0 = marks.mark("host_prep")
            hm_plan = self._hm_arena_plan(pack.digests, pack.draws)
        # what follows up to the first program call (counters, the
        # ledger record) is the hold's head, whatever prep came before
        t_head = marks.mark("launch_head")
        prep = "under_lock" if reasons else "outside_lock"
        _M_PREP.labels(prep=prep,
                       reason="+".join(reasons) or "none").inc()
        # per-dispatch H(m) arena accounting for the ledger, by
        # MESSAGE: a bypassed/disabled cache means every distinct
        # message pays h2c at the messages' bucket; otherwise misses
        # pay at the missing-message bucket and hits cost one gather
        plan_slots, plan_missing, _, plan_draws = hm_plan
        # the bucket actually dispatched is read off the plan's
        # own padded draws (first dim) — never re-derived, so a
        # change to the plan's bucket rule can't skew the ledger
        h2c_bucket = (plan_draws[0][0].shape[0]
                      if plan_draws is not None else 0)
        misses = (pack.n_unique if plan_slots is None
                  else len(plan_missing))
        h2c_stats = {"cache_hits": pack.n_unique - misses,
                     "cache_misses": misses,
                     "dispatch_bucket": h2c_bucket}
        if t_prep0 is not None:
            timeline.interval(
                "worker", "host_prep", t_head - t_prep0,
                t_mono=t_prep0, trace_id=tracing.current_trace_id())
        plan, padded = pack.plan, pack.padded
        mesh_n = (self._sharded.n_devices
                  if self._sharded is not None else 0)
        # mesh dispatches get their own shape family (the capacity
        # model's latency series must not blend an 8-chip program with
        # the single-device one; latency_for_lanes prefix-matches
        # "{lanes}x" so the admission planner still sees mesh-shaped
        # device latencies for its batch sizing)
        shape = SS.shape_label(padded, pack.kmax, mesh_n)
        # the staged jits are module-level (shared across providers)
        # and the sharded kernels are process-memoized by (device set,
        # axis) — key the seen-set on the kernel identity that will
        # actually serve the dispatch, so a reshaped provider over
        # known devices reads cache_hit, not compile
        cache_key = (self._sharded.kernel_key()
                     if self._sharded is not None else 0, shape)
        with _SEEN_LOCK:
            first = cache_key not in _SEEN_SHAPES
            _SEEN_SHAPES.add(cache_key)
        mont_path = self.mont_path
        # first dispatch of a shape pays the XLA work: diff the
        # persistent-cache counters around it to tell a fresh compile
        # from a disk cache load (racy under concurrent first
        # dispatches — the label may misattribute, the counts don't)
        cache_before = compilecache.stats() if first else None
        aot_before = aotstore.stats() if first else None
        _M_H2C_LANES.inc(n)
        _M_H2C_UNIQUE.inc(pack.n_unique)
        keys = int(np.count_nonzero(pack.pk_present))
        key_slots = padded * pack.kmax
        key_bucket = str(pack.kmax)
        _M_KEY_SLOTS_FILLED.labels(kmax=key_bucket).inc(keys)
        _M_KEY_SLOTS.labels(kmax=key_bucket).inc(key_slots)
        if mesh_n:
            _M_MESH_DISPATCH.labels(devices=str(mesh_n)).inc()
        # device section: every launch below is async (XLA compiles
        # synchronously on a first shape, then enqueues); the enqueue
        # span ends when the launches return, and the handle's
        # result() records the blocking wait as device_sync
        traces = tracing.current_traces()
        # the dispatch-ledger record: the full decision context of THIS
        # dispatch, completed by the handle's result().  open_record()
        # also merges the batching service's context annotations (plan
        # mode, brownout level, class mix) — asyncio.to_thread copied
        # them into this worker thread.  Opened here, in the device
        # half, so the ledger's order is the device's.
        if plan is not None:
            # `devices` + `epoch` stamp the LIVE device set serving
            # this dispatch: after a self-healing reshape the ledger
            # shows which records ran on the shrunken/regrown mesh
            mesh_block = {"devices": mesh_n,
                          "epoch": self.mesh_epoch,
                          "live": list(self._sharded.devices),
                          "shard_lanes": plan.shard_lanes,
                          "shard_rows": plan.shard_rows,
                          "lanes_per_shard": plan.lanes_per_shard,
                          "rows_per_shard": plan.rows_per_shard,
                          "makespan_ratio": round(
                              plan.makespan_ratio, 4)}
        else:
            mesh_block = {"devices": 0, "epoch": self.mesh_epoch}
        rec = dispatchledger.open_record(
            trace_ids=[t.trace_id for t in traces],
            shape=shape, mont_path=mont_path, randomized=pack.randomize,
            lanes=n, kmax=pack.kmax, keys=keys,
            key_pack_s=round(pack.key_pack_s, 6),
            unique_messages=pack.n_unique, rows=pack.n_rows,
            group_bucket=pack.g_bucket,
            dedup_ratio=round((n - pack.n_unique) / n, 4),
            waste={"lane": {"real": n, "padded": padded},
                   "h2c": {"real": pack.n_rows, "padded": pack.u_total},
                   "key": {"real": keys, "padded": key_slots}},
            h2c=h2c_stats,
            # one scalars stage; the field stays because the
            # benchmark's set-up log reads it (ROADMAP C15)
            msm={"path": "ladder"},
            mesh=mesh_block, prep=prep)
        if reasons:
            rec["prep_reason"] = "+".join(reasons)
        # the first program call marks `device_enqueue`; the enqueue's
        # seconds (the compile or load a first shape pays) count from
        # here as they always have
        t_dev0 = time.perf_counter()
        n_launched = len(marks.launches)
        outcome = "cache_hit"
        enqueued = False
        try:
            hm_uniq = self._hm_device(hm_plan, pack)
            if self._sharded is not None:
                # `bls.mesh_shard` fault site: a wedged SHARD wedges
                # the whole mesh dispatch.  The LIVE device names ride
                # as keys so the chaos harness can wedge exactly one
                # chip: the keyed fault fires here (the collective
                # includes it) AND at that device's isolation probe
                # (parallel/selfheal.py), and stops firing once the
                # sick device is ejected from the live set
                faults.check("bls.mesh_shard",
                             keys=self._sharded.devices)
                # scatter the canonical H(m) rows into the shard
                # layout with one gather, then the group-aligned
                # kernel runs the full dedup pipeline per shard
                hm_in = V.staged_jits()["gather"](
                    hm_uniq, jnp.asarray(pack.row_gather))
                kernel = self._sharded.kernel()
            else:
                kernel = V.verify_staged_grouped
                hm_in = hm_uniq
            ok, lane_ok = kernel(
                pack.pk_xs, pack.pk_ys, pack.pk_present, hm_in,
                pack.group_idx, pack.group_present, pack.sx,
                pack.s_large, pack.s_inf, pack.r_bits, pack.lane_valid)
            enqueued = True
            if self._sharded is not None and marks:
                # where each launch ran: H(m) and the row gather on ONE
                # chip while the others stood idle, the sharded
                # programs on the live set
                one_chip = [str(d) for d in jax.tree_util.tree_leaves(
                    hm_uniq)[0].devices()]
                for launch in marks.launches[n_launched:]:
                    launch.append(mesh_block["live"]
                                  if launch[0].startswith("mesh_")
                                  else one_chip)
        finally:
            if first:
                outcome = compilecache.classify_first_dispatch(
                    compilecache.delta(cache_before),
                    aot=aotstore.delta(aot_before))
            _M_JIT.labels(shape=shape, outcome=outcome,
                          path=mont_path).inc()
            t_enq_end = time.perf_counter()
            # on a first shape the enqueue duration IS the XLA cost
            # this dispatch paid (fresh compile or disk cache load) —
            # the doctor's cold-compile findings cite it per record,
            # and `programs` splits it by AOT program (read,
            # deserialize, compile, first call)
            rec["compile"] = {"outcome": outcome,
                              "enqueue_s": round(
                                  t_enq_end - t_dev0, 6)}
            if first:
                rec["compile"]["programs"] = aotstore.load_records(
                    since=t_dev0)
            if not enqueued:
                # a raising enqueue (fault injection, XLA error) never
                # constructs the handle whose result() would publish
                # the record — and the dispatch that DIED is exactly
                # the one the doctor most needs to see
                rec["device"] = {"enqueue_error": True}
                rec["verdict"] = None
                dispatchledger.record(rec)
        return _DispatchHandle(ok, lane_ok, n, traces, shape,
                               mont_path, t_enq_end,
                               lane_sel=pack.lane_pos, rec=rec,
                               marks=marks)
