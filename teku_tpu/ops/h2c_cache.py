"""Device-resident H(m) point cache for the dedup-aware verify pipeline.

In committee-based consensus the same ``AttestationData`` is signed by a
whole committee, so gossip keeps re-delivering signatures over the SAME
message — and hash-to-G2 is the largest per-lane stage (~2,600
mont_muls, PERF.md).  This cache keeps the mapped G2 points resident on
the device so steady-state traffic pays h2c ONCE per distinct message:
a fully-warm batch skips the h2c dispatch entirely and serves H(m) with
one gather out of the arena.

Layout: a fixed-capacity arena of four (capacity, L) limb arrays (the
affine Fq2 x and y coordinate components, Montgomery form) that lives
on the device; the host side keeps an LRU index of message digest →
arena slot.  An insert is ONE scatter program over the whole h2c
output bucket (rows past the digests carry an out-of-range slot and
are dropped), a gather ONE gather program — no per-point host/device
round trips, the point data never leaves the device, and no program's
shape depends on how many messages missed: one scatter per h2c miss
bucket, one gather per row bucket, each compiled by the first batch
that needs it.

Poison defense (fault site ``h2c.cache``): every slot records the
digest it was computed for, and a hit is RE-VERIFIED BY KEY — the slot's
recorded digest must equal the queried digest, else the entry is
treated as a miss (dropped + recomputed), never trusted blindly.  The
fault-injection tests corrupt the lookup through the site and prove a
poisoned entry cannot flip a verdict.

Knobs: ``TEKU_TPU_H2C_CACHE_CAP`` — arena capacity in points (default
4096 ≈ 2 MB of device memory; ``0``/``off`` disables the cache, the
pipeline still dedups within each batch).
"""

import threading
from collections import OrderedDict
from typing import List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..infra import faults, tracing
from ..infra.env import env_str
from ..infra.metrics import GLOBAL_REGISTRY
from . import limbs as fp

ENV_CAP = "TEKU_TPU_H2C_CACHE_CAP"
DEFAULT_CAP = 4096

_M_HITS = GLOBAL_REGISTRY.counter(
    "bls_h2c_cache_hits_total",
    "H(m) device-cache lookups served from the arena")
_M_MISSES = GLOBAL_REGISTRY.counter(
    "bls_h2c_cache_misses_total",
    "H(m) device-cache lookups that required a hash-to-curve dispatch")
# one eviction family across every bounded verify-path cache (pk wire
# cache, u-draw cache, H(m) arena): a re-validation storm shows up as a
# rate spike on ONE dashboard series per cache
_M_EVICTIONS = GLOBAL_REGISTRY.labeled_counter(
    "bls_cache_evictions_total",
    "LRU evictions from the bounded verify-path caches",
    labelnames=("cache",))


def evictions_counter(cache: str):
    """The shared eviction family, bound to one cache label (the
    provider wires its pk/u caches through this too)."""
    return _M_EVICTIONS.labels(cache=cache)


@jax.jit
def _scatter(arena, idx, hm_bucket):
    """Rows of an h2c output bucket into their arena slots; a row whose
    slot is out of range (the bucket's padding) is dropped."""
    (x0, x1), (y0, y1) = hm_bucket
    return tuple(a.at[idx].set(rows, mode="drop")
                 for a, rows in zip(arena, (x0, x1, y0, y1)))


@jax.jit
def _gather(arena, idx):
    x0, x1, y0, y1 = (a[idx] for a in arena)
    return ((x0, x1), (y0, y1))


def configured_capacity() -> int:
    raw = env_str(ENV_CAP, "") or ""
    if raw.strip().lower() in ("off", "false", "no"):
        return 0
    try:
        return int(raw) if raw else DEFAULT_CAP
    except ValueError:
        return DEFAULT_CAP


class H2cPointCache:
    """Bounded LRU of device-resident H(m) affine points.

    Thread-safe: the batching service dispatches from worker threads.
    Arena updates are functional (`.at[].set` yields new arrays), so a
    gather launched against the previous arena stays consistent.  The
    scatter and the gather are launches of the dispatch that calls
    them (`tracing.launched`, by their jitted functions' names).
    """

    def __init__(self, capacity: Optional[int] = None):
        self.capacity = (configured_capacity() if capacity is None
                         else capacity)
        self._lock = threading.Lock()
        # digest -> slot, insertion/touch order = LRU order
        self._index: "OrderedDict[bytes, int]" = OrderedDict()
        # slot -> digest it was computed for (the hit re-verification
        # record; None = never used)
        self._slot_digest: List[Optional[bytes]] = [None] * self.capacity
        self._free: List[int] = list(range(self.capacity - 1, -1, -1))
        self._arena = None      # lazily: 4 x (capacity, L) device arrays
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    @property
    def enabled(self) -> bool:
        return self.capacity > 0

    def __len__(self) -> int:
        return len(self._index)

    # ------------------------------------------------------------------
    def lookup(self, digest: bytes) -> Optional[int]:
        """Arena slot holding H(m) for `digest`, or None on miss.

        A hit is re-verified by key: the slot's recorded digest must
        match, else the entry is poisoned/stale — drop it and report a
        miss so the caller recomputes.  (`h2c.cache` fault site: tests
        corrupt the resolved slot here.)"""
        with self._lock:
            slot = self._index.get(digest)
            if slot is not None:
                # fault site: a WrongResult(value=...) poisons the
                # resolved slot — the re-verification below must catch it
                slot = faults.transform("h2c.cache", slot)
                if (not isinstance(slot, int)
                        or not 0 <= slot < self.capacity
                        or self._slot_digest[slot] != digest):
                    # poisoned entry: never trust it — evict and recompute
                    self._index.pop(digest, None)
                    self.misses += 1
                    _M_MISSES.inc()
                    return None
                self._index.move_to_end(digest)
                self.hits += 1
                _M_HITS.inc()
                return slot
            self.misses += 1
            _M_MISSES.inc()
            return None

    # ------------------------------------------------------------------
    def insert(self, digests: Sequence[bytes], hm_bucket) -> np.ndarray:
        """Store the first len(digests) rows of an h2c output bucket.

        `hm_bucket` is stage_h2c's affine tree ((x0, x1), (y0, y1)) of
        (B, L) device arrays with B >= len(digests).  Returns the (k,)
        array of assigned slots.  ONE scatter at the bucket's own width
        B: the rows past k are given the slot `capacity`, which is out
        of range and dropped, so the program launched depends on the
        miss bucket `stage_h2c` ran at and never on the miss count k.
        LRU entries are evicted as needed."""
        k = len(digests)
        if k > self.capacity:
            # an over-capacity insert would evict slots assigned
            # earlier in THIS call (duplicate scatter indices — one
            # row wins) and gather wrong points; callers bypass the
            # cache instead (provider._hm_host)
            raise ValueError(
                f"insert of {k} points exceeds arena capacity "
                f"{self.capacity}")
        bucket = hm_bucket[0][0].shape[0]
        idx = np.full(bucket, self.capacity, dtype=np.int64)
        with self._lock:
            for i, dg in enumerate(digests):
                existing = self._index.get(dg)
                if existing is not None:
                    # the same message twice in one call, or inserted
                    # by a concurrent dispatch: reuse its slot
                    idx[i] = existing
                    self._index.move_to_end(dg)
                    continue
                if not self._free:
                    old_dg, old_slot = self._index.popitem(last=False)
                    self._slot_digest[old_slot] = None
                    self._free.append(old_slot)
                    self.evictions += 1
                    _M_EVICTIONS.labels(cache="h2c").inc()
                slot = self._free.pop()
                self._index[dg] = slot
                self._slot_digest[slot] = dg
                idx[i] = slot
            if self._arena is None:
                shape = (self.capacity, fp.L)
                self._arena = tuple(
                    jnp.zeros(shape, dtype=jnp.int64) for _ in range(4))
            self._arena = tracing.launched(
                _scatter.__name__, _scatter, self._arena, idx, hm_bucket)
        return idx[:k]

    # ------------------------------------------------------------------
    def gather(self, lane_slots: np.ndarray):
        """H(m) affine tree of the given slots (one a Miller row) from
        the arena: one gather program over the four coordinate
        arrays."""
        with self._lock:
            arena = self._arena
        assert arena is not None, "gather before any insert"
        return tracing.launched(
            _gather.__name__, _gather, arena,
            np.asarray(lane_slots, dtype=np.int64))

    # ------------------------------------------------------------------
    def stats(self) -> dict:
        with self._lock:
            return {"capacity": self.capacity, "size": len(self._index),
                    "hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions}
