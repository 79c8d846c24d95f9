"""Dedup-aware verify pipeline: unique-message h2c, gather/scatter
exactness, the device-resident H(m) cache, and grouped-Miller parity.

Committee gossip signs the same AttestationData many times, so the
provider (teku_tpu/ops/provider.py) hashes-to-curve the batch's UNIQUE
messages only and folds each message's lanes into one Miller loop via
pairing bilinearity (ops/verify.py:stage_group).  These tests pin the
contract edges: all-duplicate / all-unique / duplicate-across-the-
padding-boundary batches, bit-exact gather/scatter, warm-cache batches
making ZERO h2c dispatches with verdicts identical to the cold path
(on BOTH mont_mul paths), and a poisoned cache entry never flipping a
verdict (the hit is re-verified by key, `h2c.cache` fault site).

Batch shapes stay tiny (lane buckets 4/8/16, unique bucket 8) so the
CPU-XLA compiles are shared with the other provider tests and cached
persistently.
"""

import numpy as np
import pytest

from teku_tpu.crypto import bls
from teku_tpu.crypto.bls import keygen
from teku_tpu.crypto.bls.pure_impl import PureBls12381
from teku_tpu.infra import faults
from teku_tpu.ops import h2c_cache as HC
from teku_tpu.ops import mxu
from teku_tpu.ops import verify as V
from teku_tpu.ops.provider import JaxBls12381

PURE = PureBls12381()
SKS = [keygen(bytes([80 + i]) * 32) for i in range(6)]
PKS = [PURE.secret_key_to_public_key(sk) for sk in SKS]


@pytest.fixture(scope="module")
def impl():
    impl = JaxBls12381()
    bls.set_implementation(impl)
    yield impl
    bls.reset_implementation()


@pytest.fixture(autouse=True)
def _clear_faults():
    yield
    faults.clear()


def _triples(lane_msgs, tamper_lane=None):
    """One single-key triple per lane; lanes sharing a message model a
    committee (distinct signers, same AttestationData)."""
    out = []
    for i, m in enumerate(lane_msgs):
        sign_msg = b"tampered" if i == tamper_lane else m
        out.append(([PKS[i % 6]], m, PURE.sign(SKS[i % 6], sign_msg)))
    return out


def _oracle_verdict(triples):
    return PURE.batch_verify(triples)


# --------------------------------------------------------------------------
# unique-index gather/scatter shapes
# --------------------------------------------------------------------------

def test_all_lanes_duplicate(impl):
    msgs = [b"dup-all"] * 4
    triples = _triples(msgs)
    d0 = impl.h2c_dispatch_count
    assert impl.batch_verify(triples) is True
    # one batch, one unique message -> exactly one h2c dispatch
    assert impl.h2c_dispatch_count == d0 + 1
    # and a bad signer among the duplicates still fails the batch
    assert impl.batch_verify(_triples(msgs, tamper_lane=2)) is False


def test_all_unique(impl):
    msgs = [b"uniq-%d" % i for i in range(4)]
    triples = _triples(msgs)
    assert impl.batch_verify(triples) is True
    assert impl.batch_verify(_triples(msgs, tamper_lane=1)) is False
    assert _oracle_verdict(triples) is True


def test_duplicate_across_padding_boundary(impl):
    # 3 real lanes pad to the 4-lane bucket; the duplicate pair spans
    # the last real lane, adjacent to the padding lanes (which map to
    # group row 0 — their contribution must stay masked)
    msgs = [b"pb-a", b"pb-b", b"pb-a"]
    triples = _triples(msgs)
    assert impl.batch_verify(triples) is True
    # tamper the duplicate that sits AT the padding boundary
    assert impl.batch_verify(_triples(msgs, tamper_lane=2)) is False
    assert _oracle_verdict(triples) is True


def test_multi_key_lanes_share_message(impl):
    # aggregate lanes (fast-aggregate semantics) over one message:
    # grouping must fold the in-kernel key aggregates too
    m = b"committee-agg"
    agg = PURE.aggregate_signatures(
        [PURE.sign(sk, m) for sk in SKS[:3]])
    triples = [
        (PKS[:3], m, agg),
        ([PKS[3]], m, PURE.sign(SKS[3], m)),
        ([PKS[4]], b"other", PURE.sign(SKS[4], b"other")),
    ]
    assert impl.batch_verify(triples) is True
    bad = list(triples)
    bad[0] = (PKS[:2], m, agg)      # wrong key set for the aggregate
    assert impl.batch_verify(bad) is False


# --------------------------------------------------------------------------
# gather/scatter exactness + grouped-vs-per-lane parity
# --------------------------------------------------------------------------

def test_gather_scatter_bit_exact():
    from teku_tpu.ops import examples
    (pk_xs, pk_ys, pk_present, u0, u1, group_idx, group_present,
     sig_x, s_large, s_inf, r_bits, lane_valid) = examples.example_batch(4)
    jits = V.staged_jits()
    hm_uniq = jits["h2c"](u0, u1)
    # lane_map derived from the group index
    n = pk_xs.shape[0]
    lane_map = np.zeros(n, dtype=np.int32)
    for u in range(group_idx.shape[0]):
        for g in range(group_idx.shape[1]):
            if group_present[u, g]:
                lane_map[group_idx[u, g]] = u
    hm_lanes = jits["gather"](hm_uniq, lane_map)
    # the gather is a pure scatter of rows: bit-identical limbs
    (ux, uxi), (uy, uyi) = (np.asarray(a) for a in hm_uniq[0]), \
                           (np.asarray(a) for a in hm_uniq[1])
    (lx, lxi), (ly, lyi) = hm_lanes
    assert np.array_equal(np.asarray(lx), np.asarray(ux)[lane_map])
    assert np.array_equal(np.asarray(lxi), np.asarray(uxi)[lane_map])
    assert np.array_equal(np.asarray(ly), np.asarray(uy)[lane_map])
    assert np.array_equal(np.asarray(lyi), np.asarray(uyi)[lane_map])
    # per-lane (gathered hm) and grouped pipelines agree on the verdict
    ok_lane, lane_ok1 = V.verify_staged_hm(
        pk_xs, pk_ys, pk_present, hm_lanes, sig_x, s_large, s_inf,
        r_bits, lane_valid)
    ok_grp, lane_ok2 = V.verify_staged_grouped(
        pk_xs, pk_ys, pk_present, hm_uniq, group_idx, group_present,
        sig_x, s_large, s_inf, r_bits, lane_valid)
    assert bool(np.asarray(ok_lane)) is bool(np.asarray(ok_grp)) is True
    assert np.array_equal(np.asarray(lane_ok1), np.asarray(lane_ok2))


# --------------------------------------------------------------------------
# device-resident H(m) cache: warm batches make ZERO h2c dispatches
# --------------------------------------------------------------------------

def _warm_cold_parity(impl):
    msgs = [b"warm-a", b"warm-b"] * 2
    good = _triples(msgs)
    bad = _triples(msgs, tamper_lane=3)
    cold_good = impl.batch_verify(good)
    d0 = impl.h2c_dispatch_count
    warm_good = impl.batch_verify(good)     # fully warm: same messages
    warm_bad = impl.batch_verify(bad)
    assert impl.h2c_dispatch_count == d0, \
        "fully-warm batches must make zero h2c dispatches"
    assert (cold_good, warm_good, warm_bad) == (True, True, False)
    st = impl._h2c_cache.stats()
    assert st["hits"] > 0


def test_warm_cache_zero_h2c_dispatch_vpu(impl):
    assert impl.mont_path == "vpu"     # CPU backend resolves to vpu
    _warm_cold_parity(impl)


def test_warm_cache_parity_mxu_force():
    """The cache-warm path on the MXU mont_mul engine: cold h2c output
    and warm arena round trip must be BIT-IDENTICAL limb arrays.

    Point-level bit-identity subsumes verdict identity (the downstream
    stages are deterministic in their inputs), so this gates the
    warm-vs-cold contract on the mxu path while compiling only the h2c
    stage under the forced engine — the full-pipeline warm/cold gate
    runs on the vpu path above, and cross-engine full-pipeline parity
    is owned by tests/test_ops_limbs.py's bit-identical mont_mul
    contract."""
    import hashlib
    import jax
    with mxu.force("mxu-force"):
        # a FRESH jit object retraces stage_h2c under the forced
        # engine even at an already-seen shape
        h2c_mxu = jax.jit(V.stage_h2c)
        impl = JaxBls12381()
        assert impl.mont_path == "mxu"
        msgs = [b"mxu-warm-a", b"mxu-warm-b"]
        u0, u1 = impl._uniq_draws(msgs, 8)
        cold = h2c_mxu(u0, u1)
        cache = HC.H2cPointCache(capacity=8)
        digests = [hashlib.sha256(m).digest() for m in msgs]
        cache.insert(digests, cold)
        slots = [cache.lookup(dg) for dg in digests]
        assert None not in slots            # warm: zero h2c recomputes
        warm = cache.gather(np.asarray(slots))
        (cx0, cx1), (cy0, cy1) = cold
        (wx0, wx1), (wy0, wy1) = warm
        for c, w in ((cx0, wx0), (cx1, wx1), (cy0, wy0), (cy1, wy1)):
            assert np.array_equal(np.asarray(c)[:2], np.asarray(w))


def test_cache_disabled_still_dedups(monkeypatch):
    monkeypatch.setenv(HC.ENV_CAP, "0")
    impl = JaxBls12381()
    assert not impl._h2c_cache.enabled
    msgs = [b"nocache-x"] * 3 + [b"nocache-y"]
    d0 = impl.h2c_dispatch_count
    assert impl.batch_verify(_triples(msgs)) is True
    assert impl.h2c_dispatch_count == d0 + 1   # one dispatch, 2 uniques
    # no cache: the repeat pays h2c again
    d1 = impl.h2c_dispatch_count
    assert impl.batch_verify(_triples(msgs)) is True
    assert impl.h2c_dispatch_count == d1 + 1


def test_oversized_committee_splits_across_group_rows(monkeypatch):
    # a committee larger than the group cap splits across Miller rows
    # (bounded (U, G) matrix); the rows share one H(m) point and the
    # verdict is unchanged
    monkeypatch.setenv("TEKU_TPU_H2C_GROUP_CAP", "2")
    impl = JaxBls12381()
    assert impl._group_cap == 2
    msgs = [b"split-big"] * 3 + [b"split-one"]
    d0 = impl.h2c_dispatch_count
    assert impl.batch_verify(_triples(msgs)) is True
    assert impl.h2c_dispatch_count == d0 + 1     # still ONE h2c dispatch
    assert impl.batch_verify(_triples(msgs, tamper_lane=1)) is False


def test_more_uniques_than_capacity_bypasses_cache(monkeypatch):
    # a cold batch carrying more unique messages than the WHOLE arena
    # holds would recycle slots assigned earlier in the same insert
    # (duplicate scatter indices -> wrong points served); the provider
    # bypasses the cache for such batches and insert() rejects them
    monkeypatch.setenv(HC.ENV_CAP, "2")
    impl = JaxBls12381()
    assert impl._h2c_cache.capacity == 2
    msgs = [b"overcap-%d" % i for i in range(4)]   # 4 uniques > cap 2
    d0 = impl.h2c_dispatch_count
    assert impl.batch_verify(_triples(msgs)) is True
    assert impl.h2c_dispatch_count == d0 + 1       # one bypass dispatch
    assert len(impl._h2c_cache) == 0               # arena untouched
    assert impl.batch_verify(_triples(msgs, tamper_lane=2)) is False
    with pytest.raises(ValueError):
        impl._h2c_cache.insert([bytes([i]) * 32 for i in range(3)],
                               None)


def test_cache_lru_eviction_bound(impl):
    cache = HC.H2cPointCache(capacity=4)
    jits = V.staged_jits()
    rows = jits["h2c"](
        *impl._uniq_draws([b"ev-%d" % i for i in range(6)], 8))
    digests = [bytes([i]) * 32 for i in range(6)]
    cache.insert(digests[:4], rows)
    assert len(cache) == 4 and cache.evictions == 0
    cache.insert(digests[4:], rows)     # 2 more -> 2 LRU evictions
    assert len(cache) == 4 and cache.evictions == 2
    assert cache.lookup(digests[0]) is None      # LRU victim gone
    assert cache.lookup(digests[5]) is not None


# --------------------------------------------------------------------------
# fault injection: a poisoned cache entry must not flip a verdict
# --------------------------------------------------------------------------

@pytest.mark.faults
def test_poisoned_cache_entry_does_not_flip_verdict(impl):
    msgs = [b"poison-a", b"poison-b"] * 2
    good = _triples(msgs)
    assert impl.batch_verify(good) is True          # warm the cache
    st0 = impl._h2c_cache.stats()
    # poison every lookup of the next batch: resolved slots point at
    # the WRONG arena rows — the digest re-verification must catch it,
    # drop the entry, and recompute instead of trusting the hit
    faults.inject("h2c.cache",
                  faults.WrongResult(value=impl._h2c_cache.capacity - 1,
                                     times=2))
    d0 = impl.h2c_dispatch_count
    assert impl.batch_verify(good) is True, \
        "poisoned H(m) cache entry flipped a verdict"
    st1 = impl._h2c_cache.stats()
    assert st1["misses"] > st0["misses"]     # poison detected as miss
    assert impl.h2c_dispatch_count > d0      # recomputed, not trusted
    faults.clear("h2c.cache")
    # the recomputed entries are clean again: warm + zero dispatches
    d1 = impl.h2c_dispatch_count
    assert impl.batch_verify(good) is True
    assert impl.h2c_dispatch_count == d1


# --------------------------------------------------------------------------
# dedup metrics
# --------------------------------------------------------------------------

def test_dedup_metrics_track_lanes_and_uniques(impl):
    from teku_tpu.ops import provider as pv
    lanes0 = pv._M_H2C_LANES.value
    uniq0 = pv._M_H2C_UNIQUE.value
    assert impl.batch_verify(_triples([b"metric-m"] * 4)) is True
    assert pv._M_H2C_LANES.value == lanes0 + 4
    assert pv._M_H2C_UNIQUE.value == uniq0 + 1
    assert 0.0 <= pv._dedup_ratio() < 1.0
