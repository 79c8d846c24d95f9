"""JaxBls12381 provider behind the facade — parity with the oracle.

Batch sizes are kept tiny (<= 4 triples) so the CPU-XLA compile cost of
each padded-size bucket is paid at most a handful of times.
"""

import pytest

from teku_tpu.crypto import bls
from teku_tpu.crypto.bls import keygen
from teku_tpu.crypto.bls.pure_impl import G1_INFINITY, G2_INFINITY
from teku_tpu.ops.provider import JaxBls12381


@pytest.fixture(scope="module")
def jax_impl():
    impl = JaxBls12381()
    bls.set_implementation(impl)
    yield impl
    bls.reset_implementation()


SKS = [keygen(bytes([i]) * 32) for i in range(1, 5)]
PKS = None
MSG = b"attestation data root"


def _pks():
    global PKS
    if PKS is None:
        from teku_tpu.crypto.bls.pure_impl import PureBls12381
        p = PureBls12381()
        PKS = [p.secret_key_to_public_key(sk) for sk in SKS]
    return PKS


def test_verify_roundtrip(jax_impl):
    pk = _pks()[0]
    sig = bls.sign(SKS[0], MSG)
    assert bls.verify(pk, MSG, sig)
    assert not bls.verify(pk, b"other message", sig)
    assert not bls.verify(_pks()[1], MSG, sig)


def test_verify_garbage_inputs(jax_impl):
    pk = _pks()[0]
    sig = bls.sign(SKS[0], MSG)
    assert not bls.verify(pk[:-1], MSG, sig)       # truncated pk
    assert not bls.verify(pk, MSG, sig[:-1])       # truncated sig
    assert not bls.verify(G1_INFINITY, MSG, sig)   # infinity pk invalid
    assert not bls.verify(pk, MSG, G2_INFINITY)
    bad_sig = bytes([sig[0]]) + bytes(95)
    assert not bls.verify(pk, MSG, bad_sig)


def test_fast_aggregate_verify(jax_impl):
    sigs = [bls.sign(sk, MSG) for sk in SKS[:3]]
    agg = bls.aggregate_signatures(sigs)
    assert bls.fast_aggregate_verify(_pks()[:3], MSG, agg)
    assert not bls.fast_aggregate_verify(_pks()[:2], MSG, agg)
    assert not bls.fast_aggregate_verify(_pks()[:3], b"wrong", agg)


def test_aggregate_verify_distinct_messages(jax_impl):
    msgs = [b"m-%d" % i for i in range(3)]
    sigs = [bls.sign(sk, m) for sk, m in zip(SKS[:3], msgs)]
    agg = bls.aggregate_signatures(sigs)
    assert bls.aggregate_verify(_pks()[:3], msgs, agg)
    assert not bls.aggregate_verify(_pks()[:3], list(reversed(msgs)), agg)
    assert not bls.aggregate_verify(_pks()[:2], msgs[:2], agg)


def test_batch_verify_mixed(jax_impl):
    triples = []
    for i, sk in enumerate(SKS[:3]):
        msg = b"batch-%d" % i
        triples.append(([_pks()[i]], msg, bls.sign(sk, msg)))
    # multi-key triple (fast-aggregate semantics inside one lane)
    agg_msg = b"agg lane"
    agg_sig = bls.aggregate_signatures(
        [bls.sign(sk, agg_msg) for sk in SKS[:3]])
    triples.append((_pks()[:3], agg_msg, agg_sig))
    assert bls.batch_verify(triples)
    # one corrupted lane fails the whole batch
    bad = list(triples)
    bad[1] = (bad[1][0], b"tampered", bad[1][2])
    assert not bls.batch_verify(bad)


def test_batch_verify_infinity_sig_lane(jax_impl):
    # infinity signature with a real pubkey cannot verify
    triples = [([_pks()[0]], MSG, G2_INFINITY)]
    assert not bls.batch_verify(triples)


def test_prepare_complete_split(jax_impl):
    msg = b"split path"
    semis = [
        bls.prepare_batch_verify(([_pks()[i]], msg, bls.sign(SKS[i], msg)))
        for i in range(2)
    ]
    assert all(s is not None for s in semis)
    assert bls.complete_batch_verify(semis)
    assert bls.prepare_batch_verify(([], msg, G2_INFINITY)) is None
    assert not bls.complete_batch_verify(semis + [None])


def test_eth_wrappers(jax_impl):
    assert bls.eth_fast_aggregate_verify([], b"x", G2_INFINITY)
    with pytest.raises(ValueError):
        bls.eth_aggregate_pubkeys([])
    assert bls.public_key_is_valid(_pks()[0])
    assert not bls.public_key_is_valid(G1_INFINITY)
    assert not bls.public_key_is_valid(b"\x00" * 48)


def test_non_subgroup_signature_rejected(jax_impl):
    # an on-curve G2 point outside the subgroup must be rejected on device
    import random
    from teku_tpu.crypto.bls import curve as C, fields as F
    from teku_tpu.crypto.bls.constants import P
    rng = random.Random(5)
    while True:
        x = (rng.randrange(P), rng.randrange(P))
        rhs = F.fq2_add(F.fq2_mul(F.fq2_sqr(x), x), (4, 4))
        y = F.fq2_sqrt(rhs)
        if y is None:
            continue
        p = (x, y, F.FQ2_ONE)
        if not C.g2_in_subgroup(p):
            break
    bad_sig_bytes = C.g2_compress(p)  # compress doesn't subgroup-check
    assert not bls.verify(_pks()[0], MSG, bad_sig_bytes)


# --------------------------------------------------------------------------
# The dispatch in two halves: `prepare_dispatch` (host only) and
# `launch_dispatch` (what enters the device)
# --------------------------------------------------------------------------

def _signed(n, tag=b"halves", sks=None):
    sks = SKS if sks is None else sks
    pks = _pks()
    return [([pks[i % 4]], tag + b"-%d" % i,
             bls.sign(sks[i % 4], tag + b"-%d" % i)) for i in range(n)]


@pytest.mark.parametrize("op", ["batch_verify", "fast_aggregate_verify",
                                "aggregate_verify"])
def test_host_half_never_enters_the_device(jax_impl, op):
    """With its keys in the cache, the host half launches no device
    program and makes no transfer: it may run while another dispatch
    owns the device.  The control: the `jnp` bit expansion it used to
    draw the multipliers with is refused under the same guard."""
    import jax
    import numpy as np
    from teku_tpu.ops import points as PT
    triples = _signed(3, tag=b"host-half")
    assert all(jax_impl.public_key_is_valid(pk) for pk in _pks())
    args = {"batch_verify": (triples,),
            "fast_aggregate_verify": triples[0],
            "aggregate_verify": ([t[0][0] for t in triples],
                                 [t[1] for t in triples],
                                 triples[0][2])}[op]
    with jax.transfer_guard("disallow_explicit"):
        with pytest.raises(Exception, match="Disallowed"):
            np.asarray(PT.scalar_from_uint64(
                np.ones(4, dtype=np.uint64)))
        prepared = jax_impl.prepare_dispatch(op, *args)
    assert prepared.verdict is None and not prepared.pk_miss
    (packed,) = prepared.packed
    assert not packed.pk_pending
    leaves = [packed.pk_xs, packed.pk_ys, packed.pk_present, *packed.sx,
              packed.s_large, packed.s_inf, packed.r_bits,
              packed.lane_valid, packed.group_idx, packed.group_present]
    assert all(type(a) is np.ndarray for a in leaves)
    # the multipliers: r = 1 exactly off `batch_verify`, else 64 random
    # bits a lane (never 0)
    assert packed.r_bits.shape == (packed.padded, 64)
    assert packed.r_bits.dtype == np.int64
    if op != "batch_verify":
        assert (packed.r_bits[:, :63] == 0).all()
        assert (packed.r_bits[:, 63] == 1).all()
    else:
        assert packed.r_bits.any(axis=1).all()


def test_host_verdicts_need_no_device_half(jax_impl):
    sig = bls.sign(SKS[0], MSG)
    pk = _pks()[0]
    for op, args in (("verify", (pk[:-1], MSG, sig)),
                     ("verify", (pk, MSG, sig[:-1])),
                     ("fast_aggregate_verify", ([], MSG, sig)),
                     ("aggregate_verify", ([pk], [], sig)),
                     ("batch_verify", ([([pk], MSG, b"")],))):
        assert jax_impl.prepare_dispatch(op, *args).verdict is False
    assert jax_impl.prepare_dispatch("batch_verify", []).verdict is True
    assert jax_impl.public_key_is_valid(pk)
    assert jax_impl.prepare_dispatch(
        "public_key_is_valid", pk).verdict is True
    assert jax_impl.prepare_dispatch(
        "public_key_is_valid", G1_INFINITY).verdict is False
    with pytest.raises(ValueError):
        jax_impl.prepare_dispatch("sign", 1, MSG)


def _guarded_records(impl, call):
    """Run `call(guarded)` under marks of the test's own (a direct
    caller of the guard has none); returns its result and the ledger
    records it left."""
    from teku_tpu.crypto.bls import loader
    from teku_tpu.infra import dispatchledger, tracing
    from teku_tpu.infra.metrics import MetricsRegistry
    from teku_tpu.infra.supervisor import CircuitBreaker
    breaker = CircuitBreaker(failure_threshold=2, deadline_s=600.0,
                             name="halves", registry=MetricsRegistry())
    guarded = loader.GuardedBls12381(impl, breaker,
                                     registry=MetricsRegistry())
    seq0 = dispatchledger.LEDGER.recorded_total
    with tracing.dispatch_marks("thread_hop"):
        out = call(guarded)
    return out, [r for r in dispatchledger.LEDGER.snapshot()
                 if r["seq"] > seq0]


def _prep_count(prep, reason):
    from teku_tpu.infra.metrics import GLOBAL_REGISTRY
    fam = GLOBAL_REGISTRY.metrics()["bls_dispatch_prep_total"]
    return fam.labels(prep=prep, reason=reason).value


def test_missed_key_is_validated_under_the_lock(jax_impl):
    """A key the cache lacks: the host half hands it over, the device
    half validates it under the device-entry lock and packs its lane;
    the record and the counter say so."""
    from teku_tpu.ops import h2c_cache as HC
    from teku_tpu.crypto.bls.pure_impl import PureBls12381
    arena, jax_impl._h2c_cache = jax_impl._h2c_cache, HC.H2cPointCache(0)
    try:
        sk = keygen(b"\x77" * 32)
        fresh = PureBls12381().secret_key_to_public_key(sk)
        assert jax_impl._pk_cache.get(fresh) is None
        msg = b"fresh key"
        triples = _signed(2, tag=b"miss") + [([fresh], msg,
                                              bls.sign(sk, msg))]
        prepared = jax_impl.prepare_dispatch("batch_verify", triples)
        assert set(prepared.pk_miss) == {fresh}
        assert prepared.packed[0].pk_pending == [(2, 0, fresh)]
        assert jax_impl._pk_cache.get(fresh) is None
        before = _prep_count("under_lock", "pk_miss")
        ok, (rec,) = _guarded_records(
            jax_impl, lambda g: g.batch_verify(triples))
        assert ok is True
        assert (rec["prep"], rec["prep_reason"]) == ("under_lock",
                                                     "pk_miss")
        assert _prep_count("under_lock", "pk_miss") == before + 1
        names = [n for n, _t, _s in rec["phases"]]
        assert names[:8] == ["thread_hop", "prep_wait", "host_prep",
                             "lock_wait", "launch_head", "host_prep",
                             "launch_head", "device_enqueue"]
        # the second `host_prep` (the validation) lies under the lock;
        # its `pk_validate` call is a launch, and opens no phase
        t_validate = rec["phases"][5][1]
        assert rec["lock"]["acquired"] <= t_validate + 2.5e-6
        assert rec["launches"][0][0] == "_pk_validate_kernel"
        assert rec["launches"][0][1] < rec["phases"][6][1]
        assert jax_impl._pk_cache.get(fresh)[0] == "ok"
        # the same batch again: every key cached, all prep off the lock
        before = _prep_count("outside_lock", "none")
        ok, (rec,) = _guarded_records(
            jax_impl, lambda g: g.batch_verify(triples))
        assert ok is True and rec["prep"] == "outside_lock"
        assert "prep_reason" not in rec
        assert _prep_count("outside_lock", "none") == before + 1
        assert [n for n, _t, _s in rec["phases"]][:6] == [
            "thread_hop", "prep_wait", "host_prep", "lock_wait",
            "launch_head", "device_enqueue"]
        # a forged lane behind a fresh key is false, not an error
        sk2 = keygen(b"\x78" * 32)
        fresh2 = PureBls12381().secret_key_to_public_key(sk2)
        ok, _recs = _guarded_records(
            jax_impl, lambda g: g.batch_verify(
                triples + [([fresh2], msg, bls.sign(sk, msg))]))
        assert ok is False
        # an invalid key that only the device can reject (x on no
        # curve point): false without a dispatch
        from teku_tpu.infra import dispatchledger
        off_curve = next(
            pk for pk in (bytes([0x80]) + bytes(46) + bytes([i])
                          for i in range(1, 64))
            if not PureBls12381().public_key_is_valid(pk))
        seq0 = dispatchledger.LEDGER.recorded_total
        ok, recs = _guarded_records(
            jax_impl, lambda g: g.batch_verify(
                triples + [([off_curve], msg, bls.sign(sk, msg))]))
        assert ok is False and recs == []
        assert dispatchledger.LEDGER.recorded_total == seq0
        assert jax_impl._pk_cache.get(off_curve) == ("bad",)
    finally:
        jax_impl._h2c_cache = arena


def test_arena_lookups_stay_under_the_lock(jax_impl):
    """With the H(m) arena on, its lookups, insert and gather follow
    device order (under the lock); the digests and the hash-to-field
    draws are the host half's."""
    assert jax_impl._h2c_cache.enabled
    triples = _signed(3, tag=b"arena")
    prepared = jax_impl.prepare_dispatch("batch_verify", triples)
    (packed,) = prepared.packed
    assert len(packed.digests) == len(packed.draws) == 3
    hits0 = jax_impl._h2c_cache.hits + jax_impl._h2c_cache.misses
    jax_impl.prepare_dispatch("batch_verify", triples)
    assert jax_impl._h2c_cache.hits + jax_impl._h2c_cache.misses == hits0
    ok, (rec,) = _guarded_records(
        jax_impl, lambda g: g.batch_verify(triples))
    assert ok is True
    assert (rec["prep"], rec["prep_reason"]) == ("under_lock", "arena")
    assert rec["h2c"]["cache_misses"] == 3
    ok, (rec,) = _guarded_records(
        jax_impl, lambda g: g.batch_verify(triples))
    assert ok is True and rec["h2c"]["cache_hits"] == 3


def test_arena_plan_sits_between_two_launch_head_pieces(jax_impl):
    """With the arena on, the hold begins with `launch_head` at the
    lock's edge, the arena's plan is `host_prep` under the lock, and
    the bookkeeping after it is `launch_head` again up to the first
    launch; the phases still tile."""
    assert jax_impl._h2c_cache.enabled
    triples = _signed(3, tag=b"heads")
    ok, (rec,) = _guarded_records(
        jax_impl, lambda g: g.batch_verify(triples))
    assert ok is True and rec["prep_reason"] == "arena"
    phases = rec["phases"]
    assert [n for n, _t, _s in phases] == [
        "thread_hop", "prep_wait", "host_prep", "lock_wait",
        "launch_head", "host_prep", "launch_head", "device_enqueue",
        "device_sync", "return_hop"]
    for (_n0, t0, secs), (_n1, t1, _s1) in zip(phases, phases[1:]):
        assert t0 + secs == pytest.approx(t1, abs=2.5e-6)
    assert phases[4][1] == rec["lock"]["acquired"]
    assert phases[7][1] == rec["launches"][0][1]
    assert [p for p, _t0, _s in rec["launches"]][:3] == [
        "stage_h2c", "_scatter", "_gather"]


def test_guarded_key_check_takes_the_lock_only_for_a_miss(jax_impl):
    from teku_tpu.crypto.bls.pure_impl import PureBls12381
    fresh = PureBls12381().secret_key_to_public_key(keygen(b"\x79" * 32))

    class Held:
        """A lock that counts its acquisitions."""
        def __init__(self):
            self.n = 0

        def __enter__(self):
            self.n += 1

        def __exit__(self, *exc):
            return False

    def check(pk):
        def call(guarded):
            lock = Held()
            device, _lock, turn = guarded._serving
            guarded._serving = (device, lock, turn)
            return guarded.public_key_is_valid(pk), lock.n
        return _guarded_records(jax_impl, call)[0]

    assert check(fresh) == (True, 1)
    assert check(fresh) == (True, 0)
    assert check(G1_INFINITY) == (False, 0)
