"""MSM-grade scalars stage: GLV + Pippenger adversarial parity suite.

The pippenger path (ops/msm.py) must be indistinguishable from the
ladder oracle at every level:

- the GLV constants: phi = [lambda] on G1, -psi^2 = [lambda] on G2,
  and the sampled-half-scalar map (k1, k2) -> k1 + k2*lambda mod r is
  nonzero/injective on the sampling range;
- kernel level: bucket MSMs over adversarial digit patterns (zero
  scalars, all-ones/max-duplicate bucket indices, infinity points,
  masked/excluded columns) match the oracle and the scalar_mul_bits
  ladder with IDENTICAL canonical() accumulator points;
- pipeline level: verify_staged_pippenger is verdict-bit-identical to
  verify_staged_grouped driven with the effective multipliers'
  255-bit bit arrays;
- provider level: batch_verify verdicts agree between the paths (and
  with the pure oracle) across committee-duplicated, all-unique,
  tampered, infinity-signature, and over-group-cap batches, on BOTH
  mont_mul engines (vpu and mxu-force with freshly traced stages).

Shapes stay tiny (4/8-lane buckets) so the CPU-XLA compiles are shared
across cases and cached persistently (conftest compile cache).
"""

import random
from contextlib import contextmanager

import numpy as np
import pytest

import jax

from teku_tpu.crypto.bls import curve as C
from teku_tpu.crypto.bls import keygen
from teku_tpu.crypto.bls.constants import P, R, X_ABS
from teku_tpu.crypto.bls.pure_impl import PureBls12381
from teku_tpu.ops import h2c
from teku_tpu.ops import limbs as fp
from teku_tpu.ops import msm
from teku_tpu.ops import mxu
from teku_tpu.ops import points as PT
from teku_tpu.ops import verify as V
from teku_tpu.ops.provider import JaxBls12381

rng = random.Random(0x88)

PURE = PureBls12381()
SKS = [keygen(bytes([120 + i]) * 32) for i in range(4)]
PKS = [PURE.secret_key_to_public_key(sk) for sk in SKS]
G2_INF_WIRE = bytes([0xC0] + [0] * 95)


def rand_g1():
    return C.point_mul(C.FQ_OPS, rng.randrange(1, R), C.G1_GENERATOR)


def rand_g2():
    return C.point_mul(C.FQ2_OPS, rng.randrange(1, R), C.G2_GENERATOR)


def stack_g1(points):
    return tuple(np.stack([fp.int_to_mont(p[i]) for p in points])
                 for i in range(3))


def stack_g2(points):
    return tuple(
        (np.stack([fp.int_to_mont(p[i][0]) for p in points]),
         np.stack([fp.int_to_mont(p[i][1]) for p in points]))
        for i in range(3))


def bits_of(scalars, nbits):
    """Host ints -> (N, nbits) MSB-first bit array (the ladder-oracle
    form scalar_mul_bits consumes for the 255-bit effective
    multipliers)."""
    out = np.zeros((len(scalars), nbits), dtype=np.int64)
    for i, s in enumerate(scalars):
        for j in range(nbits):
            out[i, nbits - 1 - j] = (int(s) >> j) & 1
    return out


def _triples(lane_msgs, tamper_lane=None, inf_sig_lane=None):
    out = []
    for i, m in enumerate(lane_msgs):
        if i == inf_sig_lane:
            out.append(([PKS[i % 4]], m, G2_INF_WIRE))
            continue
        sign_msg = b"tampered" if i == tamper_lane else m
        out.append(([PKS[i % 4]], m, PURE.sign(SKS[i % 4], sign_msg)))
    return out


@contextmanager
def fresh_stage_jits():
    """Retrace every staged program (the module-level jit table caches
    by shape only — a forced mont engine needs fresh jit objects)."""
    old = V._STAGED_JITS
    V._STAGED_JITS = None
    try:
        yield
    finally:
        V._STAGED_JITS = old


# --------------------------------------------------------------------------
# GLV constants + sampling
# --------------------------------------------------------------------------

def test_lambda_is_the_shared_eigenvalue():
    # G1: [lambda]P == phi(P) = (beta*x, y) — on a random subgroup
    # point, not just the generator the import-time assert uses
    p = C.to_affine(C.FQ_OPS, rand_g1())
    lam_p = C.to_affine(C.FQ_OPS, C.point_mul(
        C.FQ_OPS, msm.LAMBDA, (p[0], p[1], 1)))
    assert lam_p == (PT._BETA * p[0] % P, p[1])
    # G2: [lambda]Q == -psi^2(Q), via the device map
    qs = [rand_g2() for _ in range(2)]
    dev = jax.jit(msm.g2_lambda_point)(stack_g2(qs))
    for i, q in enumerate(qs):
        exp = C.point_mul(C.FQ2_OPS, msm.LAMBDA, q)
        assert C.point_eq(C.FQ2_OPS, PT.g2_from_device(dev, (i,)), exp)


def test_effective_scalar_nonzero_and_injective():
    # (0, 0) is the ONLY zero of k1 + k2*lambda on the range: for
    # k2 != 0, k2*lambda mod r = r - z^2*k2 with z^2*k2 < 2^160 << r,
    # so the sum can never cancel a k1 < 2^32
    z2 = (-msm.LAMBDA) % R          # = z^2 mod r
    assert z2 == X_ABS * X_ABS      # |z|^2 < sqrt(r): not wrapped
    assert msm.effective_scalar(0, 0) == 0
    seen = set()
    for _ in range(200):
        k1, k2 = rng.getrandbits(32), rng.getrandbits(32)
        r_eff = msm.effective_scalar(k1, k2)
        assert (r_eff != 0) or (k1 == 0 and k2 == 0)
        assert r_eff not in seen
        seen.add(r_eff)
    # the sampler nudges the one bad pair
    k1, k2 = msm.glv_sample_from_uint64(np.zeros(3, dtype=np.uint64))
    assert list(k1) == [1, 1, 1] and list(k2) == [0, 0, 0]


def test_digit_builder_is_msb_first():
    d = msm.glv_digits_np(np.array([0x12345678], dtype=np.uint64),
                          np.array([0xF0000001], dtype=np.uint64),
                          window=4)
    assert d.shape == (1, 2, 8)
    assert list(d[0, 0]) == [1, 2, 3, 4, 5, 6, 7, 8]
    assert list(d[0, 1]) == [15, 0, 0, 0, 0, 0, 0, 1]
    with pytest.raises(ValueError):
        msm.glv_digits_np(np.array([1 << 32], dtype=np.uint64),
                          np.array([0], dtype=np.uint64))


# --------------------------------------------------------------------------
# Kernel level: adversarial bucket patterns in ONE compiled shape
# --------------------------------------------------------------------------

def test_msm_rows_adversarial_grid():
    """4 rows x 4 cols, one compile: zero scalars, all-ones digits
    (every lane dropping into the same max bucket per window),
    duplicate points + duplicate bucket indices, an infinity point
    column, and excluded columns — vs the oracle."""
    pts = [[rand_g1() for _ in range(4)] for _ in range(4)]
    pts[2][1] = pts[2][0]                      # duplicate point
    pts[2][3] = C.infinity(C.FQ_OPS)           # infinity column
    k = np.array(
        [[0, 0, 0, 0],                         # zero scalars
         [0xFFFFFFFF] * 4,                     # all-ones: max dup buckets
         [7, 7, 0xABCD, 5],                    # dup digits + inf point
         [1, 0xDEAD, 2, 0xFFFF]],
        dtype=np.uint64)
    include = np.ones((4, 4), dtype=bool)
    include[3, 1] = include[3, 3] = False      # masked/absent columns
    digits = np.stack([msm.glv_digits_np(
        k[r], np.zeros(4, np.uint64))[:, 0, :] for r in range(4)])
    dev = jax.tree_util.tree_map(lambda *xs: np.stack(xs),
                                 *[stack_g1(row) for row in pts])
    out = jax.jit(
        lambda p, d, i: msm.msm_rows(PT.G1_KIT, p, d, i))(
            dev, digits, include)
    for r in range(4):
        exp = C.infinity(C.FQ_OPS)
        for c in range(4):
            if include[r, c]:
                exp = C.point_add(C.FQ_OPS, exp, C.point_mul(
                    C.FQ_OPS, int(k[r, c]), pts[r][c]))
        got = PT.g1_from_device(out, (r,))
        assert C.point_eq(C.FQ_OPS, got, exp), f"row {r}"
    # row of zero scalars must be exactly infinity (masked downstream)
    assert bool(np.asarray(PT.is_infinity(PT.G1_KIT, out))[0])


def _glv_ladder_g1(pk_dev, k1, k2):
    """The ladder-oracle G1 fold: [r_eff]P per lane via the 255-bit
    scalar_mul_bits walk (satellite: irregular widths pad, not
    demote)."""
    r_eff = [msm.effective_scalar(int(a), int(b)) for a, b in
             zip(k1, k2)]
    rb = bits_of(r_eff, 255)
    return jax.jit(lambda b, p: PT.scalar_mul_bits(PT.G1_KIT, b, p))(
        rb, pk_dev), r_eff


def test_grouped_msm_canonical_parity_vs_ladder():
    """g1_grouped_msm and g2_msm vs the ladder oracle given the SAME
    multipliers: canonical() affine accumulator limbs must be
    ARRAY-IDENTICAL (not just point-equal) — canonical() collapses any
    lazy representation drift, and every downstream stage (miller,
    finish) is deterministic in its inputs, so identical canonical
    accumulators subsume verdict bit-identity for the grouped
    pipeline.  The G1 fold is checked against BOTH the on-device
    255-bit scalar_mul_bits walk of the effective multipliers (the
    padded irregular-width fast path) and the host bigint oracle; the
    G2 fold against the host oracle (the device 255-bit G2 ladder
    would re-prove the same scalar_mul_bits contract at 3x the
    compile cost)."""
    lanes = 4
    pk_pts = [rand_g1() for _ in range(lanes)]
    sig_pts = [rand_g2() for _ in range(lanes - 1)] + [
        C.infinity(C.FQ2_OPS)]                 # an infinity sig lane
    pk_dev = stack_g1(pk_pts)
    sig_dev = stack_g2(sig_pts)
    k1 = np.array([5, 0, 0xFFFFFFFF, 0x1234], dtype=np.uint64)
    k2 = np.array([0, 3, 0xFFFFFFFF, 0xBEEF], dtype=np.uint64)
    digits = msm.glv_digits_np(k1, k2)
    # two groups of two lanes; lane 1 miller-masked out of group 0
    group_idx = np.array([[0, 1], [2, 3]], dtype=np.int32)
    group_present = np.ones((2, 2), dtype=bool)
    miller_mask = np.array([True, False, True, True])

    agg_pip = jax.jit(msm.g1_grouped_msm)(
        pk_dev, digits, group_idx, group_present, miller_mask)
    lad, r_eff = _glv_ladder_g1(pk_dev, k1, k2)
    inf = PT.infinity_like(PT.G1_KIT, lad[0])
    lad = PT._select_point(PT.G1_KIT, miller_mask, lad, inf)
    agg_lad = V.point_batch_sum(
        PT.G1_KIT, jax.tree_util.tree_map(
            lambda x: jnp_stack_rows(x, group_idx), lad))
    # canonical affine limbs: identical arrays, ladder vs pippenger
    pip_aff = V.to_affine_g1(agg_pip)
    lad_aff = V.to_affine_g1(agg_lad)
    for a, b in zip(pip_aff, lad_aff):
        assert np.array_equal(np.asarray(fp.canonical(a)),
                              np.asarray(fp.canonical(b)))
    # ... and identical to the HOST oracle's canonical limbs
    for u in range(2):
        exp = C.infinity(C.FQ_OPS)
        for lane in group_idx[u]:
            if not miller_mask[lane]:
                continue
            exp = C.point_add(C.FQ_OPS, exp, C.point_mul(
                C.FQ_OPS, r_eff[lane], pk_pts[lane]))
        ex, ey = C.to_affine(C.FQ_OPS, exp)
        assert np.array_equal(np.asarray(
            fp.canonical_plain(pip_aff[0]))[u], fp.int_to_limbs(ex))
        assert np.array_equal(np.asarray(
            fp.canonical_plain(pip_aff[1]))[u], fp.int_to_limbs(ey))
    # G2: whole-batch MSM vs the host oracle's canonical limbs
    wsig_pip = jax.jit(msm.g2_msm)(sig_dev, digits)
    exp2 = C.infinity(C.FQ2_OPS)
    for lane in range(lanes):
        exp2 = C.point_add(C.FQ2_OPS, exp2, C.point_mul(
            C.FQ2_OPS, r_eff[lane], sig_pts[lane]))
    ex2, ey2 = C.to_affine(C.FQ2_OPS, exp2)
    aff_pip = h2c.to_affine_g2(wsig_pip)
    for got, want in zip(
            (aff_pip[0][0], aff_pip[0][1], aff_pip[1][0], aff_pip[1][1]),
            (ex2[0], ex2[1], ey2[0], ey2[1])):
        assert np.array_equal(np.asarray(fp.canonical_plain(got))[0],
                              fp.int_to_limbs(want))


def jnp_stack_rows(x, group_idx):
    """Gather lanes into (G, U, ...) rows for point_batch_sum."""
    return np.moveaxis(np.asarray(x)[group_idx], 1, 0)


# --------------------------------------------------------------------------
# Provider level: committee shapes, both mont engines.  (Verdict
# bit-identity given IDENTICAL multipliers is owned by the canonical-
# accumulator test above — the stages downstream of scalars are
# deterministic in their inputs — so the provider grid checks the
# production sampling paths end to end against each other and the
# pure oracle.)
# --------------------------------------------------------------------------

def _adversarial_cases():
    return [
        ("dup4", _triples([b"msm-a"] * 4), True),
        ("unique", _triples([b"msm-u%d" % i for i in range(4)]), True),
        ("tamper", _triples([b"msm-a"] * 4, tamper_lane=2), False),
        ("inf-sig", _triples([b"msm-a"] * 3 + [b"msm-b"],
                             inf_sig_lane=3), False),
        ("pad", _triples([b"msm-p", b"msm-p", b"msm-q"]), True),
    ]


def _run_provider_cases():
    with msm.force("pippenger"):
        pip = JaxBls12381()
        pip_verdicts = {name: pip.batch_verify(t)
                        for name, t, _ in _adversarial_cases()}
        assert pip.msm_dispatches["ladder"] == 0
        assert pip.msm_dispatches["pippenger"] == len(pip_verdicts)
    with msm.force("ladder"):
        lad = JaxBls12381()
        lad_verdicts = {name: lad.batch_verify(t)
                        for name, t, _ in _adversarial_cases()}
        assert lad.msm_dispatches["pippenger"] == 0
    for name, triples, expect in _adversarial_cases():
        assert pip_verdicts[name] is lad_verdicts[name] is expect, name
        assert PURE.batch_verify(triples) is expect, name


def test_provider_verdict_parity_vpu():
    assert mxu.resolve() == "vpu"     # CPU backend resolves to vpu
    _run_provider_cases()


def test_provider_verdict_parity_mxu_force():
    """The same adversarial grid with every staged program freshly
    traced under the forced MXU mont_mul engine (the module jit table
    caches by shape, so parity on the second engine needs new jit
    objects)."""
    with mxu.force("mxu-force"), fresh_stage_jits():
        _run_provider_cases()


def test_committee_split_across_group_cap_rows(monkeypatch):
    """A committee larger than TEKU_TPU_H2C_GROUP_CAP splits across
    bucket-MSM rows sharing one H(m); verdicts must be unchanged.
    (The ladder path's cap-2 behavior is pinned by test_h2c_dedup's
    group-cap test at the same shapes — this covers the pippenger
    side.)"""
    monkeypatch.setenv("TEKU_TPU_H2C_GROUP_CAP", "2")
    with msm.force("pippenger"):
        impl = JaxBls12381()
        assert impl._group_cap == 2
        msgs = [b"msm-split"] * 5 + [b"msm-solo"]
        assert impl.batch_verify(_triples(msgs)) is True
        assert impl.batch_verify(_triples(msgs, tamper_lane=1)) is False
        assert PURE.batch_verify(_triples(msgs)) is True


def test_aggregate_verify_r1_on_pippenger():
    # randomize=False dispatches (k1, k2) = (1, 0): the distinct-
    # message aggregate equation needs r = 1 EXACTLY
    msgs = [b"msm-agg-0", b"msm-agg-1"]
    agg = PURE.aggregate_signatures(
        [PURE.sign(SKS[i], m) for i, m in enumerate(msgs)])
    with msm.force("pippenger"):
        impl = JaxBls12381()
        assert impl.aggregate_verify(PKS[:2], msgs, agg) is True
        assert impl.aggregate_verify(PKS[:2], msgs[::-1], agg) is False


# --------------------------------------------------------------------------
# Path resolution + metrics
# --------------------------------------------------------------------------

_EXPLICIT = "explicitly configured"
_SHARDED = "legacy lane-sharded kernel always ladders"
_MAX, _DUP = msm.AUTO_TPU_LADDER_MAX_LANES, msm.AUTO_TPU_PIPPENGER_MIN_DUP


@pytest.mark.parametrize("configured,tpu,shape,path,rule", [
    # mainnet-subnet-gossip's drains: 250 lanes inside one message
    # (8 rows of 32) or straddling two (9): the CPU's crossover sent
    # them to pippenger, the chip's reading sends them to the ladder
    ("auto", True, dict(lanes=250, rows=8), "ladder", "ladder"),
    ("auto", True, dict(lanes=250, rows=9), "ladder", "ladder"),
    # its probe's bisection halves, and backfill-unique's row a lane
    ("auto", True, dict(lanes=125, rows=4), "ladder", "ladder"),
    ("auto", True, dict(lanes=250, rows=250), "ladder", "ladder"),
    # the wider shapes read with the ladder ahead, and no shape at all
    ("auto", True, dict(lanes=1024, rows=32), "ladder", "ladder"),
    ("auto", True, dict(lanes=2048, rows=64), "ladder", "ladder"),
    ("auto", True, dict(lanes=4096, rows=4096), "ladder", "ladder"),
    ("auto", True, dict(), "ladder", "ladder"),
    ("auto", True, dict(lanes=4096), "ladder", "ladder"),
    # the two readings with pippenger ahead: the provider's widest
    # batch at 32 and at 8 lanes a row
    ("auto", True, dict(lanes=4096, rows=128), "pippenger", "pippenger"),
    ("auto", True, dict(lanes=4096, rows=512), "pippenger", "pippenger"),
    # both constants' two sides, by the exact ratio
    ("auto", True, dict(lanes=_MAX, rows=_MAX // 32), "ladder", "ladder"),
    ("auto", True, dict(lanes=_MAX + 1, rows=(_MAX + 1) // _DUP),
     "pippenger", "pippenger"),
    ("auto", True, dict(lanes=4096, rows=4096 // _DUP + 1), "ladder",
     "ladder"),
    # off a TPU auto keeps the long-validated ladder, and says so
    ("auto", False, dict(lanes=4096, rows=16), "ladder",
     msm.AUTO_RULE_NOT_TPU),
    # an explicit choice wins on either device
    ("pippenger", True, dict(lanes=250, rows=8), "pippenger", _EXPLICIT),
    ("pippenger", False, dict(lanes=1, rows=1), "pippenger", _EXPLICIT),
    ("ladder", True, dict(lanes=4096, rows=1), "ladder", _EXPLICIT),
    # the legacy lane-sharded kernel always ladders (groups cross shards)
    ("pippenger", True, dict(lanes=4096, rows=1, sharded=True), "ladder",
     _SHARDED),
])
def test_resolve_auto_rules(monkeypatch, configured, tpu, shape, path,
                            rule):
    monkeypatch.setattr(msm, "_device_is_tpu", lambda: tpu)
    with msm.force(configured):
        got, why = msm.explain(**shape)
        assert msm.resolve(**shape) == got == path
    assert why["rule"] == msm.AUTO_RULE_TPU.get(rule, rule)
    assert why["configured"] == configured
    lanes, rows = shape.get("lanes"), shape.get("rows")
    assert (why["lanes"], why["rows"]) == (lanes, rows)
    # the record names the device wherever the device had a say, and
    # on a TPU what the chip measured at this shape: what `auto`
    # takes, and what the doctor holds an explicit choice against
    assert why.get("tpu") == (None if rule == _SHARDED else tpu)
    wide_and_shared = bool(lanes and rows and lanes > _MAX
                           and lanes >= _DUP * rows)
    assert why.get("measured") == (
        None if rule == _SHARDED or not tpu
        else "pippenger" if wide_and_shared else "ladder")
    # nothing of the CPU-era crossover is left in the record
    assert not {"dup", "auto_min_lanes", "auto_min_dup"} & set(why)


def test_invalid_env_path_degrades_to_auto(monkeypatch):
    monkeypatch.setenv(msm.ENV_VAR, "bogus")
    msm.set_path(None)
    assert msm.get_path() == "auto"
    assert msm.resolve(lanes=250, rows=8) == "ladder"


def test_msm_dispatch_metrics_move():
    from teku_tpu.ops import provider as pv
    before = pv._M_MSM.labels(path="pippenger").value
    lanes_before = pv._M_MSM_LANES.labels(path="pippenger").value
    with msm.force("pippenger"):
        impl = JaxBls12381()
        assert impl.batch_verify(_triples([b"msm-metric"] * 4)) is True
    assert pv._M_MSM.labels(path="pippenger").value == before + 1
    assert pv._M_MSM_LANES.labels(path="pippenger").value \
        == lanes_before + 4


def test_g2_msm_segment_merge(monkeypatch):
    """S > 1 segmented accumulation: the per-segment bucket tables
    tree-add before the reduce (bucket sums are additive across
    disjoint column sets) — forced by pinning the process seg length
    below 2N."""
    monkeypatch.setattr(msm, "_seg_cache", [2])    # 2N=8 -> S=4
    qs = [rand_g2() for _ in range(4)]
    k1 = np.array([3, 5, 7, 11], dtype=np.uint64)
    k2 = np.array([1, 0, 2, 9], dtype=np.uint64)
    digits = msm.glv_digits_np(k1, k2)
    out = jax.jit(msm.g2_msm)(stack_g2(qs), digits)  # fresh jit: S=4
    exp = C.infinity(C.FQ2_OPS)
    for i, q in enumerate(qs):
        exp = C.point_add(C.FQ2_OPS, exp, C.point_mul(
            C.FQ2_OPS,
            msm.effective_scalar(int(k1[i]), int(k2[i])), q))
    assert C.point_eq(C.FQ2_OPS, PT.g2_from_device(out, (0,)), exp)


def test_tuning_knobs_degrade_not_raise(monkeypatch):
    """A typo'd TEKU_TPU_MSM_WINDOW / TEKU_TPU_MSM_SEG must degrade to
    the default with a warning — never start failing live dispatches
    (same contract as an invalid TEKU_TPU_MSM)."""
    monkeypatch.setattr(msm, "_warned_window", [False])
    monkeypatch.setenv(msm.ENV_WINDOW, "nine")
    assert msm.window_env() == 4
    monkeypatch.setenv(msm.ENV_WINDOW, "9")        # out of 1..8
    assert msm.window_env() == 4
    monkeypatch.setenv(msm.ENV_WINDOW, "2")
    assert msm.window_env() == 2
    monkeypatch.setattr(msm, "_seg_cache", [])
    monkeypatch.setenv(msm.ENV_SEG, "31")          # not a pow-2
    assert msm._seg_len() == 32
    monkeypatch.setattr(msm, "_seg_cache", [])
    monkeypatch.setenv(msm.ENV_SEG, "8")
    assert msm._seg_len() == 8
    # the seg choice is process-pinned (g2_msm only runs under jit:
    # a per-call env read would silently stop mattering after the
    # first trace anyway — see msm._seg_len)
    monkeypatch.setenv(msm.ENV_SEG, "16")
    assert msm._seg_len() == 8


def test_capacity_latency_series_split_by_msm_path():
    """Same-padded-shape dispatches can run EITHER
    scalars program; the capacity model's per-(shape, path) latency
    series must not blend them (the admission controller plans
    batches from these p50s)."""
    from teku_tpu.infra import capacity
    with msm.force("pippenger"):
        impl = JaxBls12381()
        assert impl.batch_verify(_triples([b"msm-cap"] * 4)) is True
    snap = capacity.snapshot()["shapes"]
    paths = {p for per_shape in snap.values() for p in per_shape}
    assert any(p.endswith("+pip") for p in paths), paths
