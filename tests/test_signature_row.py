"""The signature's row: e(-g1, S) without a program of its own.

The summed weighted signature S = sum [r_i]sig_i is taken to affine on
the ONE inversion `stage_group` / `stage_lane_affine` already pay
(`affine_with_signature`), its Miller loop is the last row of
`stage_miller`'s scan, and `stage_finish` keeps the product and the
final exponentiation.  Checked here, on the CPU at small shapes:

- A. the shared inversion against `h2c.to_affine_g2` / `to_affine_g1`
  (what the parent ran) and the host oracle, with S at infinity, an
  aggregate at infinity, both, and no G1 point at all (the mesh tail);
- B. `stage_miller`'s rows against `miller_loop` row for row, the last
  ONE when `s_mask` is false;
- C. `stage_finish`'s verdict against the parent's formula written out
  below and against the oracle's verdict, for the grouped and the
  per-lane pipeline under both multiplier engines.
"""

import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from teku_tpu.crypto.bls import curve as C
from teku_tpu.crypto.bls import hash_to_curve as OH
from teku_tpu.crypto.bls import keygen
from teku_tpu.crypto.bls.constants import R
from teku_tpu.crypto.bls.pure_impl import PureBls12381
from teku_tpu.ops import h2c
from teku_tpu.ops import limbs as fp
from teku_tpu.ops import mxu
from teku_tpu.ops import pairing as PR
from teku_tpu.ops import points as PT
from teku_tpu.ops import provider as PV
from teku_tpu.ops import towers as T
from teku_tpu.ops import verify as V

rng = random.Random(0x31)

PURE = PureBls12381()
SKS = [keygen(bytes([160 + i]) * 32) for i in range(4)]
G2_INF_WIRE = bytes([0xC0] + [0] * 95)


def rand_g1():
    return C.point_mul(C.FQ_OPS, rng.randrange(1, R), C.G1_GENERATOR)


def rand_g2():
    return C.point_mul(C.FQ2_OPS, rng.randrange(1, R), C.G2_GENERATOR)


def rescaled(ops, p):
    """The same point with a Jacobian z other than one (what a sum of
    points hands the affine conversion)."""
    lam = rng.randrange(2, R)
    if ops is C.FQ2_OPS:
        lam = (lam, rng.randrange(1, R))
    l2 = ops.mul(lam, lam)
    return (ops.mul(p[0], l2), ops.mul(p[1], ops.mul(l2, lam)),
            ops.mul(p[2], lam))


def stack_g1(points, coords=3):
    """Points as the device's limb arrays: Jacobian triples, or with
    `coords=2` affine pairs."""
    return tuple(np.stack([fp.int_to_mont(p[i]) for p in points])
                 for i in range(coords))


def stack_g2(points, coords=3):
    return tuple(
        (np.stack([fp.int_to_mont(p[i][0]) for p in points]),
         np.stack([fp.int_to_mont(p[i][1]) for p in points]))
        for i in range(coords))


def plain(x):
    return np.asarray(fp.canonical_plain(x))


def flat_g2(aff):
    """An affine G2 point's four coordinates as plain limbs, in the
    order of the device tree's leaves."""
    (x0, x1), (y0, y1) = aff
    return np.stack([fp.int_to_limbs(c) for c in (x0, x1, y0, y1)])


# --------------------------------------------------------------------------
# A. one inversion for the G1 rows and S
# --------------------------------------------------------------------------

ROWS = 4
INVERSION_CASES = {"plain": (False, None),
                   "S-at-infinity": (True, None),
                   "an-aggregate-at-infinity": (False, 2),
                   "both-at-infinity": (True, 1)}


@pytest.fixture(scope="module")
def shared_inversion():
    @jax.jit
    def both(pk_jac, wsig):
        return (V.affine_with_signature(pk_jac, wsig),
                V.affine_with_signature(None, wsig),
                V.to_affine_g1(pk_jac), h2c.to_affine_g2(wsig))
    return both


@pytest.mark.parametrize("case", list(INVERSION_CASES))
def test_shared_inversion_is_the_two_private_ones(shared_inversion, case):
    s_inf, g1_inf_row = INVERSION_CASES[case]
    pts = [rescaled(C.FQ_OPS, rand_g1()) for _ in range(ROWS)]
    if g1_inf_row is not None:
        pts[g1_inf_row] = C.infinity(C.FQ_OPS)
    s = C.infinity(C.FQ2_OPS) if s_inf else rescaled(C.FQ2_OPS, rand_g2())
    (agg_aff, s_aff, s_mask), (none_aff, s_aff_alone, s_mask_alone), \
        g1_today, s_today = shared_inversion(stack_g1(pts), stack_g2([s]))
    assert none_aff is None
    assert np.asarray(s_mask).shape == (1,)
    assert bool(np.asarray(s_mask)[0]) is not s_inf
    assert bool(np.asarray(s_mask_alone)[0]) is not s_inf
    if not s_inf:
        want = flat_g2(C.to_affine(C.FQ2_OPS, s))
        for got in (s_aff, s_aff_alone, s_today):
            assert np.array_equal(
                plain(jnp.stack(jax.tree_util.tree_leaves(got)))[:, 0],
                want)
    new, old = plain(jnp.stack(agg_aff)), plain(jnp.stack(g1_today))
    for row, p in enumerate(pts):
        if row == g1_inf_row:
            continue        # garbage coordinates, the mask's to carry
        for coord, e in enumerate(C.to_affine(C.FQ_OPS, p)):
            assert np.array_equal(new[coord, row], fp.int_to_limbs(e))
            assert np.array_equal(new[coord, row], old[coord, row])


# --------------------------------------------------------------------------
# B. the last row of stage_miller
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def miller_rows():
    @jax.jit
    def rows(pk_aff, hm_aff, mask, s_aff, s_mask):
        ml = V.stage_miller(pk_aff, hm_aff, mask, s_aff, s_mask)
        want_rows = PR.miller_loop(pk_aff, hm_aff, mask=mask)
        want_sig = PR.miller_loop(V._neg_g1_row(), s_aff, mask=s_mask)
        head = jax.tree_util.tree_map(lambda x: x[:-1], ml)
        tail = jax.tree_util.tree_map(lambda x: x[-1:], ml)
        return (T.fq12_eq(head, want_rows), T.fq12_eq(tail, want_sig),
                T.fq12_is_one(tail))
    return rows


def _affine_g1(points):
    return stack_g1([C.to_affine(C.FQ_OPS, p) for p in points], 2)


def _affine_g2(points):
    return stack_g2([C.to_affine(C.FQ2_OPS, p) for p in points], 2)


@pytest.mark.parametrize("s_present", [True, False],
                         ids=["S-present", "S-masked"])
def test_stage_millers_last_row_is_the_signatures(miller_rows, s_present):
    pk_aff = _affine_g1([rand_g1(), rand_g1()])
    hm_aff = _affine_g2([rand_g2(), rand_g2()])
    s_aff = _affine_g2([rand_g2()])
    heads, tail, tail_is_one = miller_rows(
        pk_aff, hm_aff, np.asarray([True, False]), s_aff,
        np.asarray([s_present]))
    assert np.asarray(heads).tolist() == [True, True]
    assert np.asarray(tail).tolist() == [True]
    assert np.asarray(tail_is_one).tolist() == [not s_present]


# --------------------------------------------------------------------------
# C. the verdict against the parent's formula, both pipelines, both
# engines
# --------------------------------------------------------------------------

def parent_finish(ml_rows, wsig):
    """What `stage_finish(ml, wsig)` computed before the signature's
    row moved: a private affine conversion of S, a private width-1
    Miller loop, then the product and the final exponentiation."""
    s_sum = PT.point_batch_sum(PT.G2_KIT, wsig)
    s_inf = PT.is_infinity(PT.G2_KIT, s_sum)
    s_aff = h2c.to_affine_g2(tuple(
        jax.tree_util.tree_map(lambda x: x[None], c) for c in s_sum))
    neg_g1 = (jnp.asarray(V._NEG_G1_X)[None], jnp.asarray(V._NEG_G1_Y)[None])
    ml_s = PR.miller_loop(neg_g1, s_aff, mask=~s_inf[None])
    f = T.fq12_mul(PR.batch_product(ml_rows),
                   jax.tree_util.tree_map(lambda x: x[0], ml_s))
    return PR.pairing_check(f)


LANES, MSGS = 4, [b"row-a", b"row-b"]
VERDICT_CASES = {"valid": True, "one-forged": False,
                 "all-infinity-signatures": False, "all-padding": True}


def _batch(case):
    """Four single-key lanes over two messages, in the kernels' argument
    form (H(m) from the host oracle: no hash-to-curve program here)."""
    lane_msg = [0, 1, 0, 1]
    pk_xs = np.zeros((LANES, 1, fp.L), dtype=np.int64)
    pk_ys = np.zeros((LANES, 1, fp.L), dtype=np.int64)
    sig_bytes = np.zeros((LANES, 2, 48), dtype=np.uint8)
    s_large = np.zeros(LANES, dtype=bool)
    s_inf = np.zeros(LANES, dtype=bool)
    triples = []
    for i in range(LANES):
        x, y = C.to_affine(C.FQ_OPS, C.point_mul(
            C.FQ_OPS, SKS[i], C.G1_GENERATOR))
        pk_xs[i, 0], pk_ys[i, 0] = fp.int_to_mont(x), fp.int_to_mont(y)
        signed = b"forged" if (case == "one-forged" and i == 2) \
            else MSGS[lane_msg[i]]
        wire = G2_INF_WIRE if case == "all-infinity-signatures" \
            else PURE.sign(SKS[i], signed)
        sig_bytes[i], s_large[i], s_inf[i] = PV._parse_g2_wire(wire)
        triples.append(([PURE.secret_key_to_public_key(SKS[i])],
                        MSGS[lane_msg[i]], wire))
    hm_uniq = _affine_g2([OH.hash_to_g2(m) for m in MSGS])
    lane_map = np.asarray(lane_msg, dtype=np.int32)
    hm_lanes = jax.tree_util.tree_map(lambda x: x[lane_map], hm_uniq)
    group_idx = np.asarray([[0, 2], [1, 3]], dtype=np.int32)
    r_bits = PT.scalar_bits_np(np.asarray(
        [rng.randrange(1, 1 << 64) for _ in range(LANES)],
        dtype=np.uint64))
    lane_valid = np.full(LANES, case != "all-padding")
    head = (pk_xs, pk_ys, np.ones((LANES, 1), dtype=bool))
    tail = ((PV.bytes_to_limbs_np(sig_bytes[:, 1]),
             PV.bytes_to_limbs_np(sig_bytes[:, 0])), s_large, s_inf,
            r_bits, lane_valid)
    return {"grouped": head + (hm_uniq, group_idx,
                               np.ones((2, 2), dtype=bool)) + tail,
            "per-lane": head + (hm_lanes,) + tail,
            "triples": triples}


@pytest.fixture(scope="module", params=["vpu", "mxu-force"])
def engine(request):
    """Stage programs traced under the engine, each wrapped to keep
    what it returned; and the parent's formula, traced under the same
    engine."""
    old = V._STAGED_JITS
    kept = {}

    def keeping(name, fn):
        # a new function object: jax keeps traces by function, so a
        # second `jax.jit(V.stage_x)` would reuse the first engine's
        jitted = jax.jit(lambda *args: fn(*args))

        def run(*args):
            kept[name] = jitted(*args)
            return kept[name]
        return run

    try:
        with mxu.force(request.param):
            V._STAGED_JITS = {name: keeping(name, fn) for name, fn in (
                ("prepare", V.stage_prepare), ("scalars", V.stage_scalars),
                ("affine", V.stage_lane_affine), ("group", V.stage_group),
                ("miller", V.stage_miller), ("finish", V.stage_finish))}
            yield kept, jax.jit(lambda *args: parent_finish(*args))
    finally:
        V._STAGED_JITS = old


@pytest.fixture(scope="module")
def batches():
    return {case: _batch(case) for case in VERDICT_CASES}


@pytest.mark.parametrize("pipeline", ["grouped", "per-lane"])
@pytest.mark.parametrize("case", list(VERDICT_CASES))
def test_verdict_is_the_parents_formula(engine, batches, case, pipeline):
    kept, parent = engine
    kernel = {"grouped": V.verify_staged_grouped,
              "per-lane": V.verify_staged_hm}[pipeline]
    kept.clear()
    ok, lane_ok = kernel(*batches[case][pipeline])
    rows = 2 if pipeline == "grouped" else LANES
    ml = kept["miller"]
    assert jax.tree_util.tree_leaves(ml)[0].shape[0] == rows + 1
    _pk_r_jac, wsig = kept["scalars"]
    want = parent(jax.tree_util.tree_map(lambda x: x[:-1], ml), wsig)
    assert bool(np.asarray(ok)) is bool(np.asarray(want))
    assert bool(np.asarray(ok)) is VERDICT_CASES[case]
    # the signature's row is masked exactly when S is the infinity point
    s_mask = kept["group" if pipeline == "grouped" else "affine"][-1]
    assert bool(np.asarray(s_mask)[0]) is (
        case not in ("all-infinity-signatures", "all-padding"))
    if case != "all-padding":
        # what the caller makes of it is the oracle's verdict
        verdict = bool(np.asarray(ok)) and bool(np.asarray(lane_ok).all())
        assert verdict is PURE.batch_verify(batches[case]["triples"])
