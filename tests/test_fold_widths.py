"""The pairwise fold at a narrower step (`towers.tree_fold_pairs`).

A pow-2 fold runs as one loop whose step combines a fixed width of
pairs; where a pair holds a wide batch the width halves while a step
stays at or above `FOLD_STEP_MIN_POINTS` points, and a round wider than
the step takes one step a chunk.  Checked here, on the CPU at small
shapes:

- A. the fold against the fixed-width rolled form (written out below,
  every round one step at the width of the first): bit-identical for G1
  `point_add` and `fq12_mul`, with the threshold lowered so that small
  folds step narrower;
- B. the threshold: at the one-key serving shapes the traced program
  is the rolled form's; at the (512, 256) key fold of the 512-key
  bucket the loop steps 8 pairs, 66 steps;
- C. `stage_prepare` over a mixed key matrix (lanes of one key beside
  lanes of 40-60) against the oracle's aggregates, in both forms.
"""

import random
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from teku_tpu.crypto.bls import curve as C
from teku_tpu.crypto.bls.constants import P
from teku_tpu.crypto.bls.pure_impl import PureBls12381
from teku_tpu.ops import limbs as fp
from teku_tpu.ops import points as PT
from teku_tpu.ops import provider as PV
from teku_tpu.ops import towers as T
from teku_tpu.ops import verify as V

rng = random.Random(0x42)

PURE = PureBls12381()


def rolled_fold(combine, t):
    """The fixed-width form: every round of a pow-2 fold as one
    fori_loop at width n/2, pairing [0, h) with [h, 2h)."""
    n = jax.tree_util.tree_leaves(t)[0].shape[0]
    half = n // 2

    def fold(k, t):
        h = half >> k
        a = jax.tree_util.tree_map(lambda x: x[:half], t)
        b = jax.tree_util.tree_map(
            lambda x: lax.dynamic_slice_in_dim(x, h, half, axis=0), t)
        s = combine(a, b)
        return jax.tree_util.tree_map(
            lambda x, y: jnp.concatenate([y, x[half:]], axis=0), t, s)

    t = lax.fori_loop(0, n.bit_length() - 1, fold, t)
    return jax.tree_util.tree_map(lambda x: x[0], t)


def _g1_points(n, batch):
    """Jacobian G1 points of shape (n, *batch): multiples of the
    generator with z other than one, infinity points, and partners
    equal to a lane or its negation (the unified add's doubling and
    cancelling cases in the first round)."""
    count = n * int(np.prod(batch, dtype=int))
    pts, acc = [], C.infinity(C.FQ_OPS)
    for _ in range(count):
        acc = C.point_add(C.FQ_OPS, acc, C.G1_GENERATOR)
        lam = rng.randrange(2, P)
        pts.append((acc[0] * lam * lam % P, acc[1] * pow(lam, 3, P) % P,
                    acc[2] * lam % P))
    stride = count // n                    # one fold lane = stride points

    def lane(i):
        return slice(i * stride, (i + 1) * stride)

    half = n // 2                  # lane i meets lane i + half first
    inf = C.infinity(C.FQ_OPS)
    pts[lane(1)] = [inf] * stride
    pts[lane(half + 2)] = [inf] * stride
    pts[lane(half)] = pts[lane(0)]                          # P + P
    pts[lane(half + 3)] = [C.point_neg(C.FQ_OPS, p)         # P + -P
                           for p in pts[lane(3)]]
    return tuple(np.stack([fp.int_to_mont(p[i]) for p in pts])
                 .reshape((n, *batch, fp.L)) for i in range(3))


def _fq12s(n, batch):
    def comp():
        return np.stack([fp.int_to_mont(rng.randrange(P))
                         for _ in range(n * int(np.prod(batch, dtype=int)))]
                        ).reshape((n, *batch, fp.L))
    return tuple(tuple((comp(), comp()) for _ in range(3))
                 for _ in range(2))


FOLDS = {"g1": (_g1_points, lambda a, b: PT.point_add(PT.G1_KIT, a, b)),
         "fq12": (_fq12s, T.fq12_mul)}


@pytest.mark.parametrize("kind,n,batch", [
    ("g1", 8, (3,)), ("g1", 64, ()), ("g1", 512, (2,)),
    ("fq12", 8, ()), ("fq12", 64, (2,)), ("fq12", 512, ()),
])
def test_narrow_step_fold_is_the_rolled_fold(monkeypatch, kind, n, batch):
    make, combine = FOLDS[kind]
    t = make(n, batch)
    points = int(np.prod(batch, dtype=int))
    # steps of n/8 pairs: the three widest rounds take 4, 2 and 1 steps
    # a chunk, the narrower ones one step each
    monkeypatch.setattr(T, "FOLD_STEP_MIN_POINTS", n // 8 * points)
    widths = []

    def counted(a, b):
        widths.append(jax.tree_util.tree_leaves(a)[0].shape[0])
        return combine(a, b)

    got = jax.jit(lambda t: T.tree_fold_pairs(counted, t))(t)
    assert widths == [n // 8]
    want = jax.jit(lambda t: rolled_fold(combine, t))(t)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.shape == w.shape == (*batch, fp.L)
        assert np.array_equal(np.asarray(g), np.asarray(w))


def _g1_avals(n, batch):
    x = jax.ShapeDtypeStruct((n, *batch, fp.L), jnp.int64)
    return (x, x, x)


def _g2_avals(n):
    x = jax.ShapeDtypeStruct((n, fp.L), jnp.int64)
    return ((x, x),) * 3


def _fq12_avals(n):
    x = jax.ShapeDtypeStruct((n, fp.L), jnp.int64)
    return (((x, x),) * 3,) * 2


SERVING = {
    # stage_scalars' signature sum at 256 lanes
    "g2-signature-sum": (lambda a, b: PT.point_add(PT.G2_KIT, a, b),
                         _g2_avals(256)),
    # stage_group's fold of 16-lane groups over 32 rows
    "g1-group-fold": (lambda a, b: PT.point_add(PT.G1_KIT, a, b),
                      _g1_avals(16, (32,))),
    # stage_finish's product of 256 Miller rows
    "fq12-batch-product": (T.fq12_mul, _fq12_avals(256)),
}


@pytest.mark.parametrize("shape", sorted(SERVING))
def test_one_key_shapes_keep_the_rolled_program(shape):
    combine, avals = SERVING[shape]
    got = jax.make_jaxpr(lambda t: T.tree_fold_pairs(combine, t))(avals)
    want = jax.make_jaxpr(lambda t: rolled_fold(combine, t))(avals)
    assert str(got) == str(want)


def test_the_512_key_fold_steps_8_pairs():
    widths = []

    def counted(a, b):
        widths.append(jax.tree_util.tree_leaves(a)[0].shape[0])
        return PT.point_add(PT.G1_KIT, a, b)

    jaxpr = str(jax.make_jaxpr(lambda t: T.tree_fold_pairs(counted, t))(
        _g1_avals(512, (256,))))
    # ONE combine body of 8 pairs (8 x 256 = 2048 points), 66 steps:
    # 32 + 16 + 8 + 4 + 2 + 1 for the rounds of 256 ... 8 pairs, one
    # each for 4, 2 and 1; 528 combines a lane against the rolled
    # form's 9 x 256 = 2304
    assert widths == [8]
    assert "length=66" in jaxpr


# --------------------------------------------------------------------------
# C. stage_prepare over a mixed key matrix
# --------------------------------------------------------------------------

KMAX = 64
# live keys a lane: one-key lanes beside 40-60-key lanes; lane 5's keys
# cancel in pairs (its aggregate is infinity), lane 7 is padding
LANE_KEYS = [1, 1, 47, 1, 60, 42, 1, 0]
SIG_INF_LANE = 6


@pytest.fixture(scope="module")
def mixed_matrix():
    n = len(LANE_KEYS)
    pk_xs = np.zeros((n, KMAX, fp.L), dtype=np.int64)
    pk_ys = np.zeros((n, KMAX, fp.L), dtype=np.int64)
    present = np.zeros((n, KMAX), dtype=bool)
    wires, sig_wires = [], []
    for lane, keys in enumerate(LANE_KEYS):
        pts = [C.point_mul(C.FQ_OPS, rng.randrange(1, 1 << 32),
                           C.G1_GENERATOR) for _ in range(keys)]
        if lane == 5:
            pts = [p if j % 2 == 0 else C.point_neg(C.FQ_OPS, pts[j - 1])
                   for j, p in enumerate(pts)]
        slots = sorted(rng.sample(range(KMAX), keys))   # padding between
        for slot, p in zip(slots, pts):
            x, y = C.to_affine(C.FQ_OPS, p)
            pk_xs[lane, slot] = fp.int_to_mont(x)
            pk_ys[lane, slot] = fp.int_to_mont(y)
            present[lane, slot] = True
        wires.append([C.g1_compress(p) for p in pts])
        sig = C.infinity(C.FQ2_OPS) if lane == SIG_INF_LANE else \
            C.point_mul(C.FQ2_OPS, rng.randrange(1, 1 << 32),
                        C.G2_GENERATOR)
        sig_wires.append(C.g2_compress(sig))
    parsed = [PV._parse_g2_wire(w) for w in sig_wires]
    sig_bytes = np.stack([p[0] for p in parsed])
    args = (pk_xs, pk_ys, present,
            (PV.bytes_to_limbs_np(sig_bytes[:, 1]),
             PV.bytes_to_limbs_np(sig_bytes[:, 0])),
            np.asarray([p[1] for p in parsed]),
            np.asarray([p[2] for p in parsed]),
            np.asarray([keys > 0 for keys in LANE_KEYS]))
    return args, wires


@pytest.mark.parametrize("form", ["rolled", "narrow-step"])
def test_stage_prepare_sums_a_mixed_key_matrix(monkeypatch, mixed_matrix,
                                               form):
    args, wires = mixed_matrix
    if form == "narrow-step":
        # at this size the loop steps 8 pairs (64 points), 10 steps
        monkeypatch.setattr(T, "FOLD_STEP_MIN_POINTS", 64)
    scans = re.findall(r"length=(\d+)", str(
        jax.make_jaxpr(lambda *a: V.stage_prepare(*a))(*args)))
    assert ("10" in scans) is (form == "narrow-step")
    assert ("6" in scans) is (form == "rolled")
    pk_jac, _sig_jac, lane_ok, miller_mask = jax.jit(
        lambda *a: V.stage_prepare(*a))(*args)
    lane_ok, miller_mask = np.asarray(lane_ok), np.asarray(miller_mask)
    for lane, keys in enumerate(LANE_KEYS):
        got = C.g1_compress(PT.g1_from_device(pk_jac, (lane,)))
        want_inf = keys == 0 or lane == 5
        if keys:
            assert got == PURE.aggregate_public_keys(wires[lane]), lane
        else:
            assert got == C.g1_compress(C.infinity(C.FQ_OPS))
        assert bool(miller_mask[lane]) is not want_inf, lane
        assert bool(lane_ok[lane]) is not want_inf, lane
