"""Device hash-to-G2 vs the oracle (which carries RFC 9380 vectors).

The served map is RFC 9380 appendix F.2's division-free form: `sqrt_ratio`
hands the affine y', the isogeny's fractions go straight to Jacobian, and
`stage_h2c` holds one Fermat inversion (its closing `to_affine_g2`).  Each
piece is held to the oracle here, the whole to the RFC's own vectors."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from teku_tpu.crypto.bls import curve as C
from teku_tpu.crypto.bls import fields as F
from teku_tpu.crypto.bls import hash_to_curve as OH
from teku_tpu.crypto.bls.constants import (ISO3_X_DEN, P, SSWU_A2, SSWU_B2,
                                            SSWU_Z2)
from teku_tpu.ops import h2c
from teku_tpu.ops import limbs as fp
from teku_tpu.ops import points as PT
from teku_tpu.ops import towers as T
from teku_tpu.ops import verify as V

from .test_bls_known_vectors import H2C_DST, J101_VECTORS

MSGS = [b"", b"abc", b"hello world", b"\x00" * 32, b"q" * 100]

rng = random.Random(0x33)


def rand_fq2():
    return (rng.randrange(P), rng.randrange(P))


def to_device(elems):
    """Oracle Fq2 values as one batched Montgomery limb pair."""
    return (np.stack([T.fq2_const(e)[0] for e in elems]),
            np.stack([T.fq2_const(e)[1] for e in elems]))


def test_map_to_curve_matches_oracle():
    us = []
    for m in MSGS:
        us.extend(OH.hash_to_field_fq2(m, 2))
    x, y = jax.jit(h2c.map_to_curve_sswu)(to_device(us))
    for i, u in enumerate(us):
        ex, ey = OH.map_to_curve_sswu_g2(u)
        assert T.fq2_from_device(x, (i,)) == ex
        assert T.fq2_from_device(y, (i,)) == ey


def test_full_hash_to_g2_matches_oracle():
    u0, u1 = h2c.messages_to_fields(MSGS)
    out = jax.jit(h2c.hash_to_g2_device)(u0, u1)
    for i, m in enumerate(MSGS):
        got = PT.g2_from_device(out, (i,))
        expect = OH.hash_to_g2(m)
        assert C.point_eq(C.FQ2_OPS, got, expect)


# --------------------------------------------------------------------------
# sqrt_ratio: (U/V)^((q+7)/16) from one exponentiation, tested by c^2 V == U
# --------------------------------------------------------------------------

ROOTS_OF_UNITY = {"1": F.FQ2_ONE, "R1": T._SQRT_M1, "R2": T._SQRT_C2,
                  "R3": T._SQRT_C3}


def _ratio_case(want):
    """A random (U, V) whose ratio is a non-residue (`want` None) or a
    residue whose root is `want` times the exponentiation's candidate."""
    while True:
        u, v = rand_fq2(), rand_fq2()
        ratio = F.fq2_mul(u, F.fq2_inv(v))
        cand = F.fq2_pow(ratio, T.SQRT_EXP)
        fits = [name for name, r in ROOTS_OF_UNITY.items()
                if F.fq2_sqr(F.fq2_mul(r, cand)) == ratio]
        if (fits[0] if fits else None) == want:
            return u, v


SQRT_RATIO_CASES = {
    "residue-by-1": _ratio_case("1"), "residue-by-R1": _ratio_case("R1"),
    "residue-by-R2": _ratio_case("R2"), "residue-by-R3": _ratio_case("R3"),
    "non-residue": _ratio_case(None), "U-zero": ((0, 0), rand_fq2())}


@pytest.fixture(scope="module")
def sqrt_ratios():
    us, vs = zip(*SQRT_RATIO_CASES.values())
    found, root = jax.jit(h2c.sqrt_ratio)(to_device(us), to_device(vs))
    return np.asarray(found), root


@pytest.mark.parametrize("case", list(SQRT_RATIO_CASES))
def test_sqrt_ratio_matches_oracle(sqrt_ratios, case):
    i = list(SQRT_RATIO_CASES).index(case)
    u, v = SQRT_RATIO_CASES[case]
    found, root = sqrt_ratios
    want = F.fq2_sqrt(F.fq2_mul(u, F.fq2_inv(v)))
    assert bool(found[i]) == (want is not None)
    if want is not None:
        assert T.fq2_from_device(root, (i,)) in (want, F.fq2_neg(want))


# --------------------------------------------------------------------------
# the map: y' affine and signed, on both branches
# --------------------------------------------------------------------------

def _x1_of(u):
    """The map's first x for a non-exceptional u (RFC 9380 6.6.2)."""
    z_u2 = F.fq2_mul(SSWU_Z2, F.fq2_sqr(u))
    tv = F.fq2_add(F.fq2_sqr(z_u2), z_u2)
    return F.fq2_mul(
        F.fq2_neg(F.fq2_mul(SSWU_B2, F.fq2_inv(SSWU_A2))),
        F.fq2_add(F.FQ2_ONE, F.fq2_inv(tv)))


def _draw(sgn0, on_x1):
    """A random u of the given sign whose g(x1) is (not) a square."""
    while True:
        u = rand_fq2()
        square = F.fq2_sqrt(OH._gx_prime(_x1_of(u))) is not None
        if F.fq2_sgn0(u) == sgn0 and square == on_x1:
            return u


MAP_CASES = {"sgn0-0-on-x1": _draw(0, True), "sgn0-1-on-x1": _draw(1, True),
             "sgn0-0-on-x2": _draw(0, False), "sgn0-1-on-x2": _draw(1, False),
             "u-zero-exceptional": (0, 0)}


@pytest.fixture(scope="module")
def mapped():
    return jax.jit(h2c.map_to_curve_sswu_proj)(
        to_device(list(MAP_CASES.values())))


@pytest.mark.parametrize("case", list(MAP_CASES))
def test_map_proj_gives_the_affine_signed_y(mapped, case):
    i = list(MAP_CASES).index(case)
    u = MAP_CASES[case]
    xn, xd, y = (T.fq2_from_device(c, (i,)) for c in mapped)
    ex, ey = OH.map_to_curve_sswu_g2(u)
    assert F.fq2_mul(xn, F.fq2_inv(xd)) == ex
    assert y == ey
    assert F.fq2_sgn0(y) == F.fq2_sgn0(u)
    if "on-x" in case:
        assert (ex == _x1_of(u)) == case.endswith("x1")


# --------------------------------------------------------------------------
# isogeny + Jacobian conversion, and the kernel's point
# --------------------------------------------------------------------------

# x_den = (x - x0)^2 and y_den = (x - x0)^3: the kernel's one finite x
KERNEL_X = F.fq2_neg(F.fq2_mul(ISO3_X_DEN[1], F.fq2_inv((2, 0))))
ISO_CASES = {"xd-one": F.FQ2_ONE, "xd-random": rand_fq2(),
             "xd-random-again": rand_fq2(), "kernel-x": rand_fq2()}
ISO_POINTS = {case: OH.map_to_curve_sswu_g2(rand_fq2())
              for case in ISO_CASES}
ISO_POINTS["kernel-x"] = (KERNEL_X, rand_fq2())


@pytest.fixture(scope="module")
def isogenous():
    xn = [F.fq2_mul(ISO_POINTS[c][0], d) for c, d in ISO_CASES.items()]
    ys = [ISO_POINTS[c][1] for c in ISO_CASES]

    @jax.jit
    def run(xn, xd, y):
        q = h2c.iso_to_jacobian(*h2c.iso_map_proj(xn, xd, y))
        first = jax.tree_util.tree_map(lambda x: x[:1], q)
        # every row added to the first: the kernel's row must be the
        # neutral element, not a point (0, 0, 1) that is on no curve
        return q, PT.is_infinity(PT.G2_KIT, q), PT.point_add(
            PT.G2_KIT, q, jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (len(ISO_CASES),) + x.shape[1:]),
                first))
    return run(to_device(xn), to_device(list(ISO_CASES.values())),
               to_device(ys))


@pytest.mark.parametrize("case", list(ISO_CASES))
def test_isogeny_goes_straight_to_jacobian(isogenous, case):
    i = list(ISO_CASES).index(case)
    q, at_infinity, summed = isogenous
    first = C.from_affine(
        C.FQ2_OPS, *OH.iso_map_g2(ISO_POINTS[list(ISO_CASES)[0]]))
    if case == "kernel-x":
        assert bool(at_infinity[i])
        assert T.fq2_from_device(q[2], (i,)) == (0, 0)
        want_sum = first
    else:
        want = C.from_affine(C.FQ2_OPS, *OH.iso_map_g2(ISO_POINTS[case]))
        assert not bool(at_infinity[i])
        assert C.point_eq(C.FQ2_OPS, PT.g2_from_device(q, (i,)), want)
        want_sum = C.point_add(C.FQ2_OPS, want, first)
    assert C.point_eq(C.FQ2_OPS, PT.g2_from_device(summed, (i,)), want_sum)


# --------------------------------------------------------------------------
# the whole pipeline against RFC 9380 J.10.1, at three widths
# --------------------------------------------------------------------------

@pytest.mark.parametrize("width", [1, 3, 16])
def test_hash_to_g2_device_rfc_j101(width):
    fillers = [bytes([i]) * (i + 1) for i in range(width)]
    msgs = (list(J101_VECTORS) + fillers)[:width]
    u0, u1 = h2c.messages_to_fields(msgs, H2C_DST)
    out = jax.jit(h2c.hash_to_g2_device)(u0, u1)
    for i, m in enumerate(msgs):
        got = C.to_affine(C.FQ2_OPS, PT.g2_from_device(out, (i,)))
        if m in J101_VECTORS:
            x0, x1, y0, y1 = J101_VECTORS[m]
            assert got == ((x0, x1), (y0, y1))
        else:
            assert got == C.to_affine(C.FQ2_OPS, OH.hash_to_g2(m, H2C_DST))


# --------------------------------------------------------------------------
# structure: what the gain rests on
# --------------------------------------------------------------------------

def test_stage_h2c_has_one_fermat(monkeypatch):
    """`stage_h2c` raises to P - 2 once (its closing `to_affine_g2`) and
    runs ONE Fq2 exponentiation (the two draws share it at double
    width): the map itself holds no inversion."""
    calls = {"fermat": 0, "fq2_pow": []}
    pow_static, fq2_pow_static = fp.pow_static, T.fq2_pow_static

    def counted_pow(a, e, *args, **kw):
        calls["fermat"] += e == P - 2
        return pow_static(a, e, *args, **kw)

    def counted_fq2_pow(a, e):
        calls["fq2_pow"].append(a[0].shape)
        return fq2_pow_static(a, e)

    monkeypatch.setattr(fp, "pow_static", counted_pow)
    monkeypatch.setattr(T, "fq2_pow_static", counted_fq2_pow)
    width = 4
    u = jax.ShapeDtypeStruct((width, fp.L), np.int64)
    # a new function object: jax keeps traces by function, and this
    # trace has to be this test's
    jax.eval_shape(lambda u0, u1: V.stage_h2c(u0, u1), (u, u), (u, u))
    assert calls["fermat"] == 1
    assert calls["fq2_pow"] == [(2, width, fp.L)]
