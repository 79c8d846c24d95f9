"""Batched hash-to-G2 for TPU (RFC 9380 BLS12381G2_XMD:SHA-256_SSWU_RO_).

Split host/device at the hash boundary: expand_message_xmd is SHA-256
over short inputs (microseconds on host, no device win), while the field
math — simplified SWU, 3-isogeny, cofactor clearing — runs batched and
branch-free on device.  The reference client hashes inside native blst
(reference: infrastructure/bls/src/main/java/tech/pegasys/teku/bls/impl/
blst/HashToCurve.java:23 — the DST this module shares via the oracle).

DIVISIONLESS DESIGN.  A batched field inversion (`limbs.inv_many`) is
one Fermat exponentiation at width 1, a ~95-digit windowed scan that
costs a v5e chip ~41 ms however many elements share it (PERF.md §6,
PR 33), so the served path `hash_to_g2_device` holds none: the only one
in `stage_h2c` is the caller's `to_affine_g2` on the finished point.
This is RFC 9380 appendix F.2's straight-line form, the one blst follows:

- SSWU computes x = xn/xd without dividing (x1n = -B(tv2+1),
  x1d = A*tv2, with the exceptional case selected in).
- y' comes out AFFINE from `sqrt_ratio` (F.2.1; q = p^2 = 9 mod 16):
  with U = the numerator of g(x1) over V = xd^3,
  U V^7 (U V^15)^((q-9)/16) = (U/V)^((q+7)/16), ONE exponentiation of
  the plain square root's length, and a candidate c is tested by
  c^2 V == U.  The RFC's sign rule sgn0(u) == sgn0(y') is applied to
  that y' directly.
- The 3-isogeny maps numerators and denominators homogeneously
  (x = XN/XD, y = YN/YD with YN = y' * y_num, YD = y_den: both have
  degree 3, so no power of xd is left over).
- (XN/XD, YN/YD) becomes Jacobian by multiplying through: Z = XD YD,
  X = XN XD YD^2, Y = YN XD^3 YD^2 (`iso_to_jacobian`), and the two
  draws are added as Jacobian points.

The two draws share the one exponentiation at double width, and the
second candidate set needs none: gx2 = Z^3 u^6 gx1, so candidates for
sqrt(g(x2)) are the same power times u^3 (Z^3)^((q+7)/16).

Cofactor clearing is Budroni-Pintore via the psi endomorphism, matching
the oracle's production path (crypto/bls/hash_to_curve.py:152-158).
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..crypto.bls import fields as F
from ..crypto.bls import hash_to_curve as OH
from ..crypto.bls.constants import (DST_G2_POP, ISO3_X_DEN, ISO3_X_NUM,
                                    ISO3_Y_DEN, ISO3_Y_NUM, P, SSWU_A2,
                                    SSWU_B2, SSWU_Z2, X_ABS)
from . import limbs as fp
from . import points as PT
from . import towers as T

# --------------------------------------------------------------------------
# Host-computed constants (oracle arithmetic, converted once)
# --------------------------------------------------------------------------

_Z3_POW_E = F.fq2_pow(
    F.fq2_mul(F.fq2_sqr(SSWU_Z2), SSWU_Z2), T.SQRT_EXP)

_C = {name: T.fq2_const(val) for name, val in dict(
    A=SSWU_A2, B=SSWU_B2, Z=SSWU_Z2, Z3E=_Z3_POW_E,
    R1=T._SQRT_M1, R2=T._SQRT_C2, R3=T._SQRT_C3,
).items()}


def _c(name, like):
    return T._bcast2(_C[name], like)


# --------------------------------------------------------------------------
# Map to curve: projective SSWU on E', fully batched, no inversions
# --------------------------------------------------------------------------

def fq2_sgn0(a):
    """RFC 9380 sgn0 on a Montgomery-form element (device)."""
    plain = T.fq2_from_mont(a)
    a0_odd = plain[0][..., 0] & 1
    a0_zero = fp.is_zero(plain[0])
    a1_odd = plain[1][..., 0] & 1
    return a0_odd | (a0_zero.astype(jnp.int64) & a1_odd)


def _sqrt_ratio_cand(U, V):
    """(U/V)^((q+7)/16) without dividing, as U V^7 (U V^15)^((q-9)/16)
    (V^(q-1) = 1): RFC 9380 F.2.1's sqrt_ratio for q = p^2 = 9 mod 16.
    One exponentiation of SQRT_EXP's length; U, V one-unit, V != 0."""
    V2 = T.fq2_compress(T.fq2_sqr(V))
    V4, V3 = (T.fq2_compress(c) for c in T._fq2u(T.fq2_mul(
        T._fq2s([V2, V2]), T._fq2s([V2, V]))))
    V8, V7 = (T.fq2_compress(c) for c in T._fq2u(T.fq2_mul(
        T._fq2s([V4, V4]), T._fq2s([V4, V3]))))
    V15, UV7 = (T.fq2_compress(c) for c in T._fq2u(T.fq2_mul(
        T._fq2s([V8, U]), T._fq2s([V7, V7]))))
    power = T.fq2_pow_static(T.fq2_mul(U, V15), T.SQRT_EXP - 1)
    return T.fq2_compress(T.fq2_mul(UV7, power))


def _roots_of_ratios(cands, targets, V):
    """For each pair (cand, target): the first of the four root-of-unity
    multiples {1, R1, R2, R3} * cand whose square times V is target, as
    (found, root); root is cand where none is.  The multiples, their
    squares and every match test run as ONE wide call each (lane axis
    -2: [cand, R1 cand, R2 cand, R3 cand, cand2, R1 cand2, ...])."""
    k = len(cands)
    roots = T._fq2s([_c(r, V) for r in ("R1", "R2", "R3")] * k)
    scaled = T._fq2u(T.fq2_mul(
        roots, T._fq2s([c for c in cands for _ in range(3)])))
    tries = [t for j, c in enumerate(cands)
             for t in [c] + scaled[3 * j:3 * j + 3]]
    wide_v = (V[0][..., None, :], V[1][..., None, :])
    d = T.fq2_sub(T.fq2_mul(T.fq2_sqr(T._fq2s(tries)), wide_v),
                  T._fq2s([t for t in targets for _ in range(4)]))
    match = jnp.all(fp.canonical(jnp.stack(d, axis=-2)) == 0,
                    axis=(-2, -1))                       # (..., 4k)
    out = []
    for j in range(k):
        found = jnp.zeros(match.shape[:-1], dtype=bool)
        y = cands[j]
        for i in range(4 * j, 4 * j + 4):
            m = match[..., i] & ~found
            y = T.fq2_select(m, tries[i], y)
            found |= m
        out.append((found, y))
    return out


def sqrt_ratio(U, V):
    """RFC 9380 F.2.1 sqrt_ratio, batched: (is_square, a root of U/V)
    for one-unit U and V != 0; the root is garbage where U/V is a
    non-residue.  The map below runs the same pieces over both of its
    candidate sets at once; this form is the test surface."""
    return _roots_of_ratios([_sqrt_ratio_cand(U, V)], [U], V)[0]


def map_to_curve_sswu_proj(u):
    """Batched divisionless simplified SWU: Fq2 u -> (xn, xd, y) on E'
    with x = xn/xd and y AFFINE, carrying the RFC's sign
    (sgn0(y) == sgn0(u))."""
    one = T._bcast2(T.FQ2_ONE_NP, u)
    u2 = T.fq2_sqr(u)
    tv = T.fq2_compress(T.fq2_mul(_c("Z", u), u2))
    tv2 = T.fq2_compress(T.fq2_add(T.fq2_sqr(tv), tv))   # Z^2 u^4 + Z u^2
    tv2_zero = T.fq2_is_zero(tv2)
    # x1 = (-B/A)(1 + 1/tv2)  ==  -B(tv2+1) / (A tv2); exceptional case
    # tv2 == 0  ->  x1 = B/(Z A)
    r1 = T._fq2u(T.fq2_mul(
        T._fq2s([_c("B", u), _c("A", u)]),
        T._fq2s([T.fq2_add(tv2, one),
                 T.fq2_select(tv2_zero, _c("Z", u), tv2)])))
    x1n = T.fq2_select(tv2_zero, _c("B", u), T.fq2_neg(r1[0]))
    xd = T.fq2_compress(r1[1])
    x1n = T.fq2_compress(x1n)

    # g(x1) = gx1n / xd^3 with gx1n = x1n^3 + A x1n xd^2 + B xd^3
    sq = T._fq2u(T.fq2_sqr(T._fq2s([x1n, xd])))
    x1n2, xd2 = (T.fq2_compress(s) for s in sq)
    r2 = T._fq2u(T.fq2_mul(
        T._fq2s([x1n2, xd2, T.fq2_compress(T.fq2_mul(_c("A", u), x1n))]),
        T._fq2s([x1n, xd, xd2])))
    x1n3, xd3, axd2 = r2
    xd3 = T.fq2_compress(xd3)
    gx1n = T.fq2_compress(T.fq2_add(T.fq2_add(x1n3, axd2),
                                    T.fq2_mul(_c("B", u), xd3)))

    cand = _sqrt_ratio_cand(gx1n, xd3)
    # second candidate set for x2 = tv*x1: g(x2) = tv^3 g(x1) = Z^3 u^6 g(x1)
    u3 = T.fq2_compress(T.fq2_mul(u2, u))
    cand2 = T.fq2_mul(T.fq2_mul(u3, _c("Z3E", u)), cand)
    tv3 = T.fq2_compress(T.fq2_mul(T.fq2_compress(T.fq2_sqr(tv)), tv))
    gx2n = T.fq2_compress(T.fq2_mul(tv3, gx1n))
    (found1, y1), (_, y2) = _roots_of_ratios(
        [cand, cand2], [gx1n, gx2n], xd3)

    xn = T.fq2_select(found1, x1n, T.fq2_compress(T.fq2_mul(tv, x1n)))
    y = T.fq2_select(found1, y1, y2)
    y = T.fq2_select(fq2_sgn0(u) != fq2_sgn0(y), T.fq2_neg(y), y)
    return T.fq2_compress(xn), xd, T.fq2_compress(y)


def iso_map_proj(xn, xd, y):
    """3-isogeny E' -> E on a projective x, division-free.

    Input x = xn/xd and the affine y; output x = XN/XD, y = YN/YD with
    XN, XD and YN/y, YD homogeneous in (xn, xd)."""
    sq = T._fq2u(T.fq2_sqr(T._fq2s([xn, xd])))
    xn2, xd2 = (T.fq2_compress(s) for s in sq)
    r = T._fq2u(T.fq2_mul(T._fq2s([xn2, xd2]), T._fq2s([xn, xd])))
    xn3, xd3 = (T.fq2_compress(s) for s in r)
    xd_pows = [None, xd, xd2, xd3]
    xn_pows = [None, xn, xn2, xn3]

    # every term k_i xn^i xd^(d-i) of all four polynomials takes the
    # same two multiplies (by xn^i, then by xd^(d-i)); each round is ONE
    # wide call over all terms instead of one call per term
    polys = (ISO3_X_NUM, ISO3_X_DEN, ISO3_Y_NUM, ISO3_Y_DEN)
    terms = [(k, i, len(co) - 1 - i)
             for co in polys for i, k in enumerate(co)]
    vals = [T._bcast2(T.fq2_const(k), xn) for k, _, _ in terms]

    def on_lanes(op, vals, lanes, *rest):
        """vals with op applied to the `lanes` subset as one wide call."""
        done = T._fq2u(op(T._fq2s([vals[j] for j in lanes]), *rest))
        vals = list(vals)
        for j, v in zip(lanes, done):
            vals[j] = v
        return vals

    by_xn = [j for j, (_, i, _) in enumerate(terms) if i]
    by_xd = [j for j, (_, _, e) in enumerate(terms) if e]
    vals = on_lanes(T.fq2_mul, vals, by_xn,
                    T._fq2s([xn_pows[terms[j][1]] for j in by_xn]))
    vals = on_lanes(T.fq2_compress, vals, by_xd)
    vals = on_lanes(T.fq2_mul, vals, by_xd,
                    T._fq2s([xd_pows[terms[j][2]] for j in by_xd]))
    sums, pos = [], 0
    for co in polys:
        sums.append(functools.reduce(T.fq2_add, vals[pos:pos + len(co)]))
        pos += len(co)
    x_num, x_den, y_num, y_den = T._fq2u(T.fq2_compress(T._fq2s(sums)))

    # x_num, y_num, y_den have degree 3 and x_den degree 2: only XD
    # keeps a factor xd
    XD, YN = T._fq2u(T.fq2_compress(T.fq2_mul(
        T._fq2s([xd, y]), T._fq2s([x_den, y_num]))))
    return x_num, XD, YN, y_den


def iso_to_jacobian(XN, XD, YN, YD):
    """(XN/XD, YN/YD) as a Jacobian point without dividing: Z = XD YD,
    X = XN XD YD^2, Y = YN XD^3 YD^2.  Where XD YD = 0 (x' in the
    isogeny's kernel) that is the point at infinity, as the RFC has it."""
    Z, xn_yd, yn_xd = (T.fq2_compress(c) for c in T._fq2u(T.fq2_mul(
        T._fq2s([XD, XN, YN]), T._fq2s([YD, YD, XD]))))
    X, ZZ = (T.fq2_compress(c) for c in T._fq2u(T.fq2_mul(
        T._fq2s([xn_yd, Z]), T._fq2s([Z, Z]))))
    return X, T.fq2_compress(T.fq2_mul(yn_xd, ZZ)), Z


def map_to_curve_sswu(u):
    """Affine SSWU on E' (test/oracle parity surface, on no served
    path): the projective map and one inversion of xd."""
    xn, xd, y = map_to_curve_sswu_proj(u)
    return T.fq2_compress(T.fq2_mul(xn, T.fq2_inv(xd))), y


# --------------------------------------------------------------------------
# Cofactor clearing (Budroni-Pintore) + full pipeline
# --------------------------------------------------------------------------

def clear_cofactor(p):
    """h_eff*P = [x^2-x-1]P + [x-1]psi(P) + psi^2(2P), with the BLS
    parameter negative: [x]Q computed as -[|x|]Q."""
    def mul_x(q):
        return PT.point_neg(PT.G2_KIT,
                            PT.scalar_mul_static(PT.G2_KIT, X_ABS, q))

    neg_p = PT.point_neg(PT.G2_KIT, p)

    # a = [x]P - P, then res = [x]a - P: the same step twice, scanned so
    # the graph holds ONE |x|-ladder
    def step(q, _):
        q = PT.point_add(PT.G2_KIT, mul_x(q), neg_p)
        return q, q

    _, both = lax.scan(step, p, None, length=2)
    a, res = T.tree_unstack(both, 2)
    res = PT.point_add(PT.G2_KIT, res, PT.g2_psi(a))
    dbl = PT.point_double(PT.G2_KIT, p)
    res = PT.point_add(PT.G2_KIT, res, PT.g2_psi(PT.g2_psi(dbl)))
    return res


def hash_to_g2_device(u0, u1):
    """Device pipeline: two Fq2 draws -> G2 Jacobian point (in-subgroup),
    with no field inversion.

    Both draws are stacked on a leading axis so the map (its one
    exponentiation), the isogeny and the Jacobian conversion run once at
    double width; the draws are then added as Jacobian points.  A draw
    whose x' lies in the isogeny's kernel (XD YD = 0; probability
    ~2^-380, never seen) is the point at infinity, which is what RFC
    9380 says and `point_add` selects around."""
    U = T.tree_stack([u0, u1])
    q = iso_to_jacobian(*iso_map_proj(*map_to_curve_sswu_proj(U)))
    q0, q1 = T.tree_unstack(q, 2)
    return clear_cofactor(PT.point_add(PT.G2_KIT, q0, q1))


def messages_to_fields(messages, dst: bytes = DST_G2_POP):
    """Host: list of message bytes -> batched Montgomery Fq2 draws (u0, u1).

    Mirrors the oracle's hash_to_field (crypto/bls/hash_to_curve.py:54-65).
    """
    u0c0, u0c1, u1c0, u1c1 = [], [], [], []
    for msg in messages:
        (a, b), (c, d) = OH.hash_to_field_fq2(msg, 2, dst)
        u0c0.append(fp.int_to_mont(a))
        u0c1.append(fp.int_to_mont(b))
        u1c0.append(fp.int_to_mont(c))
        u1c1.append(fp.int_to_mont(d))
    return ((np.stack(u0c0), np.stack(u0c1)),
            (np.stack(u1c0), np.stack(u1c1)))


def to_affine_g2(p):
    """Jacobian -> affine on device (one batched inversion); infinity
    lanes return garbage coords — callers carry the infinity mask."""
    return affine_g2_given(p, T.fq2_inv(p[2]))


def affine_g2_given(p, zinv):
    """to_affine_g2 for a caller that already holds z^-1 (an inversion
    shared with other work: ops/verify.py:affine_with_signature)."""
    zinv2 = T.fq2_sqr(zinv)
    x = T.fq2_mul(p[0], zinv2)
    y = T.fq2_mul(p[1], T.fq2_mul(zinv2, zinv))
    out = fp.compress(jnp.stack([x[0], x[1], y[0], y[1]], axis=-2))
    return ((out[..., 0, :], out[..., 1, :]),
            (out[..., 2, :], out[..., 3, :]))
