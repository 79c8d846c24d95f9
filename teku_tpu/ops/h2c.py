"""Batched hash-to-G2 for TPU (RFC 9380 BLS12381G2_XMD:SHA-256_SSWU_RO_).

Split host/device at the hash boundary: expand_message_xmd is SHA-256
over short inputs (microseconds on host, no device win), while the field
math — simplified SWU, 3-isogeny, cofactor clearing — runs batched and
branch-free on device.  The reference client hashes inside native blst
(reference: infrastructure/bls/src/main/java/tech/pegasys/teku/bls/impl/
blst/HashToCurve.java:23 — the DST this module shares via the oracle).

DIVISIONLESS DESIGN.  Field inversion (Fermat, a ~380-iteration scan) is
the compile-time and runtime hotspot, so the map runs fully projective:

- SSWU computes x = xn/xd and y = yp/xd^3 without ever dividing (the
  RFC's non-division form: x1n = -B(tv2+1), x1d = A*tv2, with the
  exceptional case selected in).  The square root is taken on
  gval = gx_num * xd^3 — same residue class as gx, so the QR decision
  and the 4-candidate constant-time sqrt shape are unchanged — and the
  root IS the projective y: (yp)^2 = gval  <=>  (yp/xd^3)^2 = gx.
- The 3-isogeny maps numerators/denominators homogeneously
  (x = XN/XD, y = YN/YD), still division-free.
- ONE batched inversion (limbs.inv_many — a single Fermat for the whole
  batch via Montgomery's trick) converts both draws of every lane to
  affine, where the RFC sgn0 sign is applied.

Square roots use ONE Fq2 exponentiation per draw via the SSWU identity
gx2 = Z^3 u^6 gx1: candidates for sqrt(gval2) reuse the same power times
u^3 (Z^3)^((q+7)/16) (q = p^2 ≡ 9 mod 16).

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


def map_to_curve_sswu_proj(u):
    """Batched divisionless simplified SWU: Fq2 u -> (xn, xd, yp) on E'
    with x = xn/xd and y = yp/xd^3 (sgn0 sign NOT yet applied)."""
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

    # gx1n = x1n^3 + A x1n xd^2 + B xd^3  (numerator of g(x1) over xd^3)
    sq = T._fq2u(T.fq2_sqr(T._fq2s([x1n, xd])))
    x1n2, xd2 = (T.fq2_compress(s) for s in sq)
    r2 = T._fq2u(T.fq2_mul(
        T._fq2s([x1n2, xd2, T.fq2_compress(T.fq2_mul(_c("A", u), x1n))]),
        T._fq2s([x1n, xd, xd2])))
    x1n3, xd3, axd2 = r2
    xd3 = T.fq2_compress(xd3)
    gx1n = T.fq2_add(T.fq2_add(x1n3, axd2),
                     T.fq2_mul(_c("B", u), xd3))
    # the sqrt runs on gval = gx1n * xd^3: same QR class as g(x1), and a
    # root yp of gval is exactly the projective y (y = yp/xd^3)
    gval = T.fq2_compress(T.fq2_mul(T.fq2_compress(gx1n), xd3))

    cand = T.fq2_pow_static(gval, T.SQRT_EXP)
    # second candidate set for x2 = tv*x1: gval2 = tv^3 gval = Z^3 u^6 gval
    u3 = T.fq2_compress(T.fq2_mul(u2, u))
    cand2 = T.fq2_mul(T.fq2_mul(u3, _c("Z3E", u)), cand)
    tv3 = T.fq2_compress(T.fq2_mul(T.fq2_compress(T.fq2_sqr(tv)), tv))
    gval2 = T.fq2_compress(T.fq2_mul(tv3, gval))

    # the four root-of-unity multiples of both candidates, their squares
    # and the eight match tests, each as ONE wide call (lane axis -2:
    # [cand, R1 cand, R2 cand, R3 cand, cand2, R1 cand2, ...])
    roots = T._fq2s([_c(r, u) for r in ("R1", "R2", "R3")] * 2)
    scaled = T._fq2u(T.fq2_mul(roots, T._fq2s([cand] * 3 + [cand2] * 3)))
    tries = [cand] + scaled[:3] + [cand2] + scaled[3:]
    d = T.fq2_sub(T.fq2_sqr(T._fq2s(tries)),
                  T._fq2s([gval] * 4 + [gval2] * 4))
    match = jnp.all(fp.canonical(jnp.stack(d, axis=-2)) == 0,
                    axis=(-2, -1))                       # (..., 8)

    def first_match(tries, match):
        found = jnp.zeros(tv2_zero.shape, dtype=bool)
        y = tries[0]
        for i, t in enumerate(tries):
            m = match[..., i] & ~found
            y = T.fq2_select(m, t, y)
            found |= m
        return found, y

    found1, y1 = first_match(tries[:4], match[..., :4])
    _, y2 = first_match(tries[4:], match[..., 4:])

    xn = T.fq2_select(found1, x1n, T.fq2_compress(T.fq2_mul(tv, x1n)))
    yp = T.fq2_select(found1, y1, y2)
    return T.fq2_compress(xn), xd, T.fq2_compress(yp)


def iso_map_proj(xn, xd, yp):
    """3-isogeny E' -> E on projective inputs, division-free.

    Input x = xn/xd, y = yp/xd^3; output x = XN/XD, y = YN/YD with all
    four homogeneous in (xn, xd)."""
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

    XN = x_num                                   # deg 3
    XD = T.fq2_mul(xd, x_den)                    # deg 2 -> * xd
    YN = T.fq2_mul(yp, y_num)                    # y factor: yp/xd^3
    YD = T.fq2_mul(xd3, y_den)                   # matching xd^3
    return XN, T.fq2_compress(XD), T.fq2_compress(YN), T.fq2_compress(YD)


def _proj_to_affine_signed(u, XN, XD, YN, YD):
    """Batched projective -> affine with RFC sgn0(u) sign fix; ONE
    inversion of XD*YD per element, batched into a single Fermat
    exponentiation across the whole batch (limbs.inv_many)."""
    pinv = T.fq2_inv(T.fq2_compress(T.fq2_mul(XD, YD)))
    r = T._fq2u(T.fq2_mul(T._fq2s([XN, YN]),
                          T._fq2s([T.fq2_compress(T.fq2_mul(pinv, YD)),
                                   T.fq2_compress(T.fq2_mul(pinv, XD))])))
    x, y = (T.fq2_compress(c) for c in r)
    flip = fq2_sgn0(u) != fq2_sgn0(y)
    y = T.fq2_select(flip, T.fq2_neg(y), y)
    return x, T.fq2_compress(y)


def map_to_curve_sswu(u):
    """Affine SSWU on E' (test/oracle parity surface): projective map +
    affine conversion + sgn0 sign."""
    xn, xd, yp = map_to_curve_sswu_proj(u)
    # y = yp/xd^3: reuse the generic converter with XD=xd, YN=yp, YD=xd^3
    xd3 = T.fq2_compress(T.fq2_mul(T.fq2_compress(T.fq2_sqr(xd)), xd))
    return _proj_to_affine_signed(u, xn, xd, yp, xd3)


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
    """Device pipeline: two Fq2 draws -> G2 Jacobian point (in-subgroup).

    Both draws are stacked on a leading axis so the map, the isogeny and
    the (single, batched) inversion run once at double width.

    The RFC's sgn0 sign applies to the E' point BEFORE the isogeny
    (y' = yp/xd^3); flipping y' flips the isogeny output, so the affine
    y' (needed only for its sign) and the affine E coordinates are all
    recovered from ONE shared inversion of xd^3 * XD * YD."""
    U = T.tree_stack([u0, u1])
    xn, xd, yp = map_to_curve_sswu_proj(U)
    XN, XD, YN, YD = iso_map_proj(xn, xd, yp)
    xd3 = T.fq2_compress(T.fq2_mul(T.fq2_compress(T.fq2_sqr(xd)), xd))
    xd3_XD = T.fq2_compress(T.fq2_mul(xd3, XD))
    pinv = T.fq2_inv(T.fq2_compress(T.fq2_mul(xd3_XD, YD)))  # batched
    r = T._fq2u(T.fq2_mul(
        T._fq2s([T.fq2_compress(T.fq2_mul(XD, YD)),
                 T.fq2_compress(T.fq2_mul(xd3, YD)),
                 xd3_XD]),
        T._fq2s([pinv, pinv, pinv])))
    inv_xd3, inv_XD, inv_YD = (T.fq2_compress(c) for c in r)
    r2 = T._fq2u(T.fq2_mul(T._fq2s([yp, XN, YN]),
                           T._fq2s([inv_xd3, inv_XD, inv_YD])))
    y_prime, x, y = (T.fq2_compress(c) for c in r2)
    flip = fq2_sgn0(U) != fq2_sgn0(y_prime)
    y = T.fq2_select(flip, T.fq2_neg(y), y)
    y = T.fq2_compress(y)
    one = T._bcast2(T.FQ2_ONE_NP, x)
    (x0, y0, o0), (x1, y1, o1) = T.tree_unstack((x, y, one), 2)
    r = PT.point_add(PT.G2_KIT, (x0, y0, o0), (x1, y1, o1))
    return clear_cofactor(r)


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
