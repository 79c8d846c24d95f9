"""Branch-free elliptic-curve group ops for G1/G2 on TPU (JAX).

Jacobian-coordinate arithmetic over the limb fields, written as total
functions: every operation (including the exceptional cases — infinity
inputs, P == Q, P == -Q) is computed unconditionally and resolved with
lane selects, so the same compiled kernel is correct for every input and
batching is plain broadcasting.  This is the TPU replacement for blst's
P1/P2 point arithmetic behind the reference's BLS provider (reference:
infrastructure/bls/src/main/java/tech/pegasys/teku/bls/impl/blst/
BlstBLS12381.java; points parsed/validated in BlstPublicKey.java /
BlstSignature.java).

Fast subgroup membership uses endomorphism eigenvalue identities instead
of a full [r] scalar multiplication (the approach production pairing
libraries use):
- G1: phi(P) == [-z^2]P with phi(x,y) = (beta*x, y), beta a primitive
  cube root of unity.  ker(phi - lambda) has degree lambda^2+lambda+1 =
  z^4 - z^2 + 1 = r, so the identity holds exactly on the r-torsion.
- G2: psi(Q) == [z]Q with psi the untwist-Frobenius-twist map; on G2 psi
  acts as [p] and p ≡ z (mod r).
Both identities are validated against the oracle's multiply-by-r checks
in tests/test_ops_points.py.

Scalar multiplication over runtime scalars (the 64-bit batch-verify
random multipliers) is a scan over bit lanes — double always, add
selected — i.e. constant-time by construction.
"""

from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..crypto.bls import fields as F
from ..crypto.bls.constants import B_G1, B_G2, P, X_ABS
from . import limbs as fp
from . import towers as T


class FieldKit(NamedTuple):
    """Static namespace of field ops a curve group is generic over."""
    add: callable
    sub: callable
    mul: callable
    sqr: callable
    neg: callable
    double: callable
    is_zero: callable
    eq: callable
    select: callable
    const: callable       # host int-tuple / int -> device constant
    b_coeff: object       # curve b as a host constant (device-ready)
    stack: callable       # list of elements -> wide-lane element
    unstack: callable     # wide-lane element -> list
    zero_many: callable   # list of (lazy) elements -> list of zero-masks
    compress: callable    # lazy element -> one-unit element


def _fp_const(v: int):
    return jnp.asarray(fp.int_to_mont(v))


def _fq2_const(v):
    c = T.fq2_const(v)
    return (jnp.asarray(c[0]), jnp.asarray(c[1]))


def _fp_stack(elems):
    return jnp.stack(elems, axis=-2)


def _fp_unstack(s):
    return [s[..., i, :] for i in range(s.shape[-2])]


def _fp_zero_many(elems):
    """Batched ≡0-mod-P tests: ONE canonical map for all of them."""
    c = fp.canonical(jnp.stack(elems, axis=-2))
    z = jnp.all(c == 0, axis=-1)
    return [z[..., i] for i in range(len(elems))]


def _fq2_zero_many(elems):
    c = fp.canonical(jnp.stack(
        [comp for e in elems for comp in e], axis=-2))
    z = jnp.all(c == 0, axis=-1)
    return [z[..., 2 * i] & z[..., 2 * i + 1] for i in range(len(elems))]


def _fq2_compress(a):
    return T.fq2_compress(a)


G1_KIT = FieldKit(
    add=fp.add, sub=fp.sub, mul=fp.mont_mul, sqr=fp.mont_sqr, neg=fp.neg,
    double=fp.double, is_zero=fp.is_zero, eq=fp.eq, select=fp.select,
    const=_fp_const, b_coeff=B_G1, stack=_fp_stack, unstack=_fp_unstack,
    zero_many=_fp_zero_many, compress=fp.compress,
)

G2_KIT = FieldKit(
    add=T.fq2_add, sub=T.fq2_sub, mul=T.fq2_mul, sqr=T.fq2_sqr,
    neg=T.fq2_neg, double=T.fq2_double, is_zero=T.fq2_is_zero,
    eq=T.fq2_eq, select=T.fq2_select, const=_fq2_const, b_coeff=B_G2,
    stack=T._fq2s, unstack=T._fq2u,
    zero_many=_fq2_zero_many, compress=_fq2_compress,
)


# --------------------------------------------------------------------------
# Point structure: (X, Y, Z) tuple of field elements; Z == 0 <=> infinity.
# --------------------------------------------------------------------------

def leaf_shape(x):
    """Shape of a field element's first array leaf.

    Tower elements nest coordinate tuples ((c0, c1) for Fq2, deeper
    for Fq6/Fq12); every leaf shares one (batch..., L) shape, so the
    first leaf names it.  Shared by the broadcast helpers below and
    scalar_mul_static's dense-exponent fallback (which used to unwrap
    tuples with its own while-loop)."""
    while isinstance(x, tuple):
        x = x[0]
    return x.shape


def _broadcast_const(k: FieldKit, c, like):
    if k is G1_KIT:
        return jnp.broadcast_to(c, like.shape)
    shape = leaf_shape(like)
    return (jnp.broadcast_to(c[0], shape), jnp.broadcast_to(c[1], shape))


def _zero_like(k: FieldKit, x):
    if k is G1_KIT:
        return jnp.zeros_like(x)
    return (jnp.zeros_like(x[0]), jnp.zeros_like(x[1]))


def infinity_like(k: FieldKit, x):
    """Infinity with the batch shape of field element x."""
    one = _broadcast_const(k, k.const(1 if k is G1_KIT else (1, 0)), x)
    return (one, one, _zero_like(k, x))


def is_infinity(k: FieldKit, p):
    return k.is_zero(p[2])


def point_neg(k: FieldKit, p):
    return (p[0], k.neg(p[1]), p[2])


def point_double(k: FieldKit, p):
    """Jacobian doubling (a=0).  Total: doubling infinity gives Z3=0.
    Independent multiplies batched into wide-lane rounds; intermediates
    compressed where lazy unit counts would breach the mul contract.
    Inputs must be one-unit coordinates; output is compressed."""
    X1, Y1, Z1 = p
    A, B, YZ = k.unstack(k.mul(k.stack([X1, Y1, Y1]),
                               k.stack([X1, Y1, Z1])))
    XB, E = k.unstack(k.compress(k.stack(
        [k.add(X1, B), k.add(k.add(A, A), A)])))
    XB2, C, Fv = k.unstack(k.mul(k.stack([XB, B, E]),
                                 k.stack([XB, B, E])))
    D = k.sub(k.sub(XB2, A), C)
    D = k.add(D, D)
    D, X3 = k.unstack(k.compress(k.stack([D, k.sub(Fv, k.add(D, D))])))
    C2 = k.add(C, C)
    C4 = k.add(C2, C2)
    C8 = k.add(C4, C4)
    Y3 = k.sub(k.mul(E, k.sub(D, X3)), C8)
    Z3 = k.add(YZ, YZ)
    X3, Y3, Z3 = k.unstack(k.compress(k.stack([X3, Y3, Z3])))
    return (X3, Y3, Z3)


def point_add(k: FieldKit, p, q):
    """Unified Jacobian addition: every exceptional case (either input at
    infinity, P == Q, P == -Q) is computed and selected lane-wise; the
    four predicate zero-tests share one canonical map.  Inputs must be
    one-unit coordinates; output is compressed."""
    X1, Y1, Z1 = p
    X2, Y2, Z2 = q
    Z1Z1, Z2Z2, Z1Z2 = k.unstack(k.mul(k.stack([Z1, Z2, Z1]),
                                       k.stack([Z1, Z2, Z2])))
    U1, U2, Z2c, Z1c = k.unstack(k.mul(
        k.stack([X1, X2, Z2, Z1]),
        k.stack([Z2Z2, Z1Z1, Z2Z2, Z1Z1])))
    S1, S2 = k.unstack(k.mul(k.stack([Y1, Y2]), k.stack([Z2c, Z1c])))
    H = k.sub(U2, U1)
    sdiff = k.sub(S2, S1)
    H, rr = k.unstack(k.compress(k.stack([H, k.add(sdiff, sdiff)])))
    H2 = k.add(H, H)
    I, R2 = k.unstack(k.mul(k.stack([H2, rr]), k.stack([H2, rr])))
    J, V, ZZH = k.unstack(k.mul(
        k.stack([H, U1, k.add(Z1Z2, Z1Z2)]),
        k.stack([I, I, H])))
    X3 = k.sub(k.sub(R2, J), k.add(V, V))
    RVX, S1J = k.unstack(k.mul(k.stack([rr, S1]),
                               k.stack([k.sub(V, X3), J])))
    Y3 = k.sub(RVX, k.add(S1J, S1J))
    Z3 = ZZH
    out = tuple(k.unstack(k.compress(k.stack([X3, Y3, Z3]))))

    same_x, same_y, p_inf, q_inf = k.zero_many([H, sdiff, Z1, Z2])
    finite = (~p_inf) & (~q_inf)
    # P == Q (and both finite): double
    dbl = point_double(k, p)
    use_dbl = finite & same_x & same_y
    # P == -Q: infinity (select via zeroing Z)
    to_inf = finite & same_x & ~same_y
    out = _select_point(k, use_dbl, dbl, out)
    out = (out[0], out[1], k.select(to_inf, k.sub(out[2], out[2]), out[2]))
    out = _select_point(k, p_inf, q, out)
    out = _select_point(k, q_inf & ~p_inf, p, out)
    return out


def _select_point(k: FieldKit, cond, a, b):
    return tuple(k.select(cond, x, y) for x, y in zip(a, b))


def point_eq(k: FieldKit, p, q):
    """Equality in Jacobian coordinates (cross-multiplied), total; all
    four zero-tests share one canonical map."""
    Z1Z1, Z2Z2 = k.unstack(k.mul(k.stack([p[2], q[2]]),
                                 k.stack([p[2], q[2]])))
    Z2c, Z1c = k.unstack(k.mul(k.stack([q[2], p[2]]),
                               k.stack([Z2Z2, Z1Z1])))
    m = k.unstack(k.mul(k.stack([p[0], q[0], p[1], q[1]]),
                        k.stack([Z2Z2, Z1Z1, Z2c, Z1c])))
    x_eq, y_eq, p_inf, q_inf = k.zero_many(
        [k.sub(m[0], m[1]), k.sub(m[2], m[3]), p[2], q[2]])
    both_inf = p_inf & q_inf
    one_inf = p_inf ^ q_inf
    return (x_eq & y_eq & ~one_inf) | both_inf


# --------------------------------------------------------------------------
# Scalar multiplication
# --------------------------------------------------------------------------

SCALAR_WINDOW = 4


def ladder_plan(nbits: int, window: int):
    """Host-side plan for scalar_mul_bits: MSB zero-padding to a
    window multiple + window count.  Returns (pad, n_windows)."""
    pad = -nbits % window
    return pad, (nbits + pad) // window


def ladder_op_counts(nbits: int, window: int) -> dict:
    """Executed point-op counts of the windowed ladder for a given
    bit width — the observable the irregular-width regression test
    pins (and PERF.md's cost model cites).  Derived from the SAME
    ladder_plan scalar_mul_bits executes."""
    _, nwin = ladder_plan(nbits, window)
    return {
        "doubles": (nwin - 1) * window,
        "adds": nwin - 1,              # one gathered add per digit
        "table_adds": 1 << window,     # build scan length
        "total": (nwin - 1) * (window + 1) + (1 << window),
    }


def scalar_mul_bits(k: FieldKit, bits, p, window: int = SCALAR_WINDOW):
    """[s]P for runtime scalars given as a bit array.

    bits: int array (..., NBITS), MSB first, matching P's batch shape.

    Fixed-window ladder: the bit-serial form pays a double AND a
    (select-discarded but computed) add per bit — for the 64-bit batch
    multipliers that is 64 doubles + 64 adds.  A per-lane 2^w table
    (2^w - 2 adds once) and one gathered add per w-bit digit pays
    64 doubles + 16 adds + 14 build adds: ~35% fewer point ops in the
    scalars stage.  Still constant-time: every digit gathers and adds
    (digit 0 adds the infinity row, which point_add absorbs).

    Irregular widths (e.g. 33-bit GLV half-scalars, 255-bit parity
    oracles) are MSB zero-padded to a window multiple instead of
    demoting to the bit-serial ladder — a leading zero digit just
    starts the accumulator at the (absorbed) infinity row, and the
    op count stays the windowed one (ladder_op_counts pins the win).
    """
    nbits = bits.shape[-1]
    pad, _ = ladder_plan(nbits, window)
    if pad:
        bits = jnp.concatenate(
            [jnp.zeros(bits.shape[:-1] + (pad,), bits.dtype), bits],
            axis=-1)
        nbits += pad
    # table rows [0]P..[2^w - 1]P, stacked on a leading axis.  Built
    # with a scan so the graph holds ONE point_add body (an unrolled
    # build inlines 2^w - 2 adds and measurably bloats XLA compiles).
    def build(carry, _):
        return point_add(k, carry, p), carry
    _, table = lax.scan(build, infinity_like(k, p[0]), None,
                        length=1 << window)

    def gather(d):
        # leaf (2^w, ..., L); d (...,) -> (..., L)
        def take(leaf):
            idx = jnp.broadcast_to(
                d[None, ..., None], (1,) + d.shape + (leaf.shape[-1],))
            return jnp.take_along_axis(leaf, idx, axis=0)[0]
        return jax.tree_util.tree_map(take, table)

    # MSB-first base-2^w digits, scanned: (..., nbits) -> (nwin, ...)
    weights = jnp.asarray([1 << (window - 1 - t) for t in range(window)],
                          dtype=bits.dtype)
    digits = jnp.moveaxis(
        (bits.reshape(bits.shape[:-1] + (-1, window)) * weights)
        .sum(axis=-1), -1, 0)

    def body(acc, d):
        acc = lax.fori_loop(0, window,
                            lambda _, a: point_double(k, a), acc)
        acc = point_add(k, acc, gather(d))
        return acc, None

    acc = gather(digits[0])              # leading doubles of inf elided
    acc, _ = lax.scan(body, acc, digits[1:])
    return acc


def scalar_mul_static(k: FieldKit, e: int, p):
    """[e]P for a static non-negative exponent.

    The bit pattern is static, so zero bits pay ONLY a doubling: one
    lax.scan over the bits doubles every iteration and takes the
    point_add through a lax.cond at the (few) one-bits — for the BLS
    parameter (Hamming weight 6) this drops ~58 of 64 adds versus a
    naive double-and-always-add ladder, and the graph holds one double
    body and one add body whatever the exponent."""
    assert e >= 0
    if e == 0:
        return infinity_like(k, p[0])
    bits = bin(e)[3:]    # acc starts at P (top bit)
    if bits.count("1") > 16:
        # DENSE exponent: the windowed ladder does fewer adds than one
        # per one-bit
        nbits = len(bits) + 1
        bit_arr = jnp.asarray([int(c) for c in bin(e)[2:]],
                              dtype=jnp.int64)
        lane_shape = leaf_shape(p[0])[:-1]   # bits over the batch dims
        bit_arr = jnp.broadcast_to(bit_arr, lane_shape + (nbits,))
        return scalar_mul_bits(k, bit_arr, p)

    def iteration(acc, bit):
        acc = point_double(k, acc)
        return lax.cond(bit, lambda a: point_add(k, a, p), lambda a: a,
                        acc), None

    acc, _ = lax.scan(iteration, p,
                      jnp.asarray([c == "1" for c in bits], dtype=bool))
    return acc


def point_batch_sum(k: FieldKit, p):
    """Sum points over the leading batch axis via log-depth pairwise
    adds.  (Lives here so the KZG kernels (ops/kzg.py) and the verify
    pipeline (ops/verify.py) share one reduction.)"""
    return T.tree_fold_pairs(lambda a, b: point_add(k, a, b), p)


def scalar_from_uint64(vals):
    """uint64 scalar array (...,) -> int64 bit array (..., 64) MSB first."""
    vals = jnp.asarray(vals).astype(jnp.uint64)
    shifts = jnp.arange(63, -1, -1, dtype=jnp.uint64)
    return ((vals[..., None] >> shifts) & 1).astype(jnp.int64)


def scalar_bits_np(vals) -> np.ndarray:
    """`scalar_from_uint64` on the host: the same (..., 64) int64 bits,
    MSB first, with no device program and no read-back.  The provider's
    host half draws a dispatch's multipliers with this one (it may run
    while another dispatch owns the device, and the chip serves its
    queue in order: a read-back would wait out that whole dispatch)."""
    vals = np.asarray(vals, dtype=np.uint64)
    shifts = np.arange(63, -1, -1, dtype=np.uint64)
    return ((vals[..., None] >> shifts) & np.uint64(1)).astype(np.int64)


# --------------------------------------------------------------------------
# Endomorphisms + fast subgroup checks
# --------------------------------------------------------------------------

# beta: primitive cube root of unity in Fq (acts x -> beta*x on G1).
# Only ONE of the two non-trivial cube roots has eigenvalue -z^2 (the
# other has eigenvalue z^2 - 1 mod r and would reject every valid point),
# so the import-time assert below verifies the eigenvalue identity
# phi(G) == [-z^2]G on the G1 generator itself.
_BETA = pow(2, (P - 1) // 3, P)
if _BETA == 1:  # pragma: no cover - 2 is not a cube in Fq for this P
    _BETA = pow(3, (P - 1) // 3, P)
assert _BETA != 1 and pow(_BETA, 3, P) == 1


def _check_beta_eigenvalue() -> None:
    from ..crypto.bls.constants import R as _R
    from ..crypto.bls.curve import FQ_OPS, G1_GENERATOR, point_mul, to_affine
    gx, gy = G1_GENERATOR[0], G1_GENERATOR[1]
    lam = (-(X_ABS * X_ABS)) % _R
    expect = to_affine(FQ_OPS, point_mul(FQ_OPS, lam, (gx, gy, 1)))
    assert expect == (_BETA * gx % P, gy), (
        "beta has the wrong GLV eigenvalue")


_check_beta_eigenvalue()

# psi constants: untwist-Frobenius-twist on our tower (w^2 = v, v^3 = xi):
#   x-part picks up (v^(p-1))^-1 = FROB6_C1^-1
#   y-part picks up (w^(p-1))^-3 = FROB12_C1^-3
_PSI_X = F.fq2_inv(F.FROB6_C1)
_PSI_Y = F.fq2_inv(F.fq2_mul(F.fq2_mul(F.FROB12_C1, F.FROB12_C1), F.FROB12_C1))


def g1_phi(p):
    """GLV endomorphism (x, y, z) -> (beta*x, y, z)."""
    beta = _fp_const(_BETA)
    return (fp.mont_mul(p[0], beta), p[1], p[2])


def g2_psi(q):
    """Untwist-Frobenius-twist endomorphism on E'(Fq2)."""
    cx = _fq2_const(_PSI_X)
    cy = _fq2_const(_PSI_Y)
    return (T.fq2_mul(T.fq2_conj(q[0]), cx),
            T.fq2_mul(T.fq2_conj(q[1]), cy),
            T.fq2_conj(q[2]))


def g1_in_subgroup(p):
    """phi(P) == [-z^2]P  (infinity counts as in-subgroup)."""
    lhs = g1_phi(p)
    rhs = point_neg(G1_KIT, scalar_mul_static(G1_KIT, X_ABS * X_ABS, p))
    return point_eq(G1_KIT, lhs, rhs) | is_infinity(G1_KIT, p)


def g2_in_subgroup(q):
    """psi(Q) == [z]Q with z < 0  (infinity counts as in-subgroup)."""
    lhs = g2_psi(q)
    rhs = point_neg(G2_KIT, scalar_mul_static(G2_KIT, X_ABS, q))
    return point_eq(G2_KIT, lhs, rhs) | is_infinity(G2_KIT, q)


# --------------------------------------------------------------------------
# On-curve checks + batched decompression (y-recovery)
# --------------------------------------------------------------------------

def is_on_curve(k: FieldKit, p):
    """Y^2 == X^3 + b*Z^6, total (infinity is on-curve)."""
    b = _broadcast_const(k, k.const(k.b_coeff), p[0])
    z2 = k.sqr(p[2])
    z6 = k.mul(k.sqr(z2), z2)
    lhs = k.sqr(p[1])
    rhs = k.add(k.mul(k.sqr(p[0]), p[0]), k.mul(b, z6))
    return k.eq(lhs, rhs) | is_infinity(k, p)


def g1_recover_y(x_plain, y_is_large):
    """Batched G1 decompression from plain-form x limbs.

    Returns (valid, point).  valid=False lanes: x not on curve.
    Subgroup check NOT included (separate, it costs a scalar mul).
    """
    x = fp.to_mont(x_plain)
    b = jnp.broadcast_to(_fp_const(B_G1), x.shape)
    rhs = fp.add(fp.mont_mul(fp.mont_sqr(x), x), b)
    y = fp.sqrt_candidate(rhs)
    ok = fp.eq(fp.mont_sqr(y), rhs)
    # wire sign: flip if computed root's "largeness" mismatches the flag
    half = jnp.asarray(fp.int_to_limbs((P - 1) // 2))
    y_plain = fp.from_mont(y)
    large = fp.gt(y_plain, half)
    y = fp.select(large == y_is_large, y, fp.neg(y))
    one = jnp.broadcast_to(jnp.asarray(fp.ONE_MONT), x.shape)
    return ok, (x, y, one)


def g2_recover_y(x_plain, y_is_large):
    """Batched G2 decompression from plain-form Fq2 x limbs (c0, c1)."""
    x = (fp.to_mont(x_plain[0]), fp.to_mont(x_plain[1]))
    b = _broadcast_const(G2_KIT, _fq2_const(B_G2), x)
    rhs = T.fq2_add(T.fq2_mul(T.fq2_sqr(x), x), b)
    ok, y = T.fq2_sqrt(rhs)
    y = T.fq2_compress(y)
    large = T.fq2_is_large(T.fq2_from_mont(y))
    y = T.fq2_select(large == y_is_large, y, T.fq2_neg(y))
    one = _broadcast_const(G2_KIT, _fq2_const((1, 0)), x)
    return ok, (x, y, one)


# --------------------------------------------------------------------------
# Host conversions (tests / boundaries)
# --------------------------------------------------------------------------

def g1_to_device(p_jac):
    """Oracle G1 Jacobian point (ints) -> device point (unbatched)."""
    return tuple(jnp.asarray(fp.int_to_mont(c)) for c in p_jac)


def g1_from_device(p, index=()):
    return tuple(fp.mont_to_int(np.asarray(c)[index]) for c in p)


def g2_to_device(p_jac):
    return tuple(T.fq2_to_device(c) for c in p_jac)


def g2_from_device(p, index=()):
    return tuple(T.fq2_from_device(c, index) for c in p)
