"""Batched optimal ate pairing on BLS12-381 for TPU (JAX).

Miller loop in Jacobian coordinates on the twist with sparse line
multiplications, mirroring the oracle's production loop
(teku_tpu/crypto/bls/pairing.py) on limb towers; the reference client
gets this from blst's Pairing (mul_n_aggregate / commit / merge /
finalverify, reference: infrastructure/bls/src/main/java/tech/pegasys/
teku/bls/impl/blst/BlstBLS12381.java:124-189).

Compile/runtime structure: the BLS parameter |z| = 0xD201000000010000 has
Hamming weight 6.  The 63 Miller iterations are ONE lax.scan over the
static bit vector; the 5 iterations that also add take the add-step
through a lax.cond on the scanned bit, so the compiled graph holds one
doubling body and one add body (the TPU compiler pays per mont_mul call
site, PERF.md "On the chip"), the runtime does no wasted add-steps, and
everything broadcasts over leading batch dims.

Final exponentiation: easy part then the Hayashida-Hayasaka-Teruya
x-chain hard part, computing f^(3d) (cofactor 3 preserves is_one /
equality / bilinearity — see the oracle's derivation and import-time
assert in crypto/bls/pairing.py:220-229); cyclotomic powers use
Granger-Scott squaring.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..crypto.bls.constants import X_ABS
from . import limbs as fp
from . import towers as T

# bits of |z| below the MSB, MSB first: the scanned add/no-add schedule
_X_BITS = np.array([c == "1" for c in bin(X_ABS)[3:]], dtype=np.bool_)


# --------------------------------------------------------------------------
# Line-evaluation steps (Jacobian on the twist E'/Fq2)
# --------------------------------------------------------------------------

def _dbl_step(t, px_neg, py):
    """Double T; line through T evaluated at P as sparse (c0, c1, c2).
    Independent fq2 multiplies are gathered into wide calls per round;
    lazy intermediates are compressed before they would breach the limb
    layer's operand-magnitude contract.  T coords must be one unit."""
    X, Y, Z = t
    A, B, Z2 = T._fq2u(T.fq2_sqr(T._fq2s([X, Y, Z])))
    XB, E = T._fq2u(T.fq2_compress(T._fq2s(
        [T.fq2_add(X, B), T.fq2_add(T.fq2_add(A, A), A)])))
    # round 2: squares of (X+B), B, E and product Y*Z
    r2 = T._fq2u(T.fq2_mul(T._fq2s([XB, B, E, Y]),
                           T._fq2s([XB, B, E, Z])))
    XB2, Cc, Fv, YZ = r2
    D = T.fq2_sub(T.fq2_sub(XB2, A), Cc)
    D = T.fq2_add(D, D)
    Z3 = T.fq2_add(YZ, YZ)
    D, X3, Z3 = T._fq2u(T.fq2_compress(T._fq2s(
        [D, T.fq2_sub(Fv, T.fq2_add(D, D)), Z3])))
    C2 = T.fq2_add(Cc, Cc)
    C4 = T.fq2_add(C2, C2)
    C8 = T.fq2_add(C4, C4)
    # round 3: E*(D-X3), Z3*Z2, E*X, E*Z2
    r3 = T._fq2u(T.fq2_mul(T._fq2s([E, Z3, E, E]),
                           T._fq2s([T.fq2_sub(D, X3), Z2, X, Z2])))
    EDX, Z3Z2, EX, EZ2 = r3
    Y3 = T.fq2_sub(EDX, C8)
    X3, Y3, Z3 = T._fq2u(T.fq2_compress(T._fq2s([X3, Y3, Z3])))
    # scale by the G1 coordinates (two fq2-by-fp muls in one width-4 call)
    xiz = T.fq2_mul_by_xi(Z3Z2)
    sc = fp.mont_mul(
        jnp.stack([xiz[0], xiz[1], EZ2[0], EZ2[1]], axis=-2),
        jnp.stack([py, py, px_neg, px_neg], axis=-2))
    c0 = (sc[..., 0, :], sc[..., 1, :])
    c1 = T.fq2_sub(EX, T.fq2_add(B, B))
    c2 = (sc[..., 2, :], sc[..., 3, :])
    return (X3, Y3, Z3), (c0, c1, c2)


def _add_step(t, q, px_neg, py):
    """Mixed-add affine Q into T; chord line at P as sparse coeffs.
    T coords and affine Q must be one unit."""
    X, Y, Z = t
    xq, yq = q
    Z2 = T.fq2_sqr(Z)
    r1 = T._fq2u(T.fq2_mul(T._fq2s([xq, Z2]), T._fq2s([Z2, Z])))
    U2, Z3cu = r1
    S2 = T.fq2_mul(yq, Z3cu)
    H, r = T._fq2u(T.fq2_compress(T._fq2s(
        [T.fq2_sub(U2, X), T.fq2_sub(S2, Y)])))
    r2 = T._fq2u(T.fq2_mul(T._fq2s([H, r, Z]), T._fq2s([H, r, H])))
    H2, R2, Z3 = r2
    r3 = T._fq2u(T.fq2_mul(T._fq2s([H, X, r, yq]),
                           T._fq2s([H2, H2, xq, Z3])))
    H3, V, RXQ, YQZ3 = r3
    X3 = T.fq2_sub(T.fq2_sub(R2, H3), T.fq2_add(V, V))
    r4 = T._fq2u(T.fq2_mul(T._fq2s([r, Y]),
                           T._fq2s([T.fq2_sub(V, X3), H3])))
    Y3 = T.fq2_sub(r4[0], r4[1])
    X3, Y3, Z3 = T._fq2u(T.fq2_compress(T._fq2s([X3, Y3, Z3])))
    xiz3 = T.fq2_mul_by_xi(Z3)
    sc = fp.mont_mul(
        jnp.stack([xiz3[0], xiz3[1], r[0], r[1]], axis=-2),
        jnp.stack([py, py, px_neg, px_neg], axis=-2))
    c0 = (sc[..., 0, :], sc[..., 1, :])
    c1 = T.fq2_sub(RXQ, YQZ3)
    c2 = (sc[..., 2, :], sc[..., 3, :])
    return (X3, Y3, Z3), (c0, c1, c2)


def _mul_by_line(f, line):
    """f * (c0 + (c1 v + c2 v^2) w): all 18 fq2 multiplies of the two
    sparse v-products and two by-fq2 products in ONE wide call."""
    c0, c1, c2 = line
    f0, f1 = f
    A = T._fq2s([f1[1], f1[2], f1[0], f1[2], f1[0], f1[1],
                 f0[1], f0[2], f0[0], f0[2], f0[0], f0[1],
                 f0[0], f0[1], f0[2], f1[0], f1[1], f1[2]])
    B = T._fq2s([c2, c1, c1, c2, c2, c1,
                 c2, c1, c1, c2, c2, c1,
                 c0, c0, c0, c0, c0, c0])
    p = T._fq2u(T.fq2_mul(A, B))

    def sparse_combine(m):
        # (a0 + a1 v + a2 v^2)(c1 v + c2 v^2) from products
        # m = [a1c2, a2c1, a0c1, a2c2, a0c2, a1c1]
        return (T.fq2_mul_by_xi(T.fq2_add(m[0], m[1])),
                T.fq2_add(m[2], T.fq2_mul_by_xi(m[3])),
                T.fq2_add(m[4], m[5]))

    t1 = sparse_combine(p[0:6])
    s0 = sparse_combine(p[6:12])
    f0c0 = (p[12], p[13], p[14])
    f1c0 = (p[15], p[16], p[17])
    res0 = T.fq6_add(f0c0, (T.fq2_mul_by_xi(t1[2]), t1[0], t1[1]))
    res1 = T.fq6_add(s0, f1c0)
    return T.fq12_compress((res0, res1))


# --------------------------------------------------------------------------
# Miller loop
# --------------------------------------------------------------------------

def miller_loop(p, q, mask=None):
    """Batched Miller loop.

    p: affine G1 (x, y) Montgomery limb arrays; q: affine G2 ((x,y) Fq2).
    mask: optional bool batch array — lanes where False produce ONE (the
    contribution of an infinity input, matching the oracle's convention).
    Returns the un-exponentiated Fq12 Miller value, conjugated for the
    negative BLS parameter.
    """
    px, py = p
    px_neg = fp.neg(px)
    t = (q[0], q[1], T._bcast2(T.FQ2_ONE_NP, q[0]))
    f = T.fq12_ones(px.shape[:-1])

    def add_iter(state):
        f, t = state
        t, line = _add_step(t, q, px_neg, py)
        return _mul_by_line(f, line), t

    def iteration(state, bit):
        f, t = state
        f = T.fq12_sqr(f)
        t, line = _dbl_step(t, px_neg, py)
        f = _mul_by_line(f, line)
        return lax.cond(bit, add_iter, lambda st: st, (f, t)), None

    (f, t), _ = lax.scan(iteration, (f, t), jnp.asarray(_X_BITS))

    f = T.fq12_conj(f)   # negative BLS parameter
    if mask is not None:
        f = T.fq12_select(mask, f, T.fq12_ones(px.shape[:-1]))
    return f


def batch_product(f):
    """Product of Fq12 values over the leading batch axis (axis 0) via
    log2-depth pairwise reduction."""
    return T.tree_fold_pairs(T.fq12_mul, f)


# --------------------------------------------------------------------------
# Final exponentiation
# --------------------------------------------------------------------------

def _cyclo_pow_abs_x(f):
    """f^|z| for cyclotomic f: one scan of Granger-Scott squarings over
    the bits of |z|, multiplying by f (lax.cond) at the one-bits."""
    def iteration(r, bit):
        r = T.fq12_cyclo_sqr(r)
        return lax.cond(bit, lambda x: T.fq12_mul(x, f), lambda x: x,
                        r), None

    result, _ = lax.scan(iteration, f, jnp.asarray(_X_BITS))
    return result


def _pow_z(f):
    """f^z for cyclotomic f (z < 0: conjugate == inverse there)."""
    return T.fq12_conj(_cyclo_pow_abs_x(f))


# The HHT hard part is five steps of one shape, cur <- cur^z * other:
#   a = g^z * conj(g);  a = a^z * conj(a);  b = a^z * frob(a);
#   t = b^z * 1;        c = t^z * frob^2(b)
# so it runs as ONE scan over this schedule (the graph holds a single
# x-power chain).  Columns: Frobenius power of `other`, conjugate it,
# take it from the saved b instead of cur, it is one, save result as b.
_HARD_STEPS = np.array([[0, 1, 0, 0, 0],
                        [0, 1, 0, 0, 0],
                        [1, 0, 0, 0, 1],
                        [0, 0, 0, 1, 0],
                        [2, 0, 1, 0, 0]], dtype=np.int32)


def _where12(flag, a, b):
    return jax.tree_util.tree_map(lambda x, y: jnp.where(flag, x, y),
                                  a, b)


def final_exponentiation(f):
    """f^(3*(p^12-1)/r): easy part, then the HHT x-chain hard part
    (identical chain to the oracle: crypto/bls/pairing.py:247-259)."""
    g = T.fq12_mul(T.fq12_conj(f), T.fq12_inv(f))
    g = T.fq12_mul(T.fq12_frobenius(g, 2), g)
    one = T.fq12_ones(g[0][0][0].shape[:-1])

    def step(carry, sched):
        cur, b = carry
        n_frob, conj, from_b, is_one, save_b = sched
        other = lax.fori_loop(0, n_frob,
                              lambda _, x: T.fq12_frobenius(x, 1),
                              _where12(from_b != 0, b, cur))
        other = _where12(conj != 0, T.fq12_conj(other), other)
        other = _where12(is_one != 0, one, other)
        cur = T.fq12_mul(_pow_z(cur), other)
        return (cur, _where12(save_b != 0, cur, b)), None

    (c, b), _ = lax.scan(step, (g, g), jnp.asarray(_HARD_STEPS))
    c = T.fq12_mul(c, T.fq12_conj(b))                    # b^(z^2+p^2-1)
    return T.fq12_mul(c, T.fq12_mul(T.fq12_sqr(g), g))   # * g^3


def pairing_check(f):
    """final_exponentiation(f) == 1 (per-lane or scalar)."""
    return T.fq12_is_one(final_exponentiation(f))
