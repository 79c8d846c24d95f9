"""fp381 fixed-width limb arithmetic for TPU (JAX).

The base field Fq of BLS12-381 (381-bit prime P) as 15 limbs of 26 bits
in int64 lanes, Montgomery form (a*R mod P, R = 2^390).  This replaces
the native blst limb arithmetic the reference client calls through JNI
(reference: infrastructure/bls/src/main/java/tech/pegasys/teku/bls/impl/
blst/BlstBLS12381.java — there delegated to C/asm).

LAZY-REDUCTION DESIGN.  Serial carry chains are the enemy of both XLA
compile time and TPU runtime, so they are paid only where mathematically
required:

- `add`/`sub`/`neg`/`double`/`mul_small` are PURE ELEMENTWISE lane ops —
  no carry propagation, no mod-P reduction.  Limbs are signed and are
  allowed to grow; int64 headroom absorbs it.
- `compress` folds a value back to one "unit" (low limbs canonical in
  [0, 2^W), small signed top limb) with a single carry scan.
- `mont_mul`/`mont_sqr` accept bounded lazy operands and emit one
  compressed unit with value in (-P, 2P): one reduction scan plus one
  compress scan, and NO conditional subtraction.
- Exact mod-P representatives exist only where semantics demand them
  (`canonical`, used by eq / is-zero / wire-format comparisons): a
  Montgomery multiply maps any lazy value x to x*R mod P in [0, P),
  which is a bijection on residue classes, so comparing canonical
  images decides equality.

Operand-magnitude contract: a compressed unit has low limbs < 2^W and
|top limb| < 2^22.  Callers may feed mont_mul sums/differences of units
as long as units(a) * units(b) <= 64 — the product-column bound
15 * (ua*2^W)(ub*2^W) then stays under 2^62.  Call sites that approach
the bound carry a comment.  Everything broadcasts over leading batch
dims; batching is plain array broadcasting.

TWO mont_mul engines live behind one contract: the VPU pad-and-sum
path below, and the MXU int8 digit-split matmul path (ops/mxu.py) —
`mont_mul`/`mont_sqr` dispatch at trace time on the process-global
path config (`--mont-path` / TEKU_TPU_MONT_MUL; auto = vpu).  Both emit one compressed unit in (-P, 2P)
through the SAME `_mont_reduce` scan, so outputs are bit-identical.

Layer validation: tests/test_ops_limbs.py checks every op against the
pure-Python oracle (teku_tpu/crypto/bls/fields.py), on both paths.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..crypto.bls.constants import P
from . import mxu as _mxu

# --------------------------------------------------------------------------
# Representation constants
# --------------------------------------------------------------------------

W = 26                    # bits per limb
L = 15                    # limb count (15*26 = 390 >= 381)
MASK = (1 << W) - 1
RADIX = 1 << W

R_MOD_P = (1 << (W * L)) % P          # Montgomery R mod P
R2_MOD_P = (R_MOD_P * R_MOD_P) % P    # R^2 mod P (to_mont multiplier)
N0INV = (-pow(P, -1, RADIX)) % RADIX  # -P^-1 mod 2^W


def int_to_limbs(x: int) -> np.ndarray:
    """Host-side: python int -> canonical limb vector (NOT Montgomery form)."""
    if not 0 <= x < (1 << (W * L)):
        raise ValueError("value out of limb range")
    return np.array([(x >> (W * i)) & MASK for i in range(L)], dtype=np.int64)


def limbs_to_int(a) -> int:
    """Host-side: (possibly lazy, signed) limb vector -> python int mod P."""
    a = np.asarray(a)
    return sum(int(a[..., i]) << (W * i) for i in range(L)) % P


P_LIMBS = int_to_limbs(P)
ZERO = np.zeros(L, dtype=np.int64)
ONE_MONT = int_to_limbs(R_MOD_P)          # 1 in Montgomery form
ONE_PLAIN = int_to_limbs(1)
R2_LIMBS = int_to_limbs(R2_MOD_P)


def int_to_mont(x: int) -> np.ndarray:
    """Host-side: python int mod P -> Montgomery-form limb vector."""
    return int_to_limbs((x % P) * R_MOD_P % P)


def mont_to_int(a) -> int:
    """Host-side: Montgomery-form limbs -> python int mod P."""
    return limbs_to_int(a) * pow(R_MOD_P, -1, P) % P


# --------------------------------------------------------------------------
# Lazy elementwise ops (no carries, no reduction)
# --------------------------------------------------------------------------

def add(a, b):
    return a + b


def sub(a, b):
    return a - b


def neg(a):
    return -a


def double(a):
    return a + a


def mul_small(a, k: int):
    """Multiply by a small static int (grows units by |k|)."""
    return a * k


def select(cond, a, b):
    """Lane select: cond True -> a, else b.  cond shape = batch shape."""
    return jnp.where(cond[..., None], a, b)


# --------------------------------------------------------------------------
# Carry machinery
# --------------------------------------------------------------------------

def compress(r):
    """One signed carry scan; folds the final carry into the top limb.

    Input: any lazy value with |limbs| < 2^62 and |value| < 2^(W*L+20).
    Output: value-preserving unit — limbs 0..L-2 in [0, 2^W), top limb
    signed with |top| ~ value / 2^(W*(L-1)).
    """
    def step(c, col):
        v = col + c
        return v >> W, v & MASK
    c0 = jnp.zeros(r.shape[:-1], dtype=jnp.int64)
    c, limbs = lax.scan(step, c0, jnp.moveaxis(r, -1, 0))
    limbs = jnp.moveaxis(limbs, 0, -1)
    return limbs.at[..., L - 1].add(c * RADIX)


def _sub_with_borrow(a, b):
    """(a - b) limbwise with sequential borrow; canonical inputs.
    Returns (diff, borrow): borrow 0 if a >= b else -1."""
    a, b = jnp.broadcast_arrays(a, b)
    def step(c, cols):
        v = cols[0] - cols[1] + c
        return v >> W, v & MASK
    c0 = jnp.zeros(a.shape[:-1], dtype=jnp.int64)
    c, limbs = lax.scan(step, c0,
                        (jnp.moveaxis(a, -1, 0), jnp.moveaxis(b, -1, 0)))
    return jnp.moveaxis(limbs, 0, -1), c


def _cond_sub_p(a):
    """Canonical-limbed a in [0, 2P) -> a mod P."""
    p = jnp.asarray(P_LIMBS)
    d, borrow = _sub_with_borrow(a, p)
    return jnp.where((borrow != 0)[..., None], a, d)


def gt(a, b):
    """a > b as integers; both inputs must be truly canonical."""
    _, borrow = _sub_with_borrow(b, a)
    return borrow != 0


# --------------------------------------------------------------------------
# Montgomery multiplication
# --------------------------------------------------------------------------

def _pad_last(x, lo, hi):
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(lo, hi)])


def _mont_reduce(t):
    """Word-serial Montgomery reduction of 2L product columns (one scan),
    then compress.  Signed columns are fine: `& MASK` and arithmetic
    shifts compute the correct residues/floors.  Output value in (-P, 2P).
    """
    p_pad = _pad_last(jnp.asarray(P_LIMBS), 0, L)

    def red(t, _):
        m = ((t[..., 0] & MASK) * N0INV) & MASK
        t = t + m[..., None] * p_pad
        c = t[..., 0] >> W
        head = t[..., 1:2] + c[..., None]
        t = jnp.concatenate(
            [head, t[..., 2:], jnp.zeros_like(t[..., :1])], axis=-1)
        return t, None

    t, _ = lax.scan(red, t, None, length=L)
    return compress(t[..., :L])


def _product_columns(a, b):
    """The 2L schoolbook product columns t[k] = sum_{i+j=k} a[i]*b[j].

    One outer product, skewed so row i sits i columns to the right (the
    zero-padded rows are flattened and re-cut one column narrower, which
    shifts each row by its index), then one reduction over the rows: a
    handful of ops per call site where pad-and-sum over L rows cost the
    TPU compiler most of a mont_mul's compile time.  Exact int64 sums,
    so the columns are bit-identical to any other summation order."""
    a, b = jnp.broadcast_arrays(a, b)
    outer = a[..., :, None] * b[..., None, :]             # (..., L, L)
    rows = _pad_last(outer, 0, L)                         # (..., L, 2L)
    flat = rows.reshape(rows.shape[:-2] + (2 * L * L,))
    skew = flat[..., :L * (2 * L - 1)].reshape(
        flat.shape[:-1] + (L, 2 * L - 1))
    return _pad_last(skew.sum(axis=-2), 0, 1)


def mont_mul_vpu(a, b):
    """Montgomery product a*b*R^-1 (one unit out, value in (-P, 2P)):
    schoolbook product columns, no scatters, no carries."""
    return _mont_reduce(_product_columns(a, b))


def mont_sqr_vpu(a):
    """Montgomery squaring (the product columns of a with itself)."""
    return _mont_reduce(_product_columns(a, a))


# MXU path: same operand contract, same _mont_reduce, product columns
# built as batched int8 digit-split dot_general (ops/mxu.py)
mont_mul_mxu, mont_sqr_mxu = _mxu.make_digit_kernels(
    L, W, P.bit_length(), compress, _mont_reduce)


def mont_mul(a, b):
    """Montgomery product via the configured engine (vpu | mxu).

    The path is resolved at TRACE time from the process-global config;
    a jitted program keeps the path it was traced with."""
    if _mxu.active():
        return mont_mul_mxu(a, b)
    return mont_mul_vpu(a, b)


def mont_sqr(a):
    """Montgomery squaring via the configured engine (vpu | mxu)."""
    if _mxu.active():
        return mont_sqr_mxu(a)
    return mont_sqr_vpu(a)


def to_mont(a):
    """Plain limbs -> Montgomery form (one unit)."""
    return mont_mul(a, jnp.asarray(R2_LIMBS))


# --------------------------------------------------------------------------
# Canonical representatives (equality / wire formats)
# --------------------------------------------------------------------------

def canonical(a):
    """Map any bounded lazy value to THE canonical limbs of (a*R) mod P.

    a*R mod P is a bijection on residue classes, so canonical images
    decide equality and zero-ness; callers comparing against constants
    must pass them through the same map.
    """
    y = mont_mul(a, jnp.asarray(R2_LIMBS))   # value in (-P, 2P)
    y = compress(y + jnp.asarray(P_LIMBS))   # (0, 3P), canonical limbs
    return _cond_sub_p(_cond_sub_p(y))


def canonical_plain(a):
    """Exact canonical plain-form (non-Montgomery) limbs of a Montgomery
    unit — for wire-format comparisons (sign bit, x < P checks)."""
    one = jnp.zeros_like(a).at[..., 0].set(1)
    y = mont_mul(a, one)                     # value = plain, in (-P, 2P)
    y = compress(y + jnp.asarray(P_LIMBS))
    return _cond_sub_p(_cond_sub_p(y))


def is_zero(a):
    """a ≡ 0 mod P, for any bounded lazy value."""
    return jnp.all(canonical(a) == 0, axis=-1)


def eq(a, b):
    """a ≡ b mod P, for bounded lazy values."""
    return is_zero(a - b)


def from_mont(a):
    """Montgomery unit -> canonical plain limbs."""
    return canonical_plain(a)


# --------------------------------------------------------------------------
# Exponentiation with a static exponent (scan over constant bit vector)
# --------------------------------------------------------------------------

POW_WINDOW = 4


def pow_static(a, e: int, window: int = POW_WINDOW):
    """a^e mod P for a static python-int exponent; a a Montgomery unit.

    Fixed-window exponentiation as a traced scan: the bit-serial form
    pays one sqr AND one (select-discarded but still computed) mul per
    bit — 2 mont ops/bit.  A 2^w table (built once: 2^w - 2 muls) and a
    scan over the exponent's static base-2^w digits pays w sqrs + ONE
    gathered mul per digit: for the 381-bit Fermat exponents that
    dominate the verify pipeline (inversion, sqrt, sqrt_ratio) this is
    ~489 mont ops instead of ~760.  The graph stays O(1) in exponent
    length (one scan body; digits are a scanned array).
    """
    if e == 0:
        return jnp.broadcast_to(jnp.asarray(ONE_MONT), a.shape)
    if e.bit_length() <= window:
        # tiny exponent: square-and-multiply unrolled is smaller than
        # any table
        acc = a
        for bit in bin(e)[3:]:
            acc = mont_sqr(acc)
            if bit == "1":
                acc = mont_mul(acc, a)
        return acc
    n_digits = (e.bit_length() + window - 1) // window
    digits = np.array(
        [(e >> (window * i)) & ((1 << window) - 1)
         for i in range(n_digits)][::-1], dtype=np.int64)
    # table[d] = a^d, d in [0, 2^w) — scan-built so the graph holds
    # one mont_mul body, not 2^w - 2 inlined copies
    one = jnp.broadcast_to(jnp.asarray(ONE_MONT), np.shape(a))

    def build(carry, _):
        return mont_mul(carry, a), carry
    _, table = lax.scan(build, one, None, length=1 << window)

    def body(acc, d):
        acc = lax.fori_loop(0, window, lambda _, x: mont_sqr(x), acc)
        acc = mont_mul(acc, jnp.take(table, d, axis=0))
        return acc, None

    # top digit is nonzero (bit_length > window): start from its row
    acc = jnp.take(table, jnp.asarray(digits[0]), axis=0)
    acc, _ = lax.scan(body, acc, jnp.asarray(digits[1:]))
    return acc


# Rows a Fermat scan runs on.  The chip runs the scan's ~489 sequential
# mont_muls 7-8x faster on a tile of rows than on one row (PERF.md
# §7.9), so a narrow inversion is padded to a tile.  Inside the programs
# (each alone, median of 7; PERF.md §6, PR 37) stage_finish at 17 rows
# read 59.23 / 25.05 / 24.37 / 24.36 ms and stage_group at 256 rows
# 42.02 / 7.59 / 6.49 / 6.41 ms with the scan at 1 / 16 / 64 / 128 rows.
_FERMAT_ROWS = 128


def inv(a):
    """Field inverse via Fermat (a^(P-2)); inv(0) ≡ 0 (callers select
    around it, branch-free).

    An operand of fewer than _FERMAT_ROWS elements runs the scan on a
    tile: its rows, then rows of ONE_MONT (inv(1) = 1), of which only
    the live ones are kept.  Each row of the scan is independent, so
    the live rows are bit-identical to the narrow scan's."""
    flat = a.reshape((-1, L))
    m = flat.shape[0]
    if m < _FERMAT_ROWS:
        ones = jnp.broadcast_to(jnp.asarray(ONE_MONT),
                                (_FERMAT_ROWS - m, L))
        flat = jnp.concatenate([flat, ones], axis=0)
    return pow_static(flat, P - 2)[:m].reshape(a.shape)


def inv_many(a):
    """Batched field inverse: ONE Fermat exponentiation for the whole
    batch via Montgomery's trick, parallelized with a prefix/suffix
    product scan.

    a: (..., L) Montgomery units, any batch shape (flattened internally).
    Cost: one a^(P-2) scan (on `inv`'s tile of rows) plus ~2*log2(M)
    mont_muls per element (one rolled log-depth scan + the recombine),
    versus one full 380-bit Fermat scan per element for `inv` — the
    dominant compile-time and runtime win of the verification kernel.

    inv_many(0) ≡ 0 per-lane (zero lanes are masked out of the product
    so they cannot poison the batch).
    """
    shape = a.shape
    flat = a.reshape((-1, L))
    m = flat.shape[0]
    if m == 1:
        out = inv(flat)
        return out.reshape(shape)
    zero = is_zero(flat)                                  # (M,)
    one = jnp.broadcast_to(jnp.asarray(ONE_MONT), flat.shape)
    safe = jnp.where(zero[:, None], one, flat)
    # prefix AND suffix products in ONE rolled Hillis-Steele scan: row
    # 0 runs forward, row 1 over the reversed batch; round k multiplies
    # each lane by the lane 2^k before it (ones shifted in).  One
    # mont_mul body in the graph instead of the ~4*log2(M) inlined
    # copies of two associative scans.
    both = jnp.stack([safe, safe[::-1]])                  # (2, M, L)
    ones = jnp.broadcast_to(jnp.asarray(ONE_MONT), both.shape)

    def sweep(k, x):
        shifted = lax.dynamic_slice_in_dim(
            jnp.concatenate([ones, x], axis=1), m - (1 << k), m, axis=1)
        return mont_mul(x, shifted)

    both = lax.fori_loop(0, (m - 1).bit_length(), sweep, both)
    pre, suf = both[0], both[1][::-1]
    tinv = inv(pre[-1:])                                  # ONE Fermat
    left = jnp.concatenate([one[:1], pre[:-1]], axis=0)   # prod before i
    right = jnp.concatenate([suf[1:], one[:1]], axis=0)   # prod after i
    out = mont_mul(mont_mul(left, right), tinv)
    out = jnp.where(zero[:, None], 0, out)
    return out.reshape(shape)


def sqrt_candidate(a):
    """a^((P+1)/4) — the square root when a is a QR (P = 3 mod 4).
    Caller must check candidate^2 == a."""
    return pow_static(a, (P + 1) // 4)
