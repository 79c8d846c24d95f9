"""Hashing to G2: the suite BLS12381G2_XMD:SHA-256_SSWU_RO_ of RFC 9380.

Written from the RFC: expand_message_xmd (5.3.1), hash_to_field (5.2),
the simplified SWU map for AB = 0 (6.6.3: map to the isogenous curve
E2': y^2 = x^3 + 240i x + 1012(1 + i) with Z = -(2 + i), then the
3-isogeny of appendix E.3), and the cofactor clearing of appendix G.3
by the endomorphism psi.  `tests/test_reference.py` pins it to the
vectors of appendix J.10.1 and K.1.
"""

import hashlib

from .ec import add2j, affine2, dbl2, mul2j, neg2j
from .fp import (ONE2, P, X, XI, ZERO2, add2, conj2, inv2, mul2, neg2,
                 pow2, sqr2, sqrt2)

# the eth2 ciphersuite's tag (proof-of-possession scheme)
DST_POP = b"BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_"


def expand_message_xmd(msg: bytes, dst: bytes, n: int) -> bytes:
    if len(dst) > 255:
        raise ValueError("tag longer than 255 bytes")
    ell = -(-n // 32)
    if ell > 255 or n > 65535:
        raise ValueError("too many bytes asked for")
    tag = dst + bytes([len(dst)])
    b0 = hashlib.sha256(bytes(64) + msg + n.to_bytes(2, "big") + b"\x00"
                        + tag).digest()
    blocks = [hashlib.sha256(b0 + b"\x01" + tag).digest()]
    for i in range(2, ell + 1):
        mixed = bytes(a ^ b for a, b in zip(b0, blocks[-1]))
        blocks.append(hashlib.sha256(mixed + bytes([i]) + tag).digest())
    return b"".join(blocks)[:n]


def hash_to_field(msg: bytes, dst: bytes, count: int = 2):
    """`count` elements of Fp2, each coordinate from 64 uniform bytes."""
    width = 64
    stream = expand_message_xmd(msg, dst, count * 2 * width)
    out = []
    for i in range(count):
        at = 2 * width * i
        out.append(tuple(
            int.from_bytes(stream[at + j * width:at + (j + 1) * width],
                           "big") % P for j in (0, 1)))
    return out


# ---- simplified SWU onto E2' ---------------------------------------------

A_ISO = (0, 240)
B_ISO = (1012, 1012)
Z_SWU = (P - 2, P - 1)


def sgn0(a) -> int:
    """The sign of an Fp2 element (RFC 9380 4.1, m = 2)."""
    return a[0] & 1 if a[0] else a[1] & 1


def _g_iso(x):
    return add2(add2(mul2(sqr2(x), x), mul2(A_ISO, x)), B_ISO)


def map_to_curve_sswu(u):
    """A point (x, y) of E2' for the field element u."""
    zu2 = mul2(Z_SWU, sqr2(u))
    tv1 = add2(sqr2(zu2), zu2)
    minus_b_over_a = neg2(mul2(B_ISO, inv2(A_ISO)))
    if tv1 == ZERO2:
        x1 = mul2(B_ISO, inv2(mul2(Z_SWU, A_ISO)))
    else:
        x1 = mul2(minus_b_over_a, add2(ONE2, inv2(tv1)))
    y = sqrt2(_g_iso(x1))
    x = x1
    if y is None:
        x = mul2(zu2, x1)
        y = sqrt2(_g_iso(x))
    if sgn0(u) != sgn0(y):
        y = neg2(y)
    return x, y


# ---- the 3-isogeny E2' -> E2 (RFC 9380 appendix E.3) -----------------------
# coefficients by rising power of x'; the denominators are monic

_K = 0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97D6
X_NUM = (
    (_K, _K),
    (0, 0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71A),
    (0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71E,
     0x8AB05F8BDD54CDE190937E76BC3E447CC27C3D6FBD7063FCD104635A790520C0A395554E5C6AAAA9354FFFFFFFFE38D),
    (0x171D6541FA38CCFAED6DEA691F5FB614CB14B4E7F4E810AA22D6108F142B85757098E38D0F671C7188E2AAAAAAAA5ED1, 0),
)
X_DEN = ((0, P - 72), (12, P - 12), ONE2)
_L = 0x1530477C7AB4113B59A4C18B076D11930F7DA5D4A07F649BF54439D87D27E500FC8C25EBF8C92F6812CFC71C71C6D706
Y_NUM = (
    (_L, _L),
    (0, 0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97BE),
    (0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71C,
     0x8AB05F8BDD54CDE190937E76BC3E447CC27C3D6FBD7063FCD104635A790520C0A395554E5C6AAAA9354FFFFFFFFE38F),
    (0x124C9AD43B6CF79BFBF7043DE3811AD0761B0F37A1E26286B0E977C69AA274524E79097A56DC4BD9E1B371C71C718B10, 0),
)
Y_DEN = ((P - 432, P - 432), (0, P - 216), (18, P - 18), ONE2)


def _poly(coefficients, x):
    out = ZERO2
    for c in reversed(coefficients):
        out = add2(mul2(out, x), c)
    return out


def iso_map(x, y):
    """The image on E2 of the point (x, y) of E2'."""
    return (mul2(_poly(X_NUM, x), inv2(_poly(X_DEN, x))),
            mul2(y, mul2(_poly(Y_NUM, x), inv2(_poly(Y_DEN, x)))))


# ---- clearing the cofactor (RFC 9380 appendix G.3) -------------------------

_PSI_X = inv2(pow2(XI, (P - 1) // 3))
_PSI_Y = inv2(pow2(XI, (P - 1) // 2))


def psi(p):
    """The untwist-Frobenius-twist endomorphism of E2."""
    x, y, z = p
    return (mul2(_PSI_X, conj2(x)), mul2(_PSI_Y, conj2(y)), conj2(z))


def clear_cofactor(p):
    """h_eff times p, by the endomorphism (Budroni-Pintore)."""
    def by_x(q):        # the parameter x is negative
        return neg2j(mul2j(-X, q))
    t1 = by_x(p)
    t2 = psi(p)
    t3 = add2j(psi(psi(dbl2(p))), neg2j(t2))
    t2 = by_x(add2j(t1, t2))
    t3 = add2j(add2j(t3, t2), neg2j(t1))
    return add2j(t3, neg2j(p))


def hash_to_g2(msg: bytes, dst: bytes = DST_POP):
    """The point of G2 for `msg` (Jacobian, Z = 1)."""
    u0, u1 = hash_to_field(msg, dst, 2)
    q0 = iso_map(*map_to_curve_sswu(u0))
    q1 = iso_map(*map_to_curve_sswu(u1))
    total = add2j((q0[0], q0[1], ONE2), (q1[0], q1[1], ONE2))
    x, y = affine2(clear_cofactor(total))
    return (x, y, ONE2)

