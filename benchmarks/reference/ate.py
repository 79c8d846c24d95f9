"""The pairing of BLS12-381, by its definition.

The optimal ate pairing e(P, Q) = f_{|x|,Q}(P)^((p^12 - 1)/r) for P in
G1 and Q in G2 (draft-irtf-cfrg-pairing-friendly-curves, appendix A).
Nothing is optimised: Q is carried from the twist E2 into E(Fp12) by
(x, y) -> (x/w^2, y/w^3), Miller's loop runs there in affine
coordinates with the chord-and-tangent lines evaluated at P, and the
final exponentiation is one power by (p^12 - 1)/r, taken in two steps.
The sign of x only conjugates the result.
"""

from .ec import affine1, affine2
from .fp import (ONE12, P, R, X, XI, ZERO2, ZERO6, add12,
                 conj12, inv2, inv12, mul2, mul12, pow12, sub12)

_XI_INV = inv2(XI)


def _fp12(a: int):
    return (((a % P, 0), ZERO2, ZERO2), ZERO6)


def untwist(q):
    """The affine point (x, y) of E2 as a point of E over Fp12: with
    w^2 = v and v^3 = 1 + i, 1/w^2 = v^2/(1 + i), 1/w^3 = v w/(1 + i)."""
    x, y = q
    return (((ZERO2, ZERO2, mul2(x, _XI_INV)), ZERO6),
            (ZERO6, (ZERO2, mul2(y, _XI_INV), ZERO2)))


def _line(t, q, at):
    """The line through t and q (the tangent where they are equal)
    evaluated at the point `at`, and t + q; all affine over Fp12."""
    (x1, y1), (x2, y2), (xp, yp) = t, q, at
    if x1 != x2:
        slope = mul12(sub12(y2, y1), inv12(sub12(x2, x1)))
    elif y1 == y2:
        three_x2 = mul12(mul12(x1, x1), _fp12(3))
        slope = mul12(three_x2, inv12(add12(y1, y1)))
    else:
        # a vertical line: t + q is the point at infinity
        return sub12(xp, x1), None
    value = sub12(sub12(yp, y1), mul12(slope, sub12(xp, x1)))
    x3 = sub12(sub12(mul12(slope, slope), x1), x2)
    y3 = sub12(mul12(slope, sub12(x1, x3)), y1)
    return value, (x3, y3)


def miller_loop(p, q):
    """f_{|x|,Q}(P), conjugated because x is negative.  p: a Jacobian
    point of G1, q: one of G2; neither at infinity."""
    xp, yp = affine1(p)
    at = (_fp12(xp), _fp12(yp))
    base = untwist(affine2(q))
    f, t = ONE12, base
    for bit in bin(-X)[3:]:
        value, t = _line(t, t, at)
        f = mul12(mul12(f, f), value)
        if bit == "1":
            value, t = _line(t, base, at)
            f = mul12(f, value)
    return conj12(f)


def final_exponentiation(f):
    """f to the power (p^12 - 1)/r = (p^6 - 1) (p^6 + 1)/r."""
    f = mul12(conj12(f), inv12(f))
    return pow12(f, (P ** 6 + 1) // R)


def pairing(p, q):
    return final_exponentiation(miller_loop(p, q))


def product_is_one(pairs) -> bool:
    """Whether the product of e(P_i, Q_i) is 1."""
    f = ONE12
    for p, q in pairs:
        f = mul12(f, miller_loop(p, q))
    return final_exponentiation(f) == ONE12
