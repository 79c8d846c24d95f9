"""The fields of BLS12-381, in Python integers.

Written from the curve's definition (draft-irtf-cfrg-pairing-friendly-
curves, section 4.2.1) and not from the program: the parameter x gives
the group order r = x^4 - x^2 + 1 and the prime p = (x - 1)^2 r / 3 + x;
Fp2 = Fp[i]/(i^2 + 1), Fp6 = Fp2[v]/(v^3 - (1 + i)),
Fp12 = Fp6[w]/(w^2 - v).  An Fp2 element is a pair (real, imaginary) of
integers below p, an Fp6 element three of those, an Fp12 element two of
those.
"""

X = -0xD201000000010000
R = X ** 4 - X ** 2 + 1
P = (X - 1) ** 2 * R // 3 + X

# ---- Fp2 ----------------------------------------------------------------

ZERO2 = (0, 0)
ONE2 = (1, 0)
XI = (1, 1)          # the non-residue 1 + i


def add2(a, b):
    return ((a[0] + b[0]) % P, (a[1] + b[1]) % P)


def sub2(a, b):
    return ((a[0] - b[0]) % P, (a[1] - b[1]) % P)


def neg2(a):
    return (-a[0] % P, -a[1] % P)


def mul2(a, b):
    a0, a1 = a
    b0, b1 = b
    return ((a0 * b0 - a1 * b1) % P, (a0 * b1 + a1 * b0) % P)


def sqr2(a):
    a0, a1 = a
    return ((a0 + a1) * (a0 - a1) % P, 2 * a0 * a1 % P)


def scale2(a, k):
    """a times an integer."""
    return (a[0] * k % P, a[1] * k % P)


def conj2(a):
    return (a[0], -a[1] % P)


def inv2(a):
    a0, a1 = a
    t = pow(a0 * a0 + a1 * a1, -1, P)
    return (a0 * t % P, -a1 * t % P)


def pow2(a, e):
    out = ONE2
    for bit in bin(e)[2:]:
        out = sqr2(out)
        if bit == "1":
            out = mul2(out, a)
    return out


def sqrt_fp(a):
    """A square root in Fp (p = 3 mod 4), or None."""
    s = pow(a, (P + 1) // 4, P)
    return s if s * s % P == a % P else None


def sqrt2(a):
    """A square root in Fp2, or None: from the norm, as for complex
    numbers."""
    a0, a1 = a
    if a1 == 0:
        s = sqrt_fp(a0)
        if s is not None:
            return (s, 0)
        s = sqrt_fp(-a0 % P)
        return None if s is None else (0, s)
    n = sqrt_fp((a0 * a0 + a1 * a1) % P)
    if n is None:
        return None
    half = pow(2, -1, P)
    for t in ((a0 + n) * half % P, (a0 - n) * half % P):
        s = sqrt_fp(t)
        if s:
            root = (s, a1 * pow(2 * s, -1, P) % P)
            if sqr2(root) == (a0 % P, a1 % P):
                return root
    return None


# ---- Fp6 = Fp2[v]/(v^3 - XI) ---------------------------------------------

ZERO6 = (ZERO2, ZERO2, ZERO2)
ONE6 = (ONE2, ZERO2, ZERO2)


def add6(a, b):
    return (add2(a[0], b[0]), add2(a[1], b[1]), add2(a[2], b[2]))


def sub6(a, b):
    return (sub2(a[0], b[0]), sub2(a[1], b[1]), sub2(a[2], b[2]))


def neg6(a):
    return (neg2(a[0]), neg2(a[1]), neg2(a[2]))


def mul6(a, b):
    a0, a1, a2 = a
    b0, b1, b2 = b
    # (a0 + a1 v + a2 v^2)(b0 + b1 v + b2 v^2) with v^3 = XI
    c0 = add2(mul2(a0, b0),
              mul2(XI, add2(mul2(a1, b2), mul2(a2, b1))))
    c1 = add2(add2(mul2(a0, b1), mul2(a1, b0)), mul2(XI, mul2(a2, b2)))
    c2 = add2(add2(mul2(a0, b2), mul2(a1, b1)), mul2(a2, b0))
    return (c0, c1, c2)


def mul6_v(a):
    """a times v."""
    return (mul2(XI, a[2]), a[0], a[1])


def inv6(a):
    a0, a1, a2 = a
    # the adjugate of multiplication by a, over its norm to Fp2
    t0 = sub2(sqr2(a0), mul2(XI, mul2(a1, a2)))
    t1 = sub2(mul2(XI, sqr2(a2)), mul2(a0, a1))
    t2 = sub2(sqr2(a1), mul2(a0, a2))
    norm = add2(mul2(a0, t0),
                mul2(XI, add2(mul2(a2, t1), mul2(a1, t2))))
    k = inv2(norm)
    return (mul2(t0, k), mul2(t1, k), mul2(t2, k))


# ---- Fp12 = Fp6[w]/(w^2 - v) ---------------------------------------------

ONE12 = (ONE6, ZERO6)


def add12(a, b):
    return (add6(a[0], b[0]), add6(a[1], b[1]))


def sub12(a, b):
    return (sub6(a[0], b[0]), sub6(a[1], b[1]))


def mul12(a, b):
    a0, a1 = a
    b0, b1 = b
    return (add6(mul6(a0, b0), mul6_v(mul6(a1, b1))),
            add6(mul6(a0, b1), mul6(a1, b0)))


def conj12(a):
    """a to the power p^6."""
    return (a[0], neg6(a[1]))


def inv12(a):
    a0, a1 = a
    k = inv6(sub6(mul6(a0, a0), mul6_v(mul6(a1, a1))))
    return (mul6(a0, k), neg6(mul6(a1, k)))


def pow12(a, e):
    out = ONE12
    for bit in bin(e)[2:]:
        out = mul12(out, out)
        if bit == "1":
            out = mul12(out, a)
    return out
