"""The groups G1 and G2 of BLS12-381 and their wire format.

E: y^2 = x^3 + 4 over Fp holds G1; E2: y^2 = x^3 + 4(1 + i) over Fp2
holds G2 (draft-irtf-cfrg-pairing-friendly-curves 4.2.1, generators
from there).  Points are Jacobian triples (X, Y, Z) standing for
(X/Z^2, Y/Z^3); Z = 0 is the point at infinity.  The addition and
doubling formulas are the textbook ones for a = 0 (Explicit-Formulas
Database, add-2007-bl and dbl-2009-l).  The wire format is ZCash's, as
the eth2 specification prescribes: big-endian x with three flag bits
(compressed, infinity, y is the larger root) on the first byte.
"""

from .fp import (ONE2, P, R, ZERO2, add2, inv2, mul2, neg2, scale2, sqr2,
                 sqrt2, sqrt_fp, sub2)

B1 = 4
B2 = (4, 4)

G1 = (0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB,
      0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1,
      1)
G2 = ((0x024AA2B2F08F0A91260805272DC51051C6E47AD4FA403B02B4510B647AE3D1770BAC0326A805BBEFD48056C8C121BDB8,
       0x13E02B6052719F607DACD3A088274F65596BD0D09920B61AB5DA61BBDC7F5049334CF11213945D57E5AC7D055D042B7E),
      (0x0CE5D527727D6E118CC9CDC6DA2E351AADFD9BAA8CBDD3A76D429A695160D12C923AC9CC3BACA289E193548608B82801,
       0x0606C4A02EA734CC32ACD2B02BC28B99CB3E287E85A763AF267492AB572E99AB3F370D275CEC1DA1AAA9075FF05F79BE),
      ONE2)

INF1 = (0, 1, 0)
INF2 = (ZERO2, ONE2, ZERO2)

# ---- G1: coordinates are integers ------------------------------------


def dbl1(p):
    x, y, z = p
    if z == 0:
        return p
    a = x * x % P
    b = y * y % P
    c = b * b % P
    d = 2 * ((x + b) ** 2 - a - c) % P
    e = 3 * a
    x3 = (e * e - 2 * d) % P
    return (x3, (e * (d - x3) - 8 * c) % P, 2 * y * z % P)


def add1(p, q):
    x1, y1, z1 = p
    x2, y2, z2 = q
    if z1 == 0:
        return q
    if z2 == 0:
        return p
    zz1 = z1 * z1 % P
    zz2 = z2 * z2 % P
    u1 = x1 * zz2 % P
    u2 = x2 * zz1 % P
    s1 = y1 * z2 * zz2 % P
    s2 = y2 * z1 * zz1 % P
    if u1 == u2:
        return dbl1(p) if s1 == s2 else INF1
    h = u2 - u1
    i = 4 * h * h % P
    j = h * i % P
    r = 2 * (s2 - s1)
    v = u1 * i % P
    x3 = (r * r - j - 2 * v) % P
    return (x3, (r * (v - x3) - 2 * s1 * j) % P,
            ((z1 + z2) ** 2 - zz1 - zz2) * h % P)


def neg1(p):
    return (p[0], -p[1] % P, p[2])


def affine1(p):
    """(x, y), or None for the point at infinity."""
    x, y, z = p
    if z == 0:
        return None
    k = pow(z, -1, P)
    return (x * k * k % P, y * k * k * k % P)


# ---- G2: coordinates are Fp2 pairs -----------------------------------


def dbl2(p):
    x, y, z = p
    if z == ZERO2:
        return p
    a = sqr2(x)
    b = sqr2(y)
    c = sqr2(b)
    d = scale2(sub2(sub2(sqr2(add2(x, b)), a), c), 2)
    e = scale2(a, 3)
    x3 = sub2(sqr2(e), scale2(d, 2))
    return (x3, sub2(mul2(e, sub2(d, x3)), scale2(c, 8)),
            scale2(mul2(y, z), 2))


def add2j(p, q):
    x1, y1, z1 = p
    x2, y2, z2 = q
    if z1 == ZERO2:
        return q
    if z2 == ZERO2:
        return p
    zz1 = sqr2(z1)
    zz2 = sqr2(z2)
    u1 = mul2(x1, zz2)
    u2 = mul2(x2, zz1)
    s1 = mul2(mul2(y1, z2), zz2)
    s2 = mul2(mul2(y2, z1), zz1)
    if u1 == u2:
        return dbl2(p) if s1 == s2 else INF2
    h = sub2(u2, u1)
    i = sqr2(scale2(h, 2))
    j = mul2(h, i)
    r = scale2(sub2(s2, s1), 2)
    v = mul2(u1, i)
    x3 = sub2(sub2(sqr2(r), j), scale2(v, 2))
    return (x3, sub2(mul2(r, sub2(v, x3)), scale2(mul2(s1, j), 2)),
            mul2(sub2(sub2(sqr2(add2(z1, z2)), zz1), zz2), h))


def neg2j(p):
    return (p[0], neg2(p[1]), p[2])


def affine2(p):
    x, y, z = p
    if z == ZERO2:
        return None
    k = inv2(z)
    kk = sqr2(k)
    return (mul2(x, kk), mul2(y, mul2(kk, k)))


# ---- scalar multiplication: fixed windows of four bits ----------------


def _mul(k, p, add, dbl, inf):
    if k < 0:
        raise ValueError("negative scalar")
    table = [inf, p]
    for _ in range(14):
        table.append(add(table[-1], p))
    out = inf
    for shift in range((max(k.bit_length(), 1) + 3) // 4 * 4 - 4, -1, -4):
        out = dbl(dbl(dbl(dbl(out))))
        nibble = (k >> shift) & 15
        if nibble:
            out = add(out, table[nibble])
    return out


def mul1(k, p):
    return _mul(k, p, add1, dbl1, INF1)


def mul2j(k, p):
    return _mul(k, p, add2j, dbl2, INF2)


def on_curve1(p):
    xy = affine1(p)
    return xy is None or (xy[1] ** 2 - xy[0] ** 3 - B1) % P == 0


def on_curve2(p):
    xy = affine2(p)
    if xy is None:
        return True
    x, y = xy
    return sqr2(y) == add2(mul2(sqr2(x), x), B2)


def in_g1(p):
    """On the curve and of order dividing r."""
    return on_curve1(p) and mul1(R, p)[2] == 0


def in_g2(p):
    return on_curve2(p) and mul2j(R, p)[2] == ZERO2


# ---- the wire format ---------------------------------------------------

_HALF = (P - 1) // 2
_COMPRESSED, _INFINITY, _LARGER = 0x80, 0x40, 0x20


def _larger2(y):
    """Whether y is the lexicographically larger of y and -y, the
    imaginary part deciding first."""
    return y[1] > _HALF if y[1] else y[0] > _HALF


def compress1(p) -> bytes:
    xy = affine1(p)
    if xy is None:
        return bytes([_COMPRESSED | _INFINITY]) + bytes(47)
    out = bytearray(xy[0].to_bytes(48, "big"))
    out[0] |= _COMPRESSED | (_LARGER if xy[1] > _HALF else 0)
    return bytes(out)


def compress2(p) -> bytes:
    xy = affine2(p)
    if xy is None:
        return bytes([_COMPRESSED | _INFINITY]) + bytes(95)
    x, y = xy
    out = bytearray(x[1].to_bytes(48, "big") + x[0].to_bytes(48, "big"))
    out[0] |= _COMPRESSED | (_LARGER if _larger2(y) else 0)
    return bytes(out)


def _flags(data: bytes, size: int):
    if len(data) != size:
        raise ValueError("wrong length")
    if not data[0] & _COMPRESSED:
        raise ValueError("not in compressed form")
    infinity = bool(data[0] & _INFINITY)
    larger = bool(data[0] & _LARGER)
    body = bytes([data[0] & 0x1F]) + data[1:]
    if infinity and (larger or any(body)):
        raise ValueError("malformed point at infinity")
    return infinity, larger, body


def decompress1(data: bytes):
    """The point on E that the 48 bytes name (its subgroup is not
    checked); ValueError where they name none."""
    infinity, larger, body = _flags(data, 48)
    if infinity:
        return INF1
    x = int.from_bytes(body, "big")
    if x >= P:
        raise ValueError("x is not reduced")
    y = sqrt_fp((x * x * x + B1) % P)
    if y is None:
        raise ValueError("x is not on the curve")
    if (y > _HALF) != larger:
        y = P - y
    return (x, y, 1)


def decompress2(data: bytes):
    infinity, larger, body = _flags(data, 96)
    if infinity:
        return INF2
    x = (int.from_bytes(body[48:], "big"), int.from_bytes(body[:48], "big"))
    if x[0] >= P or x[1] >= P:
        raise ValueError("x is not reduced")
    y = sqrt2(add2(mul2(sqr2(x), x), B2))
    if y is None:
        raise ValueError("x is not on the curve")
    if _larger2(y) != larger:
        y = neg2(y)
    return (x, y, ONE2)
