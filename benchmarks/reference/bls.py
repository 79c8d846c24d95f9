"""Keys, signatures and verification of the eth2 ciphersuite
BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_ (draft-irtf-cfrg-bls-
signature, minimal-pubkey-size: keys in G1, signatures in G2), as the
consensus specification uses it."""

from typing import Sequence

from . import ate, ec
from .fp import R, ZERO2
from .h2c import hash_to_g2


def public_key(secret: int) -> bytes:
    return ec.compress1(ec.mul1(secret % R, ec.G1))


def sign_point(secret: int, hm) -> bytes:
    """The signature of the message whose hash to G2 is `hm`."""
    return ec.compress2(ec.mul2j(secret % R, hm))


def sign(secret: int, message: bytes) -> bytes:
    return sign_point(secret, hash_to_g2(message))


def fast_aggregate_verify(public_keys: Sequence[bytes], message: bytes,
                          signature: bytes) -> bool:
    """eth2 FastAggregateVerify: e(sum pk_i, H(m)) == e(g1, sig), each
    key valid (in G1 and not the identity) and the signature in G2;
    malformed input is False."""
    if not public_keys:
        return False
    try:
        sig = ec.decompress2(signature)
        keys = [ec.decompress1(pk) for pk in public_keys]
    except ValueError:
        return False
    if not ec.in_g2(sig):
        return False
    total = ec.INF1
    for key in keys:
        if key[2] == 0 or not ec.in_g1(key):
            return False
        total = ec.add1(total, key)
    if total[2] == 0:
        return False
    if sig[2] == ZERO2:
        return False
    return ate.product_is_one([(total, hash_to_g2(message)),
                               (ec.neg1(ec.G1), sig)])


def verify(public_key_bytes: bytes, message: bytes, signature: bytes
           ) -> bool:
    return fast_aggregate_verify([public_key_bytes], message, signature)
