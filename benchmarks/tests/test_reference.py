"""The plain reference against published vectors, and its independence.

RFC 9380 appendix K.1 (expand_message_xmd, SHA-256) and J.10.1
(BLS12381G2_XMD:SHA-256_SSWU_RO_); the eth2 BLS vectors of
ethereum/bls12-381-tests (`sign`, `fast_aggregate_verify`: the three
well-known test keys); the generators' ZCash encodings.  A wrong tag,
`expand_message`, isogeny table, sign convention or byte order fails
here, whatever the program does.
"""

import os
import subprocess
import sys

import pytest

from benchmarks.reference import ate, bls, ec, fp, h2c

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def test_the_curve_parameters_are_the_published_ones():
    assert fp.P == 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
    assert fp.R == 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
    assert ec.in_g1(ec.G1) and ec.in_g2(ec.G2)
    assert ec.compress1(ec.G1).hex() == (
        "97f1d3a73197d7942695638c4fa9ac0fc3688c4f9774b905a14e3a3f171bac58"
        "6c55e83ff97a1aeffb3af00adb22c6bb")
    assert ec.compress2(ec.G2).hex() == (
        "93e02b6052719f607dacd3a088274f65596bd0d09920b61ab5da61bbdc7f5049"
        "334cf11213945d57e5ac7d055d042b7e024aa2b2f08f0a91260805272dc51051"
        "c6e47ad4fa403b02b4510b647ae3d1770bac0326a805bbefd48056c8c121bdb8")


@pytest.mark.parametrize("msg,want", [
    (b"", "68a985b87eb6b46952128911f2a4412bbc302a9d759667f87f7a21d803f07235"),
    (b"abc",
     "d8ccab23b5985ccea865c6c97b6e5b8350e794e603b4b97902f53a8a0d605615"),
])
def test_expand_message_xmd_rfc9380_k1(msg, want):
    tag = b"QUUX-V01-CS02-with-expander-SHA256-128"
    assert h2c.expand_message_xmd(msg, tag, 0x20).hex() == want


@pytest.mark.parametrize("msg,x0,x1,y0,y1", [
    (b"",
     0x0141EBFBDCA40EB85B87142E130AB689C673CF60F1A3E98D69335266F30D9B8D4AC44C1038E9DCDD5393FAF5C41FB78A,
     0x05CB8437535E20ECFFAEF7752BADDF98034139C38452458BAEEFAB379BA13DFF5BF5DD71B72418717047F5B0F37DA03D,
     0x0503921D7F6A12805E72940B963C0CF3471C7B2A524950CA195D11062EE75EC076DAF2D4BC358C4B190C0C98064FDD92,
     0x12424AC32561493F3FE3C260708A12B7C620E7BE00099A974E259DDC7D1F6395C3C811CDD19F1E8DBF3E9ECFDCBAB8D6),
    (b"abc",
     0x02C2D18E033B960562AAE3CAB37A27CE00D80CCD5BA4B7FE0E7A210245129DBEC7780CCC7954725F4168AFF2787776E6,
     0x139CDDBCCDC5E91B9623EFD38C49F81A6F83F175E80B06FC374DE9EB4B41DFE4CA3A230ED250FBE3A2ACF73A41177FD8,
     0x1787327B68159716A37440985269CF584BCB1E621D3A7202BE6EA05C4CFE244AEB197642555A0645FB87BF7466B2BA48,
     0x00AA65DAE3C8D732D10ECD2C50F8A1BAF3001578F71C694E03866E9F3D49AC1E1CE70DD94A733534F106D4CEC0EDDD16),
])
def test_hash_to_g2_rfc9380_j10_1(msg, x0, x1, y0, y1):
    tag = b"QUUX-V01-CS02-with-BLS12381G2_XMD:SHA-256_SSWU_RO_"
    x, y, _ = h2c.hash_to_g2(msg, tag)
    assert (x, y) == ((x0, x1), (y0, y1))


def test_the_hash_lands_in_g2_whatever_the_message():
    for i in range(3):
        assert ec.in_g2(h2c.hash_to_g2(bytes([i]) * 32))


def test_the_pairing_is_bilinear_and_not_degenerate():
    e = ate.pairing(ec.G1, ec.G2)
    assert e != fp.ONE12 and fp.pow12(e, fp.R) == fp.ONE12
    assert ate.pairing(ec.mul1(7, ec.G1), ec.mul2j(11, ec.G2)) \
        == fp.pow12(e, 77)


# ethereum/bls12-381-tests: the three test keys and what they sign
SECRETS = [
    0x263DBD792F5B1BE47ED85F8938C0F29586AF0D3AC7B977F21C278FE1462040E3,
    0x47B8192D77BF871B62E87859D653922725724A5C031AFEABC60BCEF5FF665138,
    0x328388AFF0D4A5B7DC9205ABD374E7E98F3CD9F3418EDB4EAFDA5FB16473D216,
]
PUBLIC = [bytes.fromhex(h) for h in (
    "a491d1b0ecd9bb917989f0e74f0dea0422eac4a873e5e2644f368dffb9a6e20f"
    "d6e10c1b77654d067c0618f6e5a7f79a",
    "b301803f8b5ac4a1133581fc676dfedc60d891dd5fa99028805e5ea5b08d3491"
    "af75d0707adab3b70c6a6a580217bf81",
    "b53d21a4cfd562c469cc81514d4ce5a6b577d8403d32a394dc265dd190b47fa9"
    "f829fdd7963afdf972e5e77854051f6f")]
SIGN_56 = bytes.fromhex(
    "882730e5d03f6b42c3abc26d3372625034e1d871b65a8a6b900a56dae22da98a"
    "bbe1b68f85e49fe7652a55ec3d0591c20767677e33e5cbb1207315c41a9ac03b"
    "e39c2e7668edc043d6cb1d9fd93033caa8a1c5b0e84bedaeb6c64972503a43eb")
AGGREGATE_AB = bytes.fromhex(
    "9712c3edd73a209c742b8250759db12549b3eaf43b5ca61376d9f30e2747dbcf"
    "842d8b2ac0901d2a093713e20284a7670fcf6954e9ab93de991bb9b313e66478"
    "5a075fc285806fa5224c82bde146561b446ccfc706a64b8579513cfc4ff1d930")


def test_eth2_sign_vector():
    assert [bls.public_key(sk) for sk in SECRETS] == PUBLIC
    assert bls.sign(SECRETS[0], b"\x56" * 32) == SIGN_56
    assert bls.verify(PUBLIC[0], b"\x56" * 32, SIGN_56)
    assert not bls.verify(PUBLIC[1], b"\x56" * 32, SIGN_56)
    assert not bls.verify(PUBLIC[0], b"\x57" * 32, SIGN_56)


def test_eth2_fast_aggregate_verify_vectors():
    msg = b"\xab" * 32
    assert bls.fast_aggregate_verify(PUBLIC, msg, AGGREGATE_AB)
    # fast_aggregate_verify_extra_pubkey, _tampered_signature,
    # _na_pubkeys_*, _infinity_pubkey
    extra = PUBLIC + [bls.public_key(1)]
    assert not bls.fast_aggregate_verify(extra, msg, AGGREGATE_AB)
    tampered = AGGREGATE_AB[:-4] + b"\xff\xff\xff\xff"
    assert not bls.fast_aggregate_verify(PUBLIC, msg, tampered)
    infinity_sig = b"\xc0" + bytes(95)
    assert not bls.fast_aggregate_verify([], msg, infinity_sig)
    assert not bls.fast_aggregate_verify([], msg, bytes(96))
    infinity_key = b"\xc0" + bytes(47)
    assert not bls.fast_aggregate_verify(PUBLIC + [infinity_key], msg,
                                         AGGREGATE_AB)


def test_a_point_outside_the_subgroup_is_refused():
    # a point of E2 that the cofactor has not been cleared from
    x, y = h2c.iso_map(*h2c.map_to_curve_sswu((5, 7)))
    outside = (x, y, fp.ONE2)
    assert ec.on_curve2(outside) and not ec.in_g2(outside)
    assert not bls.verify(PUBLIC[0], b"\x56" * 32, ec.compress2(outside))


def test_the_reference_shares_nothing_with_the_program():
    """It imports neither the program nor JAX, and none of its files is
    a copy of a file of the program's own oracle."""
    code = ("import sys; from benchmarks.reference import bls; "
            "bad = [m for m in sys.modules if m.split('.')[0] in "
            "('teku_tpu', 'jax', 'jaxlib', 'numpy')]; "
            "print(bad); sys.exit(1 if bad else 0)")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    oracle = os.path.join(ROOT, "teku_tpu", "crypto", "bls")
    theirs = set()
    for name in os.listdir(oracle):
        if name.endswith(".py"):
            with open(os.path.join(oracle, name), "rb") as fh:
                theirs.add(fh.read())
    ours = os.path.join(ROOT, "benchmarks", "reference")
    for name in os.listdir(ours):
        if name.endswith(".py") and name != "__init__.py":
            with open(os.path.join(ours, name), "rb") as fh:
                assert fh.read() not in theirs, name
