"""The one scalars stage, against the host oracle.

`stage_scalars` -> `stage_group` is what every dispatch runs between
`stage_prepare` and the Miller loops: a 64-bit multiplier a lane, the
r-weighted public keys folded into one pairing input a Miller row, the
r-weighted signatures folded into one G2 point that `stage_group` takes
to affine on the rows' one inversion (tests/test_signature_row.py has
that row's own cases).  Every case here is
compared with `crypto/bls/curve.py` / `PureBls12381` on the host, never
with another device path:

- A. the two stages in ONE compiled shape (4 rows x 4 lanes), a row
  pattern a case (eleven): each row's aggregate point-for-point against
  `sum [r_i]pk_i`, and the G2 fold `wsig` against `sum [r_i]sig_i`;
- B. every verb of the provider under both multiplier engines (`vpu`,
  `mxu-force` with freshly traced stages) against the oracle's verdict;
- C. `stage_group`'s row layouts, canonical limbs against the oracle's
  affine coordinates.

Shapes stay tiny so the CPU compiles are few and shared.
"""

import random
import secrets

import numpy as np
import pytest

import jax

from teku_tpu.crypto.bls import curve as C
from teku_tpu.crypto.bls import keygen
from teku_tpu.crypto.bls.constants import R
from teku_tpu.crypto.bls.pure_impl import PureBls12381
from teku_tpu.ops import limbs as fp
from teku_tpu.ops import mxu
from teku_tpu.ops import points as PT
from teku_tpu.ops import verify as V
from teku_tpu.ops.provider import JaxBls12381

rng = random.Random(0x30)

PURE = PureBls12381()
SKS = [keygen(bytes([130 + i]) * 32) for i in range(4)]
PKS = [PURE.secret_key_to_public_key(sk) for sk in SKS]
G1_INF_WIRE = bytes([0xC0] + [0] * 47)
G2_INF_WIRE = bytes([0xC0] + [0] * 95)
MAX64 = (1 << 64) - 1


def rand_g1():
    return C.point_mul(C.FQ_OPS, rng.randrange(1, R), C.G1_GENERATOR)


def rand_g2():
    return C.point_mul(C.FQ2_OPS, rng.randrange(1, R), C.G2_GENERATOR)


def stack_g1(points):
    return tuple(np.stack([fp.int_to_mont(p[i]) for p in points])
                 for i in range(3))


def stack_g2(points):
    return tuple(
        (np.stack([fp.int_to_mont(p[i][0]) for p in points]),
         np.stack([fp.int_to_mont(p[i][1]) for p in points]))
        for i in range(3))


def weighted_sum(ops, scalars, points, keep=None):
    acc = C.infinity(ops)
    for i, (r, p) in enumerate(zip(scalars, points)):
        if keep is None or keep[i]:
            acc = C.point_add(ops, acc, C.point_mul(ops, int(r), p))
    return acc


def assert_affine_rows(agg_aff, u_mask, want_rows):
    """`stage_group`'s output against the oracle's points: canonical
    plain limbs equal to the affine coordinates, `u_mask` false exactly
    where the row sums to infinity."""
    xs = np.asarray(fp.canonical_plain(agg_aff[0]))
    ys = np.asarray(fp.canonical_plain(agg_aff[1]))
    for u, want in enumerate(want_rows):
        if C.is_infinity(C.FQ_OPS, want):
            assert not bool(np.asarray(u_mask)[u]), f"row {u}"
            continue
        assert bool(np.asarray(u_mask)[u]), f"row {u}"
        ex, ey = C.to_affine(C.FQ_OPS, want)
        assert np.array_equal(xs[u], fp.int_to_limbs(ex)), f"row {u}"
        assert np.array_equal(ys[u], fp.int_to_limbs(ey)), f"row {u}"


def assert_signature_row(s_aff, s_mask, want):
    """`stage_group`'s second result, the summed signature taken to
    affine on the aggregates' inversion, against the oracle's point."""
    assert np.asarray(s_mask).shape == (1,)
    if C.is_infinity(C.FQ2_OPS, want):
        assert not bool(np.asarray(s_mask)[0])
        return
    assert bool(np.asarray(s_mask)[0])
    (ex0, ex1), (ey0, ey1) = C.to_affine(C.FQ2_OPS, want)
    for got, e in zip(jax.tree_util.tree_leaves(s_aff),
                      (ex0, ex1, ey0, ey1)):
        assert np.array_equal(np.asarray(fp.canonical_plain(got))[0],
                              fp.int_to_limbs(e))


def _nudged_multipliers(monkeypatch, lanes):
    """What the provider's host half draws when the entropy source
    hands it zeros: the multipliers of `lanes` real lanes, as ints."""
    impl = JaxBls12381(min_bucket=lanes)
    for sk, pk in zip(SKS, PKS):
        x, y = C.to_affine(C.FQ_OPS, C.point_mul(
            C.FQ_OPS, sk, C.G1_GENERATOR))
        impl._pk_cache.put(pk, ("ok", fp.int_to_mont(x),
                                fp.int_to_mont(y)))
    monkeypatch.setattr(secrets, "token_bytes", lambda n: bytes(n))
    triples = [([PKS[i % 4]], b"nudge-%d" % i, G2_INF_WIRE)
               for i in range(lanes)]
    (packed,) = impl.prepare_dispatch("batch_verify", triples).packed
    weights = 1 << np.arange(63, -1, -1, dtype=np.uint64)
    return [int((packed.r_bits[i].astype(np.uint64) * weights).sum())
            for i in range(lanes)]


# --------------------------------------------------------------------------
# A. stage_scalars -> stage_group, one compiled shape, a pattern a row
# --------------------------------------------------------------------------

ROWS, ROW_LANES = 4, 4
LANES = ROWS * ROW_LANES

G1_PATTERNS = ["r-one", "r-max", "nudged-from-zero", "duplicate-key",
               "p-and-minus-p", "group-absent", "miller-masked",
               "one-lane-row", "infinity-key", "all-masked-row",
               "r-one-beside-r-max"]
G2_PATTERNS = ["infinity-sig", "duplicate-sigs", "sig-and-minus-sig",
               "lane-invalid"]


def _grid_call(g1_patterns, g2_pattern, nudged):
    """One 16-lane input of the shared shape: four G1 row patterns and
    one G2 pattern, with what the oracle expects of them."""
    pks = [rand_g1() for _ in range(LANES)]
    sigs = [rand_g2() for _ in range(LANES)]
    r = [rng.randrange(1, 1 << 64) for _ in range(LANES)]
    present = np.ones((ROWS, ROW_LANES), dtype=bool)
    miller = np.ones(LANES, dtype=bool)
    for row, pattern in enumerate(g1_patterns):
        lo = row * ROW_LANES
        if pattern == "r-one":            # what randomize=False sends
            r[lo:lo + ROW_LANES] = [1] * ROW_LANES
        elif pattern == "r-max":
            r[lo:lo + ROW_LANES] = [MAX64] * ROW_LANES
        elif pattern == "nudged-from-zero":
            r[lo:lo + ROW_LANES] = nudged
        elif pattern == "duplicate-key":
            pks[lo + 1] = pks[lo]
            r[lo + 1] = r[lo]
        elif pattern == "p-and-minus-p":  # the row sums to infinity
            pks[lo + 1] = C.point_neg(C.FQ_OPS, pks[lo])
            pks[lo + 3] = C.point_neg(C.FQ_OPS, pks[lo + 2])
            r[lo + 1], r[lo + 3] = r[lo], r[lo + 2]
        elif pattern == "group-absent":
            present[row, 2] = False
        elif pattern == "miller-masked":
            miller[lo + 1] = False
        elif pattern == "one-lane-row":
            present[row, 1:] = False
        elif pattern == "infinity-key":   # adds nothing, masks nothing
            pks[lo + 2] = C.infinity(C.FQ_OPS)
        elif pattern == "all-masked-row":  # the row sums to infinity
            miller[lo:lo + ROW_LANES] = False
        elif pattern == "r-one-beside-r-max":
            r[lo:lo + ROW_LANES] = [1, MAX64, MAX64, 1]
    if g2_pattern == "infinity-sig":
        sigs[5] = C.infinity(C.FQ2_OPS)
    elif g2_pattern == "duplicate-sigs":
        sigs[6] = sigs[9] = sigs[2]
    elif g2_pattern == "sig-and-minus-sig":
        sigs[7] = C.point_neg(C.FQ2_OPS, sigs[3])
        r[7] = r[3]
    elif g2_pattern == "lane-invalid":
        # what stage_prepare hands on for a lane_valid-false lane: the
        # infinity signature and a false Miller mask
        sigs[10] = C.infinity(C.FQ2_OPS)
        miller[10] = False
    group_idx = np.arange(LANES, dtype=np.int32).reshape(ROWS, ROW_LANES)
    want_rows = []
    for row in range(ROWS):
        lanes = list(group_idx[row])
        keep = [bool(present[row, c] and miller[lane])
                for c, lane in enumerate(lanes)]
        want_rows.append(weighted_sum(
            C.FQ_OPS, [r[i] for i in lanes], [pks[i] for i in lanes],
            keep))
    want_wsig = weighted_sum(C.FQ2_OPS, r, sigs)
    r_bits = PT.scalar_bits_np(np.asarray(r, dtype=np.uint64))
    return ((stack_g1(pks), stack_g2(sigs), r_bits, miller, group_idx,
             present), want_rows, want_wsig)


@pytest.fixture(scope="module")
def grid():
    """The four calls of the one compiled (16 lanes, 4 x 4) shape: the
    G1 patterns four a call, a G2 pattern each."""
    with pytest.MonkeyPatch.context() as mp:
        nudged = _nudged_multipliers(mp, ROW_LANES)

    @jax.jit
    def scalars_then_group(pk_jac, sig_jac, r_bits, miller_mask,
                           group_idx, group_present):
        pk_r_jac, wsig = V.stage_scalars(pk_jac, sig_jac, r_bits)
        agg_aff, u_mask, s_aff, s_mask = V.stage_group(
            pk_r_jac, miller_mask, group_idx, group_present, wsig)
        return agg_aff, u_mask, wsig, s_aff, s_mask

    calls = []
    for c, g2_pattern in enumerate(G2_PATTERNS):
        g1 = G1_PATTERNS[ROWS * c:ROWS * c + ROWS]
        args, want_rows, want_wsig = _grid_call(g1, g2_pattern, nudged)
        agg_aff, u_mask, wsig, s_aff, s_mask = scalars_then_group(*args)
        assert_signature_row(s_aff, s_mask, want_wsig)
        calls.append((agg_aff, u_mask, wsig, want_rows, want_wsig))
    assert scalars_then_group._cache_size() == 1
    return {"calls": calls, "nudged": nudged}


@pytest.mark.parametrize("pattern", G1_PATTERNS)
def test_a_rows_aggregate_is_the_oracles_weighted_sum(grid, pattern):
    at = G1_PATTERNS.index(pattern)
    agg_aff, u_mask, _wsig, want_rows, _ = grid["calls"][at // ROWS]
    row = at % ROWS
    if pattern in ("p-and-minus-p", "all-masked-row"):
        assert C.is_infinity(C.FQ_OPS, want_rows[row])
    if pattern == "nudged-from-zero":
        assert grid["nudged"] == [1] * ROW_LANES
    assert_affine_rows(
        jax.tree_util.tree_map(lambda x: x[row:row + 1], agg_aff),
        u_mask[row:row + 1], want_rows[row:row + 1])


@pytest.mark.parametrize("pattern", G2_PATTERNS)
def test_wsig_is_the_oracles_weighted_signature_sum(grid, pattern):
    agg_aff, u_mask, wsig, want_rows, want_wsig = \
        grid["calls"][G2_PATTERNS.index(pattern)]
    assert np.asarray(wsig[2][0]).shape[0] == 1   # a (1,)-batched point
    assert C.point_eq(C.FQ2_OPS, PT.g2_from_device(wsig, (0,)),
                      want_wsig)
    # the call's rows too (the G2 pattern's lane sits in one of them)
    assert_affine_rows(agg_aff, u_mask, want_rows)


# --------------------------------------------------------------------------
# B. every verb on the one path, both multiplier engines
# --------------------------------------------------------------------------

def _triples(lane_msgs, tamper_lane=None, inf_sig_lane=None):
    out = []
    for i, m in enumerate(lane_msgs):
        if i == inf_sig_lane:
            out.append(([PKS[i % 4]], m, G2_INF_WIRE))
            continue
        sign_msg = b"tampered" if i == tamper_lane else m
        out.append(([PKS[i % 4]], m, PURE.sign(SKS[i % 4], sign_msg)))
    return out


def _verb_cases():
    agg_msg = b"stage-fav"
    agg_sig = PURE.aggregate_signatures(
        [PURE.sign(sk, agg_msg) for sk in SKS])
    msgs = [b"stage-av-%d" % i for i in range(4)]
    av_sig = PURE.aggregate_signatures(
        [PURE.sign(sk, m) for sk, m in zip(SKS, msgs)])
    return {
        "dup4": ("batch_verify", (_triples([b"stage-a"] * 4),)),
        "unique": ("batch_verify", (_triples(
            [b"stage-u%d" % i for i in range(4)]),)),
        "tamper": ("batch_verify", (_triples(
            [b"stage-a"] * 4, tamper_lane=2),)),
        "inf-sig": ("batch_verify", (_triples(
            [b"stage-a"] * 3 + [b"stage-b"], inf_sig_lane=3),)),
        "pad": ("batch_verify", (_triples(
            [b"stage-p", b"stage-p", b"stage-q"]),)),
        "fav-valid": ("fast_aggregate_verify", (PKS, agg_msg, agg_sig)),
        "fav-tampered": ("fast_aggregate_verify",
                         (PKS, b"stage-other", agg_sig)),
        "fav-infinity-key": ("fast_aggregate_verify",
                             (PKS[:3] + [G1_INF_WIRE], agg_msg,
                              agg_sig)),
        # sound only because r = 1 exactly off `batch_verify`: one
        # signature stands for all four lanes
        "av-valid": ("aggregate_verify", (PKS, msgs, av_sig)),
        "av-swapped": ("aggregate_verify",
                       (PKS, [msgs[1], msgs[0]] + msgs[2:], av_sig)),
    }


VERB_CASES = ["dup4", "unique", "tamper", "inf-sig", "pad", "fav-valid",
              "fav-tampered", "fav-infinity-key", "av-valid",
              "av-swapped"]
VERB_WANT = dict(zip(VERB_CASES, [True, True, False, False, True, True,
                                  False, False, True, False]))


@pytest.fixture(scope="module", params=["vpu", "mxu-force"])
def engine_impl(request):
    """A provider whose staged programs were traced under the engine:
    the module's jit table caches by shape alone, so the second engine
    needs fresh jit objects."""
    old = V._STAGED_JITS
    V._STAGED_JITS = None
    try:
        with mxu.force(request.param):
            assert mxu.resolve() == request.param.split("-")[0]
            yield JaxBls12381()
    finally:
        V._STAGED_JITS = old


@pytest.fixture(scope="module")
def verb_cases():
    return _verb_cases()


@pytest.mark.parametrize("case", VERB_CASES)
def test_verb_verdict_is_the_oracles(engine_impl, verb_cases, case):
    verb, args = verb_cases[case]
    want = getattr(PURE, verb)(*args)
    assert want is VERB_WANT[case]
    before = engine_impl.dispatch_count
    assert getattr(engine_impl, verb)(*args) is want
    # the verdict is the device's, not a host short-cut — but for the
    # infinity key, which the host's wire checks already refuse
    dispatched = engine_impl.dispatch_count - before
    assert dispatched == (0 if case == "fav-infinity-key" else 1)


# --------------------------------------------------------------------------
# C. stage_group's row layouts
# --------------------------------------------------------------------------

@pytest.mark.parametrize("rows,row_lanes,half", [
    (4, 1, False),      # a row a lane: backfill-unique's layout
    (2, 2, False),
    (1, 4, False),      # one message: the gossip drain's layout
    (2, 4, True)],      # rows half full: a committee's ragged last row
    ids=["4x1", "2x2", "1x4", "2x4-half-present"])
def test_stage_group_layout(rows, row_lanes, half):
    lanes = rows * row_lanes
    pts = [rand_g1() for _ in range(lanes)]
    group_idx = np.asarray(rng.sample(range(lanes), lanes),
                           dtype=np.int32).reshape(rows, row_lanes)
    present = np.ones((rows, row_lanes), dtype=bool)
    if half:
        present[:, row_lanes // 2:] = False
    sig = rand_g2()
    agg_aff, u_mask, s_aff, s_mask = jax.jit(V.stage_group)(
        stack_g1(pts), np.ones(lanes, dtype=bool), group_idx, present,
        stack_g2([sig]))
    want = [weighted_sum(C.FQ_OPS, [1] * row_lanes,
                         [pts[i] for i in group_idx[u]], present[u])
            for u in range(rows)]
    assert_affine_rows(agg_aff, u_mask, want)
    assert_signature_row(s_aff, s_mask, sig)
