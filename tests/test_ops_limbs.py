"""fp381 limb arithmetic vs the pure-Python oracle.

Layer validation for BOTH mont_mul engines: the VPU pad-and-sum path
and the MXU int8 digit-split matmul path (ops/mxu.py) run against the
same oracle, including adversarial operands at the documented
``units(a) * units(b) <= 64`` lazy-reduction contract edge, plus a
cross-path parity gate asserting bit-identical ``canonical()`` images.
"""

import random
import re

import numpy as np
import pytest

import jax

from teku_tpu.crypto.bls.constants import P, R
from teku_tpu.ops import limbs as fp
from teku_tpu.ops import modfield, mxu

rng = random.Random(0xB15)

PATH_KERNELS = {
    "vpu": (fp.mont_mul_vpu, fp.mont_sqr_vpu),
    "mxu": (fp.mont_mul_mxu, fp.mont_sqr_mxu),
}


def rand_fq():
    return rng.randrange(P)


EDGE = [0, 1, 2, P - 1, P - 2, (P - 1) // 2, fp.R_MOD_P, P - fp.R_MOD_P]


def batch_mont(values):
    return np.stack([fp.int_to_mont(v) for v in values])


def unbatch(arr):
    return [fp.mont_to_int(np.asarray(arr)[i]) for i in range(arr.shape[0])]


def test_limb_roundtrip():
    for v in EDGE + [rand_fq() for _ in range(20)]:
        assert fp.limbs_to_int(fp.int_to_limbs(v)) == v
        assert fp.mont_to_int(fp.int_to_mont(v)) == v


def test_add_sub_neg():
    a_vals = EDGE + [rand_fq() for _ in range(24)]
    b_vals = list(reversed(EDGE)) + [rand_fq() for _ in range(24)]
    a, b = batch_mont(a_vals), batch_mont(b_vals)
    assert unbatch(fp.add(a, b)) == [(x + y) % P for x, y in zip(a_vals, b_vals)]
    assert unbatch(fp.sub(a, b)) == [(x - y) % P for x, y in zip(a_vals, b_vals)]
    assert unbatch(fp.neg(a)) == [(-x) % P for x in a_vals]


def test_mont_mul_sqr():
    a_vals = EDGE + [rand_fq() for _ in range(24)]
    b_vals = list(reversed(EDGE)) + [rand_fq() for _ in range(24)]
    a, b = batch_mont(a_vals), batch_mont(b_vals)
    assert unbatch(fp.mont_mul(a, b)) == [x * y % P for x, y in zip(a_vals, b_vals)]
    assert unbatch(fp.mont_sqr(a)) == [x * x % P for x in a_vals]


def test_mul_broadcast():
    # (4,1,L) x (3,L) -> (4,3,L)
    a_vals = [rand_fq() for _ in range(4)]
    b_vals = [rand_fq() for _ in range(3)]
    a = batch_mont(a_vals)[:, None, :]
    b = batch_mont(b_vals)
    out = np.asarray(fp.mont_mul(a, b))
    assert out.shape == (4, 3, fp.L)
    for i in range(4):
        for j in range(3):
            assert fp.mont_to_int(out[i, j]) == a_vals[i] * b_vals[j] % P


def test_to_from_mont_device():
    vals = EDGE + [rand_fq() for _ in range(8)]
    plain = np.stack([fp.int_to_limbs(v) for v in vals])
    m = fp.to_mont(plain)
    back = np.asarray(fp.from_mont(m))
    assert [fp.limbs_to_int(back[i]) for i in range(len(vals))] == vals


def test_is_zero_eq_select():
    a = batch_mont([0, 1, P - 1, 0])
    b = batch_mont([0, 1, 1, 5])
    assert list(np.asarray(fp.is_zero(a))) == [True, False, False, True]
    assert list(np.asarray(fp.eq(a, b))) == [True, True, False, False]
    sel = fp.select(fp.eq(a, b), a, b)
    assert unbatch(sel) == [0, 1, 1, 5]


def test_mul_small():
    a_vals = [rand_fq() for _ in range(6)] + [P - 1]
    a = batch_mont(a_vals)
    for k in (0, 1, 2, 3, 8):
        assert unbatch(fp.mul_small(a, k)) == [v * k % P for v in a_vals]


def _inv_oracle(v):
    return pow(v, -1, P) if v % P else 0      # inv(0) = 0 convention


# row counts around the tile `inv` pads a narrow operand to
INV_ROWS = [1, 2, fp._FERMAT_ROWS - 1, fp._FERMAT_ROWS, fp._FERMAT_ROWS + 1]


@pytest.mark.parametrize("path", ["vpu", "mxu-force"])
@pytest.mark.parametrize("rows", INV_ROWS)
def test_pow_static_and_inv(rows, path):
    """inv on `rows` elements (zero rows and rows of P - 1 among them)
    is the field inverse, and bit-for-bit the unpadded Fermat scan's
    limbs: a padded tile keeps its live rows exact."""
    r = random.Random(rows)
    specials = [0, P - 1, 1]
    a_vals = [P - 1] if rows == 1 else [
        specials[i // 2 % 3] if i % 2 else r.randrange(P)
        for i in range(rows)]
    a = batch_mont(a_vals)
    with mxu.force(path):
        if rows == INV_ROWS[2]:
            for e in (1, 2, 3, 65537, (P - 1) // 2):
                assert unbatch(fp.pow_static(a, e)) == [pow(v, e, P)
                                                          for v in a_vals]
            assert unbatch(fp.inv(batch_mont([0]))) == [0]
        got = np.asarray(fp.inv(a))
        assert (got == np.asarray(fp.pow_static(a, P - 2))).all()
    assert got.shape == a.shape
    assert unbatch(got) == [_inv_oracle(v) for v in a_vals]


@pytest.mark.parametrize("path", ["vpu", "mxu-force"])
@pytest.mark.parametrize("vals", [[5], [0], [P - 1, 0, 7, 0, 1, 0]],
                         ids=["one", "one-zero", "zero-lanes"])
def test_inv_many(vals, path):
    """inv_many's one-element batch goes to inv whole; zero lanes of a
    batch come out 0 and do not poison the others' shared product."""
    a = batch_mont(vals)
    with mxu.force(path):
        got = np.asarray(fp.inv_many(a))
        if len(vals) == 1:
            assert (got == np.asarray(fp.inv(a))).all()
    assert unbatch(got) == [_inv_oracle(v) for v in vals]


def _while_loops(text):
    """Each stablehlo.while of a lowered module as (its carried types,
    the carried types of every loop it runs), following the func.calls
    its regions make: a scan's body is a function of its own."""
    loops, funcs = [], {}   # [types, inner loop ids, callees]; fn -> ids, callees
    open_loops, depth, fn = [], 0, None
    for line in text.splitlines():
        name = re.search(r"func\.func \w* ?@([\w.]+)", line)
        if name:
            fn = funcs.setdefault(name.group(1), ([], set()))
        if "stablehlo.while(" in line:
            for i, _ in open_loops:
                loops[i][1].append(len(loops))
            fn[0].append(len(loops))
            open_loops.append((len(loops), depth))
            loops.append([line.rsplit(") : ", 1)[1], [], set()])
        for callee in re.findall(r"call @([\w.]+)", line):
            fn[1].add(callee)
            for i, _ in open_loops:
                loops[i][2].add(callee)
        depth += line.count("{") - line.count("}")
        while open_loops and "}" in line and depth <= open_loops[-1][1]:
            open_loops.pop()

    def runs(ids, callees, seen):
        out = [loops[i][0] for i in ids]
        for c in callees - seen:
            seen.add(c)
            out += runs(*funcs[c], seen)
        return out

    return [(t, runs(inner, callees, set())) for t, inner, callees in loops]


@pytest.mark.parametrize("stage", ["finish", "group"])
def test_fermat_scan_runs_on_a_tile(stage):
    """Each Fermat scan of a staged program carries _FERMAT_ROWS rows,
    and no loop inside one carries a width-1 (1, L) / (L, 1) element
    (the shape at which a scan step cost the chip 83 us: PERF.md §7.9).
    Engagement is decided from static shapes at trace time, so the
    lowered program is the witness."""
    from teku_tpu.ops import shapeset
    from teku_tpu.ops import verify as V
    avals = next(av for _, av, meta in shapeset.enumerate_programs(
        max_batch=8, min_bucket=8, h2c_min_bucket=8)
        if meta["stage"] == stage and meta.get("profile") == "x1")
    fn = {"finish": V.stage_finish, "group": V.stage_group}[stage]
    with mxu.force("vpu"):
        loops = _while_loops(jax.jit(fn).lower(*avals).as_text())
    n_digits = -(-(P - 2).bit_length() // fp.POW_WINDOW)
    digits = f"tensor<{n_digits - 1}xi64>"
    rows = fp._FERMAT_ROWS
    scans = [(t, inner) for t, inner in loops if digits in t]
    assert len(scans) == 1                     # ONE inversion a program
    for types, inner in scans:
        assert f"tensor<{rows}x{fp.L}xi64>" in types
        assert f"tensor<{1 << fp.POW_WINDOW}x{rows}x{fp.L}xi64>" in types
        assert inner, "the scan's mont_muls are loops of their own"
        for t in [types] + inner:
            assert f"tensor<1x{fp.L}xi64>" not in t
            assert f"tensor<{fp.L}x1xi64>" not in t
    # and the scan's table of a^d is built at the same width
    assert not any(f"tensor<{1 << fp.POW_WINDOW}x1x{fp.L}xi64>" in t
                   for t, _ in loops)


def test_sqrt_candidate():
    for _ in range(6):
        r = rand_fq()
        sq = r * r % P
        cand = fp.mont_to_int(np.asarray(fp.sqrt_candidate(batch_mont([sq]))[0]))
        assert cand in (r, P - r)


# --------------------------------------------------------------------------
# Adversarial operand bounds at the lazy-reduction contract edge, on
# BOTH multiplier paths (units(a) * units(b) <= 64; ops/limbs.py)
# --------------------------------------------------------------------------

def _lazy_operand(n_units: int, sign_rng):
    """A signed sum of n_units Montgomery units: (lazy_limbs, value)."""
    acc = np.zeros(fp.L, dtype=np.int64)
    value = 0
    for _ in range(n_units):
        v = rand_fq()
        s = sign_rng.choice((1, -1))
        acc = acc + s * np.asarray(fp.int_to_mont(v), dtype=np.int64)
        value = (value + s * v) % P
    return acc, value


@pytest.mark.parametrize("path", sorted(PATH_KERNELS))
@pytest.mark.parametrize("ua,ub", [(1, 64), (2, 32), (4, 16), (8, 8),
                                   (16, 4), (64, 1)])
def test_mont_mul_lazy_contract_edge(path, ua, ub):
    """Signed lazy sums at every (ua, ub) split of the ua*ub = 64
    contract edge must reduce to the oracle product on both paths."""
    mont_mul, _ = PATH_KERNELS[path]
    sign_rng = random.Random(ua * 1000 + ub)
    lanes = 4
    la, lb, expect = [], [], []
    for _ in range(lanes):
        a, va = _lazy_operand(ua, sign_rng)
        b, vb = _lazy_operand(ub, sign_rng)
        la.append(a)
        lb.append(b)
        expect.append(va * vb % P)
    out = np.asarray(mont_mul(np.stack(la), np.stack(lb)))
    got = [fp.mont_to_int(out[i]) for i in range(lanes)]
    assert got == expect


@pytest.mark.parametrize("path", sorted(PATH_KERNELS))
def test_mont_mul_top_limb_magnitude(path):
    """Operands whose compressed top limb sits near the +-2^22 unit
    bound (and beyond, at the 64-unit lazy bound) stay exact: the MXU
    digit split must carry the top limb's sign and overflow."""
    mont_mul, mont_sqr = PATH_KERNELS[path]
    top = fp.W * (fp.L - 1)                      # bit 364
    cases = []
    for top_mag in ((1 << 22) - 1, (1 << 21) + 1):
        v = ((top_mag << top) + rng.randrange(1 << top)) % P
        cases.append(v)
    # maximal canonical value: top limb at its largest canonical size
    cases += [P - 1, P - 2]
    a = np.stack([np.asarray(fp.int_to_mont(v), dtype=np.int64)
                  for v in cases])
    # drive the top limb NEGATIVE and large via signed-sum lazies:
    # a (1 unit) x neg (32 units) hits ua*ub = 32; mont_sqr uses an
    # 8-unit operand so the squared contract 8*8 = 64 sits AT the edge
    neg = np.stack([-32 * row for row in a])
    out = np.asarray(mont_mul(a, neg))
    for i, v in enumerate(cases):
        assert fp.mont_to_int(out[i]) == (v * (-32 * v)) % P
    sq = np.asarray(mont_sqr(np.stack([-8 * row for row in a])))
    for i, v in enumerate(cases):
        assert fp.mont_to_int(sq[i]) == (8 * v) ** 2 % P


def test_cross_path_parity_bit_identical():
    """vpu and mxu mont_mul/mont_sqr must produce BIT-IDENTICAL
    canonical() images on shared random vectors — the gate for
    swapping the engine under the live kernels."""
    prng = random.Random(0xA11CE)
    lanes = 32
    a = np.stack([np.asarray(fp.int_to_mont(prng.randrange(P)))
                  for _ in range(lanes)])
    b = np.stack([np.asarray(fp.int_to_mont(prng.randrange(P)))
                  for _ in range(lanes)])
    # plus lazy signed sums (units 2 and 4), like real call sites feed
    lazy_a = a - np.roll(a, 1, axis=0)
    lazy_b = b + np.roll(b, 3, axis=0) - np.roll(a, 5, axis=0) + a
    for x, y in ((a, b), (lazy_a, lazy_b), (lazy_b, lazy_a)):
        vpu = np.asarray(fp.canonical(fp.mont_mul_vpu(x, y)))
        mxu_ = np.asarray(fp.canonical(fp.mont_mul_mxu(x, y)))
        assert (vpu == mxu_).all()
    sq_v = np.asarray(fp.canonical(fp.mont_sqr_vpu(lazy_b)))
    sq_m = np.asarray(fp.canonical(fp.mont_sqr_mxu(lazy_b)))
    assert (sq_v == sq_m).all()


def test_dispatch_follows_path_config():
    """fp.mont_mul routes by the process-global config: forced mxu and
    forced vpu must agree bit-for-bit (trace-time dispatch).  Both
    ends are pinned so an ambient TEKU_TPU_MONT_MUL doesn't leak in."""
    a = batch_mont([rand_fq() for _ in range(4)])
    b = batch_mont([rand_fq() for _ in range(4)])
    with mxu.force("vpu"):
        assert mxu.resolve() == "vpu"
        base = np.asarray(fp.mont_mul(a, b))
    with mxu.force("mxu-force"):
        assert mxu.resolve() == "mxu"
        forced = np.asarray(fp.mont_mul(a, b))
    assert (np.asarray(fp.canonical(base))
            == np.asarray(fp.canonical(forced))).all()


def test_generic_field_cross_path_parity():
    """modfield.make_field carries both engines too (Fr for KZG): the
    scalar field's 10-limb digit split needs 5 digit planes — cover it
    against the bigint oracle and across paths."""
    FR = modfield.make_field(R, "fr")
    prng = random.Random(0xF2)
    xs = [0, 1, R - 1, R - 2] + [prng.randrange(R) for _ in range(12)]
    ys = list(reversed(xs))
    a = np.stack([np.asarray(FR.int_to_mont(v)) for v in xs])
    b = np.stack([np.asarray(FR.int_to_mont(v)) for v in ys])
    lazy_a = a - np.roll(b, 2, axis=0)
    va = [(x - y2) % R for x, y2 in zip(xs, np.roll(ys, 2).tolist())]
    out_v = np.asarray(FR.mont_mul_vpu(lazy_a, b))
    out_m = np.asarray(FR.mont_mul_mxu(lazy_a, b))
    for i in range(len(xs)):
        assert FR.mont_to_int(out_v[i]) == va[i] * ys[i] % R
        assert FR.mont_to_int(out_m[i]) == va[i] * ys[i] % R
    assert (np.asarray(FR.canonical(out_v))
            == np.asarray(FR.canonical(out_m))).all()
