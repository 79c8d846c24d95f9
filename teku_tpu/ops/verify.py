"""Batched BLS signature verification kernel — the TPU north star.

One jitted dispatch verifies a whole batch of (aggregate-pubkey, message,
signature) triples with the random-multiplier scheme (ethresear.ch/5407),
replacing the reference's native pairing loop (reference:
infrastructure/bls/src/main/java/tech/pegasys/teku/bls/impl/blst/
BlstBLS12381.java:124-189 — mul_n_aggregate / commit / merge /
finalverify, and BLS.batchVerify at bls/BLS.java:230-254):

  ok  <=>  prod_i e([r_i]pk_i, H(m_i)) * e(-g1, sum_i [r_i]sig_i) == 1

Everything after SHA-256 message expansion runs on device in fixed shapes:
signature decompression + psi-endomorphism subgroup checks, hash-to-G2
(SSWU + isogeny + Budroni-Pintore), constant-time 64-bit scalar
multiplications, the batched Miller loops, a log-depth product/point-sum
reduction over the batch, and one shared final exponentiation.

DEDUP-AWARE: committee-based consensus signs the same AttestationData
across whole committees, so a gossip batch has far fewer UNIQUE
messages than lanes.  The pipeline exploits this twice: hash-to-G2
runs over the unique-message bucket only (stage_h2c + stage_gather_hm
scatters the points back to lanes), and — since the pairing is
bilinear in G1 — stage_group folds every message's r-weighted pubkeys
into ONE Miller loop per unique (prod_i e([r_i]pk_i, H(m)) ==
e(sum_i [r_i]pk_i, H(m))), collapsing the two dominant per-lane stages
by the duplication factor with an unchanged verdict.

Lanes carry masks instead of branches: padding lanes (valid=False)
contribute the identity; infinity signatures contribute the infinity
point exactly like the oracle (crypto/bls/pure_impl.py:205-214).

THE SIGNATURE'S ROW.  The pair e(-g1, S) of the summed weighted
signature S = sum_i [r_i]sig_i has no program of its own: its affine
conversion shares the one Fermat inversion of the G1 affine conversion
(affine_with_signature, inside stage_group / stage_lane_affine), its
Miller loop is one more row of stage_miller's scan (the last), and
stage_finish keeps the product over the rows and the final
exponentiation.  The group-sharded mesh kernel does the same a shard:
by bilinearity prod_k e(-g1, S_k) == e(-g1, sum_k S_k), so each shard's
row is its OWN partial sum S_k and only Fq12 partial products cross the
chips.  The legacy lane-sharded kernel knows S only after its
all_gather, so its tail (_finish) runs the three pieces on that one
row.
"""

import numpy as np

import jax
import jax.numpy as jnp

from ..crypto.bls import curve as C
from . import h2c
from . import limbs as fp
from . import pairing as PR
from . import points as PT
from . import towers as T

# -g1 generator, host-computed affine constant
_NEG_G1 = C.to_affine(C.FQ_OPS, C.point_neg(C.FQ_OPS, C.G1_GENERATOR))
_NEG_G1_X = np.asarray(fp.int_to_mont(_NEG_G1[0]))
_NEG_G1_Y = np.asarray(fp.int_to_mont(_NEG_G1[1]))


# re-exported for the KZG/parallel callers
point_batch_sum = PT.point_batch_sum


def _neg_g1_row():
    """P of the signature's row: -g1, a (1,)-batched affine point."""
    return (jnp.asarray(_NEG_G1_X)[None], jnp.asarray(_NEG_G1_Y)[None])


def _affine_g1_given(p, zinv):
    """to_affine_g1 for a caller that already holds the z^-1 batch."""
    zinv2 = fp.mont_sqr(zinv)
    t = fp.mont_mul(jnp.stack([p[0], fp.mont_mul(zinv2, zinv)], axis=-2),
                    jnp.stack([zinv2, p[1]], axis=-2))
    return (t[..., 0, :], t[..., 1, :])


def to_affine_g1(p):
    """Batched Jacobian -> affine for G1 (one batched inversion: a
    single Fermat exponentiation for the whole batch)."""
    return _affine_g1_given(p, fp.inv_many(p[2]))


def affine_with_signature(pk_jac, wsig):
    """The signature-row helper: ONE Fermat inversion converts a batch
    of Jacobian G1 points AND the summed weighted signature to affine.

    pk_jac: Jacobian G1, (U, L) leaves — or None where there are no G1
        points to convert (the mesh kernels' post-gather tail).
    wsig: the (1,)-batched Jacobian G2 sum S that stage_scalars returns.

    The one `inv_many` runs over the U `z`s and the Fq norm of S.z
    (z0^2 + z1^2, what towers.fq2_inv would invert alone); S.z^-1 is
    conj(S.z) * norm^-1 and the coordinates follow as in to_affine_g2.
    inv_many masks zero lanes out of its shared product, so an infinity
    S and an infinity G1 point cannot poison each other; both come out
    with garbage coordinates that `s_mask` / the caller's mask carry out
    of the Miller loop.

    Returns (pk_aff or None, s_aff ((1, L) leaves), s_mask (1,))."""
    norm = T.fq2_norm(wsig[2])
    zs = norm if pk_jac is None else jnp.concatenate(
        [pk_jac[2], norm], axis=0)
    zinv = fp.inv_many(zs)
    s_aff = h2c.affine_g2_given(wsig, T.fq2_inv_given(wsig[2], zinv[-1:]))
    pk_aff = None if pk_jac is None else _affine_g1_given(pk_jac,
                                                          zinv[:-1])
    return pk_aff, s_aff, ~PT.is_infinity(PT.G2_KIT, wsig)


def _aggregate_lane_pks(pk_xs, pk_ys, pk_present):
    """Per-lane pubkey aggregation INSIDE the dispatch: (N, K, L) padded
    affine key matrices -> one Jacobian aggregate per lane + infinity
    flag.  Replaces the reference's host-side aggregate loop (and round
    2's per-triple device round trips) with a log2(K)-depth masked tree
    sum that ships in the same compiled program."""
    one = jnp.broadcast_to(jnp.asarray(fp.ONE_MONT), pk_xs.shape)
    if pk_xs.shape[-2] == 1:
        pk_jac = (pk_xs[..., 0, :], pk_ys[..., 0, :], one[..., 0, :])
        inf = PT.infinity_like(PT.G1_KIT, pk_jac[0])
        pk_jac = PT._select_point(PT.G1_KIT, ~pk_present[..., 0],
                                 inf, pk_jac)
    else:
        jac = (pk_xs, pk_ys, one)
        inf = PT.infinity_like(PT.G1_KIT, pk_xs)
        jac = PT._select_point(PT.G1_KIT, pk_present, jac, inf)
        jac = jax.tree_util.tree_map(
            lambda x: jnp.moveaxis(x, -2, 0), jac)   # (K, N, L)
        pk_jac = point_batch_sum(PT.G1_KIT, jac)
    return pk_jac, PT.is_infinity(PT.G1_KIT, pk_jac)


def _lane_work(pk_xs, pk_ys, pk_present, hm_aff, sig_x_plain, sig_large,
               sig_inf, r_bits, lane_valid):
    """Per-lane pipeline of the lane-sharded mesh kernel (shardable
    over the batch axis with no communication), COMPOSED from the stage
    functions below and the pieces the row stages are made of
    (to_affine_g1, PR.miller_loop), so the sharded kernel and the staged
    dispatch can never diverge.  No signature row here: a shard holds a
    partial sum only, so that row is `_finish`'s, after the gather.

    Takes the per-lane H(m) AFFINE points (`hm_aff`), not the field
    draws: hash-to-curve runs over the batch's UNIQUE messages upstream
    (stage_h2c on a smaller bucket + stage_gather_hm, or the provider's
    device-resident H(m) cache) — in committee-based consensus a batch
    has far fewer distinct messages than lanes, and h2c is the largest
    per-lane stage.

    Returns (ml (N-lane Fq12 values), wsig (the weighted sig points'
    sum, (1,)-batched), lane_ok (N,))."""
    pk_jac, sig_jac, lane_ok, miller_mask = stage_prepare(
        pk_xs, pk_ys, pk_present, sig_x_plain, sig_large, sig_inf,
        lane_valid)
    pk_r_jac, wsig = stage_scalars(pk_jac, sig_jac, r_bits)
    ml = PR.miller_loop(to_affine_g1(pk_r_jac), hm_aff, mask=miller_mask)
    return ml, wsig, lane_ok


def _finish(ml_prod, wsig):
    """The legacy lane-sharded kernel's post-gather tail, where the
    signature's row cannot ride the shards' inversion and scan (S
    exists only after the all_gather): the same helper, a Miller loop
    on that one row, and stage_finish's verdict.  `wsig` is
    (1,)-batched, `ml_prod` the product of every shard's rows."""
    _, s_aff, s_mask = affine_with_signature(None, wsig)
    ml_s = PR.miller_loop(_neg_g1_row(), s_aff, mask=s_mask)
    sig_row = jax.tree_util.tree_map(lambda x: x[0], ml_s)
    return PR.pairing_check(T.fq12_mul(ml_prod, sig_row))


# --------------------------------------------------------------------------
# The staged programs: the verification split into separately-jitted
# stages.  One monolithic kernel's TPU-XLA compile is unbounded in
# practice (>60 min observed on v5e); each stage compiles in minutes,
# caches independently in the persistent compile cache, and the chain
# keeps all intermediates on device.
# --------------------------------------------------------------------------

def stage_prepare(pk_xs, pk_ys, pk_present, sig_x_plain, sig_large,
                  sig_inf, lane_valid):
    """Key aggregation + signature decompression/subgroup checks."""
    pk_jac, pk_inf = _aggregate_lane_pks(pk_xs, pk_ys, pk_present)
    dec_ok, sig_pt = PT.g2_recover_y(sig_x_plain, sig_large)
    in_sub = PT.g2_in_subgroup(sig_pt)
    sig_ok = (dec_ok & in_sub) | sig_inf
    use_inf = sig_inf | ~sig_ok | ~lane_valid
    sig_jac = PT._select_point(
        PT.G2_KIT, use_inf, PT.infinity_like(PT.G2_KIT, sig_pt[0]), sig_pt)
    return pk_jac, sig_jac, sig_ok & ~pk_inf, lane_valid & ~pk_inf


def stage_h2c(u0, u1):
    """Hash-to-G2 map + cofactor clearing + batched affine.

    Runs over the UNIQUE-message bucket, not lanes: callers dedup the
    batch's messages, dispatch this at the (smaller, pow-2) unique
    width, and scatter the mapped points back with stage_gather_hm."""
    return h2c.to_affine_g2(h2c.hash_to_g2_device(u0, u1))


def stage_gather_hm(hm_uniq, lane_map):
    """Scatter the unique-message H(m) points back into lanes: one
    device gather of the affine coordinate arrays along the unique
    axis.  `lane_map` is the (N,) unique index of each lane's message
    (padding lanes may carry any in-range index — downstream masks,
    not the gathered point, decide their contribution)."""
    return jax.tree_util.tree_map(lambda x: x[lane_map], hm_uniq)


def _signature_sum(sigs):
    """Sum of Jacobian G2 points over axis 0, kept (1,)-batched: the
    form every consumer of the signature sum takes."""
    return jax.tree_util.tree_map(
        lambda x: x[None], point_batch_sum(PT.G2_KIT, sigs))


def stage_scalars(pk_jac, sig_jac, r_bits):
    """Random-multiplier scalar muls (Jacobian G1 out — the affine
    conversion happens per-lane in stage_lane_affine or per-UNIQUE in
    stage_group, whichever path runs).

    `wsig` comes back as the SUM of the weighted signatures, a
    (1,)-batched point: the pairing only ever consumes that sum, and
    stage_group / stage_lane_affine take it to affine next."""
    pk_r_jac = PT.scalar_mul_bits(PT.G1_KIT, r_bits, pk_jac)
    return pk_r_jac, _signature_sum(
        PT.scalar_mul_bits(PT.G2_KIT, r_bits, sig_jac))


def stage_lane_affine(pk_r_jac, wsig):
    """Per-lane batched G1 affine (the non-grouped pipeline) and, on
    the same inversion, the signature's row: (pk_r_aff, s_aff, s_mask)
    as affine_with_signature returns them."""
    return affine_with_signature(pk_r_jac, wsig)


def _group_aggregates(pk_r_jac, miller_mask, group_idx, group_present):
    """Each unique message's lanes summed: (Jacobian aggregates (U, L),
    u_mask (U,) false where a row sums to infinity)."""
    inf = PT.infinity_like(PT.G1_KIT, pk_r_jac[0])
    masked = PT._select_point(PT.G1_KIT, miller_mask, pk_r_jac, inf)
    grouped = jax.tree_util.tree_map(lambda x: x[group_idx], masked)
    inf_g = PT.infinity_like(PT.G1_KIT, grouped[0])
    grouped = PT._select_point(PT.G1_KIT, group_present, grouped, inf_g)
    if group_idx.shape[1] == 1:
        agg = jax.tree_util.tree_map(lambda x: x[:, 0], grouped)
    else:
        gmoved = jax.tree_util.tree_map(
            lambda x: jnp.moveaxis(x, 1, 0), grouped)   # (G, U, L)
        agg = point_batch_sum(PT.G1_KIT, gmoved)
    return agg, ~PT.is_infinity(PT.G1_KIT, agg)


def stage_group(pk_r_jac, miller_mask, group_idx, group_present, wsig):
    """Fold each unique message's lanes into ONE pairing input, and
    take the summed signature to affine on the same inversion.

    The pairing is bilinear in its G1 argument, so lanes sharing H(m)
    satisfy prod_i e([r_i]pk_i, H(m)) == e(sum_i [r_i]pk_i, H(m)): the
    per-lane Miller loops of a committee-duplicated batch collapse to
    one loop per UNIQUE message.  Masked lanes (padding/invalid) enter
    the sum as infinity — exactly the identity contribution the
    per-lane mask gave them — and a unique whose aggregate is infinity
    is masked out of the Miller stage (e(infinity, Q) == 1).

    group_idx: (U, G) lane indices of each unique's lanes (padded rows
    arbitrary); group_present: (U, G) False for group padding;
    wsig: stage_scalars' (1,)-batched signature sum.
    Returns ((x, y) affine aggregates (U, L), u_mask (U,), s_aff,
    s_mask): this program owns the signature's affine conversion."""
    agg, u_mask = _group_aggregates(pk_r_jac, miller_mask, group_idx,
                                    group_present)
    # ONE batched inversion at unique width + 1, not lane width
    # (infinity aggregates give garbage coords — u_mask carries them
    # out of the Miller loop, s_mask an infinity signature sum)
    agg_aff, s_aff, s_mask = affine_with_signature(agg, wsig)
    return agg_aff, u_mask, s_aff, s_mask


def stage_miller(pk_r_aff, hm_aff, mask, s_aff, s_mask):
    """Miller loops — width-polymorphic: per-lane inputs on the
    hm-gather path, per-unique aggregates on the grouped path — with
    the signature's row (-g1, S) appended INSIDE the program as the
    last row, so one scan at width U + 1 does what a private width-1
    loop did, and the host's packing and row buckets never see it.
    Returns (U + 1)-row Fq12 values; the last is ONE when s_mask is
    false."""
    def cat(a, b):
        return jnp.concatenate([a, b], axis=0)

    return PR.miller_loop(
        jax.tree_util.tree_map(cat, pk_r_aff, _neg_g1_row()),
        jax.tree_util.tree_map(cat, hm_aff, s_aff),
        mask=cat(mask, s_mask))


def fold_rows(ml):
    """Product of stage_miller's rows, before the final exponentiation.
    The U message rows fold as a power of two (tree_fold_pairs rolls
    only those into one loop); the signature's row, the last, joins
    with one more multiplication."""
    rows = jax.tree_util.tree_map(lambda x: x[:-1], ml)
    sig_row = jax.tree_util.tree_map(lambda x: x[-1], ml)
    return T.fq12_mul(PR.batch_product(rows), sig_row)


def stage_finish(ml):
    """Product of stage_miller's rows + final exponentiation + verdict."""
    return PR.pairing_check(fold_rows(ml))


_STAGED_JITS = None
_STAGED_LOCK = __import__("threading").Lock()


def staged_jits():
    global _STAGED_JITS
    if _STAGED_JITS is None:
        with _STAGED_LOCK:      # batch_verify runs via asyncio.to_thread
            if _STAGED_JITS is None:
                from ..infra import aotstore
                from . import mxu
                # mont path is part of the traced program, so it is
                # part of the store identity (an executable traced
                # for vpu must not serve an mxu process)
                mont = mxu.resolve()

                def _wrap(name, fn):
                    return aotstore.wrap(f"stage:{name}:{mont}",
                                         jax.jit(fn))
                _STAGED_JITS = {
                    "prepare": _wrap("prepare", stage_prepare),
                    "h2c": _wrap("h2c", stage_h2c),
                    "gather": _wrap("gather", stage_gather_hm),
                    "scalars": _wrap("scalars", stage_scalars),
                    "affine": _wrap("affine", stage_lane_affine),
                    "group": _wrap("group", stage_group),
                    "miller": _wrap("miller", stage_miller),
                    "finish": _wrap("finish", stage_finish),
                }
    return _STAGED_JITS


def _stage_runner(on_stage):
    import time
    jits = staged_jits()

    def run(name, *args):
        t0 = time.time()
        out = jits[name](*args)
        if on_stage is not None:
            jax.block_until_ready(out)
            on_stage(name, time.time() - t0)
        return out

    return run


def verify_staged_hm(pk_xs, pk_ys, pk_present, hm_aff, sig_x_plain,
                     sig_large, sig_inf, r_bits, lane_valid,
                     on_stage=None):
    """The staged PER-LANE pipeline downstream of hash-to-curve:
    per-lane H(m) affine points in (the provider's H(m) cache or
    stage_h2c + stage_gather_hm supplies them), verdict out.  This is
    the parity surface for the grouped path and the composition the
    sharded kernel uses.  `on_stage(name, seconds)` reports per-stage
    wall time (bench)."""
    run = _stage_runner(on_stage)
    pk_jac, sig_jac, lane_ok, miller_mask = run(
        "prepare", pk_xs, pk_ys, pk_present, sig_x_plain, sig_large,
        sig_inf, lane_valid)
    pk_r_jac, wsig = run("scalars", pk_jac, sig_jac, r_bits)
    pk_r_aff, s_aff, s_mask = run("affine", pk_r_jac, wsig)
    ml = run("miller", pk_r_aff, hm_aff, miller_mask, s_aff, s_mask)
    ok = run("finish", ml)
    return ok, lane_ok


def verify_staged_grouped(pk_xs, pk_ys, pk_present, hm_uniq, group_idx,
                          group_present, sig_x_plain, sig_large,
                          sig_inf, r_bits, lane_valid, on_stage=None):
    """The staged GROUPED pipeline: unique-width H(m) points in (from
    stage_h2c over uniques or the device H(m) cache), per-message
    pubkey aggregation via stage_group, Miller loops at UNIQUE width."""
    run = _stage_runner(on_stage)
    pk_jac, sig_jac, lane_ok, miller_mask = run(
        "prepare", pk_xs, pk_ys, pk_present, sig_x_plain, sig_large,
        sig_inf, lane_valid)
    pk_r_jac, wsig = run("scalars", pk_jac, sig_jac, r_bits)
    agg_aff, u_mask, s_aff, s_mask = run(
        "group", pk_r_jac, miller_mask, group_idx, group_present, wsig)
    ml = run("miller", agg_aff, hm_uniq, u_mask, s_aff, s_mask)
    ok = run("finish", ml)
    return ok, lane_ok


def verify_staged(pk_xs, pk_ys, pk_present, u0, u1, group_idx,
                  group_present, sig_x_plain, sig_large, sig_inf,
                  r_bits, lane_valid, on_stage=None):
    """The batched verification dispatch from the unique messages'
    draws, via the staged programs (single device, dedup-aware).

    pk_xs/pk_ys: (N, K, L) Montgomery limbs — per-triple pubkeys, each
        already validated (subgroup, non-infinity) by the caller's
        cache, padded to K along axis 1; aggregation happens in-kernel.
    u0/u1: Fq2 draws of the batch's UNIQUE messages' hash_to_field
        (host SHA-256), padded to a pow-2 bucket U <= N — h2c runs at
        unique width, not lane width.
    group_idx/group_present: (U, G) lane indices/mask of each unique
        message's lanes (stage_group: bilinearity folds those lanes
        into one Miller loop per unique).
    pk_present: (N, K) — False for key-padding slots.
    sig_x_plain: ((N, L), (N, L)) plain-form Fq2 x of each signature;
    sig_large: (N,) wire sign bit; sig_inf: (N,) infinity-signature mask.
    r_bits: (N, 64) bits of the nonzero random multipliers, MSB first.
    lane_valid: (N,) — False for padding lanes.
    `on_stage(name, seconds)` reports per-stage wall time (bench).

    Returns (ok, lane_ok): ok is the whole-batch pairing verdict;
    lane_ok flags lanes whose signature failed decompression/subgroup
    checks or whose keys aggregated to infinity (the caller must AND
    `ok` with all valid lanes' lane_ok).
    """
    run = _stage_runner(on_stage)
    hm_uniq = run("h2c", u0, u1)
    return verify_staged_grouped(pk_xs, pk_ys, pk_present, hm_uniq,
                                 group_idx, group_present, sig_x_plain,
                                 sig_large, sig_inf, r_bits, lane_valid,
                                 on_stage=on_stage)


# the programs of one group-sharded mesh dispatch, in launch order: the
# four shard-local stages, then the one cross-chip exchange
MESH_STAGES = ("prepare", "scalars", "group", "miller", "exchange")


def verify_kernel_sharded_grouped(mesh, axis: str = "dp"):
    """Multi-chip variant of the DEDUP-AWARE pipeline: message groups
    are the sharding unit, so every chip keeps the unique-message
    Miller grouping that the lane-sharded kernel forfeits, and runs
    the single-chip stage functions on its own lanes and rows.

    GROUP-ALIGNED contract (the provider's shard planner,
    teku_tpu/parallel.plan_group_shards, builds these layouts):

    - lanes are PERMUTED so each shard's lane block holds exactly the
      lanes of the message-group rows that shard owns (a group never
      crosses a shard boundary); lane-sharded inputs: pk_xs/pk_ys
      (N, K, L), pk_present (N, K), sig_x ((N, L), (N, L)), sig_large/
      sig_inf/lane_valid (N,), r_bits (N, 64);
    - group rows are ROW-sharded: hm_rows (the per-row H(m) affine
      tree, (U, L) leaves), group_idx (U, G) of SHARD-LOCAL lane
      indices, group_present (U, G).  Padding rows aggregate to
      infinity and mask themselves out of the Miller stage.

    Returns {name: function} over MESH_STAGES, each jitted apart by
    the caller like the staged programs of one chip (a stage compiles
    and caches on its own: a new group bucket costs `group` alone),
    each named `mesh_<name>` on the profiler's module line:

    - `prepare`, `scalars`, `group`: stage_prepare / stage_scalars /
      stage_group under shard_map, every argument and result sharded
      over its leading axis, NO communication.  A shard's `wsig` is
      its OWN partial sum S_k: by bilinearity prod_k e(-g1, S_k) ==
      e(-g1, sum_k S_k), so the signature's row rides each shard's one
      inversion and its scan exactly as on one chip.
    - `miller`: stage_miller at the shard's rows + that row, folded
      (fold_rows) to ONE pre-exponentiation Fq12 partial a shard
      ((n_shards, ...) leaves).  An empty shard's S_k is infinity and
      its rows are masked, so its partial is ONE.
    - `exchange`: ONE all_gather of the Fq12 partials, the only
      cross-chip traffic; their product and the final exponentiation
      (stage_finish's) are replicated.  Returns `ok`.

    `lane_ok` (from `prepare`) is in the PERMUTED lane order (callers
    un-permute on the host).
    """
    from jax.sharding import PartitionSpec as P

    def miller_fold(agg_aff, hm_rows, u_mask, s_aff, s_mask):
        partial = fold_rows(
            stage_miller(agg_aff, hm_rows, u_mask, s_aff, s_mask))
        return jax.tree_util.tree_map(lambda x: x[None], partial)

    def exchange(partials):
        gathered = jax.tree_util.tree_map(
            lambda x: jax.lax.all_gather(x[0], axis), partials)
        return PR.pairing_check(PR.batch_product(gathered))

    def over_shards(name, fn, out_specs):
        # every array of the pipeline, lanes, rows, a shard's (1,)
        # signature sum and its partial alike, is sharded over its
        # leading axis: one spec stands for every leaf
        sharded = jax.shard_map(fn, mesh=mesh, in_specs=P(axis),
                                out_specs=out_specs, check_vma=False)
        # a jitted function's name is its module's on the profiler's
        # line, which is all a trace reader sees of it
        sharded.__name__ = sharded.__qualname__ = f"mesh_{name}"
        return sharded

    stages = {"prepare": stage_prepare, "scalars": stage_scalars,
              "group": stage_group, "miller": miller_fold}
    out = {name: over_shards(name, fn, P(axis))
           for name, fn in stages.items()}
    out["exchange"] = over_shards("exchange", exchange, P())
    return out


def verify_kernel_sharded(mesh, axis: str = "dp"):
    """LEGACY multi-chip variant: lanes sharded over `axis` with NO
    message grouping (every lane pays its own Miller row — groups
    would cross shard boundaries), per-device local reductions, then
    an all_gather of one Fq12 value + one G2 point per device rides
    the ICI; the final exponentiation is replicated.  The production
    mesh path uses verify_kernel_sharded_grouped, which keeps the
    dedup pipeline by making group rows the sharding unit; this form
    remains the dryrun/CI harness kernel and the hm-input parity
    surface.

    hm-INPUT contract: the caller supplies per-lane H(m) affine points
    (hash-to-curve over unique messages is a global operation — the
    provider runs it once, cache-aware, before sharding lanes), so the
    shard function's inputs are all lane-sharded.

    Returns a function taking (pk_xs, pk_ys, pk_present, hm, sig_x,
    sig_large, sig_inf, r_bits, lane_valid) with verify_staged's result
    (to be called with GLOBAL batch arrays; N must divide the mesh size).
    """
    from jax.sharding import PartitionSpec as P

    lane = P(axis)
    lane2 = P(axis, None)       # (N, L) and (N, 64)
    lane3 = P(axis, None, None)  # (N, K, L)

    def shard_fn(pk_xs, pk_ys, pk_present, hm, sig_x, sig_large,
                 sig_inf, r_bits, lane_valid):
        ml, wsig, lane_ok = _lane_work(pk_xs, pk_ys, pk_present, hm,
                                       sig_x, sig_large, sig_inf, r_bits,
                                       lane_valid)
        local_prod = PR.batch_product(ml)
        local_sum = point_batch_sum(PT.G2_KIT, wsig)
        # gather the tiny per-device partials and combine identically
        gathered_prod = jax.tree_util.tree_map(
            lambda x: jax.lax.all_gather(x, axis), local_prod)
        gathered_sum = jax.tree_util.tree_map(
            lambda x: jax.lax.all_gather(x, axis), local_sum)
        ok = _finish(PR.batch_product(gathered_prod),
                     _signature_sum(gathered_sum))
        return ok, lane_ok

    in_specs = (lane3, lane3, lane2,
                ((lane2, lane2), (lane2, lane2)),   # hm affine x, y
                (lane2, lane2), lane, lane, lane2, lane)
    out_specs = (P(), lane)
    return jax.shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def aggregate_points_kernel(kit, xs, ys, present):
    """Sum a padded batch of affine points; absent lanes are infinity.
    Returns the Jacobian sum."""
    one = PT._broadcast_const(kit, kit.const(1 if kit is PT.G1_KIT else (1, 0)),
                              xs)
    jac = (xs, ys, one)
    inf = PT.infinity_like(kit, xs)
    jac = PT._select_point(kit, present, jac, inf)
    return point_batch_sum(kit, jac)
