"""The canonical serving shape set: one enumerable compile registry.

Every observability layer points at the compile wall (PERF.md:
``compile_wall_share`` 0.91, 842 s cold compile, the doctor's 136 s
cache-load finding) — and the fix requires knowing EXACTLY which
programs serving will dispatch.  This module is that registry: the
pow-2 bucket policy (lane bucket x unique-h2c bucket x group cap x
mont path x mesh width) as pure functions, plus the
enumeration of (kernel, argument avals) pairs the warmup/serving path
traces — the input to ``cli precompile`` and the coverage oracle for
the doctor's ``cold_compile_on_hot_path`` finding.

Anti-drift contract: ``ops/provider.py`` imports THESE functions for
its dispatch bucketing (it has no private copy), so the registry and
dispatch reality cannot diverge — tests/test_shapeset.py pins the
sharing both structurally (same function objects) and behaviorally
(``batch_plan`` reproduces the dispatch ledger's shape fields).

Pure-policy helpers up top are host-only (importable without jax);
``enumerate_programs`` imports jax lazily to chain ``jax.eval_shape``
through the real stage functions, so intermediate-stage avals are
DERIVED from the kernels, never hand-maintained.
"""

from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from ..infra.pow2 import next_pow2

# The policy constants provider buckets with (its env knobs default to
# these — the drift test pins the equality):
H2C_MIN_BUCKET_DEFAULT = 8      # TEKU_TPU_H2C_MIN_BUCKET
GROUP_CAP_DEFAULT = 32          # TEKU_TPU_H2C_GROUP_CAP
PK_VALIDATE_FLOOR = 16          # pubkey-validation bucket floor
# the service-tier dispatch defaults (loader.make_supervisor /
# make_mesh_healer) — what a default `cli node` boot warms
SERVICE_MAX_BATCH = 256
SERVICE_MIN_BUCKET = 16
# the key bucket a mainnet-preset node warms for its aggregates
# (`aggregate_key_bucket` of the mainnet preset): what `cli precompile`
# builds and the doctor's coverage oracle assumes
SERVICE_KEY_BUCKET = 512
# the block key bucket of a mainnet node at ~1,000,000 validators
# (`block_key_bucket`: an Electra block's slot-wide aggregate of 64
# committees of 489), likewise
SERVICE_BLOCK_KEY_BUCKET = 32768
# the most keys one lane may hold: the widest valid signature of the
# mainnet preset, an Electra attestation (EIP-7549 `committee_bits`)
# over every committee of its slot, MAX_VALIDATORS_PER_COMMITTEE x
# MAX_COMMITTEES_PER_SLOT; the provider refuses a wider lane
MAX_KEYS_PER_LANE = 2048 * 64
# a block's signature set as the node warms it: the proposer's
# signature, the RANDAO reveal, the SyncAggregate and
# MAX_ATTESTATIONS_ELECTRA (8) attestations, a lane each
BLOCK_LANES = 3 + 8


# --------------------------------------------------------------------------
# Bucket policy (pure, host-only — provider imports these)
# --------------------------------------------------------------------------

def lane_bucket(n: int, min_bucket: int) -> int:
    """Padded lane width of an n-lane single-device dispatch."""
    return max(next_pow2(n), min_bucket)


def kmax_bucket(max_keys: int) -> int:
    """Padded keys-per-lane width (the `kmax` shape axis)."""
    return next_pow2(max_keys)


def aggregate_key_bucket(cfg, active_validators: Optional[int] = None
                         ) -> int:
    """The `kmax` bucket of the largest aggregate a node of this
    preset verifies on gossip: the `SyncAggregate` (`SYNC_COMMITTEE_SIZE`
    keys) and, where the node knows how many validators are active, an
    attestation committee's aggregate on the `beacon_aggregate_and_proof`
    topic (the spec's `get_committee_count_per_slot` split of the active
    set; the largest committee is the ceiling).  It covers ONE
    committee: an Electra block's attestations aggregate a whole slot's
    committees, which is `block_key_bucket`'s.  `cfg` is a preset
    (`spec.config.SpecConfig`).  Mainnet: 512 (a committee of 489 at
    1,000,000 validators); minimal: 32."""
    largest = cfg.SYNC_COMMITTEE_SIZE
    if active_validators:
        largest = max(largest, _committees(cfg, active_validators)[1])
    return kmax_bucket(largest)


def _committees(cfg, active_validators: int) -> Tuple[int, int]:
    """(committees a slot, members of the largest committee) at
    `active_validators`: the spec's `get_committee_count_per_slot`
    split of the active set."""
    per_slot = max(1, min(
        cfg.MAX_COMMITTEES_PER_SLOT,
        active_validators // cfg.SLOTS_PER_EPOCH
        // cfg.TARGET_COMMITTEE_SIZE))
    committee = -(-active_validators // (cfg.SLOTS_PER_EPOCH * per_slot))
    return per_slot, min(committee, cfg.MAX_VALIDATORS_PER_COMMITTEE)


def block_key_bucket(cfg, active_validators: Optional[int] = None
                     ) -> int:
    """The `kmax` bucket of the widest signature a block of this node
    carries.  From Electra on (`cfg.ELECTRA_FORK_EPOCH` scheduled) an
    on-chain attestation aggregates every committee of its slot that
    voted the same `AttestationData` (EIP-7549), so a block's majority
    vote carries up to committees a slot x the largest committee
    (mainnet at 1,000,000 validators: 64 x 489 = 31,296 -> 32,768).
    Before Electra an attestation is one committee's, which
    `aggregate_key_bucket` already covers with the SyncAggregate; so
    does a node that does not know its active count."""
    from ..spec.config import FAR_FUTURE_EPOCH
    bucket = aggregate_key_bucket(cfg, active_validators)
    if not active_validators or cfg.ELECTRA_FORK_EPOCH == FAR_FUTURE_EPOCH:
        return bucket
    per_slot, committee = _committees(cfg, active_validators)
    return max(bucket, kmax_bucket(per_slot * committee))


def _row_size(g) -> int:
    return g if isinstance(g, int) else len(g)


def group_rows(groups: Sequence, group_cap: int) -> List[Tuple[int,
                                                               object]]:
    """Miller rows for per-unique-message lane groups: committees
    larger than the group cap split across rows (a message may own
    several rows; the provider resolves H(m) once a message and hands
    every one of its rows the same point).  Each group is a
    lane COUNT (registry enumeration) or a lane-index list (provider
    dispatch — this is the split rule `_pack` runs); rows
    keep the caller's form: [(unique index, count-or-chunk)]."""
    rows: List[Tuple[int, object]] = []
    for u, g in enumerate(groups):
        size = _row_size(g)
        for off in range(0, size, group_cap):
            if isinstance(g, int):
                rows.append((u, min(group_cap, size - off)))
            else:
                rows.append((u, g[off:off + group_cap]))
    return rows


def group_bucket(rows: Sequence[Tuple[int, object]]) -> int:
    """Padded lanes-per-row width (the (U, G) gather's G axis)."""
    return next_pow2(max(_row_size(g) for _, g in rows))


def unique_bucket(n_rows: int, h2c_min_bucket: int) -> int:
    """The canonical unique bucket: the width of the H(m) tree the
    Miller loops read (one entry a row) and, with the arena off, of
    the h2c dispatch over the batch's distinct messages.  Computed
    from the batch alone — identical for single-device and mesh
    dispatch of the same batch."""
    return max(next_pow2(n_rows), h2c_min_bucket)


def h2c_miss_bucket(n_missing: int, h2c_min_bucket: int) -> int:
    """Width of the h2c dispatch serving a batch's arena misses, and
    of the arena's insert behind it: `n_missing` counts distinct
    MESSAGES, never the rows a split committee owns."""
    return max(next_pow2(n_missing), h2c_min_bucket)


def pk_validate_bucket(n: int) -> int:
    """Width of the pubkey-validation dispatch for n cache misses."""
    return max(next_pow2(n), PK_VALIDATE_FLOOR)


def shape_label(padded: int, kmax: int, mesh_devices: int = 0) -> str:
    """The ledger/metric `shape` string for a padded dispatch."""
    return f"{padded}x{kmax}" + (
        f"@m{mesh_devices}" if mesh_devices else "")


def batch_plan(lane_groups: Sequence[int], *, min_bucket: int,
               kmax: int = 1,
               h2c_min_bucket: int = H2C_MIN_BUCKET_DEFAULT,
               group_cap: int = GROUP_CAP_DEFAULT,
               mesh_devices: int = 0,
               h2c_missing: Optional[int] = None) -> dict:
    """The full bucket decision for one batch profile, exactly as
    ``provider._pack`` makes it.  ``lane_groups`` is the
    batch's lanes-per-unique-message profile (``[1]*256`` = all
    unique, ``[8]*32`` = committee-duplicated, ``[250]`` = one
    committee over 8 rows of 32); ``h2c_missing`` is how many distinct
    MESSAGES miss the H(m) arena (default: all — the cold-boot case;
    0 = fully warm, no h2c program).  H(m) is resolved once a message,
    so ``h2c_missing`` and ``h2c_bucket`` (the width of ``stage_h2c``
    and of the arena's insert) count messages, as the ledger record's
    ``h2c`` block does, while ``rows`` / ``u_hm`` count Miller rows."""
    lanes = sum(lane_groups)
    rows = group_rows(lane_groups, group_cap)
    g_bucket = group_bucket(rows)
    u_hm = unique_bucket(len(rows), h2c_min_bucket)
    if mesh_devices >= 2:
        from .. import parallel
        plan = parallel.plan_group_shards(
            [(u, list(range(_row_size(g)))) for u, g in rows], lanes,
            mesh_devices,
            min_lanes=max(min_bucket, mesh_devices) // mesh_devices,
            min_rows=max(h2c_min_bucket // mesh_devices, 1))
        padded = plan.padded
        u_total = plan.rows_total
        lanes_per_shard = plan.lanes_per_shard
        rows_per_shard = plan.rows_per_shard
    else:
        padded = lane_bucket(lanes, min_bucket)
        u_total = u_hm
        lanes_per_shard = rows_per_shard = None
    missing = len(lane_groups) if h2c_missing is None else h2c_missing
    return {
        "lanes": lanes, "kmax": kmax, "rows": len(rows),
        "messages": len(lane_groups), "h2c_missing": missing,
        "group_bucket": g_bucket, "u_hm": u_hm, "padded": padded,
        "u_total": u_total,
        # one scalars stage; the key stays because the benchmark's
        # shape signature reads it (ROADMAP C15)
        "msm_path": "ladder",
        "mesh_devices": mesh_devices if mesh_devices >= 2 else 0,
        "lanes_per_shard": lanes_per_shard,
        "rows_per_shard": rows_per_shard,
        "h2c_bucket": (h2c_miss_bucket(missing, h2c_min_bucket)
                       if missing else 0),
        "shape": shape_label(
            padded, kmax,
            mesh_devices if mesh_devices >= 2 else 0),
    }


# --------------------------------------------------------------------------
# The warmup batch profiles (mirrors loader._warmup_batches)
# --------------------------------------------------------------------------

def block_buckets(block_key_bucket: int) -> List[int]:
    """Every key bucket a valid block's widest lane can fall in, the
    block bucket first: a late or low-participation slot's majority
    vote is narrower, down to one key."""
    out, k = [], kmax_bucket(block_key_bucket)
    while k:
        out.append(k)
        k //= 2
    return out


def warmup_profiles(max_batch: int, key_bucket: Optional[int] = None,
                    block_key_bucket: Optional[int] = None
                    ) -> List[Tuple[str, List[int], Optional[int], int]]:
    """The (name, lane_groups, h2c_missing, kmax) profiles supervisor
    WARMING and the selfheal reshape warm dispatch, in order: the x1
    probe shape, the all-unique primary bucket, (>= 8 lanes) the
    committee-duplicated shape whose messages the all-unique batch
    already put in the H(m) arena (zero h2c) and, given a `key_bucket`,
    the aggregate-and-proof drain twice.  It is `max_batch` lanes in
    thirds, a task being a selection proof (1 key, one message all tasks
    share), the aggregator's signature (1 key, a message of its own) and
    the aggregate (`key_bucket` keys, its committee's message): first a
    slot's first drain, every message fresh; then a later drain
    (`aggregate_forged`, one aggregate forged), whose slot root and
    committee messages the first put in the arena, so only the
    aggregators' own messages are hashed.  Given a `block_key_bucket`,
    a block's signature set (`BLOCK_LANES` lanes, a message each, its
    widest lane at that bucket) twice, every message fresh but the
    RANDAO root (the x1 probe's), the second (`block_forged`) with its
    widest lane forged; then `block_narrow` at each narrower bucket a
    block can reach (`block_buckets`), on the first block's messages,
    so that no block's key bucket compiles on the hot path."""
    profiles: List[Tuple[str, List[int], Optional[int], int]] = [
        ("x1", [1], None, 1),
        (f"x{max_batch}", [1] * max_batch, None, 1),
    ]
    if max_batch >= 8:
        profiles.append(
            (f"x{max_batch}dup8", [8] * (max_batch // 8), 0, 1))
    if key_bucket is not None and max_batch >= 3:
        tasks = max_batch // 3
        groups = [tasks] + [1] * (2 * tasks)
        kmax = kmax_bucket(key_bucket)
        profiles += [("aggregate", groups, None, kmax),
                     ("aggregate_forged", groups, tasks, kmax)]
    if block_key_bucket is not None and max_batch >= BLOCK_LANES:
        groups = [1] * BLOCK_LANES
        widest, *narrower = block_buckets(block_key_bucket)
        profiles += [("block", groups, BLOCK_LANES - 1, widest),
                     ("block_forged", groups, BLOCK_LANES - 1, widest)]
        profiles += [("block_narrow", groups, 0, k) for k in narrower]
    return profiles


def serving_shapes(max_batch: int = SERVICE_MAX_BATCH,
                   min_bucket: int = SERVICE_MIN_BUCKET,
                   mesh_devices: int = 0,
                   h2c_min_bucket: int = H2C_MIN_BUCKET_DEFAULT,
                   group_cap: int = GROUP_CAP_DEFAULT,
                   key_bucket: Optional[int] = None,
                   block_key_bucket: Optional[int] = None) -> set:
    """The ledger `shape` strings ``cli precompile`` covers for one
    serving config — the doctor's cold_compile_on_hot_path coverage
    oracle.  Includes every duplication profile from all-unique down
    to fully-duplicated at each pow-2 batch size up to max_batch (the
    warmup profiles are a subset), at one key a lane and, given a
    `key_bucket`, at that many; given a `block_key_bucket`, a block's
    lanes at every bucket `block_buckets` names."""
    shapes = set()
    for kmax in (block_buckets(block_key_bucket)
                 if block_key_bucket is not None else ()):
        shapes.add(batch_plan(
            [1] * BLOCK_LANES, min_bucket=min_bucket, kmax=kmax,
            h2c_min_bucket=h2c_min_bucket, group_cap=group_cap,
            mesh_devices=mesh_devices)["shape"])
    kmaxes = (1,) if key_bucket is None else (1, key_bucket)
    size = 1
    while size <= next_pow2(max_batch):
        dup = 1
        while dup <= size:
            groups = [dup] * (size // dup)
            if groups:
                for kmax in kmaxes:
                    plan = batch_plan(
                        groups, min_bucket=min_bucket, kmax=kmax,
                        h2c_min_bucket=h2c_min_bucket,
                        group_cap=group_cap, mesh_devices=mesh_devices)
                    shapes.add(plan["shape"])
            dup *= 2
        size *= 2
    return shapes


# --------------------------------------------------------------------------
# Program enumeration (jax from here down)
# --------------------------------------------------------------------------

def _sds(shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def enumerate_programs(*, max_batch: int = SERVICE_MAX_BATCH,
                       min_bucket: int = SERVICE_MIN_BUCKET,
                       key_bucket: Optional[int] = None,
                       block_key_bucket: Optional[int] = None,
                       h2c_min_bucket: int = H2C_MIN_BUCKET_DEFAULT,
                       group_cap: int = GROUP_CAP_DEFAULT,
                       mesh: Optional[object] = None,
                       axis: str = "dp"
                       ) -> Iterator[Tuple[str, tuple, dict]]:
    """Yield (kernel name, argument avals, meta) for every program a
    supervisor boot of this config dispatches — the precompile work
    list.  Stage-input avals are chained through the REAL stage
    functions with ``jax.eval_shape``; kernel names match the ones
    ``ops/verify.py``/``teku_tpu/parallel`` register with the AOT
    store.  ``mesh`` is a live ``jax.sharding.Mesh`` (or None for
    single-device); mesh programs additionally need the gather
    scatter program and the sharded kernel itself.  `key_bucket` adds
    the aggregate profile (``warmup_profiles``) at that many keys a
    lane, `block_key_bucket` the block profiles.
    """
    import jax
    import numpy as np

    from . import limbs as fp
    from . import mxu
    from . import verify as V

    mont = mxu.resolve()
    i64 = np.int64
    i32 = np.int32
    b_ = np.bool_
    mesh_devices = 0
    if mesh is not None:
        mesh_devices = int(np.prod(
            [mesh.shape[a] for a in mesh.axis_names]))

    def stage_name(name: str) -> str:
        return f"stage:{name}:{mont}"

    seen: set = set()

    def emit(kernel: str, avals: tuple, meta: dict):
        from ..infra import aotstore
        key = (kernel, aotstore.shape_sig(avals))
        if key in seen:
            return None
        seen.add(key)
        return kernel, avals, meta

    # the probe's pubkey-validation program (one arena miss)
    pk_n = pk_validate_bucket(1)
    out = emit(f"pk_validate:{mont}",
               (_sds((pk_n, fp.L), i64), _sds((pk_n,), b_)),
               {"shape": f"pkv{pk_n}", "stage": "pk_validate"})
    if out:
        yield out

    for name, lane_groups, h2c_missing, kmax in warmup_profiles(
            max_batch, key_bucket, block_key_bucket):
        plan = batch_plan(lane_groups, min_bucket=min_bucket,
                          kmax=kmax, h2c_min_bucket=h2c_min_bucket,
                          group_cap=group_cap,
                          mesh_devices=mesh_devices,
                          h2c_missing=h2c_missing)
        meta = {"profile": name, "shape": plan["shape"],
                "mont_path": mont}
        P, K, U, G = (plan["padded"], plan["kmax"], plan["u_total"],
                      plan["group_bucket"])
        # the h2c program over this profile's arena misses
        if plan["h2c_bucket"]:
            mb = plan["h2c_bucket"]
            u_half = (_sds((mb, fp.L), i64), _sds((mb, fp.L), i64))
            out = emit(stage_name("h2c"), (u_half, u_half),
                       {**meta, "stage": "h2c", "bucket": mb})
            if out:
                yield out
        # the H(m) tree at arena width feeds miller (and, on the mesh
        # path, the gather scatter): leading dim is the unique bucket
        uh = plan["u_hm"]
        u_half = (_sds((uh, fp.L), i64), _sds((uh, fp.L), i64))
        hm_uniq = jax.eval_shape(V.stage_h2c, u_half, u_half)
        prepare_in = (
            _sds((P, K, fp.L), i64), _sds((P, K, fp.L), i64),
            _sds((P, K), b_),
            (_sds((P, fp.L), i64), _sds((P, fp.L), i64)),
            _sds((P,), b_), _sds((P,), b_), _sds((P,), b_))
        r_bits = _sds((P, 64), i64)
        group_idx = _sds((U, G), i32)
        group_present = _sds((U, G), b_)
        if mesh_devices >= 2:
            # mesh: the single-chip gather, then the stages as
            # sharded programs of their own and the exchange
            row_gather = _sds((U,), i32)
            hm_rows = jax.eval_shape(V.stage_gather_hm, hm_uniq,
                                     row_gather)
            out = emit(stage_name("gather"), (hm_uniq, row_gather),
                       {**meta, "stage": "gather"})
            if out:
                yield out
            from jax.sharding import NamedSharding, PartitionSpec
            from .. import parallel
            names = [str(d) for d in np.ravel(mesh.devices)]
            over_chips = NamedSharding(mesh, PartitionSpec(axis))
            fns = V.verify_kernel_sharded_grouped(mesh, axis)

            def emit_mesh(name, avals):
                # every argument arrives sharded over its leading axis
                avals = jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(
                        a.shape, a.dtype, sharding=over_chips), avals)
                return emit(
                    parallel.kernel_store_name(names, axis, name), avals,
                    {**meta, "stage": f"mesh_{name}", "axis": axis,
                     "devices": mesh_devices})

            # the chain GroupShardedVerifier.kernel runs, avals from
            # the real sharded stage functions
            chain = [("prepare", prepare_in)]
            pk_jac, sig_jac, _lane_ok, miller_mask = jax.eval_shape(
                fns["prepare"], *chain[-1][1])
            chain.append(("scalars", (pk_jac, sig_jac, r_bits)))
            pk_r_jac, wsig = jax.eval_shape(fns["scalars"],
                                            *chain[-1][1])
            chain.append(("group", (pk_r_jac, miller_mask, group_idx,
                                    group_present, wsig)))
            agg_aff, u_mask, s_aff, s_mask = jax.eval_shape(
                fns["group"], *chain[-1][1])
            chain.append(("miller", (agg_aff, hm_rows, u_mask, s_aff,
                                     s_mask)))
            chain.append(("exchange", (jax.eval_shape(
                fns["miller"], *chain[-1][1]),)))
            for name, avals in chain:
                out = emit_mesh(name, avals)
                if out:
                    yield out
            continue
        out = emit(stage_name("prepare"), prepare_in,
                   {**meta, "stage": "prepare"})
        if out:
            yield out
        prep_out = jax.eval_shape(V.stage_prepare, *prepare_in)
        pk_jac, sig_jac, _lane_ok, miller_mask = prep_out
        sc_in = (pk_jac, sig_jac, r_bits)
        out = emit(stage_name("scalars"), sc_in,
                   {**meta, "stage": "scalars"})
        if out:
            yield out
        pk_r_jac, wsig = jax.eval_shape(V.stage_scalars, *sc_in)
        grp_in = (pk_r_jac, miller_mask, group_idx, group_present, wsig)
        out = emit(stage_name("group"), grp_in,
                   {**meta, "stage": "group"})
        if out:
            yield out
        agg_aff, u_mask, s_aff, s_mask = jax.eval_shape(V.stage_group,
                                                        *grp_in)
        mil_in = (agg_aff, hm_uniq, u_mask, s_aff, s_mask)
        out = emit(stage_name("miller"), mil_in,
                   {**meta, "stage": "miller"})
        if out:
            yield out
        ml = jax.eval_shape(V.stage_miller, *mil_in)
        out = emit(stage_name("finish"), (ml,),
                   {**meta, "stage": "finish"})
        if out:
            yield out
