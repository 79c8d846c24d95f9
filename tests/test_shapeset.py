"""Shape-set registry: anti-drift pins against the dispatch path.

`ops/shapeset.py` is only useful if it CANNOT diverge from what
`provider._pack` actually dispatches — a registry that
enumerates yesterday's buckets precompiles the wrong programs and the
compile wall comes back silently.  These tests pin the sharing:

- structurally: provider imports the shapeset module object and calls
  its bucket functions (no private copies);
- behaviorally: the policy constants equal the provider/loader knob
  defaults, and `batch_plan` reproduces the bucket decisions;
- the enumeration yields the kernel names `ops/verify.py` and
  `teku_tpu/parallel` register with the AOT store, deduplicated.
"""

import inspect

import pytest

from teku_tpu.ops import provider, shapeset
from teku_tpu.ops.provider import JaxBls12381


def test_provider_imports_shapeset_functions():
    # the module object itself is shared...
    assert provider.SS is shapeset
    # ...and every bucket decision in the dispatch path calls through
    # it: a private re-implementation is drift waiting to happen
    src = inspect.getsource(provider)
    for fn in ("SS.lane_bucket(", "SS.kmax_bucket(",
               "SS.group_rows(", "SS.group_bucket(",
               "SS.unique_bucket(", "SS.h2c_miss_bucket(",
               "SS.pk_validate_bucket(", "SS.shape_label("):
        assert fn in src, f"provider must bucket via shapeset: {fn}"


def test_policy_constants_match_provider_knob_defaults():
    impl = JaxBls12381(max_batch=8, min_bucket=4)
    assert impl._h2c_min_bucket == shapeset.H2C_MIN_BUCKET_DEFAULT
    assert impl._group_cap == shapeset.GROUP_CAP_DEFAULT


def test_service_tier_constants_match_loader_defaults():
    from teku_tpu.crypto.bls import loader
    sig = inspect.signature(loader.make_supervisor)
    assert sig.parameters["max_batch"].default \
        == shapeset.SERVICE_MAX_BATCH
    assert sig.parameters["min_bucket"].default \
        == shapeset.SERVICE_MIN_BUCKET


def test_bucket_helpers():
    assert shapeset.lane_bucket(5, 4) == 8
    assert shapeset.lane_bucket(1, 16) == 16
    assert shapeset.pk_validate_bucket(1) \
        == shapeset.PK_VALIDATE_FLOOR
    assert shapeset.pk_validate_bucket(33) == 64
    assert shapeset.h2c_miss_bucket(3, 8) == 8
    assert shapeset.h2c_miss_bucket(9, 8) == 16
    assert shapeset.shape_label(64, 2) == "64x2"
    assert shapeset.shape_label(64, 1, mesh_devices=4) == "64x1@m4"


def test_group_rows_polymorphic_over_counts_and_lane_lists():
    """The registry enumerates lane COUNTS; dispatch splits lane-index
    LISTS.  Same split rule, same row profile — or the enumerated
    group/miller shapes are not the dispatched ones."""
    counts = shapeset.group_rows([70, 3], group_cap=32)
    assert counts == [(0, 32), (0, 32), (0, 6), (1, 3)]
    lists = shapeset.group_rows([list(range(70)), [70, 71, 72]],
                                group_cap=32)
    assert [(u, len(c)) for u, c in lists] \
        == [(u, n) for u, n in counts]
    assert shapeset.group_bucket(counts) \
        == shapeset.group_bucket(lists) == 32


def test_batch_plan_all_unique_and_duplicated():
    plan = shapeset.batch_plan([1] * 12, min_bucket=4)
    assert plan["padded"] == 16 and plan["rows"] == 12
    assert plan["u_hm"] == 16
    assert plan["shape"] == "16x1"
    assert plan["h2c_bucket"] == 16, "cold boot: all rows miss"

    dup = shapeset.batch_plan([8] * 4, min_bucket=4, h2c_missing=0)
    assert dup["lanes"] == 32 and dup["rows"] == 4
    assert dup["group_bucket"] == 8
    assert dup["h2c_bucket"] == 0, "fully warm arena: no h2c program"


def test_warmup_profiles_shape():
    assert shapeset.warmup_profiles(4) == [
        ("x1", [1], None, 1), ("x4", [1, 1, 1, 1], None, 1)]
    profiles = shapeset.warmup_profiles(256)
    assert [name for name, *_ in profiles] \
        == ["x1", "x256", "x256dup8"]
    name, groups, missing, kmax = profiles[2]
    assert groups == [8] * 32
    assert missing == 0, "dup8 rides the arena the x256 warm filled"


def test_serving_shapes_cover_warmup_profiles():
    shapes = shapeset.serving_shapes(max_batch=256, min_bucket=16)
    for _name, groups, missing, kmax in shapeset.warmup_profiles(256):
        plan = shapeset.batch_plan(groups, min_bucket=16, kmax=kmax,
                                   h2c_missing=missing)
        assert plan["shape"] in shapes
    assert "16x1" in shapes, "the x1 probe shape is a serving shape"


def test_enumerate_programs_names_and_dedup():
    from teku_tpu.ops import mxu
    mont = mxu.resolve()
    programs = list(shapeset.enumerate_programs(
        max_batch=8, min_bucket=4))
    kernels = [k for k, _avals, _meta in programs]
    assert f"pk_validate:{mont}" in kernels
    stages = {m["stage"] for _k, _a, m in programs}
    assert stages == {"pk_validate", "h2c", "prepare", "scalars",
                      "group", "miller", "finish"}
    for k, _avals, meta in programs:
        if meta["stage"] not in ("pk_validate",):
            assert k.startswith("stage:"), k
            assert k.endswith(f":{mont}"), k
    # dedup: no (kernel, signature) appears twice
    from teku_tpu.infra import aotstore
    keys = [(k, aotstore.shape_sig(avals))
            for k, avals, _m in programs]
    assert len(keys) == len(set(keys))


def test_enumerate_programs_mesh_kernels():
    import jax
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 virtual devices (conftest XLA_FLAGS)")
    from teku_tpu import parallel
    mesh = parallel.make_mesh(2, advertise=False)
    programs = list(shapeset.enumerate_programs(
        max_batch=8, min_bucket=4, mesh=mesh))
    mesh_progs = {m["stage"]: k for k, _a, m in programs
                  if m["stage"].startswith("mesh_")}
    from teku_tpu.ops import verify as V
    assert set(mesh_progs) == {f"mesh_{n}" for n in V.MESH_STAGES}, \
        "mesh config must enumerate every sharded program"
    devices = [str(d) for d in mesh.devices.ravel()]
    for stage, kernel in mesh_progs.items():
        # the name the serving path registers for THIS device set —
        # a healed mesh over different devices must miss, never load
        # an executable bound to the wrong device assignment
        assert kernel == parallel.kernel_store_name(
            devices, "dp", stage.removeprefix("mesh_"))
    assert any(m["stage"] == "gather" for _k, _a, m in programs)


# --------------------------------------------------------------------------
# The registry against a real launch, at the benchmark cells' own sizes.
# Nothing is compiled or run at that size here: the staged programs are
# replaced by stand-ins that note their name and argument avals and hand
# on zeros of the shapes `jax.eval_shape` gives for the real stage.
# --------------------------------------------------------------------------

def _stand_in_stages(monkeypatch):
    import jax
    import jax.numpy as jnp

    from teku_tpu.infra import aotstore
    from teku_tpu.ops import mxu
    from teku_tpu.ops import verify as V
    mont = mxu.resolve()
    ran = []

    def stand_in(name, fn):
        def run(*args):
            ran.append((f"stage:{name}:{mont}", aotstore.shape_sig(args)))
            return jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, a.dtype),
                jax.eval_shape(fn, *args))
        return run

    monkeypatch.setattr(V, "_STAGED_JITS", {
        name: stand_in(name, fn) for name, fn in (
            ("prepare", V.stage_prepare), ("h2c", V.stage_h2c),
            ("gather", V.stage_gather_hm), ("scalars", V.stage_scalars),
            ("affine", V.stage_lane_affine), ("group", V.stage_group),
            ("miller", V.stage_miller), ("finish", V.stage_finish))})
    return ran


def _cell_provider(monkeypatch, env):
    """A provider at a cell's knobs whose key cache already holds the
    signers (as after set-up), so a drain launches the stage programs
    alone."""
    import numpy as np

    from teku_tpu.ops import limbs as fp
    for var, value in env.items():
        monkeypatch.setenv(var, str(value))
    impl = JaxBls12381(max_batch=256, min_bucket=256)
    pks = [bytes([0x80]) + i.to_bytes(47, "big") for i in range(1, 257)]
    for pk in pks:
        impl._pk_cache.put(pk, ("ok", np.zeros(fp.L, dtype=np.int64),
                                np.zeros(fp.L, dtype=np.int64)))
    return impl, pks


def _drain(pks, lane_groups, tag):
    sig = bytes([0x80]) + bytes(94) + b"\x01"
    triples, lane = [], 0
    for m, size in enumerate(lane_groups):
        for _ in range(size):
            triples.append(([pks[lane]], b"%s-%d" % (tag, m), sig))
            lane += 1
    return triples


BACKFILL_ENV = {"TEKU_TPU_H2C_MIN_BUCKET": 256,
                "TEKU_TPU_H2C_GROUP_CAP": 32,
                "TEKU_TPU_H2C_CACHE_CAP": "off"}
GOSSIP_ENV = {"TEKU_TPU_H2C_MIN_BUCKET": 16,
              "TEKU_TPU_H2C_GROUP_CAP": 32}


@pytest.mark.parametrize("env,lane_groups,h2c_missing", [
    (BACKFILL_ENV, [1] * 250, None),    # backfill-unique.saturate
    (BACKFILL_ENV, [1] * 173, None),    # .poisson: a partial batch
    (GOSSIP_ENV, [250], None),          # gossip: a fresh drain
    (GOSSIP_ENV, [250], 0)],            # gossip: a hit drain
    ids=["all-unique", "open-loop-partial", "gossip-fresh",
         "gossip-hit"])
def test_enumerated_programs_are_the_launched_ones(
        monkeypatch, env, lane_groups, h2c_missing):
    """For a drain of each benchmark cell, `enumerate_programs` names
    exactly the stage programs `provider._launch` runs, argument avals
    and all, and no other."""
    from teku_tpu.infra import aotstore
    impl, pks = _cell_provider(monkeypatch, env)
    if h2c_missing == 0:        # the arena already holds the message
        ran = _stand_in_stages(monkeypatch)
        impl.batch_verify(_drain(pks, lane_groups, b"drain"))
        assert any(k.startswith("stage:h2c:") for k, _s in ran)
    ran = _stand_in_stages(monkeypatch)
    impl.batch_verify(_drain(pks, lane_groups, b"drain"))
    monkeypatch.setattr(
        shapeset, "warmup_profiles",
        lambda max_batch, key_bucket=None: [
            ("drain", lane_groups, h2c_missing, 1)])
    enumerated = {
        (kernel, aotstore.shape_sig(avals))
        for kernel, avals, meta in shapeset.enumerate_programs(
            max_batch=256, min_bucket=256,
            h2c_min_bucket=impl._h2c_min_bucket,
            group_cap=impl._group_cap)
        if meta["stage"] != "pk_validate"}
    assert set(ran) == enumerated
    assert len(ran) == len(enumerated) == (5 if h2c_missing == 0 else 6)


@pytest.mark.parametrize("env,lane_groups,rows", [
    (BACKFILL_ENV, [1] * 250, 256), (GOSSIP_ENV, [250], 16)],
    ids=["all-unique", "gossip"])
def test_registry_follows_the_signature_row(monkeypatch, env, lane_groups,
                                            rows):
    """The signature's row is added inside `stage_miller`, so the
    registry's avals follow it through `jax.eval_shape` with no field of
    `batch_plan` knowing: `stage_group` takes the (1,)-batched signature
    sum, `stage_miller` the row's Q and mask and returns one row more
    than the row bucket, which is all `stage_finish` takes."""
    from jax.tree_util import tree_leaves as jax_leaves

    from teku_tpu.ops import limbs as fp
    impl, _pks = _cell_provider(monkeypatch, env)
    monkeypatch.setattr(shapeset, "warmup_profiles",
                        lambda max_batch, key_bucket=None: [
                            ("drain", lane_groups, None, 1)])
    avals = {meta["stage"]: avals for _k, avals, meta in
             shapeset.enumerate_programs(
                 max_batch=256, min_bucket=256,
                 h2c_min_bucket=impl._h2c_min_bucket,
                 group_cap=impl._group_cap)}
    plan = shapeset.batch_plan(lane_groups, min_bucket=256,
                               h2c_min_bucket=impl._h2c_min_bucket,
                               group_cap=impl._group_cap)
    assert plan["u_hm"] == rows
    *_, wsig = avals["group"]
    assert len(avals["group"]) == 5
    assert [leaf.shape for leaf in jax_leaves(wsig)] == [(1, fp.L)] * 6
    agg_aff, hm, u_mask, s_aff, s_mask = avals["miller"]
    assert u_mask.shape == (rows,) and s_mask.shape == (1,)
    assert [leaf.shape for leaf in jax_leaves(s_aff)] == [(1, fp.L)] * 4
    (ml,) = avals["finish"]
    assert {leaf.shape for leaf in jax_leaves(ml)} == {(rows + 1, fp.L)}


@pytest.mark.parametrize("lanes,rows", [(4096, 128), (4096, 512),
                                        (2049, 256)])
def test_wide_batches_plan_the_same_stage_programs(monkeypatch, lanes,
                                                   rows):
    """Above 2048 lanes at 8 or more a row, on a TPU, the parent's rule
    planned a second scalars program; the plan and the programs are now
    those of every other shape."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    per_row = lanes // rows
    groups = [per_row] * (rows - 1) + [lanes - per_row * (rows - 1)]
    plan = shapeset.batch_plan(groups, min_bucket=16)
    assert plan["lanes"] == lanes and plan["msm_path"] == "ladder"
    monkeypatch.setattr(shapeset, "warmup_profiles",
                        lambda max_batch, key_bucket=None: [
                            ("wide", groups, None, 1)])
    stages = [m["stage"] for _k, _a, m in shapeset.enumerate_programs(
        max_batch=4096, min_bucket=16)]
    assert stages == ["pk_validate", "h2c", "prepare", "scalars",
                      "group", "miller", "finish"]
