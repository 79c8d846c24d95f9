"""The aggregate topic on the node's own path: the key bucket a node
warms, the registry that names it, and a supervised boot that serves an
aggregate drain with nothing left to compile.

A `SignedAggregateAndProof` is one task of three checks (the selection
proof, the aggregator's own signature, the aggregate of a committee's
participants); `shapeset.aggregate_key_bucket` sizes the aggregate's
key axis from the node's preset and state, `warmup_profiles` adds the
drain of `max_batch` lanes in thirds at that bucket, and the loader's
WARMING dispatches it (once as signed, once with a forged aggregate)
before the breaker-guarded provider is installed.
"""

import asyncio
import json
import os
import random

import pytest

from teku_tpu.crypto import bls
from teku_tpu.crypto.bls import loader
from teku_tpu.crypto.bls.constants import R
from teku_tpu.crypto.bls.pure_impl import PureBls12381
from teku_tpu.infra import compilecache, dispatchledger
from teku_tpu.infra.metrics import MetricsRegistry
from teku_tpu.infra.supervisor import WarmupVetoError
from teku_tpu.ops import shapeset
from teku_tpu.spec.config import MAINNET, MINIMAL

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ORACLE = PureBls12381()
MAX_BATCH = 16          # lanes a warm drain takes: 5 tasks of 3
KEY_BUCKET = 8          # the aggregate's keys a lane at this size


# --------------------------------------------------------------------------
# The bucket a node warms
# --------------------------------------------------------------------------

@pytest.mark.parametrize("cfg,active,bucket", [
    (MAINNET, None, 512),           # preset alone: the sync committee
    (MAINNET, 1_000_000, 512),      # committees of 489
    (MAINNET, 2_000_000, 1024),     # committees of 977
    (MAINNET, 16_384, 512),         # genesis minimum: committees of 128
    (MINIMAL, None, 32),
    (MINIMAL, 64, 32),              # an interop devnet: committees of 4
], ids=["mainnet-preset", "mainnet-1M", "mainnet-2M", "mainnet-genesis",
        "minimal-preset", "minimal-64"])
def test_aggregate_key_bucket(cfg, active, bucket):
    assert shapeset.aggregate_key_bucket(cfg, active) == bucket


def test_service_key_bucket_is_the_mainnet_presets():
    """What `cli precompile` builds and the doctor assumes is what a
    mainnet node's supervisor warms."""
    assert shapeset.SERVICE_KEY_BUCKET \
        == shapeset.aggregate_key_bucket(MAINNET, 1_000_000)


def test_cli_node_derives_the_bucket_from_its_state():
    from teku_tpu import cli
    from teku_tpu.spec import create_spec
    from teku_tpu.spec.genesis import interop_genesis
    spec = create_spec("minimal")
    state, _sks = interop_genesis(spec.config, 64, 1_578_009_600)
    assert cli._key_bucket(spec, state) == 32


# --------------------------------------------------------------------------
# The registry: warm profiles, serving shapes, programs
# --------------------------------------------------------------------------

def test_warmup_profiles_gain_the_aggregate_drain():
    profiles = shapeset.warmup_profiles(256, 512)
    assert [name for name, *_ in profiles] \
        == ["x1", "x256", "x256dup8", "aggregate", "aggregate_forged"]
    # 85 tasks: their selection proofs under one message, then a
    # message each for the aggregator's signature and the aggregate
    (_, first, cold, kmax), (_, later, fresh, kmax2) = profiles[-2:]
    assert first == later == [85] + [1] * 170
    assert kmax == kmax2 == 512
    # a slot's first drain hashes every message; a later one only the
    # aggregators' own: 171 and 85 misses, miss buckets 256 and 128
    for missing, h2c_bucket in ((cold, 256), (fresh, 128)):
        plan = shapeset.batch_plan(first, min_bucket=16, kmax=kmax,
                                   h2c_missing=missing)
        assert plan["shape"] == "256x512" and plan["lanes"] == 255
        assert plan["group_bucket"] == 32 and plan["u_hm"] == 256
        assert plan["h2c_bucket"] == h2c_bucket
    # without a key bucket a node warms one key a lane, as before
    assert shapeset.warmup_profiles(256) == profiles[:3]


def test_serving_shapes_cover_the_key_bucket():
    shapes = shapeset.serving_shapes(key_bucket=512)
    assert {"256x512", "16x512", "256x1"} <= shapes
    assert "256x512" not in shapeset.serving_shapes()
    for _name, groups, missing, kmax in shapeset.warmup_profiles(256, 512):
        plan = shapeset.batch_plan(groups, min_bucket=16, kmax=kmax,
                                   h2c_missing=missing)
        assert plan["shape"] in shapes


def test_doctor_flags_a_cold_key_bucket_compile():
    """The doctor's `cold_compile_on_hot_path` oracle covers the key
    bucket `cli precompile` builds."""
    from teku_tpu.infra import doctor
    rec = {"seq": 1, "shape": "256x512", "lanes": 252,
           "compile": {"outcome": "compile", "enqueue_s": 239.6}}
    found = doctor._precompile_findings([rec])
    assert [f["metrics"]["shape"] for f in found] == ["256x512"]


def test_enumerate_programs_include_the_key_bucket():
    from teku_tpu.ops import limbs as fp
    programs = list(shapeset.enumerate_programs(
        max_batch=MAX_BATCH, min_bucket=MAX_BATCH, key_bucket=KEY_BUCKET))
    agg = {m["stage"]: (avals, m) for _k, avals, m in programs
           if m.get("profile") == "aggregate"}
    # one key a lane runs the same h2c, scalars, miller and finish
    # programs; the key bucket brings its own prepare and its (16, 8)
    # group
    assert set(agg) == {"prepare", "group"}
    avals, meta = agg["prepare"]
    assert meta["shape"] == f"{MAX_BATCH}x{KEY_BUCKET}"
    assert tuple(avals[0].shape) == (MAX_BATCH, KEY_BUCKET, fp.L)
    assert not [m for _k, _a, m in shapeset.enumerate_programs(
        max_batch=MAX_BATCH, min_bucket=MAX_BATCH)
        if m.get("profile") == "aggregate"]


def test_cli_precompile_enumerates_the_key_bucket(monkeypatch, tmp_path,
                                                  capsys):
    """`cli precompile` builds the mainnet key bucket into the AOT
    store (the XLA work stubbed: the enumeration is what is pinned)."""
    from teku_tpu import cli
    from teku_tpu.infra import aotstore
    from teku_tpu.ops import limbs as fp
    built = []

    def precompile(self, avals):
        built.append((self.kernel, [tuple(a.shape) for a in
                                    _leaves(avals)]))
        return "load"

    monkeypatch.setattr(aotstore.AotDispatcher, "precompile", precompile)
    rc = cli.main(["precompile", "--store-dir", str(tmp_path),
                   "--max-batch", str(MAX_BATCH), "--min-bucket",
                   str(MAX_BATCH)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "profile=aggregate" in out
    prepares = [shapes[0] for kernel, shapes in built
                if kernel.startswith("stage:prepare:")]
    assert (MAX_BATCH, shapeset.SERVICE_KEY_BUCKET, fp.L) in prepares
    assert (MAX_BATCH, 1, fp.L) in prepares


def _leaves(tree):
    import jax
    return jax.tree_util.tree_leaves(tree)


# --------------------------------------------------------------------------
# The loader's warm batches
# --------------------------------------------------------------------------

class OracleDevice:
    """Answers each warm batch as the oracle does, and notes it."""

    min_bucket = MAX_BATCH

    def __init__(self, lie: bool = False):
        self.lie = lie
        self.batches = []

    def batch_verify(self, triples):
        self.batches.append(triples)
        if self.lie:
            return True
        return all(ORACLE.fast_aggregate_verify(list(pks), msg, sig)
                   for pks, msg, sig in triples)


def _groups(batch):
    sizes = {}
    for _pks, msg, _sig in batch:
        sizes[msg] = sizes.get(msg, 0) + 1
    return sorted(sizes.values(), reverse=True)


def test_warm_batches_follow_the_profiles():
    """Every warm dispatch is its profile's shape, the aggregate drain
    verifies and its forged twin reads False; the key buckets warmed
    are returned for the readiness snapshot."""
    device = OracleDevice()
    warmed = loader._warmup_batches(device, MAX_BATCH, KEY_BUCKET)
    assert warmed == [1, KEY_BUCKET]
    profiles = shapeset.warmup_profiles(MAX_BATCH, KEY_BUCKET)
    assert len(device.batches) == len(profiles)
    seen = set()
    for (_name, groups, missing, kmax), batch in zip(profiles,
                                                     device.batches):
        assert _groups(batch) == sorted(groups, reverse=True)
        assert max(len(pks) for pks, _m, _s in batch) == kmax
        fresh = {m for _p, m, _s in batch} - seen
        if missing is not None:
            # the arena holds what an earlier warm batch hashed
            assert len(fresh) == missing
        seen |= fresh
    first, later = device.batches[-2:]
    # the later drain: the same selection proofs and aggregates, fresh
    # own messages, its last aggregate forged
    assert [t for k, t in enumerate(first) if k % 3 == 0] \
        == [t for k, t in enumerate(later) if k % 3 == 0]
    assert [t[:2] for k, t in enumerate(first) if k % 3 == 2] \
        == [t[:2] for k, t in enumerate(later) if k % 3 == 2]
    assert not {t[1] for t in first[1::3]} & {t[1] for t in later[1::3]}
    assert ORACLE.fast_aggregate_verify(*first[-1])
    assert not ORACLE.fast_aggregate_verify(*later[-1])


def test_a_device_that_passes_the_forged_aggregate_is_vetoed():
    with pytest.raises(WarmupVetoError, match="aggregate"):
        loader._warmup_batches(OracleDevice(lie=True), MAX_BATCH,
                               KEY_BUCKET)


# --------------------------------------------------------------------------
# A supervised boot on the real provider serves the first aggregate drain
# --------------------------------------------------------------------------

def _aggregate_tasks(rng, n_tasks, participants, forge=None):
    """`n_tasks` aggregate-and-proof tasks on seeded random keys: the
    selection proofs under one slot root, an envelope message each and
    an aggregate of `participants(i)` keys each.  `forge`: (task,
    triple) signed by other keys."""
    slot_root = rng.randbytes(32)
    tasks = []
    for i in range(n_tasks):
        agg = rng.randrange(1, R)
        keys = [rng.randrange(1, R) for _ in range(participants(i))]
        envelope, committee = rng.randbytes(32), rng.randbytes(32)
        triples = [([agg], slot_root), ([agg], envelope), (keys, committee)]
        task = []
        for t, (sks, msg) in enumerate(triples):
            sk = sum(sks) % R
            if forge == (i, t):
                sk = sk % (R - 1) + 1
            task.append(([ORACLE.secret_key_to_public_key(k) for k in sks],
                         msg, ORACLE.sign(sk, msg)))
        tasks.append(task)
    return tasks


def _served():
    """`bls_verify_requests_total` of the guarded provider (the global
    registry's), by (backend, reason)."""
    from teku_tpu.infra.metrics import GLOBAL_REGISTRY
    fam = GLOBAL_REGISTRY.metrics().get("bls_verify_requests_total")
    return {key: child.value
            for key, child in (fam._items() if fam is not None else ())}


def _oracle_verdict(task):
    return all(ORACLE.fast_aggregate_verify(pks, msg, sig)
               for pks, msg, sig in task)


def test_aggregate_warm_boot_serves_the_first_drain():
    """`make_supervisor(warm=True)` with the node's key bucket warms the
    aggregate profile before READY; the first aggregate drain through
    the guarded facade then compiles nothing, trips nothing and is the
    device's, and every task's verdict (a forged aggregate, a forged
    selection proof, a participant set of one) is the oracle's."""
    reg = MetricsRegistry()
    rng = random.Random(0xA66)
    drain = [t for task in _aggregate_tasks(
        rng, MAX_BATCH // 3, lambda i: rng.randint(5, KEY_BUCKET))
        for t in task]
    singles = (_aggregate_tasks(rng, 1, lambda i: 6, forge=(0, 2))
               + _aggregate_tasks(rng, 1, lambda i: 7, forge=(0, 0))
               + _aggregate_tasks(rng, 1, lambda i: 1)
               + _aggregate_tasks(rng, 1, lambda i: KEY_BUCKET))

    async def main():
        sup = loader.make_supervisor(
            max_batch=MAX_BATCH, min_bucket=MAX_BATCH,
            key_bucket=KEY_BUCKET, registry=reg, probe_base_delay_s=0.1,
            round_delay_s=0.1)
        await sup.start()
        try:
            assert await sup.wait_ready(1200.0)
            snap = sup.snapshot()
            assert snap["warmup_cache"]["finished"] is True
            assert snap["warmup_cache"]["key_buckets"] == [1, KEY_BUCKET]
            guarded = bls.get_implementation()
            assert isinstance(guarded, loader.GuardedBls12381)
            for name in ("aggregate", "aggregate_forged"):
                assert loader._M_WARMUP.labels(
                    profile=name, kmax=str(KEY_BUCKET)).value >= 1
            # the signers' keys resolved first, in the probe's own
            # 16-key program, as a node's validator-key cache holds them
            pks = list(dict.fromkeys(
                pk for task in [drain] + singles
                for pks_, _m, _s in task for pk in pks_))
            for i in range(0, len(pks), 16):
                guarded.device._resolve_pks(pks[i:i + 16])
            seq = dispatchledger.LEDGER.recorded_total
            served_before = _served()
            before = compilecache.stats()
            assert await asyncio.to_thread(bls.batch_verify, drain)
            moved = compilecache.delta(before)
            first = [r for r in dispatchledger.LEDGER.snapshot()
                     if r.get("seq", 0) > seq]
            assert [r["shape"] for r in first] \
                == [f"{MAX_BATCH}x{KEY_BUCKET}"]
            assert first[0]["compile"]["outcome"] == "cache_hit"
            assert moved["backend_compiles"] == 0, moved
            verdicts = [await asyncio.to_thread(bls.batch_verify, task)
                        for task in singles]
            assert verdicts == [_oracle_verdict(t) for t in singles] \
                == [False, False, True, True]
            assert _served() == {
                **served_before,
                ("device", "ok"): served_before.get(("device", "ok"), 0)
                + 1 + len(singles)}
            assert guarded.breaker.state == guarded.breaker.CLOSED
            trips = reg.metrics()["bls_device_circuit_trips_total"]
            assert trips.value == 0
        finally:
            await sup.stop()
            bls.reset_implementation()

    asyncio.run(main())


# --------------------------------------------------------------------------
# The benchmark's cell plans what the node warms
# --------------------------------------------------------------------------

def _bench_json(*parts):
    with open(os.path.join(ROOT, *parts)) as fh:
        return json.load(fh)


def test_mainnet_aggregates_plan_pin():
    """`mainnet-aggregates` with `saturate-aggregates` (pure Python, no
    signing): every drain is 84 tasks = 252 lanes in a 256 x 512 shape,
    the shape the node's aggregate profile warms at mainnet."""
    from benchmarks.harness import cell, traffic
    config = _bench_json("benchmarks", "configs", "mainnet-aggregates.json")
    mix = _bench_json("benchmarks", "traffic", "saturate-aggregates.json")
    plan = traffic.plan(config, mix, 2_147_490_001, 30)
    assert plan.drain == 84 and plan.backlog == 4 * 84
    assert len(plan.pool) == 9_408          # ~3.2x what the cell takes
    knobs = config["knobs"]
    drains = [plan.pool[i:i + 84] for i in range(0, 84 * 12, 84)]
    for batch in plan.warm + drains:
        assert len(traffic.lanes(batch)) == 252
        assert max(len(t.signers) for t in traffic.lanes(batch)) <= 488
        lanes, shape, u_hm, group, _h2c = cell.shape_signature(
            batch, knobs, arena_warm=False)
        assert (lanes, shape, u_hm, group) == (False, "256x512", 256, 32)
    node = shapeset.batch_plan(
        shapeset.warmup_profiles(256, shapeset.SERVICE_KEY_BUCKET)[-1][1],
        min_bucket=256, kmax=shapeset.SERVICE_KEY_BUCKET,
        h2c_min_bucket=256)
    assert (node["shape"], node["u_hm"], node["group_bucket"]) \
        == ("256x512", 256, 32)
