"""A block's signature set on the device path: key counts past one
committee.

Since Electra (EIP-7549) one on-chain attestation aggregates every
committee of its slot that voted the same `AttestationData`, so a
mainnet block's majority vote carries ~31,300 keys at 1,000,000
validators.  The provider takes a lane up to the preset's widest valid
signature (`shapeset.MAX_KEYS_PER_LANE`) and counts a wider one it
refuses; the node warms the bucket of a block's widest lane
(`shapeset.block_key_bucket`) and every narrower one a block can reach.
"""

import asyncio
import dataclasses
import json
import os
import random

import pytest

from teku_tpu.crypto import bls
from teku_tpu.crypto.bls import loader
from teku_tpu.crypto.bls.constants import R
from teku_tpu.crypto.bls.pure_impl import PureBls12381
from teku_tpu.infra import dispatchledger
from teku_tpu.ops import shapeset
from teku_tpu.spec.config import MAINNET, MINIMAL
from teku_tpu.spec.networks import MAINNET_NETWORK

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ORACLE = PureBls12381()
ELECTRA = MAINNET_NETWORK.config            # mainnet's fork schedule
MINIMAL_ELECTRA = dataclasses.replace(MINIMAL, ELECTRA_FORK_EPOCH=0)
WIDE = 2_100            # keys of a lane just over the old 2,048 cap
SIGNERS = 48            # seeded keys the wide lanes cycle through


# --------------------------------------------------------------------------
# The ceiling and the buckets
# --------------------------------------------------------------------------

@pytest.mark.parametrize("cfg,active,bucket", [
    (ELECTRA, 1_000_000, 32_768),       # 64 committees of 489
    (ELECTRA, 2_000_000, 65_536),       # 64 of 977
    (ELECTRA, None, 512),               # no active count: the gossip one
    (MAINNET, 1_000_000, 512),          # Deneb: one committee a vote
    (MINIMAL_ELECTRA, 64, 32),          # the sync committee is wider
    (MINIMAL_ELECTRA, 1_024, 128),      # 4 committees of 32
    (MINIMAL, 1_024, 32),
], ids=["electra-1M", "electra-2M", "electra-preset", "deneb-1M",
        "minimal-64", "minimal-1024", "minimal-deneb"])
def test_block_key_bucket(cfg, active, bucket):
    assert shapeset.block_key_bucket(cfg, active) == bucket
    assert bucket >= shapeset.aggregate_key_bucket(cfg, active)


def test_a_deneb_block_lands_in_the_aggregate_bucket():
    """Before Electra a block's widest vote is one committee's: its
    bucket is the 512 that `mainnet-aggregates` warms and measures."""
    assert shapeset.block_key_bucket(MAINNET, 1_000_000) \
        == shapeset.aggregate_key_bucket(MAINNET, 1_000_000) \
        == shapeset.SERVICE_KEY_BUCKET == 512


def test_the_ceilings_are_the_presets():
    from teku_tpu.ops.provider import JaxBls12381
    assert shapeset.SERVICE_BLOCK_KEY_BUCKET \
        == shapeset.block_key_bucket(ELECTRA, 1_000_000)
    assert shapeset.MAX_KEYS_PER_LANE == 131_072 \
        == MAINNET.MAX_VALIDATORS_PER_COMMITTEE \
        * MAINNET.MAX_COMMITTEES_PER_SLOT
    assert JaxBls12381().max_keys_per_lane == shapeset.MAX_KEYS_PER_LANE
    assert shapeset.BLOCK_LANES == 3 + MAINNET.MAX_ATTESTATIONS_ELECTRA


def test_cli_node_derives_the_block_bucket_from_its_state():
    from teku_tpu import cli
    from teku_tpu.spec import create_spec
    from teku_tpu.spec.genesis import interop_genesis
    spec = create_spec("minimal")
    state, _sks = interop_genesis(spec.config, 64, 1_578_009_600)
    assert cli._block_key_bucket(spec, state) \
        == shapeset.block_key_bucket(spec.config, 64) == 32


# --------------------------------------------------------------------------
# What the node warms
# --------------------------------------------------------------------------

def test_warmup_profiles_gain_the_block():
    profiles = shapeset.warmup_profiles(256, 512, 32_768)
    names = [name for name, *_ in profiles]
    assert names[:5] == ["x1", "x256", "x256dup8", "aggregate",
                         "aggregate_forged"]
    assert names[5:7] == ["block", "block_forged"]
    assert names[7:] == ["block_narrow"] * 15
    for name, groups, missing, kmax in profiles[5:7]:
        # eleven lanes, a message each, all fresh but the RANDAO root
        assert groups == [1] * 11 and missing == 10 and kmax == 32_768
        plan = shapeset.batch_plan(groups, min_bucket=16, kmax=kmax,
                                   h2c_missing=missing)
        assert (plan["shape"], plan["u_hm"], plan["group_bucket"],
                plan["h2c_bucket"]) == ("16x32768", 16, 1, 16)
    assert [kmax for *_, kmax in profiles[7:]] \
        == [1 << i for i in range(14, -1, -1)]
    assert shapeset.block_buckets(32_768)[0] == 32_768
    assert len(shapeset.block_buckets(32_768)) == 16
    # without a block bucket nothing changes
    assert shapeset.warmup_profiles(256, 512) == profiles[:5]
    assert shapeset.warmup_profiles(8, None, 64) \
        == shapeset.warmup_profiles(8)


def _programs(plan):
    """The stage programs one dispatch of `plan` runs, by what each
    program's shape depends on (single device)."""
    p, u, g, k = (plan["padded"], plan["u_hm"], plan["group_bucket"],
                  plan["kmax"])
    out = {("prepare", p, k), ("scalars", p), ("group", p, u, g),
           ("miller", u), ("finish", u)}
    if plan["h2c_bucket"]:
        out.add(("h2c", plan["h2c_bucket"]))
    return out


def test_every_key_bucket_a_block_can_reach_is_warm():
    """A block of 3 to 11 lanes (no attestation up to eight), any
    number of them fresh, its widest lane of any count up to the block
    bucket: every program its dispatch runs is one the node's warm
    profiles ran."""
    bucket, min_bucket = 32_768, shapeset.SERVICE_MIN_BUCKET
    warmed = set()
    for _name, groups, missing, kmax in shapeset.warmup_profiles(
            shapeset.SERVICE_MAX_BATCH, 512, bucket):
        warmed |= _programs(shapeset.batch_plan(
            groups, min_bucket=min_bucket, kmax=kmax,
            h2c_missing=missing))
    widest = sorted({k + d for k in shapeset.block_buckets(bucket)
                     for d in (-1, 0, 1) if 1 <= k + d <= bucket})
    for lanes in range(3, shapeset.BLOCK_LANES + 1):
        for fresh in range(lanes + 1):
            for keys in widest:
                plan = shapeset.batch_plan(
                    [1] * lanes, min_bucket=min_bucket,
                    kmax=shapeset.kmax_bucket(keys), h2c_missing=fresh)
                assert _programs(plan) <= warmed, (lanes, fresh, keys)
    # above the block bucket (an attester slashing's indexed
    # attestation) the bucket is not warm
    plan = shapeset.batch_plan([1] * 3, min_bucket=min_bucket,
                               kmax=2 * bucket)
    assert not _programs(plan) <= warmed


def test_serving_shapes_and_programs_cover_the_block():
    shapes = shapeset.serving_shapes(key_bucket=512,
                                     block_key_bucket=32_768)
    assert {f"16x{k}" for k in shapeset.block_buckets(32_768)} <= shapes
    assert "16x32768" not in shapeset.serving_shapes(key_bucket=512)
    from teku_tpu.ops import limbs as fp
    programs = list(shapeset.enumerate_programs(
        max_batch=16, min_bucket=16, block_key_bucket=8))
    prepares = [(meta["profile"], tuple(avals[0].shape))
                for _kernel, avals, meta in programs
                if meta["stage"] == "prepare"]
    # (16, 1) is the x1 probe's program already
    assert prepares[-3:] == [("block", (16, 8, fp.L)),
                             ("block_narrow", (16, 4, fp.L)),
                             ("block_narrow", (16, 2, fp.L))]
    assert ("x1", (16, 1, fp.L)) in prepares


class _Recorder:
    """Answers each warm batch by its profile's expected verdict and
    notes it."""

    min_bucket = 16

    def __init__(self):
        self.batches = []

    def batch_verify(self, triples):
        self.batches.append(triples)
        return True


def test_warm_blocks_follow_the_profiles(monkeypatch):
    """The block profiles' dispatches: eleven lanes at each bucket, the
    wide one the third; the first block verifies and its forged twin
    does not (by the oracle); each narrower block re-uses the first's
    messages."""
    bucket = 64
    answers = []

    def warm(impl, name, kmax, batch, want):
        answers.append((name, kmax, want))
        impl.batch_verify(batch)

    monkeypatch.setattr(loader, "_warm_dispatch", warm)
    device = _Recorder()
    warmed = loader._warmup_batches(device, 16, None, bucket)
    assert warmed == [1, 2, 4, 8, 16, 32, 64]
    blocks = device.batches[-8:]
    assert [a[:2] for a in answers[-8:]] == (
        [("block", 64), ("block_forged", 64)]
        + [("block_narrow", k) for k in (32, 16, 8, 4, 2, 1)])
    assert [a[2] for a in answers[-8:]] == [True, False] + [True] * 6
    first, forged, *narrow = blocks
    for block, (_n, kmax, _w) in zip(blocks, answers[-8:]):
        assert len(block) == shapeset.BLOCK_LANES
        assert [len(pks) for pks, _m, _s in block] \
            == [1, 1, kmax] + [1] * 8
    # the RANDAO root is the x1 probe's, already in the arena
    assert first[1][1] == forged[1][1] == device.batches[0][0][1]
    assert ORACLE.fast_aggregate_verify(*first[2])
    assert not ORACLE.fast_aggregate_verify(*forged[2])
    assert not {m for _p, m, _s in first[2:]} \
        & {m for _p, m, _s in forged[2:]}
    for block in narrow:
        assert [m for _p, m, _s in block] == [m for _p, m, _s in first]
        assert ORACLE.fast_aggregate_verify(*block[2])


# --------------------------------------------------------------------------
# The device provider past the old 2,048-key cap (CPU, seeded keys)
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def keys():
    rng = random.Random(0xB10C)
    sks = [rng.randrange(1, R) for _ in range(SIGNERS)]
    return sks, [ORACLE.secret_key_to_public_key(sk) for sk in sks]


def _triple(keys, n, msg, start=0, forged=False):
    """An aggregate of `n` keys (the seeded signers in turn from
    `start`) over `msg`; `forged` signs it by other keys."""
    sks, pks = keys
    idx = [(start + q) % SIGNERS for q in range(n)]
    sk = sum(sks[i] for i in idx) % R
    if forged:
        sk = sk % (R - 1) + 1
    return [pks[i] for i in idx], msg, ORACLE.sign(sk, msg)


@pytest.fixture(scope="module")
def device(keys):
    from teku_tpu.ops.provider import JaxBls12381
    impl = JaxBls12381(max_batch=16, min_bucket=16)
    impl._resolve_pks(keys[1])
    return impl


def _block(keys, tag, forged=False):
    """A block-shaped signature set: the proposer (1 key), the RANDAO
    reveal (1), a slot-wide vote over the old cap, a minority vote, a
    late vote and a sync aggregate; the wide vote forged if asked."""
    return [_triple(keys, 1, b"%s proposer" % tag, 0),
            _triple(keys, 1, b"randao", 1),
            _triple(keys, WIDE, b"%s majority" % tag, 2, forged),
            _triple(keys, 40, b"%s minority" % tag, 3),
            _triple(keys, 3, b"%s late" % tag, 4),
            _triple(keys, 32, b"%s sync" % tag, 5)]


def test_a_triple_over_the_old_cap_verifies_on_the_device(keys, device):
    """Four lanes, one of 2,100 keys: true as signed (the parent's
    2,048-key cap read it False without a dispatch), false forged; the
    ledger record names the key bucket and the host's key seconds."""
    lanes = [_triple(keys, 1, b"over proposer"),
             _triple(keys, 1, b"over randao", 1),
             _triple(keys, WIDE, b"over vote", 2),
             _triple(keys, 5, b"over late", 3)]
    seq = dispatchledger.LEDGER.recorded_total
    assert device.batch_verify(lanes) is True
    rec = [r for r in dispatchledger.LEDGER.snapshot()
           if r.get("seq", 0) > seq][-1]
    assert rec["shape"] == "16x4096" and rec["kmax"] == 4096
    assert rec["keys"] == 2 + WIDE + 5
    assert rec["key_pack_s"] > 0
    forged = lanes[:2] + [_triple(keys, WIDE, b"over vote", 2, True)] \
        + lanes[3:]
    assert device.batch_verify(forged) is False


@pytest.mark.parametrize("forged", [False, True], ids=["valid", "forged"])
def test_a_block_set_through_the_batch_verifier_agrees_with_the_oracle(
        keys, device, forged):
    from teku_tpu.spec.verifiers import BatchSignatureVerifier
    block = _block(keys, b"forged" if forged else b"valid", forged)
    want = all(ORACLE.fast_aggregate_verify(*t) for t in block)
    assert want is not forged
    verifier = BatchSignatureVerifier()
    assert all(verifier.verify(*t) for t in block)
    bls.set_implementation(device)
    try:
        assert verifier.batch_verify() is want
    finally:
        bls.reset_implementation()


def test_a_lane_over_the_ceiling_is_refused_and_counted(keys):
    from teku_tpu.ops import provider
    impl = provider.JaxBls12381(max_keys_per_lane=8)
    before = provider._M_KEY_CAP.value
    seq = dispatchledger.LEDGER.recorded_total
    assert impl.batch_verify([_triple(keys, 8, b"at"),
                              _triple(keys, 9, b"over")]) is False
    assert provider._M_KEY_CAP.value == before + 1
    assert dispatchledger.LEDGER.recorded_total == seq, "no dispatch"


# --------------------------------------------------------------------------
# The benchmark's block cell plans what the node warms
# --------------------------------------------------------------------------

def _bench_json(*parts):
    with open(os.path.join(ROOT, *parts)) as fh:
        return json.load(fh)


def test_mainnet_electra_blocks_plan_pin():
    """`mainnet-electra-blocks` with `saturate-blocks` (pure Python, no
    signing): a drain is one block of eleven triples, its widest a
    slot-wide vote, in the 16 x 32,768 shape the node's block profile
    warms."""
    from benchmarks.harness import cell, traffic
    config = _bench_json("benchmarks", "configs",
                         "mainnet-electra-blocks.json")
    mix = _bench_json("benchmarks", "traffic", "saturate-blocks.json")
    plan = traffic.plan(config, mix, 2_147_490_001, 30)
    assert plan.drain == 1 and plan.backlog == 4
    assert len(plan.pool) == 304
    knobs = config["knobs"]
    assert knobs["service"]["max_batch"] == shapeset.BLOCK_LANES
    for batch in plan.warm + [[t] for t in plan.pool[:32]]:
        lanes = traffic.lanes(batch)
        assert len(lanes) == 11
        assert 27_000 <= max(len(t.signers) for t in lanes) <= 31_250
        assert all(len(set(t.signers)) == len(t.signers) for t in lanes)
        sig = cell.shape_signature(batch, knobs, arena_warm=False)
        assert sig == (False, "16x32768", 16, 1, 16)
    node = shapeset.batch_plan(
        shapeset.warmup_profiles(256, 512, 32_768)[5][1], min_bucket=16,
        kmax=shapeset.SERVICE_BLOCK_KEY_BUCKET, h2c_min_bucket=8)
    assert (node["shape"], node["u_hm"], node["group_bucket"]) \
        == ("16x32768", 16, 1)


class _Counting(PureBls12381):
    """Answers every batch true, noting how many triples it held."""

    def __init__(self):
        super().__init__()
        self.sizes = []

    def batch_verify(self, triples):
        self.sizes.append(len(triples))
        return True


def test_the_service_takes_one_block_a_drain():
    """At the cell's batch of 11 triples the service drains one block
    (a task of eleven) at a time, however many are queued."""
    from benchmarks.harness import traffic
    from teku_tpu.services.signatures import (
        AggregatingSignatureVerificationService)
    config = _bench_json("benchmarks", "configs",
                         "mainnet-electra-blocks.json")
    svc_knobs = config["knobs"]["service"]
    assert traffic.drain_tasks(config) == 1
    impl = _Counting()

    async def main():
        svc = AggregatingSignatureVerificationService(
            num_workers=svc_knobs["workers"],
            queue_capacity=svc_knobs["queue"],
            max_batch_size=svc_knobs["max_batch"])
        await svc.start()
        try:
            futs = [svc.verify_multi([([b"k"], b"m%d/%d" % (b, i), b"s")
                                      for i in range(11)])
                    for b in range(5)]
            return await asyncio.gather(*futs)
        finally:
            await svc.stop()

    bls.set_implementation(impl)
    try:
        assert asyncio.run(main()) == [True] * 5
    finally:
        bls.reset_implementation()
    assert impl.sizes == [11] * 5
