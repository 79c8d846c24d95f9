"""The four-chip cell's path at a size a CPU holds: four of the eight
virtual devices, `benchmarks/configs/mainnet-subnet-gossip-mesh4.json`'s
shape scaled down (a drain of 8 single-key tasks, group cap 2, a
shard's floors 2 lanes x 1 row where the cell has 64 x 4).

- the sharded programs' verdicts are the plain reference's
  (`benchmarks.reference.bls.fast_aggregate_verify`) and the single-chip
  pipeline's, on one message split over all four shards, two messages,
  an infinity signature, batches that leave two and three shards empty,
  and one forged lane, which the service's bisection finds through the
  mesh;
- the share test (`model-configs` guide, section 4): with fixed
  multipliers the product of the four shards' Fq12 partials is the
  single-chip pipeline's product before the final exponentiation, the
  signature's pair counted once;
- `plan_group_shards` gives every drain the cell can meet (one message
  of 250 and the 249 straddles of two, 464 tasks a message, rows of at
  most 32) 64 lanes x 4 rows a shard at group bucket 32: one shape;
- a mesh dispatch's ledger record stamps each program launch, with
  the chips it ran on, inside `device_enqueue`.
"""

import asyncio

import numpy as np
import pytest

import jax

from benchmarks.harness import traffic
from benchmarks.reference import bls as ref
from teku_tpu import parallel
from teku_tpu.crypto import bls
from teku_tpu.infra import dispatchledger
from teku_tpu.ops import pairing as PR
from teku_tpu.ops import shapeset
from teku_tpu.ops import towers as T
from teku_tpu.ops import verify as V
from teku_tpu.ops.provider import JaxBls12381
from teku_tpu.services.signatures import (
    AggregatingSignatureVerificationService)

SEED = 3_000_000_019
SIGNERS = 8
DRAIN = 8
GROUP_CAP = 2
H2C_MIN_BUCKET = 4              # // 4 chips: 1 row a shard
MIN_BUCKET = 8                  # // 4 chips: 2 lanes a shard
_G2_INF = bytes([0xC0] + [0] * 95)


def _message(tag: str) -> bytes:
    return f"{SEED}/mesh4/{tag}".encode().ljust(32, b".")


def _triple(signer: int, message: bytes, forged: bool = False):
    sk = traffic.secret_key(SEED, signer)
    return ([ref.public_key(sk)], message,
            ref.sign(sk + (1 if forged else 0), message))


def _batch(groups, tag, forged_at=None):
    """`groups` lanes a message, signers in turn (no pair twice)."""
    out = []
    for g, size in enumerate(groups):
        for _ in range(size):
            out.append(_triple(len(out) % SIGNERS, _message(f"{tag}/{g}"),
                               forged=len(out) == forged_at))
    return out


@pytest.fixture(scope="module")
def impls():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices (see conftest XLA_FLAGS)")
    mp = pytest.MonkeyPatch()
    mp.setenv("TEKU_TPU_H2C_GROUP_CAP", str(GROUP_CAP))
    mp.setenv("TEKU_TPU_H2C_MIN_BUCKET", str(H2C_MIN_BUCKET))
    try:
        mesh = parallel.make_mesh(devices=jax.devices()[:4],
                                  advertise=False)
        meshed = JaxBls12381(max_batch=DRAIN, min_bucket=MIN_BUCKET,
                             mesh=mesh)
        single = JaxBls12381(max_batch=DRAIN, min_bucket=MIN_BUCKET)
    finally:
        mp.undo()
    assert meshed.mesh_info["n_devices"] == 4
    return meshed, single


# name -> (lanes a message, forged lane, lanes whose signature is the
# point at infinity, shards that hold a row)
CASES = {
    "one_message_over_four_shards": ([8], None, (), 4),
    "two_messages": ([4, 4], None, (), 4),
    "straddle_6_2": ([6, 2], None, (), 4),
    "forged_lane": ([8], 5, (), 4),
    "infinity_signature": ([8], None, (3,), 4),
    "two_shards_empty": ([1, 1], None, (), 2),
    "three_shards_empty": ([2], None, (), 1),
    "one_task": ([1], None, (), 1),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_mesh_verdict_is_the_references_and_one_chips(impls, name):
    meshed, single = impls
    groups, forged_at, inf_at, shards_used = CASES[name]
    triples = _batch(groups, name, forged_at)
    for i in inf_at:
        triples[i] = (triples[i][0], triples[i][1], _G2_INF)
    want = all(ref.fast_aggregate_verify(*t) for t in triples)
    assert want is (forged_at is None and not inf_at)
    seq = dispatchledger.LEDGER.recorded_total
    assert meshed.batch_verify(triples) is want
    assert single.batch_verify(triples) is want
    rec = next(r for r in dispatchledger.LEDGER.snapshot()
               if r["seq"] > seq and r["mesh"]["devices"] == 4)
    assert sum(1 for rows in rec["mesh"]["shard_rows"] if rows) \
        == shards_used
    assert (rec["mesh"]["lanes_per_shard"],
            rec["mesh"]["rows_per_shard"]) == (2, 1)


def test_the_services_bisection_finds_the_forged_task_through_the_mesh(
        impls):
    meshed, _single = impls
    triples = _batch([DRAIN], "bisect", forged_at=6)

    async def main():
        bls.set_implementation(meshed)
        try:
            svc = AggregatingSignatureVerificationService(
                num_workers=1, max_batch_size=DRAIN, split_threshold=2)
            await svc.start()
            futs = [svc.verify(*t) for t in triples]
            got = await asyncio.gather(*futs)
            await svc.stop()
        finally:
            bls.reset_implementation()
        return got

    seq = dispatchledger.LEDGER.recorded_total
    got = asyncio.run(main())
    assert got == [ref.fast_aggregate_verify(*t) for t in triples]
    assert got == [i != 6 for i in range(DRAIN)]
    # the whole, its halves and quarters, then one by one: all sharded
    records = [r for r in dispatchledger.LEDGER.snapshot()
               if r["seq"] > seq]
    assert len(records) >= 7
    assert {r["mesh"]["devices"] for r in records} == {4}


def _packed(impl, triples):
    semis = [impl.prepare_batch_verify(t) for t in triples]
    return impl._pack(semis, randomize=False)


def _hm(impl, pack):
    return impl._hm_device(
        impl._hm_arena_plan(pack.digests, pack.draws), pack)


def _mesh_product(meshed, triples):
    """The four shards' pre-exponentiation partials and their product,
    by the provider's own packing and the mesh's own programs."""
    pack = _packed(meshed, triples)
    hm_rows = V.staged_jits()["gather"](
        _hm(meshed, pack), np.asarray(pack.row_gather))
    programs = meshed._sharded.programs()
    pk_jac, sig_jac, _ok, mask = programs["prepare"](
        pack.pk_xs, pack.pk_ys, pack.pk_present, pack.sx, pack.s_large,
        pack.s_inf, pack.lane_valid)
    pk_r, wsig = programs["scalars"](pk_jac, sig_jac, pack.r_bits)
    agg, u_mask, s_aff, s_mask = programs["group"](
        pk_r, mask, pack.group_idx, pack.group_present, wsig)
    partials = programs["miller"](agg, hm_rows, u_mask, s_aff, s_mask)
    assert jax.tree_util.tree_leaves(partials)[0].shape[0] == 4
    # a shard without a row contributes the identity
    ones = np.asarray(T.fq12_is_one(partials))
    assert list(~ones) == [bool(rows) for rows in pack.plan.shard_rows]
    return PR.batch_product(partials)


def _chip_product(single, triples):
    """The single-chip pipeline's product of its rows and its ONE
    signature row, before the final exponentiation."""
    one = _packed(single, triples)
    jits = V.staged_jits()
    pk_jac, sig_jac, _ok, mask = jits["prepare"](
        one.pk_xs, one.pk_ys, one.pk_present, one.sx, one.s_large,
        one.s_inf, one.lane_valid)
    pk_r, wsig = jits["scalars"](pk_jac, sig_jac, one.r_bits)
    agg, u_mask, s_aff, s_mask = jits["group"](
        pk_r, mask, one.group_idx, one.group_present, wsig)
    return V.fold_rows(
        jits["miller"](agg, _hm(single, one), u_mask, s_aff, s_mask))


@pytest.mark.parametrize("groups,forged", [
    ([8], (1, 6)), ([6, 2], (7,)), ([2], (0,)), ([8], ())])
def test_the_shards_partials_multiply_to_one_chips_product(
        impls, groups, forged):
    """The share test.  With fixed multipliers, the product over the
    four shards of each shard's rows and its OWN signature row
    (-g1, S_k) is, as a value of the pairing, the single-chip
    pipeline's product of all rows and its ONE signature row
    (-g1, sum_k S_k): the signature's pair is counted once and no
    message row twice.  Miller values are defined only up to factors
    the final exponentiation kills, so the two products are compared
    after it; forged lanes keep that value away from ONE, where every
    sound batch would agree trivially."""
    meshed, single = impls
    triples = _batch(groups, f"share/{groups}/{forged}")
    for i in forged:
        triples[i] = _triple(i % SIGNERS, triples[i][1], forged=True)
    on_mesh = PR.final_exponentiation(_mesh_product(meshed, triples))
    on_chip = PR.final_exponentiation(_chip_product(single, triples))
    assert bool(T.fq12_eq(on_mesh, on_chip))
    assert bool(T.fq12_is_one(on_chip)) is (not forged)


# ---- the cell's own size: planning only, nothing is compiled ----------

def _cell_rows(first: int):
    """Rows of a 250-task drain whose first `first` tasks end one
    message and whose rest begin the next (`first` 0: one message)."""
    groups = [g for g in (list(range(first)),
                          list(range(first, 250))) if g]
    return shapeset.group_rows(groups, 32)


def test_every_drain_of_the_cell_plans_one_shape():
    for first in range(250):
        rows = _cell_rows(first)
        assert len(rows) in (8, 9), first
        plan = parallel.plan_group_shards(
            rows, 250, 4, min_lanes=256 // 4, min_rows=16 // 4)
        assert (plan.lanes_per_shard, plan.rows_per_shard) == (64, 4)
        assert (plan.padded, plan.rows_total) == (256, 16)
        # the LPT bound: whole rows of at most 32 never load a shard
        # past its 64-lane floor, nor give it a fourth row
        assert max(plan.shard_lanes) <= 64, (first, plan.shard_lanes)
        assert max(plan.shard_rows) <= 3, (first, plan.shard_rows)
        assert sum(plan.shard_lanes) == 250
        assert shapeset.group_bucket(rows) == 32
        by_plan = shapeset.batch_plan(
            [first, 250 - first] if first else [250], min_bucket=256,
            h2c_min_bucket=16, group_cap=32, mesh_devices=4)
        assert (by_plan["shape"], by_plan["lanes_per_shard"],
                by_plan["rows_per_shard"], by_plan["group_bucket"]) \
            == ("256x1@m4", 64, 4, 32)


@pytest.mark.parametrize("groups,group_bucket", [
    ([125], 32), ([63], 32), ([40, 22], 32), ([32], 32), ([31], 32),
    ([12, 13], 16), ([16], 16), ([15], 16), ([1], 1)])
def test_the_probes_halves_keep_the_cells_lanes_and_rows(groups,
                                                         group_bucket):
    by_plan = shapeset.batch_plan(groups, min_bucket=256,
                                  h2c_min_bucket=16, group_cap=32,
                                  mesh_devices=4)
    assert (by_plan["shape"], by_plan["lanes_per_shard"],
            by_plan["rows_per_shard"]) == ("256x1@m4", 64, 4)
    # only `mesh_group` takes the group bucket: a half whose largest
    # row is under 17 lanes costs that one program a shape
    assert by_plan["group_bucket"] == group_bucket


def test_a_mesh_record_names_its_programs_and_where_they_ran(impls):
    meshed, _single = impls
    seq = dispatchledger.LEDGER.recorded_total
    assert meshed.batch_verify(_batch([4, 4], "record"))
    rec = next(r for r in dispatchledger.LEDGER.snapshot()
               if r["seq"] > seq)
    mesh = rec["mesh"]
    launches = rec["launches"]
    names = [launch[0] for launch in launches]
    # a fresh drain: hashed and put into the arena on one chip, then
    # the row gather, then the sharded stages and the exchange
    assert names == ["stage_h2c", "_scatter", "_gather",
                     "stage_gather_hm"] + [f"mesh_{s}"
                                           for s in V.MESH_STAGES]
    one_chip = {tuple(launch[3]) for launch in launches[:4]}
    assert one_chip == {(str(jax.devices()[0]),)}
    assert all(launch[3] == mesh["live"] and len(launch[3]) == 4
               for launch in launches[4:])
    # inside device_enqueue, after the single-chip launches
    enq0 = next(t0 for name, t0, _s in rec["phases"]
                if name == "device_enqueue")
    assert 0.0 < launches[4][1] - enq0 <= rec["compile"]["enqueue_s"]
    # the same messages again: no hashing, the arena's gather alone
    seq = dispatchledger.LEDGER.recorded_total
    assert meshed.batch_verify(_batch([4, 4], "record"))
    rec = next(r for r in dispatchledger.LEDGER.snapshot()
               if r["seq"] > seq)
    assert [launch[0] for launch in rec["launches"]][:2] \
        == ["_gather", "stage_gather_hm"]
