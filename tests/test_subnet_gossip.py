"""One attestation subnet's stream at a size a CPU holds: H(m) is
resolved once a MESSAGE, and the arena's insert compiles nothing.

The shape is `benchmarks/configs/mainnet-subnet-gossip.json`'s, scaled
down: consecutive runs of 13 single-key tasks share one signing root
(464 on mainnet), a drain takes 8 (250), the group cap is 2 (32), so a
committee is split over several Miller rows and successive drains are
fresh (one new message), straddling (the end of one message and the
start of the next) and all-hit in turn.  Pinned here:

- every verdict is the plain reference's
  (`benchmarks.reference.bls.fast_aggregate_verify`), one task forged;
- arena lookups, misses, the `stage_h2c` bucket and the rows it hashes
  count MESSAGES, in the ledger record and in
  `bls_h2c_cache_misses_total`: a drain misses 0 or 1 times although
  its fresh committee owns 2 to 4 rows;
- `shapeset.batch_plan` reproduces the record's `h2c` block;
- once the first fresh drain has run, no later miss count inside the
  miss bucket compiles anything (`jax.monitoring`'s backend-compile
  events read 0 over drains with 1, 2 and 3 fresh messages);
- at the cell's own size a drain's `shapeset.batch_plan` is one
  shape whatever its messages, and the programs enumerated for that
  serving config hold one `scalars` and the committee's `group`.
"""

import hashlib

import numpy as np
import pytest

import jax

from benchmarks.harness import traffic
from benchmarks.reference import bls as ref
from teku_tpu.infra import dispatchledger
from teku_tpu.ops import h2c_cache as HC
from teku_tpu.ops import shapeset
from teku_tpu.ops.provider import JaxBls12381

SEED = 3_000_000_019            # a driver-sized seed
SIGNERS = 16                    # > 13: no (signer, message) twice
PER_MESSAGE = 13
DRAIN = 8
GROUP_CAP = 2
H2C_MIN_BUCKET = 4
FORGED_AT = 19                  # inside the all-hit drain (tasks 16..23)

# the stream's four drains: lanes per message, messages that miss
STREAM = [([8], 1),             # fresh: message 0, four rows of 2
          ([5, 3], 1),          # straddles 0 | 1: only 1 is new
          ([8], 0),             # all of it message 1: a hit
          ([2, 6], 1)]          # straddles 1 | 2
# then drains that meet 1, 2 and 3 fresh messages in one miss bucket
FRESH = [[8], [4, 4], [3, 3, 2]]

_COMPILES = []


def _on_compile(event, duration, **kw):
    if event.endswith("backend_compile_duration"):
        _COMPILES.append(kw.get("fun_name", "?"))


jax.monitoring.register_event_duration_secs_listener(_on_compile)


def _message(tag: str, group: int) -> bytes:
    return hashlib.sha256(f"{SEED}/{tag}/{group}".encode()).digest()


def _triples(specs):
    return [([ref.public_key(traffic.secret_key(SEED, s.signer))],
             s.message,
             ref.sign(traffic.secret_key(SEED, s.signer)
                      + (1 if s.forged else 0), s.message))
            for s in specs]


def _drain(impl, triples):
    """One drain through `batch_verify`, with what it moved."""
    hashed = []
    whole = impl._h2c_dispatch

    def spy(draws):
        # rows of the h2c input that carry a message's draws
        hashed.append(int(np.count_nonzero(draws[0][0].any(axis=1))))
        return whole(draws)

    impl._h2c_dispatch = spy
    misses0 = HC._M_MISSES.value
    hits0 = HC._M_HITS.value
    compiles0 = len(_COMPILES)
    try:
        verdict = impl.batch_verify(triples)
    finally:
        del impl._h2c_dispatch
    return {"verdict": verdict,
            "rec": dispatchledger.LEDGER.snapshot()[-1],
            "misses": HC._M_MISSES.value - misses0,
            "hits": HC._M_HITS.value - hits0,
            "hashed_rows": hashed,
            "compiles": _COMPILES[compiles0:]}


@pytest.fixture(scope="module")
def runs():
    mp = pytest.MonkeyPatch()
    mp.setenv("TEKU_TPU_H2C_GROUP_CAP", str(GROUP_CAP))
    mp.setenv("TEKU_TPU_H2C_MIN_BUCKET", str(H2C_MIN_BUCKET))
    mp.delenv(HC.ENV_CAP, raising=False)
    try:
        impl = JaxBls12381(max_batch=DRAIN, min_bucket=DRAIN)
    finally:
        mp.undo()
    assert (impl._group_cap, impl._h2c_min_bucket) == (GROUP_CAP,
                                                       H2C_MIN_BUCKET)
    assert impl._h2c_cache.enabled
    n = DRAIN * len(STREAM)
    specs = [traffic.TaskSpec((5 + i) % SIGNERS,
                              _message("subnet", i // PER_MESSAGE),
                              forged=i == FORGED_AT) for i in range(n)]
    triples = _triples(specs)
    out = {"impl": impl, "specs": specs, "triples": triples,
           "want": [ref.fast_aggregate_verify(*t) for t in triples],
           "stream": [], "fresh": []}
    for d in range(len(STREAM)):
        out["stream"].append(
            _drain(impl, triples[d * DRAIN:(d + 1) * DRAIN]))
    for k, groups in enumerate(FRESH):
        fresh = [traffic.TaskSpec(i % SIGNERS, _message(f"fresh{k}", g))
                 for g, size in enumerate(groups) for i in range(size)]
        out["fresh"].append(_drain(impl, _triples(fresh)))
    out["forged_alone"] = impl.fast_aggregate_verify(*triples[FORGED_AT])
    return out


def _groups(specs):
    groups = {}
    for s in specs:
        groups[s.message] = groups.get(s.message, 0) + 1
    return list(groups.values())


def test_the_stream_is_fresh_straddling_and_all_hit_in_turn(runs):
    for d, (groups, _missing) in enumerate(STREAM):
        assert _groups(runs["specs"][d * DRAIN:(d + 1) * DRAIN]) == groups
    # no (signer, message) twice: the service would coalesce the twins
    pairs = {(s.signer, s.message) for s in runs["specs"]}
    assert len(pairs) == len(runs["specs"])


@pytest.mark.parametrize("d", range(len(STREAM)))
def test_every_verdict_is_the_references(runs, d):
    want = runs["want"][d * DRAIN:(d + 1) * DRAIN]
    assert want == [not s.forged
                    for s in runs["specs"][d * DRAIN:(d + 1) * DRAIN]]
    assert runs["stream"][d]["verdict"] is all(want)
    if d == FORGED_AT // DRAIN:
        assert all(want) is False
        assert runs["forged_alone"] is runs["want"][FORGED_AT] is False


@pytest.mark.parametrize("d", range(len(STREAM)))
def test_misses_are_counted_by_message(runs, d):
    groups, missing = STREAM[d]
    got = runs["stream"][d]
    rec = got["rec"]
    rows = sum(-(-g // GROUP_CAP) for g in groups)
    assert (rec["lanes"], rec["unique_messages"], rec["rows"]) \
        == (DRAIN, len(groups), rows)
    assert rows > len(groups), "a committee owns several rows"
    # one lookup a message: the ledger and /metrics agree, 0 or 1 miss
    assert rec["h2c"]["cache_misses"] == got["misses"] == missing
    assert rec["h2c"]["cache_hits"] == got["hits"] \
        == len(groups) - missing
    assert (rec["prep"], rec["prep_reason"].split("+")[-1]) \
        == ("under_lock", "arena")
    # a drain whose messages are all in the arena runs no stage_h2c;
    # a fresh committee's message is hashed once, whatever its rows
    assert got["hashed_rows"] == ([missing] if missing else [])
    assert rec["h2c"]["dispatch_bucket"] \
        == (H2C_MIN_BUCKET if missing else 0)


def test_a_committee_split_over_rows_hashes_its_message_once(runs):
    first = runs["stream"][0]
    assert first["rec"]["rows"] == 4 and first["rec"]["group_bucket"] == 2
    assert first["rec"]["unique_messages"] == 1
    assert first["rec"]["h2c"] == {"cache_hits": 0, "cache_misses": 1,
                                   "dispatch_bucket": H2C_MIN_BUCKET}
    assert first["hashed_rows"] == [1]
    # and the arena holds one point for it, not one a row
    assert len(runs["impl"]._h2c_cache) == 3 + sum(map(len, FRESH))


@pytest.mark.parametrize("d", range(len(STREAM)))
def test_batch_plan_reproduces_the_records_h2c_block(runs, d):
    groups, missing = STREAM[d]
    rec = runs["stream"][d]["rec"]
    plan = shapeset.batch_plan(groups, min_bucket=DRAIN,
                               h2c_min_bucket=H2C_MIN_BUCKET,
                               group_cap=GROUP_CAP, h2c_missing=missing)
    assert (plan["shape"], plan["rows"], plan["messages"],
            plan["group_bucket"], plan["u_total"]) \
        == (rec["shape"], rec["rows"], rec["unique_messages"],
            rec["group_bucket"], rec["waste"]["h2c"]["padded"])
    assert {"cache_hits": plan["messages"] - plan["h2c_missing"],
            "cache_misses": plan["h2c_missing"],
            "dispatch_bucket": plan["h2c_bucket"]} == rec["h2c"]
    # the cold-boot default: every MESSAGE misses, not every row
    cold = shapeset.batch_plan(groups, min_bucket=DRAIN,
                               h2c_min_bucket=H2C_MIN_BUCKET,
                               group_cap=GROUP_CAP)
    assert cold["h2c_missing"] == len(groups) < cold["rows"]
    assert cold["h2c_bucket"] == H2C_MIN_BUCKET


@pytest.mark.parametrize("k", range(len(FRESH)))
def test_no_later_miss_count_compiles(runs, k):
    groups = FRESH[k]
    got = runs["fresh"][k]
    assert got["verdict"] is True
    assert got["rec"]["h2c"] == {"cache_hits": 0,
                                 "cache_misses": len(groups),
                                 "dispatch_bucket": H2C_MIN_BUCKET}
    assert got["hashed_rows"] == [len(groups)]
    # every shape of this drain ran in the stream above (row buckets 4
    # and 8, miss bucket 4); a new miss COUNT is no new shape
    assert got["compiles"] == []
    assert got["rec"]["compile"]["outcome"] == "cache_hit"


# the cell's knobs at mainnet size (`mainnet-subnet-gossip.json`)
CELL = dict(min_bucket=256, h2c_min_bucket=16, group_cap=32)


@pytest.mark.parametrize("groups,missing,rows", [
    ([250], 1, 8),              # fresh drain inside one message
    ([250], 0, 8),              # hit drain
    ([214, 36], 1, 9),          # the end of one message, the start of the next
    ([125], 0, 4),              # the probe's bisection, first halves
    ([63], 0, 2)])
def test_a_gossip_drain_plans_one_shape(groups, missing, rows):
    plan = shapeset.batch_plan(groups, h2c_missing=missing, **CELL)
    assert (plan["rows"], plan["shape"], plan["u_hm"],
            plan["group_bucket"]) == (rows, "256x1", 16, 32)
    assert plan["h2c_bucket"] == (16 if missing else 0)


def test_the_cells_serving_config_enumerates_one_scalars_program():
    """What `cli precompile`, the loader's warm batches and the AOT
    store hold for this config: one `scalars` for its one lane shape,
    and the committee-duplicated warm profile's `group` (256 lanes, 8 a
    message)."""
    programs = list(shapeset.enumerate_programs(
        max_batch=256, **CELL))
    stages = [m["stage"] for _k, _a, m in programs]
    assert stages.count("scalars") == 1         # one lane shape
    dup = [a for _k, a, m in programs
           if m["stage"] == "group" and m["profile"] == "x256dup8"]
    assert [tuple(a[2].shape) for a in dup] == [(32, 8)]
