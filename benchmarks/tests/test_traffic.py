"""Traffic is a pure function of (configuration, mix, seed), and its
batches have the shape the cell's `why` names."""

import json
import os

import pytest

from benchmarks.harness import cell, traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(kind, name):
    # the cells' own files, and the test-size ones beside the tests
    folder = os.path.join(BENCH, "tests", "data") \
        if name.startswith("tiny-") else os.path.join(BENCH, kind)
    with open(os.path.join(folder, f"{name}.json")) as fh:
        return json.load(fh)


# every cell of BENCHMARK.json, and the grouped structure (several
# signers under one message) that no cell uses yet
CELLS = [("backfill-unique", "saturate"),
         ("backfill-unique", "poisson"),
         ("tiny-gossip", "tiny-saturate")]


@pytest.mark.parametrize("config,mix", CELLS)
def test_plan_is_a_pure_function_of_its_arguments(config, mix):
    cfg, mx = _load("configs", config), _load("traffic", mix)
    a = traffic.plan(cfg, mx, 3_000_000_019, 10)
    b = traffic.plan(cfg, mx, 3_000_000_019, 10)
    assert a == b
    c = traffic.plan(cfg, mx, 3_000_000_020, 10)
    # another seed: the same sizes, other keys, messages and gaps
    assert len(c.pool) == len(a.pool)
    assert [len(w) for w in c.warm] == [len(w) for w in a.warm]
    assert c.pool[0].message != a.pool[0].message
    assert traffic.secret_key(1, 0) != traffic.secret_key(2, 0)
    if a.due_s is not None:
        assert c.due_s != a.due_s and len(c.due_s) == len(a.due_s)


@pytest.mark.parametrize("config,mix", CELLS)
def test_nothing_is_replayed_and_one_probe_task_is_forged(config, mix):
    cfg, mx = _load("configs", config), _load("traffic", mix)
    plan = traffic.plan(cfg, mx, 7, 10)
    everything = plan.pool + plan.probe + [s for w in plan.warm for s in w]
    assert len({(s.signer, s.message) for s in everything}) \
        == len(everything)
    assert [s.forged for s in plan.probe].count(True) == 1
    assert plan.probe[plan.meta["forged_at"]].forged
    assert not any(s.forged for s in plan.pool)


@pytest.mark.parametrize("config,mix", [("backfill-unique", "saturate"),
                                        ("tiny-gossip", "tiny-saturate")])
def test_every_drain_has_the_planned_shape(config, mix):
    """Whole service batches of the pool bucket, by the program's own
    rule (`shapeset.batch_plan`), to the shape the configuration
    states."""
    from teku_tpu.ops import shapeset
    cfg = _load("configs", config)
    plan = traffic.plan(cfg, _load("traffic", mix), 11, 10)
    want = cfg["full_batch_shape"]
    env = cfg["knobs"]["env"]
    batch = cfg["knobs"]["service"]["max_batch"]
    assert len(plan.pool) % batch == 0
    for lo in range(0, len(plan.pool), batch):
        groups = {}
        for s in plan.pool[lo:lo + batch]:
            groups[s.message] = groups.get(s.message, 0) + 1
        got = shapeset.batch_plan(
            list(groups.values()), min_bucket=cfg["knobs"]["min_bucket"],
            h2c_min_bucket=env["TEKU_TPU_H2C_MIN_BUCKET"],
            group_cap=env["TEKU_TPU_H2C_GROUP_CAP"])
        assert (got["lanes"], got["padded"], got["rows"],
                got["group_bucket"], got["u_total"]) == (
            want["lanes"], want["padded_lanes"], want["rows"],
            want["group_bucket"], want["unique_bucket"])
        # distinct signers under one message, as in a committee
        by_msg = {}
        for s in plan.pool[lo:lo + batch]:
            by_msg.setdefault(s.message, []).append(s.signer)
        assert all(len(set(v)) == len(v) for v in by_msg.values())


def test_every_seed_is_offered_the_same_open_loop_work():
    """The same gaps for every seed, in the seed's order; every task
    due inside the window; exponential gaps (mean = deviation)."""
    import statistics
    cfg, mx = _load("configs", "backfill-unique"), _load("traffic", "poisson")
    a = traffic.plan(cfg, mx, 5, 10)
    b = traffic.plan(cfg, mx, 2_147_483_659, 10)
    n = int(mx["rate_per_s"] * 10)
    assert len(a.pool) == len(a.due_s) == len(b.due_s) == n
    gaps = [[y - x for x, y in zip([0.0] + p.due_s, p.due_s)]
            for p in (a, b)]
    assert gaps[0] != gaps[1]
    assert sorted(gaps[0]) == pytest.approx(sorted(gaps[1]), rel=1e-9)
    assert a.due_s[-1] == pytest.approx(10 - 0.5 / mx["rate_per_s"])
    mean = statistics.mean(gaps[0])
    assert mean == pytest.approx(1 / mx["rate_per_s"], rel=1e-3)
    assert statistics.pstdev(gaps[0]) == pytest.approx(mean, rel=0.02)


def test_bisection_follows_the_service():
    """The ranges set-up warms are the ones the service dispatches for
    one bad task: the whole, halves of what failed, then one by one."""
    ranges = cell.bisect_ranges(250, 17)
    assert ranges[:5] == [(0, 250), (0, 125), (0, 62), (0, 31), (0, 15)]
    assert (125, 250) in ranges and (17, 18) in ranges
    assert len(ranges) == 25
    assert cell.bisect_ranges(8, 3) == [(0, 8)] + [(i, i + 1)
                                                   for i in range(8)]


def test_signatures_are_the_references_own():
    from benchmarks.reference import bls
    specs = [traffic.TaskSpec(0, b"m" * 32), traffic.TaskSpec(1, b"m" * 32),
             traffic.TaskSpec(1, b"n" * 32, forged=True)]
    sigs = traffic._sign_chunk(5, [(s.signer, s.message, s.forged)
                                   for s in specs])
    pks = traffic._public_keys(5, [0, 1])
    got = [bls.fast_aggregate_verify([pks[s.signer]], s.message, sig)
           for s, sig in zip(specs, sigs)]
    assert got == [True, True, False]
