"""Group-aligned mesh sharding: the production multi-chip verify path.

The dedup-aware pipeline sharded across the 8-virtual-device CPU mesh
(production: ICI): whole message groups per shard, per-device partial
combines, bit-identical verdicts vs the single-device grouped pipeline
and the pure oracle — plus the host-side shard planner, mesh-spec
resolution/demotion, dedup-counter parity, the mesh fault site tripping
the breaker to oracle fallback, and the mesh observability surfaces.

Compile budget: every device test in the fast tier shares ONE sharded
kernel shape (32 lanes x kmax 1, 8 rows x group 4 over 8 shards) and
ONE single-device staged shape set; the mxu-force re-trace is an extra
full-pipeline compile and lives in the slow tier.
"""

import logging

import numpy as np
import pytest

import jax

from teku_tpu import parallel
from teku_tpu.crypto.bls import keygen
from teku_tpu.crypto.bls.pure_impl import PureBls12381
from teku_tpu.infra import capacity, faults
from teku_tpu.infra.metrics import GLOBAL_REGISTRY, MetricsRegistry
from teku_tpu.infra.supervisor import (CircuitBreaker)
from teku_tpu.ops import provider as PV
from teku_tpu.ops.provider import JaxBls12381

_G2_INF = bytes([0xC0] + [0] * 95)

pytest_plugins: list = []


# --------------------------------------------------------------------------
# host-side: shard planner + mesh-spec resolution (no device work)
# --------------------------------------------------------------------------

def test_plan_group_shards_keeps_rows_whole():
    # rows of lane-index lists with mixed sizes over 4 shards
    rows = [(0, [0, 1, 2, 3, 4]), (1, [5, 6]), (2, [7]), (3, [8, 9])]
    plan = parallel.plan_group_shards(rows, 10, 4, min_lanes=1)
    assert plan.n_shards == 4
    # pow-2 per-shard shapes, identical across shards
    assert plan.lanes_per_shard & (plan.lanes_per_shard - 1) == 0
    assert plan.rows_per_shard & (plan.rows_per_shard - 1) == 0
    assert plan.padded == 4 * plan.lanes_per_shard
    # lane_pos is injective and every row's lanes land in ONE shard
    assert len(set(plan.lane_pos.tolist())) == 10
    placed = [r for r in plan.row_layout if r >= 0]
    assert sorted(placed) == [0, 1, 2, 3]       # every row placed once
    for pos, r in enumerate(plan.row_layout):
        if r < 0:
            continue
        shard = pos // plan.rows_per_shard
        lo = shard * plan.lanes_per_shard
        hi = lo + plan.lanes_per_shard
        for lane in rows[r][1]:
            assert lo <= plan.lane_pos[lane] < hi, (pos, r, lane)


def test_plan_group_shards_balances_lanes():
    # 8 equal rows over 4 shards: 2 rows / 8 lanes per shard, no slack
    rows = [(u, list(range(u * 4, u * 4 + 4))) for u in range(8)]
    plan = parallel.plan_group_shards(rows, 32, 4)
    assert plan.lanes_per_shard == 8
    assert plan.rows_per_shard == 2
    assert plan.padded == 32                    # zero padding waste


def test_plan_respects_min_floors():
    plan = parallel.plan_group_shards([(0, [0])], 1, 2,
                                      min_lanes=4, min_rows=2)
    assert plan.lanes_per_shard == 4
    assert plan.rows_per_shard == 2


def test_resolve_mesh_devices_rules(caplog):
    r = parallel.resolve_mesh_devices
    assert r(None) == 0
    assert r("off") == 0
    assert r("0") == 0
    assert r("1", available=8) == 0             # mesh of 1 = no mesh
    assert r("auto", available=8) == 8
    assert r("auto", available=5) == 4          # largest pow-2 <=
    assert r("auto", available=1) == 0
    assert r("8", available=8) == 8
    # non-pow-2 / over-sized N DEMOTES with a warning, never raises
    parallel._warned_demotion[0] = False
    with caplog.at_level(logging.WARNING):
        assert r("6", available=8) == 4
    assert any("demoting" in rec.message for rec in caplog.records)
    assert r("100", available=8) == 8
    # garbage spec disables the mesh instead of failing boot
    assert r("many", available=8) == 0


def test_sharded_verifiers_still_raise_on_non_pow2():
    # construction keeps the hard contract; the CLI/loader resolve
    # first (resolve_mesh_devices only ever yields pow-2 or 0)
    class FakeMesh:
        axis_names = ("dp",)
        shape = {"dp": 3}
        devices = np.empty((3,), dtype=object)
    with pytest.raises(ValueError):
        parallel.GroupShardedVerifier(FakeMesh())


def test_cli_validate_mesh():
    from teku_tpu import cli
    assert cli._validate_mesh("off") == "off"
    assert cli._validate_mesh("auto") == "auto"
    assert cli._validate_mesh("4") == "4"
    # YAML parses bare off/on/no/yes as booleans before this layer:
    # the boolean spellings must normalize, never fail node boot
    assert cli._validate_mesh("false") == "off"
    assert cli._validate_mesh("no") == "off"
    assert cli._validate_mesh("0") == "off"
    assert cli._validate_mesh("true") == "auto"
    assert cli._validate_mesh("on") == "auto"
    with pytest.raises(SystemExit):
        cli._validate_mesh("zero")
    with pytest.raises(SystemExit):
        cli._validate_mesh("-2")


def test_configure_kernel_sets_mesh_env(monkeypatch):
    import os

    from teku_tpu import cli

    # _configure_kernel writes these straight to os.environ; restore
    # the process env by hand after the test
    saved = {var: os.environ.get(var)
             for var in ("TEKU_TPU_MESH", "TEKU_TPU_MONT_MUL")}

    class Args:
        mont_path = None
        mesh = "auto"
    try:
        mont, mesh = cli._configure_kernel(Args(), {})
        assert mesh == "auto"
        assert os.environ["TEKU_TPU_MESH"] == "auto"
        # numeric N forces virtual host devices ONLY if the flag is
        # absent
        monkeypatch.setenv("XLA_FLAGS", "--xla_foo")
        Args.mesh = "4"
        assert cli._configure_kernel(Args(), {})[1] == "4"
        assert "xla_force_host_platform_device_count=4" \
            in os.environ["XLA_FLAGS"]
        # already-forced flag (the test env itself) is left untouched
        monkeypatch.setenv(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        cli._configure_kernel(Args(), {})
        assert os.environ["XLA_FLAGS"] == \
            "--xla_force_host_platform_device_count=8"
    finally:
        for var, value in saved.items():
            if value is None:
                os.environ.pop(var, None)
            else:
                os.environ[var] = value


# --------------------------------------------------------------------------
# device fixtures: ONE mesh, ONE provider pair, ONE sharded shape
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh8():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices (see conftest XLA_FLAGS)")
    m = parallel.make_mesh(8)
    with m:
        yield m


@pytest.fixture(scope="module")
def keys():
    pure = PureBls12381()
    sks = [keygen(bytes([31 + i]) * 32) for i in range(8)]
    pks = [pure.secret_key_to_public_key(sk) for sk in sks]
    return pure, sks, pks


@pytest.fixture(scope="module")
def mesh_impl(mesh8):
    return JaxBls12381(mesh=mesh8, min_bucket=8)


@pytest.fixture(scope="module")
def single_impl():
    return JaxBls12381(min_bucket=8)


_seq = [0]


# lane -> unique-message map: two dup-4 committees, two dup-2 pairs,
# four singles = 16 lanes over 8 unique messages, so ONE kernel shape
# (group bucket 4, 8 rows, 4 lanes/shard over 8 shards) covers the dup
# AND unique grid axes — and its 13-lane prefix keeps the same shape
# for the padding case
_U_MAP = [0, 0, 0, 0, 1, 1, 1, 1, 2, 2, 3, 3, 4, 5, 6, 7]


def _grid_batch(pure, sks, pks, tag=None, n_lanes=16):
    """Committee-shaped mixed-duplication batch (see _U_MAP).  Fresh
    messages per call (tag) keep the H(m) caches cold for counter
    tests."""
    if tag is None:
        _seq[0] += 1
        tag = b"grid-%d" % _seq[0]
    msgs = [tag + b"-%d" % u for u in range(8)]
    triples = []
    sig_cache: dict = {}
    for lane in range(n_lanes):
        u = _U_MAP[lane]
        k = lane % 8
        if (k, u) not in sig_cache:
            sig_cache[(k, u)] = pure.sign(sks[k], msgs[u])
        triples.append(([pks[k]], msgs[u], sig_cache[(k, u)]))
    return triples


def test_mesh_self_description(mesh8, mesh_impl):
    # make_mesh logged + exported the device set (satellite: no more
    # silent first-N): the gauge and describe() agree with the mesh
    desc = parallel.describe_mesh()
    assert desc["n_devices"] == 8
    assert len(desc["devices"]) == 8
    gauge = GLOBAL_REGISTRY.gauge("bls_mesh_devices")
    assert gauge.value == 8.0
    assert mesh_impl.mesh_info["n_devices"] == 8
    assert mesh_impl.mesh_info["devices"] == desc["devices"]


def test_grouped_sharded_parity_grid(mesh_impl, single_impl, keys):
    """Verdict parity: mesh vs single-device grouped vs pure oracle on
    the dup-4 / unique / tamper / infinity-sig / padding grid.  Every
    case reuses ONE compiled sharded shape (see module docstring)."""
    pure, sks, pks = keys
    base = _grid_batch(pure, sks, pks)

    tampered = list(base)
    tampered[10] = (base[10][0], b"tampered-msg", base[10][2])

    tampered_dup = list(base)                 # corrupt a dup-4 lane
    tampered_dup[2] = (base[2][0], base[2][1],
                       pure.sign(sks[0], b"wrong"))

    inf_sig = list(base)
    inf_sig[12] = (base[12][0], base[12][1], _G2_INF)

    padded = _grid_batch(pure, sks, pks)[:13]   # non-pow-2 lane count

    cases = {"valid": base, "tamper_msg": tampered,
             "tamper_sig_in_committee": tampered_dup,
             "infinity_sig": inf_sig, "padding_13": padded}
    for name, triples in cases.items():
        want = pure.batch_verify(triples)
        got_single = single_impl.batch_verify(triples)
        got_mesh = mesh_impl.batch_verify(triples)
        assert got_single == want, f"{name}: single vs oracle"
        assert got_mesh == want, f"{name}: mesh vs oracle"
    assert mesh_impl.dispatch_count >= len(cases)
    # the mesh dispatch counter carries the closed devices label
    fam = GLOBAL_REGISTRY.labeled_counter("bls_mesh_dispatch_total")
    assert fam.labels(devices="8").value >= len(cases)


def test_sharded_dedup_counters_match_single_device(
        mesh_impl, single_impl, keys):
    """Satellite: sharded dispatch must not double-count dedup metrics.
    The same batch through the single-device and mesh providers
    reports IDENTICAL bls_h2c_lanes/unique/dispatch deltas (the mesh
    layout pads lanes/rows, but the dedup accounting is canonical)."""
    pure, sks, pks = keys

    def deltas(impl, triples):
        before = (PV._M_H2C_LANES.value, PV._M_H2C_UNIQUE.value,
                  PV._M_H2C_DISPATCH.value, impl.h2c_dispatch_count)
        assert impl.batch_verify(triples)
        return (PV._M_H2C_LANES.value - before[0],
                PV._M_H2C_UNIQUE.value - before[1],
                PV._M_H2C_DISPATCH.value - before[2],
                impl.h2c_dispatch_count - before[3])

    # FRESH messages for each provider: both pay exactly one cold h2c
    d_single = deltas(single_impl, _grid_batch(pure, sks, pks))
    d_mesh = deltas(mesh_impl, _grid_batch(pure, sks, pks))
    assert d_single == d_mesh == (16, 8, 1, 1)
    # warm re-dispatch through the mesh: dedup still counted once,
    # ZERO h2c dispatches (the arena serves the whole batch)
    warm = _grid_batch(pure, sks, pks)
    deltas(mesh_impl, warm)
    assert deltas(mesh_impl, warm)[2:] == (0, 0)


def test_mesh_latency_model_feeds_admission(mesh_impl, keys):
    """The capacity model's per-shape series carries the mesh-shaped
    dispatches (distinct `@mN` family) and latency_for_lanes still
    prefix-matches them — the admission controller's batch planner
    sees N-chip device latencies."""
    pure, sks, pks = keys
    assert mesh_impl.batch_verify(_grid_batch(pure, sks, pks))
    shapes = capacity.TELEMETRY.latency.snapshot()
    mesh_shapes = [s for s in shapes if s.endswith("@m8")]
    assert mesh_shapes, f"no mesh-labeled shapes in {list(shapes)}"
    lanes = int(mesh_shapes[0].split("x")[0])
    assert capacity.TELEMETRY.latency.latency_for_lanes(lanes)


def test_mesh_shard_hang_trips_breaker_zero_failed(mesh_impl, keys):
    """Satellite: one wedged shard (the bls.mesh_shard fault site)
    wedges the whole mesh dispatch; the breaker trips the mesh backend
    to oracle fallback and every in-flight verification still returns
    the correct verdict."""
    from teku_tpu.crypto.bls.loader import GuardedBls12381
    pure, sks, pks = keys
    br = CircuitBreaker(failure_threshold=1, deadline_s=10.0,
                        cooldown_s=60.0, name="mesh_t",
                        registry=MetricsRegistry())
    guarded = GuardedBls12381(mesh_impl, br)
    batch = _grid_batch(pure, sks, pks)
    # warm the exact dispatch shape OUTSIDE the breaker so the guarded
    # calls below measure the hang, not compile/box noise
    assert mesh_impl.batch_verify(batch)
    assert br.state == CircuitBreaker.CLOSED
    faults.inject("bls.mesh_shard", faults.Hang(12.0, times=1))
    try:
        # the wedged-shard dispatch overruns the deadline: the oracle
        # serves THIS call (correct verdict, zero failed in-flight)
        # and the breaker trips the whole mesh backend
        assert guarded.batch_verify(batch) is True
        assert br.state == CircuitBreaker.OPEN
        assert guarded.serving == "oracle"
        # while open: instant oracle service, still correct
        assert guarded.batch_verify(batch) is True
        bad = list(batch)
        bad[3] = (batch[3][0], b"mesh-tampered", batch[3][2])
        assert guarded.batch_verify(bad) is False
    finally:
        faults.clear("bls.mesh_shard")


def test_supervisor_snapshot_and_gauge_carry_mesh():
    """make_supervisor exports the name-prefixed mesh gauge and the
    readiness snapshot self-describes an installed mesh backend."""
    import asyncio

    from teku_tpu.crypto.bls import loader

    async def main():
        reg = MetricsRegistry()
        sup = loader.make_supervisor(registry=reg, warm=False,
                                     name="mesh_snap",
                                     breaker_name="mesh_snap_dev")
        gauge = reg.gauge("mesh_snap_mesh_devices")
        assert gauge.value == 0.0
        sup.mesh = {"devices": ["d0", "d1"], "n_devices": 2,
                    "axis": "dp"}
        assert gauge.value == 2.0
        assert sup.snapshot()["mesh"]["n_devices"] == 2
    asyncio.run(main())


# --------------------------------------------------------------------------
# slow tier: an extra full-pipeline re-trace (mxu-force)
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_grouped_sharded_parity_grid_mxu_force(mesh8, keys):
    """The parity grid again under TEKU_TPU_MONT_MUL=mxu-force: the
    int8 digit-split engine re-traces the whole sharded pipeline and
    the verdicts stay bit-identical to the oracle."""
    from teku_tpu.ops import mxu
    pure, sks, pks = keys
    with mxu.force("mxu-force"):
        impl = JaxBls12381(mesh=mesh8, min_bucket=8)
        base = _grid_batch(pure, sks, pks)
        bad = list(base)
        bad[9] = (base[9][0], b"mxu-tampered", base[9][2])
        assert impl.batch_verify(base) is True
        assert impl.batch_verify(bad) is False
