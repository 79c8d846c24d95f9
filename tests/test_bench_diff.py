"""tools/bench_diff.py — the bench regression gate — plus bench.py's
rolling BENCH_TRAJECTORY.json (append-only per run id)."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import bench  # noqa: E402
from tools import bench_diff  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
BASE = os.path.join(FIXTURES, "bench_base.json")
REGRESSED = os.path.join(FIXTURES, "bench_regressed.json")
BENCH_R05 = os.path.join(os.path.dirname(__file__), "..",
                         "BENCH_r05.json")


def _by_metric(out):
    return {c["metric"]: c for c in out["checks"]}


def test_regressed_fixture_is_flagged():
    out = bench_diff.compare(bench_diff.load_result(BASE),
                             bench_diff.load_result(REGRESSED))
    assert out["verdict"] == "regression"
    checks = _by_metric(out)
    # 46.1 -> 31.8 sigs/sec is far past the 10% tolerance
    assert checks["sigs_per_sec"]["status"] == "regression"
    # the guilty stage is named, not just the headline
    assert checks["stage_p50_ms.device_sync"]["status"] == "regression"
    # host_prep barely moved: not flagged
    assert checks["stage_p50_ms.host_prep"]["status"] == "ok"
    # shape 256 was cache-loaded in base but recompiled in new
    cache = checks["compile_cache_serving"]
    assert cache["status"] == "regression" and cache["new"] == ["256"]
    # dedup gates: 8x speedup fell under 1.5, warm pass dispatched h2c
    assert checks["dedup_speedup_8x"]["status"] == "regression"
    assert checks["warm_h2c_dispatches"]["status"] == "regression"
    # overload gates: p50 at 10x blew the 100 ms SLO, BLOCK_IMPORT got
    # shed, sheds inverted (gossip > optimistic), brownout flapped
    assert checks["overload_p50_ms"]["status"] == "regression"
    assert checks["overload_block_import_sheds"]["status"] \
        == "regression"
    assert checks["overload_shed_order"]["status"] == "regression"
    assert checks["overload_brownout_stable"]["status"] == "regression"


def test_base_vs_itself_passes():
    base = bench_diff.load_result(BASE)
    out = bench_diff.compare(base, base)
    assert out["verdict"] == "pass"
    assert out["regressions"] == 0
    checks = _by_metric(out)
    assert checks["sigs_per_sec"]["ratio"] == 1.0
    # the overload acceptance gates pass on the healthy fixture
    assert checks["overload_p50_ms"]["status"] == "ok"
    assert checks["overload_block_import_sheds"]["status"] == "ok"
    assert checks["overload_shed_order"]["status"] == "ok"
    assert checks["overload_brownout_stable"]["status"] == "ok"


def test_overload_gates_absent_are_skipped_and_threshold_overrides():
    """A run without the overload phase skips the gates (budget-starved
    rounds must not fail); the p50 gate threshold is operator-tunable
    via --threshold overload_p50_ms_max=N."""
    base = bench_diff.load_result(BASE)
    stripped = {k: v for k, v in base.items() if k != "overload"}
    out = bench_diff.compare(base, stripped)
    checks = _by_metric(out)
    for gate in ("overload_p50_ms", "overload_block_import_sheds",
                 "overload_shed_order", "overload_brownout_stable"):
        assert checks[gate]["status"] == "skipped"
    # tighten the SLO gate below the fixture's measured 49 ms: flags
    out = bench_diff.compare(base, base,
                             {"overload_p50_ms_max": 40.0})
    assert _by_metric(out)["overload_p50_ms"]["status"] == "regression"


def test_overlap_efficiency_gate_flags_skips_and_overrides():
    """The timeline PR gate: the latency burst's device-busy share of
    queue-nonempty time (bench stamps it from infra/timeline.py's
    attribution) must stay >= the floor; results that predate the
    timeline ring or ran with TEKU_TPU_TIMELINE=0 carry no value and
    skip; the floor defaults to 0.0 (the CPU reference box measures
    ~0 — drain-then-dispatch never overlaps) and is raised per
    deployment where enqueue genuinely overlaps device execution."""
    base = bench_diff.load_result(BASE)
    reg = bench_diff.load_result(REGRESSED)
    assert _by_metric(bench_diff.compare(base, base))[
        "overlap_efficiency"]["status"] == "ok"
    # default floor is vacuous: even the regressed fixture passes
    assert _by_metric(bench_diff.compare(base, reg))[
        "overlap_efficiency"]["status"] == "ok"
    # a deployment floor flags it
    assert _by_metric(bench_diff.compare(
        base, reg, {"overlap_efficiency_min": 0.3}))[
        "overlap_efficiency"]["status"] == "regression"
    stripped = {k: v for k, v in base.items()
                if k != "overlap_efficiency"}
    assert _by_metric(bench_diff.compare(base, stripped))[
        "overlap_efficiency"]["status"] == "skipped"
    out = bench_diff.compare(base, base,
                             {"overlap_efficiency_min": 0.9})
    assert _by_metric(out)["overlap_efficiency"]["status"] \
        == "regression"


def test_current_bench_r05_vs_itself_passes():
    """The acceptance gate: the checked-in BENCH_r05 (driver envelope
    with a `parsed` key, budget-starved phases missing) must compare
    clean against itself — absent metrics are skipped, never failed."""
    r05 = bench_diff.load_result(BENCH_R05)
    assert r05["metric"] == "bls_verify_sigs_per_sec"   # unwrapped
    out = bench_diff.compare(r05, r05)
    assert out["verdict"] == "pass"
    checks = _by_metric(out)
    # r05 predates the dedup-sweep/latency_stages evidence: skipped
    assert checks["dedup_speedup_8x"]["status"] == "skipped"
    assert checks["sigs_per_sec"]["status"] == "ok"


def test_threshold_override_changes_verdict():
    base = bench_diff.load_result(BASE)
    slower = dict(base)
    slower["value"] = base["value"] * 0.85        # -15%
    assert bench_diff.compare(base, slower)["verdict"] == "regression"
    out = bench_diff.compare(base, slower,
                             {"sigs_per_sec": 0.2, "p50_ms": 10.0,
                              "p99_ms": 10.0, "stage_p50_ms": 10.0})
    assert _by_metric(out)["sigs_per_sec"]["status"] == "ok"


def test_cli_exit_codes_and_json(tmp_path, capsys):
    assert bench_diff.main([BASE, BASE]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["verdict"] == "pass"
    assert bench_diff.main([BASE, REGRESSED, "--quiet"]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["verdict"] == "regression"
    assert "sigs_per_sec" in out["failed"]
    # IO errors are a distinct exit code with a JSON error line
    assert bench_diff.main([BASE, str(tmp_path / "missing.json")]) == 2
    assert json.loads(capsys.readouterr().out)["verdict"] == "error"


# --------------------------------------------------------------------------
# BENCH_TRAJECTORY.json: rolling, append-only per run id
# --------------------------------------------------------------------------

def _result(value=46.1):
    return {"value": value, "best_batch": 256, "device": "cpu",
            "p50_ms": 5210.4, "p99_ms": 7102.9,
            "latency_stages": {
                "device_sync": {"p50_ms": 4801.0, "n": 500}},
            "detail": {"256": {"cache_load_s": 207.3}},
            "h2c_dedup": {"factors": {"8": {"speedup_vs_1x": 1.57}},
                          "warm": {"h2c_dispatches": 0}},
            "capacity": {"occupancy_ratio": 0.91}}


def test_trajectory_appends_and_refuses_same_run_id(tmp_path):
    path = str(tmp_path / "BENCH_TRAJECTORY.json")
    assert bench.append_trajectory(_result(46.1), path=path,
                                   run_id="r06") == "appended"
    assert bench.append_trajectory(_result(50.0), path=path,
                                   run_id="r07") == "appended"
    # the same run id must NOT rewrite history the gate already cited
    assert bench.append_trajectory(_result(99.9), path=path,
                                   run_id="r06") == "duplicate_run_id"
    doc = json.load(open(path))
    assert [e["run_id"] for e in doc["entries"]] == ["r06", "r07"]
    entry = doc["entries"][0]
    assert entry["sigs_per_sec"] == 46.1
    assert entry["stage_p50_ms"]["device_sync"] == 4801.0
    assert entry["cache_load_s"] == 207.3 and entry["compile_s"] == 0.0
    assert entry["dedup_speedup_8x"] == 1.57
    assert entry["warm_h2c_dispatches"] == 0


def test_trajectory_is_bounded_and_comparable(tmp_path):
    path = str(tmp_path / "BENCH_TRAJECTORY.json")
    for i in range(7):
        assert bench.append_trajectory(
            _result(40.0 + i), path=path, run_id=f"r{i:02d}",
            max_entries=5) == "appended"
    doc = json.load(open(path))
    assert len(doc["entries"]) == 5
    assert doc["entries"][-1]["run_id"] == "r06"
    # trajectory entries feed straight back into the diff gate
    out = bench_diff.compare(doc["entries"][0], doc["entries"][-1])
    assert _by_metric(out)["sigs_per_sec"]["status"] == "ok"


def test_trajectory_corrupt_file_aborts_without_overwrite(tmp_path):
    """An EXISTING but unreadable trajectory must abort the append —
    silently restarting history would overwrite the record a
    regression gate already cited.  A missing file (first run) still
    starts fresh."""
    path = tmp_path / "BENCH_TRAJECTORY.json"
    path.write_text("not json{{{")
    out = bench.append_trajectory(_result(), path=str(path),
                                  run_id="r01")
    assert out.startswith("error:")
    assert path.read_text() == "not json{{{"    # untouched
    missing = tmp_path / "fresh" / "BENCH_TRAJECTORY.json"
    missing.parent.mkdir()
    assert bench.append_trajectory(_result(), path=str(missing),
                                   run_id="r01") == "appended"
    assert len(json.load(open(missing))["entries"]) == 1


def test_mainnet_gates_on_fixtures():
    """The loadgen acceptance gates: BLOCK_IMPORT/VIP sheds == 0 under
    EVERY scenario, the critical p50 bound on production shapes only
    (adversarial floods are exempt from the latency gate, not the
    shed gate), and the dedup-ratio floor on committee-shaped mixes."""
    base = bench_diff.load_result(BASE)
    out = bench_diff.compare(base, base)
    checks = _by_metric(out)
    assert checks["mainnet_block_import_sheds.steady_state"][
        "status"] == "ok"
    assert checks["mainnet_vip_p50_ms.steady_state"]["status"] == "ok"
    assert checks["mainnet_dedup_ratio.steady_state"]["status"] == "ok"
    # adversarial scenarios carry no latency gate but keep the shed one
    assert checks["mainnet_block_import_sheds.invalid_sig_flood"][
        "status"] == "ok"
    assert "mainnet_vip_p50_ms.invalid_sig_flood" not in checks
    # non-committee-shaped mixes carry no dedup floor
    assert "mainnet_dedup_ratio.dup_collapse" not in checks

    reg = bench_diff.load_result(REGRESSED)
    out = bench_diff.compare(base, reg)
    checks = _by_metric(out)
    assert out["verdict"] == "regression"
    # block import was shed under the storm: the invariant gate fires
    assert checks["mainnet_block_import_sheds.epoch_boundary_storm"][
        "status"] == "regression"
    # vip p50 blown on a production shape
    assert checks["mainnet_vip_p50_ms.steady_state"]["status"] \
        == "regression"
    # a committee-shaped mix lost its duplication
    assert checks["mainnet_dedup_ratio.blob_storm"]["status"] \
        == "regression"


def test_mainnet_gates_absent_are_skipped_and_thresholds():
    """Runs without the mainnet phase (pre-loadgen results) compare
    clean; the p50 bound and dedup floor are operator-tunable."""
    base = bench_diff.load_result(BASE)
    stripped = {k: v for k, v in base.items() if k != "mainnet"}
    out = bench_diff.compare(base, stripped)
    assert not any(c["metric"].startswith("mainnet_")
                   for c in out["checks"])
    assert out["verdict"] == "pass"
    # tighten the critical p50 bound under the storm's measured 228 ms
    out = bench_diff.compare(base, base,
                             {"mainnet_critical_p50_ms_max": 100.0})
    checks = _by_metric(out)
    assert checks["mainnet_vip_p50_ms.epoch_boundary_storm"][
        "status"] == "regression"
    # raise the dedup floor past the fixtures' 0.30
    out = bench_diff.compare(base, base,
                             {"mainnet_dedup_ratio_min": 0.5})
    assert _by_metric(out)["mainnet_dedup_ratio.steady_state"][
        "status"] == "regression"


def test_mesh_gates_on_fixtures():
    """The PR-10 mesh acceptance gates: the device-count sweep must be
    monotonic, and on real parallel hardware (series == "measured")
    the efficiency at the max device count must hold >= 0.7x linear."""
    base = bench_diff.load_result(BASE)
    out = bench_diff.compare(base, base)
    checks = _by_metric(out)
    assert checks["mesh_monotonic"]["status"] == "ok"
    assert checks["mesh_scaling_efficiency"]["status"] == "ok"

    reg = bench_diff.load_result(REGRESSED)
    out = bench_diff.compare(base, reg)
    checks = _by_metric(out)
    assert out["verdict"] == "regression"
    assert checks["mesh_monotonic"]["status"] == "regression"
    assert checks["mesh_scaling_efficiency"]["status"] == "regression"


def test_mesh_gates_skip_when_missing_or_virtual():
    """Skip-if-missing like every phase gate; on a serialized-virtual
    sweep (one host, forced device count) the efficiency gate skips —
    the per-device projection's Amdahl saturation is expected there —
    while monotonicity of the projection is still gated.  The
    threshold is operator-tunable."""
    base = bench_diff.load_result(BASE)
    stripped = {k: v for k, v in base.items() if k != "mesh"}
    out = bench_diff.compare(base, stripped)
    checks = _by_metric(out)
    assert checks["mesh_monotonic"]["status"] == "skipped"
    assert checks["mesh_scaling_efficiency"]["status"] == "skipped"
    assert out["verdict"] == "pass"

    virtual = dict(base)
    virtual["mesh"] = dict(base["mesh"],
                           series="projected_serialized_virtual",
                           scaling_efficiency_at_max=0.35)
    out = bench_diff.compare(base, virtual)
    checks = _by_metric(out)
    assert checks["mesh_scaling_efficiency"]["status"] == "skipped"
    assert checks["mesh_monotonic"]["status"] == "ok"
    # a non-monotonic virtual projection still fails
    virtual["mesh"] = dict(virtual["mesh"], monotonic=False)
    out = bench_diff.compare(base, virtual)
    assert _by_metric(out)["mesh_monotonic"]["status"] == "regression"
    # operator override tightens the measured gate past the fixture
    out = bench_diff.compare(base, base,
                             {"mesh_efficiency_min": 0.9})
    assert _by_metric(out)["mesh_scaling_efficiency"]["status"] \
        == "regression"
    # trajectory entries carry the FLATTENED mesh fields: the gates
    # read them with the standard fallback, like every other phase
    flat = {"mesh_monotonic": True, "mesh_series": "measured",
            "mesh_scaling_efficiency": 0.8}
    checks = _by_metric(bench_diff.compare({}, flat))
    assert checks["mesh_monotonic"]["status"] == "ok"
    assert checks["mesh_scaling_efficiency"]["status"] == "ok"
    flat["mesh_series"] = "projected_serialized_virtual"
    assert _by_metric(bench_diff.compare({}, flat))[
        "mesh_scaling_efficiency"]["status"] == "skipped"


def test_chaos_gates_on_fixtures():
    """The mesh self-healing acceptance gates: zero wrong verdicts
    through eject/reshape/readmit, full grow-back, and (on measured
    series) recovery <= mesh_recovery_s_max — in BOTH the bench chaos
    phase and the loadgen chaos_device_loss scenario."""
    base = bench_diff.load_result(BASE)
    out = bench_diff.compare(base, base)
    checks = _by_metric(out)
    assert checks["chaos_wrong_verdicts"]["status"] == "ok"
    assert checks["chaos_recovered"]["status"] == "ok"
    assert checks["chaos_recovery_s"]["status"] == "ok"
    assert checks["mainnet_chaos_wrong_verdicts"]["status"] == "ok"
    assert checks["mainnet_chaos_recovered"]["status"] == "ok"
    # the chaos scenario also rides the per-scenario protected-class
    # shed gate like every other traffic shape
    assert checks["mainnet_block_import_sheds.chaos_device_loss"][
        "status"] == "ok"

    reg = bench_diff.load_result(REGRESSED)
    out = bench_diff.compare(base, reg)
    checks = _by_metric(out)
    assert out["verdict"] == "regression"
    assert checks["chaos_wrong_verdicts"]["status"] == "regression"
    assert checks["chaos_recovered"]["status"] == "regression"
    assert checks["chaos_recovery_s"]["status"] == "regression"
    assert checks["mainnet_chaos_wrong_verdicts"]["status"] \
        == "regression"
    assert checks["mainnet_chaos_recovered"]["status"] == "regression"


def test_chaos_gates_skip_when_missing_or_virtual():
    """Skip-if-missing (budget-starved runs drop the phase) and
    skip-on-virtual for the recovery-time gate: serialized virtual
    devices pay XLA compile wall time that means nothing, so only the
    correctness gates (wrong verdicts, recovered) apply there.  The
    RTO threshold is operator-tunable."""
    base = bench_diff.load_result(BASE)
    stripped = {k: v for k, v in base.items() if k != "chaos"}
    stripped["mainnet"] = {
        "scenarios": {k: v for k, v
                      in base["mainnet"]["scenarios"].items()
                      if k != "chaos_device_loss"}}
    out = bench_diff.compare(base, stripped)
    checks = _by_metric(out)
    for m in ("chaos_wrong_verdicts", "chaos_recovered",
              "chaos_recovery_s"):
        assert checks[m]["status"] == "skipped", m
    # the loadgen-chaos gates follow the per-scenario precedent:
    # absent scenario => no mainnet_* checks at all
    assert "mainnet_chaos_wrong_verdicts" not in checks
    assert "mainnet_chaos_recovered" not in checks
    # a skipped bench phase leaves a "skipped: ..." STRING, not a dict
    stringy = dict(stripped, chaos="skipped: needs >= 4 devices")
    out = bench_diff.compare(base, stringy)
    assert _by_metric(out)["chaos_wrong_verdicts"]["status"] \
        == "skipped"

    virtual = dict(base)
    virtual["chaos"] = dict(base["chaos"], series="virtual",
                            recovery_s=240.0)
    out = bench_diff.compare(base, virtual)
    checks = _by_metric(out)
    assert checks["chaos_recovery_s"]["status"] == "skipped"
    assert checks["chaos_wrong_verdicts"]["status"] == "ok"
    # a virtual run that flips a verdict still fails
    virtual["chaos"] = dict(virtual["chaos"], wrong_verdicts=1)
    assert _by_metric(bench_diff.compare(base, virtual))[
        "chaos_wrong_verdicts"]["status"] == "regression"
    # operator override tightens the measured RTO gate
    out = bench_diff.compare(base, base,
                             {"mesh_recovery_s_max": 5.0})
    assert _by_metric(out)["chaos_recovery_s"]["status"] \
        == "regression"
    # trajectory entries carry the flattened chaos fields
    flat = {"chaos_recovery_s": 8.0, "chaos_wrong_verdicts": 0,
            "chaos_series": "measured", "chaos_recovered": True}
    checks = _by_metric(bench_diff.compare({}, flat))
    assert checks["chaos_wrong_verdicts"]["status"] == "ok"
    assert checks["chaos_recovered"]["status"] == "ok"
    assert checks["chaos_recovery_s"]["status"] == "ok"


def test_ledger_gates_on_fixtures():
    """The PR-13 dispatch-ledger gates: per bench phase, lane-bucket
    padding waste must stay <= padding_waste_max (0.5) and the mesh
    shard makespan ratio <= mesh_imbalance_max (1.5)."""
    base = bench_diff.load_result(BASE)
    out = bench_diff.compare(base, base)
    checks = _by_metric(out)
    assert checks["ledger_padding_waste.latency"]["status"] == "ok"
    assert checks["ledger_padding_waste.mesh"]["status"] == "ok"
    assert checks["ledger_mesh_imbalance.mesh"]["status"] == "ok"
    # a phase without mesh dispatches skips its imbalance gate
    assert checks["ledger_mesh_imbalance.latency"]["status"] \
        == "skipped"

    reg = bench_diff.load_result(REGRESSED)
    out = bench_diff.compare(base, reg)
    checks = _by_metric(out)
    assert out["verdict"] == "regression"
    # the seeded regressions: latency-phase lane waste 0.61 > 0.5,
    # mesh-phase makespan 1.82 > 1.5
    assert checks["ledger_padding_waste.latency"]["status"] \
        == "regression"
    assert checks["ledger_mesh_imbalance.mesh"]["status"] \
        == "regression"
    assert checks["ledger_padding_waste.mesh"]["status"] == "ok"


def test_ledger_gates_skip_when_missing_and_thresholds():
    """Skip-if-missing (pre-ledger results and budget-starved runs
    carry no `ledger` block); thresholds are operator-tunable."""
    base = bench_diff.load_result(BASE)
    stripped = {k: v for k, v in base.items() if k != "ledger"}
    out = bench_diff.compare(base, stripped)
    assert not any(c["metric"].startswith("ledger_")
                   for c in out["checks"])
    assert out["verdict"] == "pass"
    # a phase that PINNED its dispatch bucket for compile budget
    # (bench latency phase) skips the waste gate: the waste measures
    # the pin, not the production planner
    pinned = json.loads(json.dumps(base))
    pinned["ledger"]["latency"]["padding_waste"]["lane"] = 0.73
    pinned["ledger"]["latency"]["pinned_min_bucket"] = 256
    out = bench_diff.compare(base, pinned)
    assert _by_metric(out)["ledger_padding_waste.latency"]["status"] \
        == "skipped"
    assert out["verdict"] == "pass"
    # tighten the waste gate below the healthy fixture's 0.0312: flags
    out = bench_diff.compare(base, base,
                             {"padding_waste_max": 0.01})
    assert _by_metric(out)["ledger_padding_waste.latency"]["status"] \
        == "regression"
    # loosen the imbalance gate past the regressed fixture's 1.82
    reg = bench_diff.load_result(REGRESSED)
    out = bench_diff.compare(base, reg,
                             {"mesh_imbalance_max": 2.0})
    assert _by_metric(out)["ledger_mesh_imbalance.mesh"]["status"] \
        == "ok"


def test_phase_focused_run_zero_value_skips_relative_gates():
    """A control-plane-focused run (BENCH_THROUGHPUT=0) reports
    value=0.0 — that is 'phase did not run', never a measured
    collapse, so the relative gates skip instead of failing."""
    base = bench_diff.load_result(BASE)
    focused = dict(base)
    focused["value"] = 0.0
    out = bench_diff.compare(base, focused)
    assert _by_metric(out)["sigs_per_sec"]["status"] == "skipped"
    assert out["verdict"] == "pass"


def test_current_bench_r09_mainnet_evidence_gates_clean():
    """The checked-in mainnet-focused BENCH_r09 run: >= 4 scenarios
    including the adversarial flood and the epoch-boundary storm, all
    mainnet gates green against the r08 base."""
    r08 = os.path.join(os.path.dirname(__file__), "..",
                       "BENCH_r08.json")
    r09 = os.path.join(os.path.dirname(__file__), "..",
                       "BENCH_r09.json")
    if not (os.path.exists(r08) and os.path.exists(r09)):
        pytest.skip("checked-in bench results not present")
    new = bench_diff.load_result(r09)
    scen = new["mainnet"]["scenarios"]
    assert len([v for v in scen.values() if isinstance(v, dict)
                and "by_class" in v]) >= 4
    assert "invalid_sig_flood" in scen
    assert "epoch_boundary_storm" in scen
    assert scen["invalid_sig_flood"]["bisect_dispatches"] > 0
    assert scen["epoch_boundary_storm"]["brownout"]["enters"] >= 1
    out = bench_diff.compare(bench_diff.load_result(r08), new)
    assert out["verdict"] == "pass"
    mainnet_checks = [c for c in out["checks"]
                      if c["metric"].startswith("mainnet_")]
    assert mainnet_checks
    assert all(c["status"] in ("ok", "skipped")
               for c in mainnet_checks)
