"""bench_diff — the bench regression gate.

Compares any two bench result JSONs (``BENCH_r*.json`` — raw bench
output or the driver wrapper with a ``parsed`` key — or one entry of
``BENCH_TRAJECTORY.json``) metric by metric with per-metric thresholds
and emits a machine-readable verdict:

    python tools/bench_diff.py BENCH_r04.json BENCH_r05.json
    python tools/bench_diff.py base.json new.json \
        --threshold sigs_per_sec=0.2 --quiet

Exit code 0 = pass, 1 = regression, 2 = usage/IO error.  The JSON
verdict on stdout is the contract future perf PRs (ROADMAP items 1, 2,
5) cite as their regression gate:

    {"verdict": "pass" | "regression",
     "regressions": <n>,
     "checks": [{"metric", "base", "new", "ratio", "threshold",
                 "direction", "status": "ok"|"regression"|"skipped"},
                ...]}

Checked metrics (a metric missing on either side is ``skipped``, never
a failure — budget-starved runs drop phases):

- ``sigs_per_sec`` (higher is better): flag when new < base·(1-thr);
- ``p50_ms`` / ``p99_ms`` and every per-stage p50 in
  ``latency_stages`` (lower is better): flag when new > base·(1+thr);
- compile-cache accounting: a shape the base run served as
  ``cache_load_s`` that the new run paid as ``compile_s`` again means
  the persistent cache stopped serving (absolute check);
- dedup gates (absolute): ``h2c_dedup`` 8x speedup ≥ 1.5 and the
  fully-warm pass's ``h2c_dispatches == 0`` — the PR-5 acceptance
  properties must not silently rot;
- overload gates (absolute, on the closed-loop ``overload.at_max``
  run): p50 under max offered load ≤ ``overload_p50_ms_max`` (default
  the 100 ms SLO), ZERO BLOCK_IMPORT sheds, shed counts ordered
  OPTIMISTIC ≥ GOSSIP, and an unflapped brownout (one enter edge, at
  most one exit) — the PR-7 acceptance properties;
- mainnet gates (absolute, per loadgen scenario in ``mainnet``):
  BLOCK_IMPORT/VIP sheds == 0 under EVERY traffic shape, vip/
  block_import p50 ≤ ``mainnet_critical_p50_ms_max`` on production
  (non-adversarial) shapes, and dedup ratio ≥
  ``mainnet_dedup_ratio_min`` on committee-shaped mixes;
- mesh gates (absolute, on the device-count sweep in ``mesh``): the
  scaling series must be monotonic in device count, and on real
  parallel hardware (``series == "measured"``) efficiency at the max
  count ≥ ``mesh_efficiency_min`` × linear (serialized-virtual runs
  report efficiency but only monotonicity is gated);
- ledger gates (absolute, per bench phase under ``ledger``): the
  dispatch decision ledger's lane-bucket padding waste ≤
  ``padding_waste_max`` and mesh shard makespan ratio ≤
  ``mesh_imbalance_max`` on every phase that emitted them
  (skip-if-missing);
- chaos gates (mesh self-healing, absolute, skip-if-missing): zero
  wrong verdicts through eject/reshape/readmit and full grow-back in
  BOTH the bench ``chaos`` phase and the loadgen ``chaos_device_loss``
  scenario, plus recovery ≤ ``mesh_recovery_s_max`` on measured
  (real-hardware) series — virtual serialized runs report recovery
  time but are compile-dominated, so the wall gate skips them.
"""

import argparse
import json
import sys
from typing import Dict, Optional

# fractional tolerance per relative metric; absolute gates are coded
DEFAULT_THRESHOLDS: Dict[str, float] = {
    "sigs_per_sec": 0.10,
    "p50_ms": 0.25,
    "p99_ms": 0.30,
    "stage_p50_ms": 0.30,
    "dedup_speedup_8x_min": 1.5,
    "overload_p50_ms_max": 100.0,
    "mainnet_critical_p50_ms_max": 300.0,
    # committee-shaped floor: steady mixes measure ~0.34, the boundary
    # storm ~0.24 (brownout sheds duplicated gossip before dispatch);
    # adversarial dup-collapse sits at ~0.03
    "mainnet_dedup_ratio_min": 0.2,
    # mesh scaling at the max device count must keep >= 0.7x linear —
    # enforced on MEASURED series (real parallel hardware) only; the
    # serialized-virtual projection reports efficiency but its Amdahl
    # saturation (replicated finish) is expected, so it is not gated
    "mesh_efficiency_min": 0.7,
    # dispatch-ledger gates (per bench phase that emitted a ledger
    # summary): pow-2 lane-bucket padding waste must stay bounded, and
    # the mesh shard makespan (max shard lane load / mean) must stay
    # near balanced — both direct throughput observables the ledger
    # (infra/dispatchledger.py) now records per dispatch
    "padding_waste_max": 0.5,
    "mesh_imbalance_max": 1.5,
    # mesh self-healing recovery-time objective: losing a chip must
    # cost eject + replan + AOT warm of the smaller shapes, bounded —
    # gated on MEASURED (real parallel hardware) series only; virtual
    # serialized runs pay compile wall time that means nothing
    "mesh_recovery_s_max": 60.0,
    # causal-timeline gate (timeline PR): while the latency burst's
    # queue holds work, the device must be executing a dispatch at
    # least this share of the time — the direct measurement of the
    # async-overlap machinery doing its job.  Skip-if-missing: absent
    # when TEKU_TPU_TIMELINE=0 or the result predates the ring.
    # Default 0.0 (vacuous): the CPU reference box MEASURES ~0 —
    # the service drains the queue into one batch and only then
    # dispatches, so the queue is empty again before the device gets
    # busy (BENCH_r18 latency phase: 0.0002).  Raise to ~0.3 on real
    # parallel hardware where enqueue overlaps device execution.
    "overlap_efficiency_min": 0.0,
}


def load_result(path: str) -> dict:
    """Read a bench result, unwrapping the driver's ``{"parsed": ...}``
    envelope when present."""
    with open(path) as fh:
        doc = json.load(fh)
    if isinstance(doc, dict) and isinstance(doc.get("parsed"), dict):
        doc = doc["parsed"]
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: not a bench result object")
    return doc


def _get(doc: dict, *path):
    for key in path:
        if not isinstance(doc, dict):
            return None
        doc = doc.get(key)
    return doc


def _stage_p50s(doc: dict) -> Dict[str, float]:
    stages = doc.get("latency_stages") or {}
    out = {}
    for stage, v in stages.items():
        if isinstance(v, dict) and isinstance(
                v.get("p50_ms"), (int, float)):
            out[stage] = float(v["p50_ms"])
    # trajectory entries carry the flattened form
    for stage, v in (doc.get("stage_p50_ms") or {}).items():
        if isinstance(v, (int, float)):
            out.setdefault(stage, float(v))
    return out


def _check(checks: list, metric: str, base, new, threshold: float,
           direction: str) -> None:
    """direction: "higher" = higher is better, "lower" = lower is
    better.  None/zero on either side = skipped (no evidence): every
    relative metric here is strictly positive when measured, so a 0
    means the phase did not run (budget-starved or phase-focused
    runs), not a measured collapse."""
    entry = {"metric": metric, "base": base, "new": new,
             "threshold": threshold, "direction": direction}
    if not isinstance(base, (int, float)) \
            or not isinstance(new, (int, float)) or base <= 0 \
            or new <= 0:
        entry["status"] = "skipped"
        checks.append(entry)
        return
    ratio = new / base
    entry["ratio"] = round(ratio, 4)
    if direction == "higher":
        regressed = ratio < 1.0 - threshold
    else:
        regressed = ratio > 1.0 + threshold
    entry["status"] = "regression" if regressed else "ok"
    checks.append(entry)


def _check_absolute(checks: list, metric: str, value, predicate,
                    detail: str) -> None:
    entry = {"metric": metric, "new": value, "direction": "absolute",
             "detail": detail}
    if value is None:
        entry["status"] = "skipped"
    else:
        entry["status"] = "ok" if predicate(value) else "regression"
    checks.append(entry)


def compare(base: dict, new: dict,
            thresholds: Optional[Dict[str, float]] = None) -> dict:
    thr = dict(DEFAULT_THRESHOLDS)
    thr.update(thresholds or {})
    checks: list = []

    _check(checks, "sigs_per_sec",
           base.get("value", base.get("sigs_per_sec")),
           new.get("value", new.get("sigs_per_sec")),
           thr["sigs_per_sec"], "higher")
    _check(checks, "p50_ms", base.get("p50_ms"), new.get("p50_ms"),
           thr["p50_ms"], "lower")
    _check(checks, "p99_ms", base.get("p99_ms"), new.get("p99_ms"),
           thr["p99_ms"], "lower")

    base_stages, new_stages = _stage_p50s(base), _stage_p50s(new)
    for stage in sorted(set(base_stages) & set(new_stages)):
        _check(checks, f"stage_p50_ms.{stage}", base_stages[stage],
               new_stages[stage], thr["stage_p50_ms"], "lower")

    # compile-cache accounting: a shape the base loaded from the
    # persistent cache must not recompile fresh in the new run
    recompiled = []
    base_detail = base.get("detail") or {}
    new_detail = new.get("detail") or {}
    for shape, bv in base_detail.items():
        nv = new_detail.get(shape)
        if isinstance(bv, dict) and isinstance(nv, dict) \
                and "cache_load_s" in bv and "compile_s" in nv:
            recompiled.append(shape)
    _check_absolute(
        checks, "compile_cache_serving",
        recompiled if (base_detail and new_detail) else None,
        lambda shapes: not shapes,
        "shapes the base run cache-loaded but the new run recompiled")

    # dedup gates (PR-5 acceptance properties, absolute)
    f8 = _get(new, "h2c_dedup", "factors", "8") or {}
    _check_absolute(
        checks, "dedup_speedup_8x",
        f8.get("speedup_vs_1x", new.get("dedup_speedup_8x")),
        lambda v: v >= thr["dedup_speedup_8x_min"],
        f"8x-duplication speedup must stay >= "
        f"{thr['dedup_speedup_8x_min']}")
    warm = _get(new, "h2c_dedup", "warm") or {}
    _check_absolute(
        checks, "warm_h2c_dispatches",
        warm.get("h2c_dispatches", new.get("warm_h2c_dispatches")),
        lambda v: v == 0,
        "a fully-warm H(m) cache must dispatch zero h2c")

    # overload gates (PR-7 acceptance properties, absolute): the
    # closed-loop phase's max-offered-load run must hold the SLO by
    # shedding the right classes, never block import, without flapping
    at_max = _get(new, "overload", "at_max") or {}
    _check_absolute(
        checks, "overload_p50_ms",
        at_max.get("p50_ms", new.get("overload_p50_ms")),
        lambda v: v <= thr["overload_p50_ms_max"],
        f"p50 under max offered load must stay <= "
        f"{thr['overload_p50_ms_max']} ms")
    sheds = at_max.get("sheds") or {}
    _check_absolute(
        checks, "overload_block_import_sheds",
        sheds.get("block_import",
                  new.get("overload_block_import_sheds")),
        lambda v: v == 0,
        "BLOCK_IMPORT must never be shed under overload")
    _check_absolute(
        checks, "overload_shed_order",
        ((sheds.get("optimistic"), sheds.get("gossip"))
         if sheds else None),
        lambda v: v[0] is not None and v[1] is not None
        and v[0] >= v[1],
        "shed counts must be ordered OPTIMISTIC >= GOSSIP")
    brownout = at_max.get("brownout") or {}
    _check_absolute(
        checks, "overload_brownout_stable",
        brownout.get("flapped") if brownout else None,
        lambda v: v is False,
        "brownout must be edge-triggered: one enter, at most one "
        "exit, no flapping")

    # causal-timeline gate (timeline PR, absolute, skip-if-missing):
    # device-busy ∩ queue-nonempty over queue-nonempty during the
    # latency burst — overlap collapsing means host work serialized
    # ahead of the device again
    _check_absolute(
        checks, "overlap_efficiency",
        new.get("overlap_efficiency"),
        lambda v: v >= thr["overlap_efficiency_min"],
        f"device-busy share of queue-nonempty time must stay >= "
        f"{thr['overlap_efficiency_min']}")

    # mesh gates (PR-10 acceptance properties, absolute, skip-if-
    # missing): the device-count sweep's scaling series must rise
    # monotonically with chips, and on real parallel hardware the
    # efficiency at the max count must stay >= mesh_efficiency_min of
    # linear.  A virtual (serialized single-host) run reports
    # efficiency but only the monotonicity of its per-device
    # projection is gated — its wall time physically cannot drop.
    mesh_block = _get(new, "mesh") or {}
    _check_absolute(
        checks, "mesh_monotonic",
        mesh_block.get("monotonic", new.get("mesh_monotonic")),
        lambda v: v is True,
        "mesh sigs/sec must rise monotonically with device count")
    mesh_series = mesh_block.get("series", new.get("mesh_series"))
    mesh_eff = mesh_block.get("scaling_efficiency_at_max",
                              new.get("mesh_scaling_efficiency"))
    _check_absolute(
        checks, "mesh_scaling_efficiency",
        mesh_eff if mesh_series == "measured" else None,
        lambda v: v >= thr["mesh_efficiency_min"],
        f"scaling efficiency at the max device count must stay >= "
        f"{thr['mesh_efficiency_min']}x linear on real hardware")

    # mainnet gates (loadgen acceptance properties, absolute, per
    # scenario): protected classes are NEVER shed under any traffic
    # shape, the critical-class p50 bound holds on every production
    # (non-adversarial) shape, and committee-shaped mixes keep the
    # dedup ratio the unique-message pipeline's wins depend on
    for name, rep in sorted((_get(new, "mainnet", "scenarios")
                             or {}).items()):
        if not isinstance(rep, dict) or "by_class" not in rep:
            continue
        sheds = rep.get("sheds") or {}
        _check_absolute(
            checks, f"mainnet_block_import_sheds.{name}",
            (sheds.get("block_import"), sheds.get("vip")),
            lambda v: v[0] == 0 and v[1] == 0,
            "BLOCK_IMPORT/VIP must never be shed, under every "
            "scenario")
        if not rep.get("adversarial"):
            for cls in ("vip", "block_import"):
                _check_absolute(
                    checks, f"mainnet_{cls}_p50_ms.{name}",
                    _get(rep, "by_class", cls, "p50_ms"),
                    lambda v: v <= thr["mainnet_critical_p50_ms_max"],
                    f"{cls} p50 must stay <= "
                    f"{thr['mainnet_critical_p50_ms_max']} ms on "
                    "production shapes")
        if rep.get("committee_shaped"):
            _check_absolute(
                checks, f"mainnet_dedup_ratio.{name}",
                rep.get("dedup_ratio"),
                lambda v: v >= thr["mainnet_dedup_ratio_min"],
                f"committee-shaped mixes must keep dedup ratio >= "
                f"{thr['mainnet_dedup_ratio_min']}")

    # chaos gates (mesh self-healing acceptance, absolute,
    # skip-if-missing): device loss must NEVER flip a verdict, the
    # mesh must grow back to full width once the fault clears, and on
    # real hardware the eject->reshape->serving recovery must beat the
    # recovery-time objective (virtual serialized runs report the time
    # but their wall clock is compile-dominated and not gated)
    chaos = _get(new, "chaos") if isinstance(_get(new, "chaos"), dict) \
        else {}
    _check_absolute(
        checks, "chaos_wrong_verdicts",
        chaos.get("wrong_verdicts", new.get("chaos_wrong_verdicts")),
        lambda v: v == 0,
        "device loss must never flip a verdict (zero wrong verdicts "
        "through eject/reshape/readmit)")
    _check_absolute(
        checks, "chaos_recovered",
        chaos.get("recovered", new.get("chaos_recovered")),
        lambda v: v is True,
        "the mesh must readmit the recovered device and grow back to "
        "its configured width")
    chaos_series = chaos.get("series", new.get("chaos_series"))
    _check_absolute(
        checks, "chaos_recovery_s",
        (chaos.get("recovery_s", new.get("chaos_recovery_s"))
         if chaos_series == "measured" else None),
        lambda v: v <= thr["mesh_recovery_s_max"],
        f"eject->reshape->on-device-serving recovery must stay <= "
        f"{thr['mesh_recovery_s_max']} s on real hardware")
    # the loadgen chaos scenario (REAL supervisor machinery under
    # traffic): zero wrong verdicts and full recovery; its
    # protected-class shed gate already rides the per-scenario
    # mainnet loop above (sheds==0 under EVERY scenario, chaos
    # included).  Emitted only when the scenario ran — pre-loadgen
    # results must compare with no mainnet_* checks at all (the
    # per-scenario precedent above)
    mchaos = _get(new, "mainnet", "scenarios", "chaos_device_loss",
                  "chaos")
    if isinstance(mchaos, dict):
        _check_absolute(
            checks, "mainnet_chaos_wrong_verdicts",
            mchaos.get("wrong_verdicts"),
            lambda v: v == 0,
            "loadgen device loss must never flip a verdict")
        _check_absolute(
            checks, "mainnet_chaos_recovered",
            mchaos.get("recovered"),
            lambda v: v is True,
            "the loadgen chaos mesh must readmit and grow back")

    # ledger gates (absolute, per phase, skip-if-missing): each bench
    # phase's dispatch-ledger summary must keep padding waste and mesh
    # shard imbalance inside the bounds — a regression here means the
    # batch/shard planners started dispatching dead work even if the
    # headline sigs/sec survived
    for phase, led in sorted((new.get("ledger") or {}).items()):
        if not isinstance(led, dict):
            continue
        waste = (led.get("padding_waste") or {}).get("lane")
        if led.get("pinned_min_bucket"):
            # the phase pinned its dispatch bucket for compile budget
            # (bench latency phase): the waste measures the pin, not
            # the production batch planner — skip, don't fail
            waste = None
        _check_absolute(
            checks, f"ledger_padding_waste.{phase}", waste,
            lambda v: v <= thr["padding_waste_max"],
            f"lane-bucket padding waste must stay <= "
            f"{thr['padding_waste_max']}")
        _check_absolute(
            checks, f"ledger_mesh_imbalance.{phase}",
            (led.get("mesh_imbalance") or {}).get("max"),
            lambda v: v <= thr["mesh_imbalance_max"],
            f"mesh shard makespan ratio must stay <= "
            f"{thr['mesh_imbalance_max']}")

    regressions = [c for c in checks if c["status"] == "regression"]
    return {"verdict": "regression" if regressions else "pass",
            "regressions": len(regressions),
            "checks": checks,
            "thresholds": thr}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="bench_diff",
        description="compare two bench result JSONs; exit 1 on "
                    "regression")
    ap.add_argument("base", help="baseline BENCH_*.json")
    ap.add_argument("new", help="candidate BENCH_*.json")
    ap.add_argument("--threshold", action="append", default=[],
                    metavar="NAME=FRACTION",
                    help="override a threshold, e.g. sigs_per_sec=0.2")
    ap.add_argument("--quiet", action="store_true",
                    help="print only the one-line verdict, not the "
                         "full check list")
    args = ap.parse_args(argv)
    overrides: Dict[str, float] = {}
    for spec in args.threshold:
        name, _, value = spec.partition("=")
        if not value:
            ap.error(f"--threshold {spec!r}: expected NAME=FRACTION")
        try:
            overrides[name] = float(value)
        except ValueError:
            ap.error(f"--threshold {spec!r}: {value!r} is not a number")
    try:
        base = load_result(args.base)
        new = load_result(args.new)
    except (OSError, ValueError) as exc:
        print(json.dumps({"verdict": "error", "error": str(exc)}))
        return 2
    out = compare(base, new, overrides)
    if args.quiet:
        out = {"verdict": out["verdict"],
               "regressions": out["regressions"],
               "failed": [c["metric"] for c in out["checks"]
                          if c["status"] == "regression"]}
    print(json.dumps(out, indent=1))
    return 1 if out["verdict"] == "regression" else 0


if __name__ == "__main__":
    sys.exit(main())
