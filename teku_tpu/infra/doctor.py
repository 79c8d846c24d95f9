"""`cli doctor` — the latency-budget explainability engine.

The observability stack now records everything a diagnosis needs: the
dispatch decision ledger (infra/dispatchledger.py — per-dispatch cost
attribution), the capacity model (infra/capacity.py — per-shape device
latency, utilization/headroom), the SLO engine (infra/health.py — burn
rates and breach events blaming trace ids), and the flight recorder
(infra/flightrecorder.py — the ordered incident timeline).  What was
missing is the JOIN: when ``attestation_verify_p50`` burns, an operator
still had to correlate four endpoints by hand.

``diagnose()`` is that join as a pure function over the four snapshots
(so the same engine serves the in-process CLI probe, the remote
``--url`` mode reading a live node's admin endpoints, and the tests):
it emits a RANKED list of findings — "p50 driven by cold compile of
shape 512x8: 3 dispatches, 41 s", "shard 3 makespan 1.8x mean",
"padding waste 0.43 at lane bucket 64" — each citing its evidence:
ledger records by seq + trace id, flight-recorder events by seq, SLO
objectives by name.  ``render_text()`` prints the human form; the raw
dict is the machine form (``cli doctor --json``).
"""

from typing import Dict, List, Optional

from . import dispatchledger, schema, timeline as timeline_mod
from .env import env_float

# findings below this severity are listed but don't flip `healthy`
ATTENTION_SEVERITY = 40.0


def _finding(kind: str, severity: float, title: str, detail: str,
             evidence: Optional[List[dict]] = None,
             metrics: Optional[dict] = None) -> dict:
    return {"kind": kind, "severity": round(min(severity, 100.0), 1),
            "title": title, "detail": detail,
            "evidence": evidence or [], "metrics": metrics or {}}


def _cite(rec: dict) -> dict:
    trace_ids = rec.get("trace_ids") or []
    return {"type": "dispatch", "seq": rec.get("seq"),
            "trace_id": trace_ids[0] if trace_ids else "",
            "shape": rec.get("shape")}


def _cite_event(ev: dict) -> dict:
    return {"type": "flight_event", "seq": ev.get("seq"),
            "kind": ev.get("kind"),
            "trace_id": ev.get("trace_id", "")}


# --------------------------------------------------------------------------
# Individual analyzers (each: records/snapshots -> findings)
# --------------------------------------------------------------------------

def _compile_findings(records: List[dict]) -> List[dict]:
    out = []
    for outcome, base, name in (("compile", 40.0, "cold compile"),
                                ("cache_load", 15.0, "cache load"),
                                ("aot_load", 5.0, "AOT store load")):
        by_shape: Dict[str, List[dict]] = {}
        for r in records:
            comp = r.get("compile") or {}
            if comp.get("outcome") == outcome:
                by_shape.setdefault(str(r.get("shape")), []).append(r)
        for shape, recs in sorted(by_shape.items()):
            total_s = sum((r.get("compile") or {}).get("enqueue_s", 0)
                          for r in recs)
            if total_s < 0.5:
                continue
            out.append(_finding(
                f"{outcome}_latency", base + min(total_s, 55),
                f"{name} of shape {shape}: {len(recs)} dispatch(es), "
                f"{total_s:.1f} s",
                "first dispatch of a shape pays the XLA work "
                "synchronously inside device_enqueue — every lane in "
                "those batches (and everything queued behind them) "
                "absorbed it; precompiling the shape set at install "
                "time (supervisor warmup) or keeping the persistent "
                "cache warm removes this from the serving path",
                evidence=[_cite(r) for r in recs[:5]],
                metrics={"shape": shape, "dispatches": len(recs),
                         "total_s": round(total_s, 2),
                         "outcome": outcome}))
    return out


def _precompile_findings(records: List[dict]) -> List[dict]:
    """``cold_compile_on_hot_path``: a serving dispatch paid a FRESH
    XLA compile for a shape the shapeset registry covers — ``cli
    precompile`` (or a prior boot's self-populated AOT store) would
    have had the executable on disk.  Distinct from the generic
    compile_latency finding: this one names the fix."""
    by_shape: Dict[str, List[dict]] = {}
    for r in records:
        comp = r.get("compile") or {}
        if comp.get("outcome") == "compile":
            by_shape.setdefault(str(r.get("shape")), []).append(r)
    if not by_shape:
        return []
    covered_memo: Dict[int, set] = {}

    def _covered(shape: str) -> bool:
        mesh_n = 0
        if "@m" in shape:
            try:
                mesh_n = int(shape.split("@m", 1)[1])
            except ValueError:
                return False
        if mesh_n not in covered_memo:
            try:
                from ..ops import shapeset
                covered_memo[mesh_n] = shapeset.serving_shapes(
                    mesh_devices=mesh_n,
                    key_bucket=shapeset.SERVICE_KEY_BUCKET,
                    block_key_bucket=shapeset.SERVICE_BLOCK_KEY_BUCKET)
            except Exception:  # pragma: no cover - odd mesh widths
                covered_memo[mesh_n] = set()
        return shape in covered_memo[mesh_n]

    out = []
    for shape, recs in sorted(by_shape.items()):
        if not _covered(shape):
            continue
        total_s = sum((r.get("compile") or {}).get("enqueue_s", 0)
                      for r in recs)
        out.append(_finding(
            "cold_compile_on_hot_path", 50.0 + min(total_s, 50),
            f"shape {shape} compiled on the serving path "
            f"({len(recs)} dispatch(es), {total_s:.1f} s) — the "
            "shapeset registry covers it",
            "this shape is in the default serving set "
            "(ops/shapeset.py), so the compile was avoidable: `cli "
            "precompile` serializes the whole set into the AOT store "
            "at install time, after which boots and first dispatches "
            "deserialize in seconds (outcome aot_load) instead of "
            "paying XLA synchronously under live traffic",
            evidence=[_cite(r) for r in recs[:5]],
            metrics={"shape": shape, "dispatches": len(recs),
                     "total_s": round(total_s, 2)}))
    return out


def _imbalance_findings(records: List[dict]) -> List[dict]:
    worst = None
    for r in records:
        mesh = r.get("mesh") or {}
        ratio = mesh.get("makespan_ratio")
        if mesh.get("devices") and isinstance(ratio, (int, float)) \
                and ratio >= 1.25:
            if worst is None or ratio > worst[0]:
                worst = (ratio, r)
    if worst is None:
        return []
    ratio, rec = worst
    mesh = rec["mesh"]
    loads = mesh.get("shard_lanes") or []
    shard = loads.index(max(loads)) if loads else -1
    n_bad = sum(1 for r in records
                if (r.get("mesh") or {}).get("makespan_ratio", 0)
                >= 1.25)
    return [_finding(
        "mesh_shard_imbalance", 30 + 40 * (min(ratio, 2.5) - 1.0),
        f"shard {shard} makespan {ratio:.2f}x mean under group-cap "
        f"rows ({mesh.get('devices')}-device mesh, {n_bad} "
        f"dispatch(es) >= 1.25x)",
        "the sharded dispatch's wall time is the slowest shard's, so "
        "the makespan ratio IS the lost scaling; whole message-group "
        "rows cannot split across shards — oversized committees "
        "(group-cap row chains) pin lanes together.  Lowering "
        "TEKU_TPU_H2C_GROUP_CAP splits committees across more, "
        "smaller rows the LPT packer can balance",
        evidence=[_cite(rec)],
        metrics={"makespan_ratio": round(ratio, 3),
                 "shard_lanes": loads, "worst_shard": shard})]


def _padding_findings(records: List[dict], summary: dict) -> List[dict]:
    out = []
    for bucket, waste in (summary.get("padding_waste_by_lane_bucket")
                          or {}).items():
        if waste < 0.3:
            continue
        recs = [r for r in records
                if ((r.get("waste") or {}).get("lane") or {}).get(
                    "padded") == int(bucket)]
        out.append(_finding(
            "padding_waste", 20 + 60 * waste,
            f"padding waste {waste:.2f} at lane bucket {bucket} "
            f"({len(recs)} dispatch(es))",
            "pow-2 bucket padding dispatched dead lanes — committee "
            "tail shapes landing just past a bucket edge pay nearly "
            "the next bucket's device time; the admission planner's "
            "latency mode (smallest covering pow-2) and flush holds "
            "that fill batches both shrink this",
            evidence=[_cite(r) for r in recs[:5]],
            metrics={"lane_bucket": int(bucket),
                     "waste_ratio": waste,
                     "dispatches": len(recs)}))
    h2c_waste = (summary.get("padding_waste") or {}).get("h2c")
    if isinstance(h2c_waste, (int, float)) and h2c_waste >= 0.5:
        out.append(_finding(
            "padding_waste_h2c", 15 + 40 * h2c_waste,
            f"unique-row padding waste {h2c_waste:.2f} at the h2c/"
            "Miller bucket",
            "the unique-message row bucket (h2c + Miller stages) is "
            "padding far past the real row count — tiny or highly "
            "deduplicated batches under a large TEKU_TPU_H2C_MIN_"
            "BUCKET floor",
            metrics={"waste_ratio": h2c_waste}))
    return out


def _h2c_findings(records: List[dict], summary: dict) -> List[dict]:
    cache = summary.get("h2c_cache") or {}
    hits, misses = cache.get("hits", 0), cache.get("misses", 0)
    dedup = summary.get("dedup_ratio")
    if misses <= hits or misses < 4:
        return []
    cold = [r for r in records
            if (r.get("h2c") or {}).get("cache_misses", 0)
            > (r.get("h2c") or {}).get("cache_hits", 0)]
    sev = 20 + 25 * (misses / max(hits + misses, 1))
    if isinstance(dedup, (int, float)) and dedup > 0.3:
        sev += 10   # committee traffic SHOULD be warm
    return [_finding(
        "h2c_cache_cold", sev,
        f"H(m) arena cold: {misses} misses vs {hits} hits over "
        f"{len(records)} dispatch(es)",
        "hash-to-curve is the largest per-unique-message stage; a "
        "cold arena pays it per dispatch instead of per distinct "
        "AttestationData.  Expected right after boot — persistent "
        "coldness under committee traffic means the arena is too "
        "small (TEKU_TPU_H2C_CACHE_CAP) or messages never repeat",
        evidence=[_cite(r) for r in cold[:3]],
        metrics={"hits": hits, "misses": misses,
                 "dedup_ratio": dedup})]


def _mesh_health_findings(events: List[dict],
                          records: List[dict],
                          mesh: Optional[dict] = None) -> List[dict]:
    """Self-healing mesh diagnosis:

    - ``mesh_degraded``: the mesh is serving below its configured
      width — 1/N-reduced device capacity right now.  The CURRENT
      state comes from the supervisor's mesh snapshot (``self_heal``
      block on the readiness body) when available: the bounded flight
      ring can roll the reshape event off while the mesh is still
      degraded (the same bug class the brownout_active finding fixed
      in PR 11); the flight events remain the evidence citations —
      the ejection carries the trace id of the dispatch that killed
      the chip — and the fallback source when no snapshot was given.
    - ``mesh_flap``: repeated eject↔readmit cycles of the same device
      — a chip that keeps passing the readmit probe and then wedging
      again under real load (marginal interconnect, thermal) costs a
      reshape + AOT warm per cycle and should be held out manually.
    """
    out = []
    ejects = [e for e in events or [] if e.get("kind") == "mesh_eject"]
    reshapes = [e for e in events or []
                if e.get("kind") == "mesh_reshape"]
    readmits = [e for e in events or []
                if e.get("kind") == "mesh_readmit"]

    def linked(evs):
        cites = [_cite_event(e) for e in evs[-3:]]
        ids = {e.get("trace_id") for e in evs if e.get("trace_id")}
        for r in records:
            if ids & set(r.get("trace_ids") or ()):
                cites.append(_cite(r))
        return cites

    # current degraded state: snapshot first (authoritative), last
    # reshape event as the fallback
    to_n = configured = epoch = None
    heal = (mesh or {}).get("self_heal") or {}
    if isinstance(heal.get("live"), (int, float)) \
            and isinstance(heal.get("configured"), (int, float)):
        to_n, configured = heal["live"], heal["configured"]
        epoch = heal.get("epoch")
    elif reshapes:
        last = reshapes[-1]
        to_n = last.get("to_devices")
        configured = last.get("configured")
        epoch = last.get("epoch")
    if isinstance(to_n, (int, float)) \
            and isinstance(configured, (int, float)) \
            and to_n < configured:
        lost = 1.0 - (to_n / configured if configured else 0.0)
        out.append(_finding(
            "mesh_degraded", 45 + 30 * lost,
            f"mesh running at {int(to_n)}/{int(configured)} "
            f"configured device(s) (epoch {epoch}, "
            f"{len(ejects)} ejection(s) in the event window)",
            "the self-healer ejected sick device(s) and reshaped "
            "onto the largest surviving pow-2 subset — serving "
            "continues on-device at reduced capacity while the "
            "background reprobe waits for the chip to recover; "
            "the cited ejections name the dispatch that killed "
            "each device.  Expect capacity to step back up 1/N "
            "at a time on readmit (PERF.md 'Mesh self-healing')",
            evidence=linked(ejects[-2:] + reshapes[-1:]),
            metrics={"live_devices": to_n,
                     "configured_devices": configured,
                     "epoch": epoch,
                     "ejects": len(ejects),
                     "recovery_s": (reshapes[-1].get("recovery_s")
                                    if reshapes else None)}))
    by_device: Dict[str, int] = {}
    for e in ejects:
        d = str(e.get("device", "?"))
        by_device[d] = by_device.get(d, 0) + 1
    flappers = {d: n for d, n in by_device.items() if n >= 2}
    if flappers:
        worst = max(flappers, key=flappers.get)
        out.append(_finding(
            "mesh_flap", 55 + 5 * min(flappers[worst], 5),
            f"device {worst} ejected {flappers[worst]}x "
            f"({len(readmits)} readmit(s) in the window)",
            "eject↔readmit cycling: the chip passes the synthetic "
            "readmit probe, rejoins the mesh, then wedges again under "
            "real load — every cycle pays a reshape + AOT warm of the "
            "sharded shape set.  A marginal device should be held out "
            "of TEKU_TPU_MESH explicitly until serviced; raising "
            "TEKU_TPU_MESH_REPROBE_S slows the flapping meanwhile",
            evidence=linked([e for e in ejects
                             if str(e.get("device")) == worst]),
            metrics={"by_device": by_device,
                     "readmits": len(readmits)}))
    return out


def _flight_findings(events: List[dict],
                     records: List[dict]) -> List[dict]:
    out = []
    by_kind: Dict[str, List[dict]] = {}
    for ev in events or []:
        by_kind.setdefault(ev.get("kind", ""), []).append(ev)

    def linked(evs):
        cites = [_cite_event(e) for e in evs[-3:]]
        ids = {e.get("trace_id") for e in evs if e.get("trace_id")}
        for r in records:
            if ids & set(r.get("trace_ids") or ()):
                cites.append(_cite(r))
        return cites

    demotions = by_kind.get("config_demotion") or []
    if demotions:
        subs = sorted({str(e.get("subsystem")) for e in demotions})
        out.append(_finding(
            "config_demotion", 45,
            f"configured path(s) demoted at boot: {', '.join(subs)}",
            "; ".join(str(e.get("detail", e.get("subsystem")))
                      for e in demotions[-3:]) +
            " — the node is NOT running the configuration it was "
            "asked for (it degraded rather than fail boot)",
            evidence=linked(demotions),
            metrics={"count": len(demotions), "subsystems": subs}))
    breaches = by_kind.get("slo_breach") or []
    if breaches:
        last = breaches[-1]
        out.append(_finding(
            "slo_breach", 80,
            f"SLO breach: {last.get('objective')} burn "
            f"{last.get('burn_rate')}",
            "the error budget is burning faster than it accrues; the "
            "cited dispatch records show what the breaching "
            "verifications actually paid for",
            evidence=linked(breaches),
            metrics={"count": len(breaches),
                     "objective": last.get("objective")}))
    brownouts = by_kind.get("brownout_enter") or []
    if brownouts:
        last = brownouts[-1]
        out.append(_finding(
            "brownout", 70,
            f"brownout entered (level {last.get('level')}): "
            f"{last.get('detail')}",
            f"utilization {last.get('utilization')}, burn "
            f"{last.get('burn_rate')} at entry — the controller is "
            "deliberately shedding to protect BLOCK_IMPORT/VIP",
            evidence=linked(brownouts),
            metrics={"enters": len(brownouts),
                     "exits": len(by_kind.get("brownout_exit") or [])}))
    failsafes = by_kind.get("flush_failsafe") or []
    if failsafes:
        last = failsafes[-1]
        out.append(_finding(
            "flush_failsafe", 50,
            f"real-time flush failsafe fired {len(failsafes)} "
            f"time(s) (failsafe {last.get('failsafe_ms')} ms)",
            "the wall clock beat the service clock during batch-fill "
            "holds — on starved hosts this silently turns flush "
            "deadlines into added latency (the r10 3.6 s block-import "
            "p50); tune TEKU_TPU_FLUSH_FAILSAFE_MS",
            evidence=linked(failsafes),
            metrics={"count": len(failsafes)}))
    sheds = by_kind.get("queue_shed") or []
    if sheds:
        classes: Dict[str, int] = {}
        for e in sheds:
            c = str(e.get("class", "?"))
            classes[c] = classes.get(c, 0) + 1 \
                + int(e.get("suppressed_since_last", 0))
        out.append(_finding(
            "queue_sheds", 55,
            f"verification tasks shed: {classes}",
            "arrivals were rejected or evicted (overflow, preemption "
            "or brownout) — offered load exceeded what the queue/"
            "device could carry",
            evidence=linked(sheds), metrics={"by_class": classes}))
    return out


def _capacity_findings(cap: Optional[dict]) -> List[dict]:
    if not cap:
        return []
    derived = cap.get("derived") or cap   # full snapshot or summary()
    util = derived.get("utilization")
    if not isinstance(util, (int, float)) or util < 0.8:
        return []
    return [_finding(
        "capacity_pressure", 40 + 40 * min(util, 1.5),
        f"utilization {util:.2f} of sustainable capacity"
        + (" (over capacity)" if util > 1.0 else ""),
        "demand is at or beyond the measured sustainable sigs/sec at "
        "the current shape mix; expect queueing (then brownout) "
        "unless the shape mix improves (bigger batches, more dedup) "
        "or capacity grows (mesh devices)",
        metrics={"utilization": util,
                 "demand_sigs_per_second": derived.get(
                     "demand_sigs_per_second"),
                 "capacity_sigs_per_second": derived.get(
                     "capacity_sigs_per_second")})]


def _admission_findings(admission: Optional[dict]) -> List[dict]:
    """The controller's CURRENT state: the flight ring shows brownout
    TRANSITIONS, but the bounded ring can roll past the enter event
    while the brownout is still on — the snapshot says what is true
    now."""
    brown = (admission or {}).get("brownout") or {}
    try:
        level = int(brown.get("level") or 0)
    except (TypeError, ValueError):
        level = 0
    if level < 1:
        return []
    inputs = admission.get("inputs") or {}
    shedding = ", ".join(brown.get("shedding") or []) or "?"
    return [_finding(
        "brownout_active", 65 + 5 * min(level, 2),
        f"brownout level {level} ACTIVE: shedding {shedding}",
        f"utilization {inputs.get('utilization')}, burn "
        f"{inputs.get('burn_rate')}, queue depth "
        f"{inputs.get('queue_depth')} right now — ledger records "
        f"stamped plan_mode=brownout{min(level, 2)} show what the "
        "surviving traffic paid while this sheds",
        metrics={"level": level, "enters": brown.get("enters"),
                 "exits": brown.get("exits"),
                 "plan": admission.get("plan")})]


def _slo_findings(slo: Optional[dict]) -> List[dict]:
    """``SloEngine.snapshot()`` (served verbatim on the readiness
    endpoint) is a mapping keyed by objective name — NOT a list."""
    out = []
    for name, obj in sorted((slo or {}).items()):
        if not isinstance(obj, dict):
            continue
        burn = obj.get("burn_rate")
        if not isinstance(burn, (int, float)) or burn <= 1.0:
            continue
        out.append(_finding(
            "slo_burn", 60 + min(30, 10 * burn),
            f"{name} burn rate {burn:.2f}",
            str(obj.get("description", "")) + " — burning error "
            "budget faster than it accrues",
            metrics={"objective": name, "burn_rate": burn,
                     "breached": obj.get("breached")}))
    return out


def _timeline_findings(timeline: Optional[dict],
                       records: List[dict]) -> List[dict]:
    """Causal-timeline analyzers: the two evidence gates the roadmap's
    open items (stage-graph executor, zero-copy ingest) need.

    - ``host_prep_serial``: at production batch sizes (>= 256 lanes)
      host-side packing dominates the end-to-end trace — the serial
      term zero-copy ingest must remove.  Cites the worst dispatch.
    - ``overlap_stall``: the device sat idle while the queue held
      work — the async-overlap machinery is NOT hiding host time.
      Cites the gap interval and the dispatch that followed it.
    """
    if not timeline:
        return []
    out = []
    traces = timeline.get("traces") or []
    events = timeline.get("events") or []
    by_trace = {t.get("trace_id"): t for t in traces}
    share_thr = env_float("TEKU_TPU_DOCTOR_HOST_PREP_SHARE", 0.35,
                          lo=0.0, hi=1.0)
    worst = None     # (share, host_prep_ms, total_ms, rec)
    for rec in records:
        if (rec.get("lanes") or 0) < 256:
            continue
        for tid in rec.get("trace_ids") or []:
            tr = by_trace.get(tid)
            if tr is None or not tr.get("total_ms"):
                continue
            hp = sum(s.get("ms", 0.0) for s in tr.get("stages", [])
                     if s.get("stage") == "host_prep")
            share = hp / tr["total_ms"]
            if share >= share_thr and (worst is None
                                       or share > worst[0]):
                worst = (share, hp, tr["total_ms"], rec)
    if worst is not None:
        share, hp, total, rec = worst
        out.append(_finding(
            "host_prep_serial", 35 + 40 * min(share, 1.0),
            f"host_prep is {share:.0%} of a {rec.get('lanes')}-lane "
            f"verify ({hp:.1f} of {total:.1f} ms)",
            "at production batch sizes the host-side limb packing is "
            "the serial term on the verify path — device overlap "
            "cannot hide work that happens before the enqueue; "
            "zero-copy ingest (packing into pinned buffers at gossip "
            "decode time) removes it",
            evidence=[_cite(rec)],
            metrics={"share": round(share, 4),
                     "host_prep_ms": round(hp, 3),
                     "total_ms": round(total, 3),
                     "lanes": rec.get("lanes"),
                     "threshold": share_thr}))
    stall_thr = env_float("TEKU_TPU_DOCTOR_OVERLAP_STALL", 0.25,
                          lo=0.0, hi=1.0)
    nonempty_s = timeline_mod._total(
        timeline_mod._phase_intervals(events, "queue_nonempty"))
    gaps = timeline_mod.stalls(events)
    gap_s = timeline_mod._total(gaps)
    if nonempty_s > 0 and gaps and gap_s / nonempty_s >= stall_thr:
        g0, g1 = max(gaps, key=lambda g: g[1] - g[0])
        # the dispatch that eventually followed the worst gap — the
        # one whose host_prep/assembly the device idled behind
        after = [r for r in records
                 if isinstance(r.get("t_mono"), (int, float))
                 and r["t_mono"] >= g0]
        evidence = ([_cite(min(after, key=lambda r: r["t_mono"]))]
                    if after else [])
        out.append(_finding(
            "overlap_stall", 30 + 50 * min(gap_s / nonempty_s, 1.0),
            f"device idle {gap_s:.3f} s of {nonempty_s:.3f} s with a "
            "nonempty queue "
            f"({gap_s / nonempty_s:.0%}, worst gap {g1 - g0:.3f} s)",
            "queued work waited while no dispatch occupied the "
            "device: batch assembly, host_prep or the enqueue path "
            "is serializing ahead of the device instead of "
            "overlapping with it",
            evidence=evidence,
            metrics={"stall_share": round(gap_s / nonempty_s, 4),
                     "stall_s": round(gap_s, 4),
                     "queue_nonempty_s": round(nonempty_s, 4),
                     "worst_gap": {"t_mono": round(g0, 6),
                                   "dur_s": round(g1 - g0, 4)},
                     "threshold": stall_thr}))
    return out


# --------------------------------------------------------------------------
# The engine
# --------------------------------------------------------------------------

def diagnose(records: List[dict],
             capacity: Optional[dict] = None,
             slo: Optional[dict] = None,
             flight_events: Optional[List[dict]] = None,
             admission: Optional[dict] = None,
             mesh: Optional[dict] = None,
             timeline: Optional[dict] = None) -> dict:
    """Rank everything the ledger + sensors can explain about the
    current latency budget.  All inputs are plain JSON-able snapshots
    (local globals or fetched from a remote node's admin endpoints);
    ``mesh`` is the supervisor's mesh self-description (the readiness
    body's ``backend.mesh``, carrying the healer's ``self_heal``
    block) so a degraded mesh stays diagnosable after its events roll
    off the bounded flight ring; ``timeline`` is the causal-timeline
    snapshot (``{"traces": [...], "events": [...]}`` — slow traces
    plus the timeline ring) powering the host_prep_serial and
    overlap_stall analyzers.  The result is a schema-versioned
    envelope (shared with the timeline export)."""
    records = list(records or [])
    summary = dispatchledger.summarize(records)
    findings: List[dict] = []
    findings += _compile_findings(records)
    findings += _precompile_findings(records)
    findings += _imbalance_findings(records)
    findings += _padding_findings(records, summary)
    findings += _h2c_findings(records, summary)
    findings += _mesh_health_findings(flight_events or [], records,
                                      mesh=mesh)
    findings += _flight_findings(flight_events or [], records)
    findings += _capacity_findings(capacity)
    findings += _admission_findings(admission)
    findings += _slo_findings(slo)
    findings += _timeline_findings(timeline, records)
    findings.sort(key=lambda f: -f["severity"])
    for rank, f in enumerate(findings, 1):
        f["rank"] = rank
    attention = [f for f in findings
                 if f["severity"] >= ATTENTION_SEVERITY]
    return schema.envelope("doctor", {
        "healthy": not attention,
        "findings": findings,
        "attention": len(attention),
        "ledger_summary": summary,
        "inputs": {
            "dispatch_records": len(records),
            "flight_events": len(flight_events or []),
            "capacity": bool(capacity),
            "slo": bool(slo),
            "admission": bool(admission),
            "timeline": bool(timeline),
        },
    })


def render_text(diagnosis: dict) -> str:
    """The human form of a diagnosis: ranked findings with their
    evidence citations (dispatch seq + trace id — the keys that join
    to /teku/v1/admin/dispatches, /traces and /flight_recorder)."""
    lines = []
    inputs = diagnosis.get("inputs", {})
    lines.append(
        f"doctor: {inputs.get('dispatch_records', 0)} dispatch "
        f"record(s), {inputs.get('flight_events', 0)} flight "
        f"event(s)")
    summary = diagnosis.get("ledger_summary") or {}
    waste = summary.get("padding_waste") or {}
    lines.append(
        f"ledger: dedup {summary.get('dedup_ratio')}, waste "
        f"lane={waste.get('lane')} h2c={waste.get('h2c')}, "
        f"compile {summary.get('compile')}, decisions "
        f"{summary.get('decisions')}")
    findings = diagnosis.get("findings") or []
    if not findings:
        lines.append("no findings — the latency budget is clean")
        return "\n".join(lines)
    verdict = ("HEALTHY (informational findings only)"
               if diagnosis.get("healthy")
               else f"{diagnosis.get('attention')} finding(s) need "
                    "attention")
    lines.append(verdict)
    for f in findings:
        lines.append(f"  #{f['rank']} [{f['severity']:5.1f}] "
                     f"{f['kind']}: {f['title']}")
        detail = f.get("detail", "")
        if detail:
            lines.append(f"       {detail}")
        for ev in f.get("evidence", []):
            if ev.get("type") == "dispatch":
                lines.append(
                    f"       evidence: dispatch seq {ev.get('seq')} "
                    f"shape {ev.get('shape')} trace "
                    f"{ev.get('trace_id') or '-'}")
            else:
                lines.append(
                    f"       evidence: flight event seq "
                    f"{ev.get('seq')} kind {ev.get('kind')} trace "
                    f"{ev.get('trace_id') or '-'}")
    return "\n".join(lines)
