"""Dispatch decision ledger: per-dispatch cost attribution.

Every verify dispatch is the product of a stack of runtime decisions —
the admission controller's batch plan and brownout level, the dedup
grouping and H(m) cache state, the mesh shard plan, pow-2 bucket
padding, and the compile-vs-cache outcome — but until this module
nothing tied them together: when the ``attestation_verify_p50`` budget
burns, the SLO engine blames a trace id while the REASONS (a new shape
compiled cold, a shard's makespan skewed, padding waste spiked) were
scattered across logs, gauges, and WARNs.

This is the ordered record: a process-global bounded ring of
structured per-dispatch records, populated by
``ops/provider.py:_launch`` (decision context) and completed
by ``_DispatchHandle.result()`` (sync duration, overlap-corrected
device time, verdict).  Each record captures:

- the originating trace ids (slow-trace ring entries and SLO breach
  events link to the exact record on this key);
- lanes real/padded and rows real/padded: padding waste SPLIT BY
  STAGE BUCKET (the lane bucket the scalars/finish stages pay vs the
  unique-h2c/Miller row bucket the dedup pipeline pays) plus the
  per-dispatch dedup ratio; ``keys`` (live public keys) and
  ``waste.key`` (``real``: live keys, ``padded``: padded lanes x the
  key bucket): the slots ``stage_prepare``'s masked key sum runs
  over, mirrored by ``bls_key_slots_filled_total{kmax}`` /
  ``bls_key_slots_dispatched_total{kmax}`` (the provider's counters;
  not a series of the padding gauge below);
- H(m) arena hits/misses and the h2c dispatch bucket actually paid;
- the resolved mesh plan (device count, per-shard row/lane loads,
  makespan ratio = max shard lane load / mean);
- the compile outcome (compile | cache_load | aot_load | cache_hit)
  with the enqueue duration that paid it and, on a first shape,
  ``compile.programs``: one load record per AOT program resolved
  inside that enqueue (``infra/aotstore.py`` ``load_records``);
- ``phases``: the dispatch's whole life as ``[name, t_mono,
  seconds]`` in order (thread_hop, prep_wait, host_prep, lock_wait,
  launch_head, device_enqueue, device_sync, return_hop, settle: the
  marks of ``infra/tracing.py``, tiling first mark to last;
  ``prep_wait`` is the wait for the guarded provider's turn to pack,
  ``host_prep`` packing alone, ``launch_head`` the held lock's host
  work up to the first program call), ``lock``:
  ``{acquired, released}`` of the guarded provider's device-entry
  lock, ``launches``: each program call as ``[program, t_mono,
  seconds]`` in launch order (on a mesh a fourth item, the chips it
  ran on), and ``parent_seq``: the failed batch a bisect dispatch came
  from (absent with tracing off);
- ``prep``: where the dispatch's host prep ran: ``outside_lock``
  (all of it in the provider's host half, before the device-entry
  lock was taken) or ``under_lock`` with ``prep_reason`` (``pk_miss``:
  a public key the cache lacked was validated on the device;
  ``arena``: the H(m) arena's slots follow device order), mirrored by
  ``bls_dispatch_prep_total{prep,reason}``;
- the admission context the service annotated (plan mode, brownout
  level, verify-class mix, flush-failsafe firing) via the
  ``annotate()`` ContextVar — ``asyncio.to_thread`` copies the
  context, so the worker-thread dispatch sees the event-loop's plan.

Derived bounded-label metrics (linted in test_metrics_exposition):

- ``bls_dispatch_padding_waste_ratio{stage}`` — cumulative dead
  fraction per stage bucket (``stage`` in the closed {lane, h2c} set;
  the lane series is the pre-PR-13 unlabeled gauge's semantics);
- ``bls_mesh_shard_imbalance_ratio`` — the most recent mesh
  dispatch's makespan ratio (1.0 = perfectly balanced shards);
- ``bls_dispatch_decision_total{mesh,plan_mode}`` — the decision
  histogram (closed vocabularies: {0, pow-2 device counts} x {none,
  latency, throughput, brownout1, brownout2}).

The ring is served by ``GET /teku/v1/admin/dispatches`` (``?last=N``,
``?trace_id=``, ``?slow=1``), summarized per bench phase into
``BENCH_*.json``, and read by the ``cli doctor`` explainability engine
(infra/doctor.py).  Like the flight recorder, the ledger is
process-global on purpose: dispatches originate in worker threads and
breaker dispatch threads, and the value of the ring IS one timeline.
"""

import threading
import time
from collections import deque
from contextlib import contextmanager
from contextvars import ContextVar
from typing import Dict, List, Optional, Tuple

from . import clock, tracing
from .env import env_int
from .metrics import GLOBAL_REGISTRY, MetricsRegistry

# degrade-never-fail: this module imports on every node boot (via the
# provider and the batching service) — a typo'd capacity must fall
# back to the default, not refuse to start the node
DEFAULT_CAPACITY = max(
    1, env_int("TEKU_TPU_DISPATCH_LEDGER_CAPACITY", 256))

# the closed {stage} vocabulary of the padding-waste family: `lane` is
# the batch-lane bucket (scalars/finish stages), `h2c` the unique-
# message row bucket (hash-to-curve + Miller stages)
WASTE_STAGES = ("lane", "h2c")

# the closed {plan_mode} vocabulary: the admission controller's batch
# mode, with an active brownout superseding (brownout level N implies
# the controller is in throughput mode by construction)
PLAN_MODES = ("none", "latency", "throughput", "brownout1", "brownout2")


# --------------------------------------------------------------------------
# Service-side annotation: how the admission plan reaches the record
# --------------------------------------------------------------------------

_ANNOTATIONS: ContextVar[dict] = ContextVar(
    "teku_tpu_dispatch_annotations", default={})


@contextmanager
def annotate(**fields):
    """Bind dispatch-record annotations to the current context for the
    duration of the block (the batching service wraps each dispatch
    with its plan mode / brownout level / class mix; the provider's
    ``_launch`` merges ``current_annotations()`` into the
    record it opens).  ``asyncio.to_thread`` copies the context, so
    the worker-thread dispatch still sees the annotations."""
    token = _ANNOTATIONS.set({**_ANNOTATIONS.get(), **fields})
    try:
        yield
    finally:
        _ANNOTATIONS.reset(token)


def current_annotations() -> dict:
    return dict(_ANNOTATIONS.get())


def plan_mode_label(mode: Optional[str], brownout_level) -> str:
    """Fold (plan mode, brownout level) into the closed {plan_mode}
    label vocabulary — an active brownout supersedes the batch mode."""
    try:
        level = int(brownout_level or 0)
    except (TypeError, ValueError):
        level = 0
    if level >= 1:
        return f"brownout{min(level, 2)}"
    return mode if mode in ("latency", "throughput") else "none"


def decision_key(rec: dict) -> Tuple[str, str]:
    """ONE definition of a record's (mesh devices, plan_mode)
    decision tuple — the bls_dispatch_decision_total label
    set AND the summarize() decisions histogram key; a second
    hand-rolled copy would let the Prometheus series and the
    endpoint/bench histograms silently diverge."""
    return (str((rec.get("mesh") or {}).get("devices", 0) or 0),
            plan_mode_label(
                (rec.get("admission") or {}).get("plan_mode"),
                (rec.get("admission") or {}).get("brownout_level")))


# --------------------------------------------------------------------------
# The ledger
# --------------------------------------------------------------------------

class DispatchLedger:
    """Bounded, thread-safe ring of JSON-able per-dispatch records."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 registry: MetricsRegistry = GLOBAL_REGISTRY):
        self.capacity = capacity
        self._records: deque = deque(maxlen=capacity)
        self._seq = 0
        self._lock = threading.Lock()
        # cumulative stage-bucket padding accounting: the gauge must be
        # the all-time ratio (like the pre-ledger unlabeled gauge), not
        # the ring-window ratio, so long-running dashboards keep their
        # semantics while the ring stays bounded
        self._real = {s: 0 for s in WASTE_STAGES}
        self._padded = {s: 0 for s in WASTE_STAGES}
        self._last_imbalance = 0.0
        self._m_waste = registry.labeled_gauge(
            "bls_dispatch_padding_waste_ratio",
            "fraction of dispatched slots that were pow-2 padding, by "
            "stage bucket (lane = batch lanes, h2c = unique-message "
            "rows)", labelnames=("stage",))
        for s in WASTE_STAGES:        # complete family from scrape 1
            self._m_waste.labels(stage=s).set(0.0)
        self._m_imbalance = registry.gauge(
            "bls_mesh_shard_imbalance_ratio",
            "makespan ratio (max shard lane load / mean) of the most "
            "recent mesh dispatch; 1.0 = balanced, 0 = no mesh "
            "dispatch yet", supplier=lambda: self._last_imbalance)
        self._m_decision = registry.labeled_counter(
            "bls_dispatch_decision_total",
            "verify dispatches by resolved decision tuple: mesh "
            "device count x admission plan mode",
            labelnames=("mesh", "plan_mode"))

    # ------------------------------------------------------------------
    def record(self, rec: dict) -> dict:
        """Append one COMPLETED dispatch record (the provider assembles
        it across _launch and the handle's result()) and update
        the derived metrics.  Returns the record with its seq."""
        waste = rec.get("waste") or {}
        with self._lock:
            self._seq += 1
            rec["seq"] = self._seq
            self._records.append(rec)
            for stage in WASTE_STAGES:
                w = waste.get(stage) or {}
                real, padded = w.get("real"), w.get("padded")
                if isinstance(real, (int, float)) \
                        and isinstance(padded, (int, float)) \
                        and padded > 0:
                    self._real[stage] += real
                    self._padded[stage] += padded
                    self._m_waste.labels(stage=stage).set(round(
                        (self._padded[stage] - self._real[stage])
                        / self._padded[stage], 6))
            mesh = rec.get("mesh") or {}
            if mesh.get("devices"):
                ratio = mesh.get("makespan_ratio")
                if isinstance(ratio, (int, float)) and ratio > 0:
                    self._last_imbalance = float(ratio)
        mesh_devices, plan_mode = decision_key(rec)
        self._m_decision.labels(
            mesh=mesh_devices, plan_mode=plan_mode).inc()
        return rec

    @property
    def recorded_total(self) -> int:
        with self._lock:
            return self._seq

    def snapshot(self, last: Optional[int] = None,
                 trace_id: Optional[str] = None,
                 slow: bool = False) -> List[dict]:
        """Records oldest-first.  ``trace_id`` filters to records whose
        dispatch carried that trace; ``slow`` filters to records linked
        to the slow-trace ring's current entries; ``last`` tails the
        (filtered) list."""
        with self._lock:
            records = list(self._records)
        if trace_id:
            records = [r for r in records
                       if trace_id in (r.get("trace_ids") or ())]
        if slow:
            slow_ids = {t["trace_id"] for t in tracing.slow_traces()}
            records = [r for r in records
                       if slow_ids & set(r.get("trace_ids") or ())]
        return records[-last:] if last else records

    def clear(self) -> None:
        with self._lock:
            self._records.clear()

    def summary(self, since_seq: int = 0) -> dict:
        """Aggregate view of the ring (records with seq > since_seq) —
        what bench embeds per phase and the doctor reads first.  A
        window that outgrew the ring is flagged: ``evicted`` counts
        the records whose seq is in range but which the bounded ring
        already dropped, so a bench phase summary (and the bench_diff
        gates on it) can never silently claim full coverage."""
        with self._lock:
            # one lock: a dispatch recorded between a snapshot and a
            # separate _seq read would be falsely reported as evicted
            records = list(self._records)
            seq = self._seq
        out = summarize(records, since_seq=since_seq)
        evicted = (seq - since_seq) - out["records"]
        if evicted > 0:
            out["evicted"] = evicted
        return out


def summarize(records: List[dict], since_seq: int = 0) -> dict:
    """Pure aggregation over ledger records: per-stage waste, dedup,
    shard imbalance, decision/compile histograms, h2c cache totals.
    Shared by the bench per-phase summaries, the admin endpoint, and
    the doctor engine (which also gets it for REMOTE records fetched
    over the admin API)."""
    records = [r for r in records if r.get("seq", 0) > since_seq]
    out: dict = {"records": len(records)}
    real = {s: 0 for s in WASTE_STAGES}
    padded = {s: 0 for s in WASTE_STAGES}
    lanes = uniq = 0
    decisions: Dict[str, int] = {}
    compile_hist: Dict[str, int] = {}
    compile_s = 0.0
    h2c_hits = h2c_misses = 0
    imb: List[float] = []
    by_bucket: Dict[int, List[int]] = {}
    for r in records:
        for stage in WASTE_STAGES:
            w = (r.get("waste") or {}).get(stage) or {}
            if isinstance(w.get("padded"), (int, float)) \
                    and w["padded"] > 0:
                real[stage] += w.get("real", 0)
                padded[stage] += w["padded"]
        lanes += r.get("lanes", 0)
        uniq += r.get("unique_messages", 0)
        lane_w = (r.get("waste") or {}).get("lane") or {}
        if lane_w.get("padded"):
            by_bucket.setdefault(int(lane_w["padded"]), []).append(
                int(lane_w.get("real", 0)))
        key = "|".join(decision_key(r))
        decisions[key] = decisions.get(key, 0) + 1
        comp = r.get("compile") or {}
        outcome = comp.get("outcome")
        if outcome:
            compile_hist[outcome] = compile_hist.get(outcome, 0) + 1
            if outcome in ("compile", "cache_load", "aot_load"):
                compile_s += comp.get("enqueue_s", 0.0)
        h2c = r.get("h2c") or {}
        h2c_hits += h2c.get("cache_hits", 0)
        h2c_misses += h2c.get("cache_misses", 0)
        ratio = (r.get("mesh") or {}).get("makespan_ratio")
        if isinstance(ratio, (int, float)) and ratio > 0:
            imb.append(float(ratio))
    out["padding_waste"] = {
        s: (round((padded[s] - real[s]) / padded[s], 4)
            if padded[s] else None) for s in WASTE_STAGES}
    out["padding_waste_by_lane_bucket"] = {
        str(b): round((b * len(rs) - sum(rs)) / (b * len(rs)), 4)
        for b, rs in sorted(by_bucket.items())}
    out["dedup_ratio"] = (round((lanes - uniq) / lanes, 4)
                          if lanes else None)
    out["decisions"] = dict(sorted(decisions.items()))
    out["compile"] = dict(sorted(compile_hist.items()))
    out["compile_s"] = round(compile_s, 3)
    out["h2c_cache"] = {"hits": h2c_hits, "misses": h2c_misses}
    out["mesh_imbalance"] = {
        "max": round(max(imb), 4) if imb else None,
        "mean": round(sum(imb) / len(imb), 4) if imb else None,
        "dispatches": len(imb)}
    return out


# the process-wide ledger every provider instance records into
LEDGER = DispatchLedger()


def record(rec: dict) -> dict:
    return LEDGER.record(rec)


def open_record(**fields) -> dict:
    """Start a record at dispatch-begin time: wall stamp + the
    service-side annotations active in the calling context."""
    ann = current_annotations()
    # the shared (t_wall, t_mono) clock-spine stamp (infra/clock.py):
    # t_wall keeps its historical form, t_mono joins the record to the
    # timeline's mono axis
    rec = clock.stamp({})
    rec["admission"] = ann
    rec.update(fields)
    # the dispatch's phase marks (infra/tracing.py) travel the same
    # way: the record takes `phases`, `lock` and `launches` BY
    # REFERENCE, so the launches made after it opened and the phases
    # that end after the provider has published it (return_hop,
    # settle, on the service's side) complete it in place
    marks = tracing.current_marks()
    if marks:
        rec["phases"] = marks.phases
        rec["lock"] = marks.lock
        rec["launches"] = marks.launches
        rec["parent_seq"] = marks.parent_seq
        marks.record = rec
    return rec
