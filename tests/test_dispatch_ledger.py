"""Dispatch decision ledger: per-dispatch cost attribution (PR 13).

The acceptance surface: real mixed dup/tamper/mesh batches through the
REAL provider must land structured records whose waste/dedup/
imbalance/compile fields are pinned against the provider's own
counters; ``?trace_id=`` lookup from a slow-trace ring entry must
return the matching record; the admission annotations must survive
``asyncio.to_thread`` into the worker-thread dispatch; and the doctor
engine must rank findings that cite ledger records by trace id.

Compile budget: the device tests reuse EXACTLY the kernel shapes
tests/test_mesh_grouped.py uses (16-lane kmax-1 grid, min_bucket 8;
the 8-shard mesh layout) so the staged programs compile once per
process and load from the persistent cache across runs.
"""

import asyncio
import threading

import pytest

import jax

from teku_tpu import parallel
from teku_tpu.crypto import bls
from teku_tpu.crypto.bls import keygen
from teku_tpu.crypto.bls.pure_impl import PureBls12381
from teku_tpu.infra import aotstore, dispatchledger, doctor, tracing
from teku_tpu.infra.flightrecorder import FlightRecorder
from teku_tpu.infra.metrics import MetricsRegistry
from teku_tpu.ops import provider as PV
from teku_tpu.ops.provider import JaxBls12381
from teku_tpu.services.admission import BatchPlan, VerifyClass
from teku_tpu.services.signatures import (
    AggregatingSignatureVerificationService)

pytest_plugins: list = []


# --------------------------------------------------------------------------
# host-only: ring, annotations, summarize, doctor engine
# --------------------------------------------------------------------------

def test_ring_is_bounded_and_counts_all_records():
    led = dispatchledger.DispatchLedger(capacity=4,
                                        registry=MetricsRegistry())
    for i in range(11):
        led.record({"lanes": 1,
                    "waste": {"lane": {"real": 3, "padded": 4}}})
    assert len(led.snapshot()) == 4
    assert led.recorded_total == 11
    assert led.snapshot()[-1]["seq"] == 11
    # cumulative waste survives ring eviction: 11 * (3 real / 4 padded)
    assert led.snapshot(last=2)[0]["seq"] == 10
    led.clear()
    assert led.snapshot() == []
    assert led.recorded_total == 11     # seq is monotonic, not reset


def test_annotations_propagate_into_worker_threads():
    """The service's plan annotations must reach open_record() even
    when the dispatch runs on a worker thread (asyncio.to_thread
    copies the ContextVar context)."""
    got = {}

    def dispatch_thread():
        got.update(dispatchledger.open_record(shape="t")["admission"])

    with dispatchledger.annotate(plan_mode="throughput",
                                 brownout_level=1,
                                 classes={"gossip": 3}):
        # plain threads do NOT inherit context; copy like to_thread
        import contextvars
        ctx = contextvars.copy_context()
        t = threading.Thread(target=lambda: ctx.run(dispatch_thread))
        t.start()
        t.join()
    assert got["plan_mode"] == "throughput"
    assert got["brownout_level"] == 1
    assert got["classes"] == {"gossip": 3}
    # outside the block the annotations are gone
    assert dispatchledger.open_record(shape="t")["admission"] == {}


def test_plan_mode_label_closed_vocabulary():
    for mode in (None, "latency", "throughput", "garbage", 7):
        for level in (None, 0, 1, 2, 3, "x"):
            label = dispatchledger.plan_mode_label(mode, level)
            assert label in dispatchledger.PLAN_MODES
    assert dispatchledger.plan_mode_label("latency", 0) == "latency"
    assert dispatchledger.plan_mode_label("throughput", 2) \
        == "brownout2"
    assert dispatchledger.plan_mode_label(None, 0) == "none"


def test_summarize_waste_imbalance_and_decisions():
    recs = [
        {"seq": 1, "lanes": 48, "unique_messages": 6,
         "waste": {"lane": {"real": 48, "padded": 64},
                   "h2c": {"real": 6, "padded": 8}},
         "h2c": {"cache_hits": 2, "cache_misses": 4},
         "msm": {"path": "ladder"},
         "mesh": {"devices": 8, "makespan_ratio": 1.8},
         "admission": {"plan_mode": "throughput",
                       "brownout_level": 0},
         "compile": {"outcome": "compile", "enqueue_s": 41.0}},
        {"seq": 2, "lanes": 16, "unique_messages": 16,
         "waste": {"lane": {"real": 16, "padded": 16},
                   "h2c": {"real": 16, "padded": 16}},
         "h2c": {"cache_hits": 16, "cache_misses": 0},
         "msm": {"path": "ladder"}, "mesh": {"devices": 0},
         "admission": {}, "compile": {"outcome": "cache_hit"}},
    ]
    s = dispatchledger.summarize(recs)
    assert s["records"] == 2
    assert s["padding_waste"]["lane"] == round(16 / 80, 4)
    assert s["padding_waste_by_lane_bucket"]["64"] == 0.25
    assert s["dedup_ratio"] == round((64 - 22) / 64, 4)
    assert s["decisions"] == {"0|none": 1, "8|throughput": 1}
    assert s["compile"] == {"cache_hit": 1, "compile": 1}
    assert s["compile_s"] == 41.0
    assert s["mesh_imbalance"]["max"] == 1.8
    assert s["h2c_cache"] == {"hits": 18, "misses": 4}
    # since_seq filters (the bench per-phase delta)
    assert dispatchledger.summarize(recs, since_seq=1)["records"] == 1


def test_doctor_ranks_findings_and_cites_records():
    records = [
        {"seq": 7, "shape": "512x8", "lanes": 300,
         "trace_ids": ["aa-000007"],
         "unique_messages": 300,
         "waste": {"lane": {"real": 300, "padded": 512},
                   "h2c": {"real": 300, "padded": 512}},
         "h2c": {"cache_hits": 0, "cache_misses": 300},
         "msm": {"path": "ladder"},
         "mesh": {"devices": 0},
         "admission": {},
         "compile": {"outcome": "compile", "enqueue_s": 41.0}},
        {"seq": 8, "shape": "64x1@m8", "lanes": 40,
         "trace_ids": ["aa-000008"],
         "unique_messages": 10,
         "waste": {"lane": {"real": 40, "padded": 64},
                   "h2c": {"real": 10, "padded": 16}},
         "h2c": {"cache_hits": 10, "cache_misses": 0},
         "msm": {"path": "ladder"},
         "mesh": {"devices": 8, "makespan_ratio": 1.8,
                  "shard_lanes": [5, 5, 5, 9, 4, 4, 4, 4]},
         "admission": {},
         "compile": {"outcome": "cache_hit"}},
    ]
    flight = [{"seq": 3, "kind": "slo_breach",
               "objective": "attestation_verify_p50",
               "burn_rate": 2.4, "trace_id": "aa-000007"},
              {"seq": 4, "kind": "config_demotion",
               "subsystem": "mesh", "requested": 6, "resolved": 4,
               "trace_id": ""}]
    diagnosis = doctor.diagnose(records, flight_events=flight)
    findings = diagnosis["findings"]
    assert findings, "doctor found nothing on a loaded scenario"
    sev = [f["severity"] for f in findings]
    assert sev == sorted(sev, reverse=True)
    assert [f["rank"] for f in findings] == list(
        range(1, len(findings) + 1))
    kinds = {f["kind"] for f in findings}
    assert {"compile_latency", "mesh_shard_imbalance",
            "slo_breach", "config_demotion"} <= kinds
    by_kind = {f["kind"]: f for f in findings}
    compile_f = by_kind["compile_latency"]
    assert "cold compile of shape 512x8" in compile_f["title"]
    assert "41.0 s" in compile_f["title"]
    # every compile citation names a dispatch record by seq + trace id
    ev = compile_f["evidence"][0]
    assert ev == {"type": "dispatch", "seq": 7,
                  "trace_id": "aa-000007", "shape": "512x8"}
    imb = by_kind["mesh_shard_imbalance"]
    assert "shard 3 makespan 1.80x mean" in imb["title"]
    assert imb["evidence"][0]["seq"] == 8
    # the SLO breach finding links the flight event's trace id back to
    # the ledger record that served that verification
    breach = by_kind["slo_breach"]
    cited = {(e["type"], e.get("seq")) for e in breach["evidence"]}
    assert ("flight_event", 3) in cited
    assert ("dispatch", 7) in cited
    assert not diagnosis["healthy"]
    # the human rendering carries the citations verbatim
    text = doctor.render_text(diagnosis)
    assert "aa-000007" in text and "512x8" in text
    # a clean ledger renders healthy
    assert doctor.diagnose([])["healthy"]


def _compile_rec(seq, shape, outcome, enqueue_s=30.0):
    return {"seq": seq, "shape": shape, "lanes": 8,
            "trace_ids": [f"bb-{seq:06d}"],
            "unique_messages": 8,
            "waste": {"lane": {"real": 8, "padded": 8},
                      "h2c": {"real": 8, "padded": 8}},
            "h2c": {"cache_hits": 8, "cache_misses": 0},
            "msm": {"path": "ladder"}, "mesh": {"devices": 0},
            "admission": {},
            "compile": {"outcome": outcome, "enqueue_s": enqueue_s}}


def test_doctor_cold_compile_on_hot_path_finding():
    """A serving dispatch that paid a FRESH compile for a shape the
    shapeset registry covers gets its own ranked finding naming the
    fix (`cli precompile` -> AOT store), citing dispatch seq + trace
    id per the PR-11 evidence contract.  Shapes OUTSIDE the registry
    (operator ran an exotic batch) and non-compile outcomes
    (aot_load, cache_load) must NOT fire it."""
    records = [
        # covered: 256x1 is the default service-tier primary bucket
        _compile_rec(11, "256x1", "compile", 314.0),
        _compile_rec(12, "256x1", "compile", 2.0),
        # covered shape but served by the AOT store: not a finding
        _compile_rec(13, "16x1", "aot_load", 0.4),
        # NOT covered (kmax 8 is outside the default serving set)
        _compile_rec(14, "512x8", "compile", 41.0),
    ]
    diagnosis = doctor.diagnose(records)
    cold = [f for f in diagnosis["findings"]
            if f["kind"] == "cold_compile_on_hot_path"]
    assert len(cold) == 1, cold
    f = cold[0]
    assert "256x1" in f["title"]
    assert f["metrics"]["dispatches"] == 2
    assert f["metrics"]["total_s"] == 316.0
    assert "precompile" in f["detail"], "the finding must name the fix"
    # evidence cites the dispatch records: seq + trace id
    cited = {(e["seq"], e["trace_id"]) for e in f["evidence"]}
    assert cited == {(11, "bb-000011"), (12, "bb-000012")}
    # severity puts an avoidable 316 s compile wall above the generic
    # compile_latency finding for the same records
    generic = [x for x in diagnosis["findings"]
               if x["kind"] == "compile_latency"]
    assert generic and f["severity"] > generic[0]["severity"]


def test_flush_failsafe_env_knob_and_evidence():
    """TEKU_TPU_FLUSH_FAILSAFE_MS bounds the WALL time a worker may
    hold a batch open when the service clock stalls (the r10 loadgen
    3.6 s block-import p50); a firing increments the counter and
    records a flight-recorder event."""
    class _FakeImpl:
        def batch_verify(self, triples):
            return True

        def fast_aggregate_verify(self, pks, msg, sig):
            # the facade's batch path verifies single-triple batches
            # through this seam
            return True

    class _HeldController:
        brownout_level = 0

        def plan(self):
            # a 5 s (virtual) fill hold: with the service clock frozen
            # it would hold a worker for 5 REAL seconds without the
            # failsafe
            return BatchPlan(batch_size=64, flush_deadline_s=5.0,
                             brownout_level=0, mode="throughput")

    async def main():
        reg = MetricsRegistry()
        rec = FlightRecorder(registry=MetricsRegistry())
        svc = AggregatingSignatureVerificationService(
            num_workers=1, registry=reg, name="failsafe_t",
            overlap=False, controller=_HeldController(),
            recorder=rec, clock=lambda: 0.0)   # frozen service clock
        await svc.start()
        fut = svc.verify([b"pk"], b"m", b"sig",
                         cls=VerifyClass.GOSSIP)
        ok = await asyncio.wait_for(fut, timeout=5.0)
        await svc.stop()
        return ok, reg, rec

    impl = _FakeImpl()
    bls.set_implementation(impl)
    import os
    os.environ["TEKU_TPU_FLUSH_FAILSAFE_MS"] = "25"
    try:
        ok, reg, rec = asyncio.run(main())
    finally:
        del os.environ["TEKU_TPU_FLUSH_FAILSAFE_MS"]
        bls.reset_implementation()
    assert ok is True
    assert reg.counter("failsafe_t_flush_failsafe_total").value >= 1
    events = [e for e in rec.snapshot()
              if e["kind"] == "flush_failsafe"]
    assert events, "failsafe firing must land in the flight recorder"
    assert events[0]["failsafe_ms"] == 25.0
    assert events[0]["flush_deadline_ms"] == 5000.0


# --------------------------------------------------------------------------
# device: records pinned against provider counters
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def keys():
    pure = PureBls12381()
    sks = [keygen(bytes([61 + i]) * 32) for i in range(8)]
    pks = [pure.secret_key_to_public_key(sk) for sk in sks]
    return pure, sks, pks


@pytest.fixture(scope="module")
def single_impl():
    return JaxBls12381(min_bucket=8)


@pytest.fixture(scope="module")
def mesh8():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices (see conftest XLA_FLAGS)")
    m = parallel.make_mesh(8)
    with m:
        yield m


@pytest.fixture(scope="module")
def mesh_impl(mesh8):
    return JaxBls12381(mesh=mesh8, min_bucket=8)


_seq = [0]

# the test_mesh_grouped lane->message grid: two dup-4 committees, two
# dup-2 pairs, four singles = 16 lanes over 8 unique messages (ONE
# compiled shape shared with that module's device tests)
_U_MAP = [0, 0, 0, 0, 1, 1, 1, 1, 2, 2, 3, 3, 4, 5, 6, 7]


def _grid_batch(pure, sks, pks, tag=None):
    if tag is None:
        _seq[0] += 1
        tag = b"ledger-%d" % _seq[0]
    msgs = [tag + b"-%d" % u for u in range(8)]
    sig_cache: dict = {}
    triples = []
    for lane in range(16):
        u, k = _U_MAP[lane], lane % 8
        if (k, u) not in sig_cache:
            sig_cache[(k, u)] = pure.sign(sks[k], msgs[u])
        triples.append(([pks[k]], msgs[u], sig_cache[(k, u)]))
    return triples


def _last_record():
    recs = dispatchledger.LEDGER.snapshot()
    assert recs, "no ledger records"
    return recs[-1]


def test_record_fields_pinned_against_provider_counters(single_impl,
                                                        keys):
    """One real mixed-duplication batch: the record's lanes/padded/
    unique/h2c/dedup/compile/verdict fields must equal the provider's
    own counter deltas, and a warm re-dispatch must flip the h2c
    fields to all-hits/zero-bucket."""
    pure, sks, pks = keys
    triples = _grid_batch(pure, sks, pks)
    # the staged programs are the process's, not the provider's: an
    # earlier file of the same xdist worker may have resolved some of
    # them at these shapes, and the first dispatch here would list the
    # rest alone.  Forgetting them makes this dispatch first
    aotstore.reset_memos()
    before = (PV._M_H2C_LANES.value,
              dispatchledger.LEDGER._padded["lane"],
              PV._M_H2C_UNIQUE.value, single_impl.h2c_dispatch_count,
              dispatchledger.LEDGER.recorded_total)
    assert single_impl.batch_verify(triples)
    rec = _last_record()
    # exactly ONE record per batch dispatch (the h2c sub-dispatch does
    # not open its own record)
    assert dispatchledger.LEDGER.recorded_total == before[4] + 1
    # lanes real == the provider's lane counter delta, padded == what
    # the ledger's cumulative waste gauge took in
    assert rec["lanes"] == PV._M_H2C_LANES.value - before[0] == 16
    assert rec["waste"]["lane"]["real"] == 16
    assert rec["waste"]["lane"]["padded"] \
        == dispatchledger.LEDGER._padded["lane"] - before[1] == 16
    # unique messages == the dedup counter delta; ratio matches
    assert rec["unique_messages"] \
        == PV._M_H2C_UNIQUE.value - before[2] == 8
    assert rec["dedup_ratio"] == round((16 - 8) / 16, 4) == 0.5
    # cold batch: 8 fresh messages missed the arena, ONE h2c dispatch
    assert rec["h2c"]["cache_misses"] == 8
    assert rec["h2c"]["cache_hits"] == 0
    assert single_impl.h2c_dispatch_count - before[3] == 1
    assert rec["h2c"]["dispatch_bucket"] >= 8
    # aot_load: a warm .jax_aot/ serves the first dispatch (the same
    # count from a cold and from a warm store)
    assert rec["compile"]["outcome"] in ("compile", "cache_load",
                                         "aot_load", "cache_hit")
    assert rec["compile"]["enqueue_s"] >= 0
    # a first dispatch lists the programs its enqueue resolved, one
    # load record each, and their costs fit inside the enqueue
    if rec["compile"]["outcome"] != "cache_hit":
        programs = rec["compile"]["programs"]
        assert {p["kernel"].split(":")[1] for p in programs} \
            >= {"h2c", "finish"}
        assert all(p["t_mono"] >= rec["t_mono"] - 1.0 for p in programs)
        assert sum(p["read_s"] + p["deserialize_s"] + p["compile_s"]
                   + p["save_s"] + p["first_call_s"] for p in programs) \
            <= rec["compile"]["enqueue_s"] + 1e-3
    # no service above: the provider's own phases, tiling
    assert [n for n, _t, _s in rec["phases"]] == [
        "host_prep", "launch_head", "device_enqueue", "device_sync",
        "return_hop"]
    for (_a, t0, secs), (_b, t1, _s) in zip(rec["phases"],
                                            rec["phases"][1:]):
        assert t0 + secs == pytest.approx(t1, abs=2.5e-6)
    assert rec["lock"] == {} and rec["parent_seq"] is None
    assert rec["verdict"] is True
    assert rec["device"]["sync_s"] >= 0
    assert rec["mesh"]["devices"] == 0
    # warm re-dispatch of the SAME batch: the arena serves every row
    h2c_before = single_impl.h2c_dispatch_count
    assert single_impl.batch_verify(triples)
    warm = _last_record()
    assert warm["h2c"] == {"cache_hits": 8, "cache_misses": 0,
                           "dispatch_bucket": 0}
    assert single_impl.h2c_dispatch_count == h2c_before
    assert warm["compile"]["outcome"] == "cache_hit"
    assert "programs" not in warm["compile"]


def test_the_fields_the_benchmark_reads_stay(single_impl, keys):
    """`benchmarks/harness/cell.py` reads `r['msm']['path']` off every
    completed ledger record and `plan['msm_path']` off `batch_plan()`:
    the program has one scalars stage and says so in both, with no
    `why` (there is no decision to explain).  ROADMAP C15 removes the
    readers and the fields together."""
    from teku_tpu.ops import shapeset
    pure, sks, pks = keys
    assert single_impl.batch_verify(_grid_batch(pure, sks, pks))
    assert _last_record()["msm"] == {"path": "ladder"}
    for groups in ([1] * 256, [250], [32] * 128):
        assert shapeset.batch_plan(
            groups, min_bucket=256)["msm_path"] == "ladder"


def test_a_record_names_its_launches_as_the_module_line_does(
        single_impl, keys):
    """Each program call of a dispatch is a launch of its record, named
    as the profiler's module line names the program (less `jit_` and
    the run id), in launch order and inside `device_enqueue`: a fresh
    drain hashes its messages into the arena, the same drain again
    gathers them alone."""
    pure, sks, pks = keys
    tail = ["stage_prepare", "stage_scalars", "stage_group",
            "stage_miller", "stage_finish"]
    triples = _grid_batch(pure, sks, pks)
    for head in (["stage_h2c", "_scatter", "_gather"], ["_gather"]):
        assert single_impl.batch_verify(triples)
        rec = _last_record()
        assert [p for p, _t0, _s in rec["launches"]] == head + tail
        phases = {n: (t0, secs) for n, t0, secs in rec["phases"]}
        enq0, enq_s = phases["device_enqueue"]
        assert rec["launches"][0][1] == enq0
        assert sum(phases["launch_head"]) == pytest.approx(enq0,
                                                           abs=2.5e-6)
        for _p, t0, secs in rec["launches"]:
            assert enq0 <= t0 and t0 + secs <= enq0 + enq_s + 2.5e-6



def _key_slot_counts():
    return {fam: {key[0]: child.value
                  for key, child in getattr(PV, fam)._items()}
            for fam in ("_M_KEY_SLOTS_FILLED", "_M_KEY_SLOTS")}


def test_key_axis_of_a_mixed_dispatch(single_impl, keys, monkeypatch):
    """1-key and many-key lanes in one dispatch at key bucket 8: the
    record's `keys` and `waste.key.real` are the live keys,
    `waste.key.padded` the padded lanes x 8, and the two key-slot
    counters move by the same under `kmax="8"` and under no other
    label.  The packer and `stage_h2c` are the real ones; the staged
    kernel is stood in for (a 16 x 8 compile of its own is not this
    test's to pay), so the verdict is the stand-in's."""
    import numpy as np
    pure, sks, pks = keys
    seen = {}

    def kernel(pk_xs, pk_ys, pk_present, *rest):
        seen["present"] = np.asarray(pk_present)
        lanes = seen["present"].shape[0]
        return np.bool_(True), np.ones(lanes, dtype=bool)

    monkeypatch.setattr(PV.V, "verify_staged_grouped", kernel)
    counts = [1, 5, 8, 1, 3, 1, 7, 1, 2, 1]         # keys a lane
    triples = []
    for lane, k in enumerate(counts):
        msg = b"key-axis-%d" % lane
        triples.append((pks[:k], msg, pure.sign(sks[0], msg)))
    before = _key_slot_counts()
    assert single_impl.batch_verify(triples)
    rec = _last_record()
    live = sum(counts)
    assert rec["kmax"] == 8 and rec["shape"] == "16x8"
    assert seen["present"].shape == (16, 8)
    assert rec["keys"] == live == int(seen["present"].sum())
    assert rec["waste"]["key"] == {"real": live, "padded": 16 * 8}
    assert rec["waste"]["lane"] == {"real": 10, "padded": 16}
    after = _key_slot_counts()
    moved = {fam: {k: v - before[fam].get(k, 0)
                   for k, v in after[fam].items()
                   if v != before[fam].get(k, 0)}
             for fam in after}
    assert moved == {"_M_KEY_SLOTS_FILLED": {"8": live},
                     "_M_KEY_SLOTS": {"8": 16 * 8}}
    # not a series of the padding gauge: its stages stay {lane, h2c}
    assert "key" not in dispatchledger.WASTE_STAGES
    assert set(dispatchledger.summarize([rec])["padding_waste"]) \
        == set(dispatchledger.WASTE_STAGES)


def test_tampered_batch_records_false_verdict(single_impl, keys):
    pure, sks, pks = keys
    triples = _grid_batch(pure, sks, pks)
    triples[10] = (triples[10][0], b"tampered", triples[10][2])
    assert not single_impl.batch_verify(triples)
    rec = _last_record()
    assert rec["verdict"] is False
    # the tamper created a 9th unique message
    assert rec["unique_messages"] == 9


def test_mesh_record_carries_shard_plan_and_imbalance(mesh_impl,
                                                      keys):
    pure, sks, pks = keys
    from teku_tpu.infra.metrics import GLOBAL_REGISTRY
    assert mesh_impl.batch_verify(_grid_batch(pure, sks, pks))
    rec = _last_record()
    assert rec["mesh"]["devices"] == 8
    assert rec["shape"].endswith("@m8")
    # whole-row sharding: the per-shard REAL lane loads sum to the
    # batch and the makespan ratio is max/mean
    lanes = rec["mesh"]["shard_lanes"]
    assert len(lanes) == 8 and sum(lanes) == 16
    expect = max(lanes) / (sum(lanes) / 8)
    assert rec["mesh"]["makespan_ratio"] == round(expect, 4)
    assert rec["mesh"]["makespan_ratio"] >= 1.0
    assert sum(rec["mesh"]["shard_rows"]) == 8
    # the gauge tracks the most recent mesh dispatch
    gauge = GLOBAL_REGISTRY.gauge("bls_mesh_shard_imbalance_ratio")
    assert gauge.value == rec["mesh"]["makespan_ratio"]
    # the decision counter carries the mesh label
    dec = GLOBAL_REGISTRY.labeled_counter("bls_dispatch_decision_total")
    assert any(key[0] == "8" for key, _ in dec._items())


def test_trace_id_lookup_joins_slow_traces_and_endpoint(single_impl,
                                                        keys):
    """The acceptance join: a slow-trace ring entry's trace id must
    look up the exact ledger record that served it, both through the
    ledger API and GET /teku/v1/admin/dispatches?trace_id=."""
    from teku_tpu.api import BeaconRestApi
    pure, sks, pks = keys
    tracing.clear_slow_traces()
    with tracing.trace("ledger_accept") as tr:
        assert single_impl.batch_verify(_grid_batch(pure, sks, pks))
    trace_id = tr.trace_id
    slow_ids = {t["trace_id"] for t in tracing.slow_traces()}
    assert trace_id in slow_ids
    # ledger-side lookup
    matches = dispatchledger.LEDGER.snapshot(trace_id=trace_id)
    assert len(matches) == 1
    assert trace_id in matches[0]["trace_ids"]
    # endpoint-side lookup (+ slow filter + tail + summary envelope)
    api = BeaconRestApi(None)

    async def drive():
        by_trace = (await api._admin_dispatches(
            query={"trace_id": trace_id}))["data"]
        slow = (await api._admin_dispatches(
            query={"slow": "1"}))["data"]
        tail = (await api._admin_dispatches(
            query={"last": "1"}))["data"]
        return by_trace, slow, tail

    by_trace, slow, tail = asyncio.run(drive())
    assert len(by_trace["records"]) == 1
    assert by_trace["records"][0]["seq"] == matches[0]["seq"]
    assert by_trace["summary"]["records"] == 1
    assert any(r["seq"] == matches[0]["seq"]
               for r in slow["records"])
    assert len(tail["records"]) == 1
    assert tail["capacity"] == dispatchledger.LEDGER.capacity
    # the doctor over the live ledger: every dispatch citation's
    # trace id resolves back to a real ledger record
    diagnosis = doctor.diagnose(dispatchledger.LEDGER.snapshot())
    all_ids = {tid for r in dispatchledger.LEDGER.snapshot()
               for tid in r.get("trace_ids", [])}
    for f in diagnosis["findings"]:
        for ev in f["evidence"]:
            if ev.get("type") == "dispatch" and ev.get("trace_id"):
                assert ev["trace_id"] in all_ids
    text = doctor.render_text(diagnosis)
    assert "dispatch record" in text


def test_service_annotations_land_in_records(single_impl, keys):
    """End-to-end plan propagation: a service drain under a live
    controller stamps plan_mode/class-mix into the record the REAL
    provider writes (the asyncio.to_thread context copy)."""
    pure, sks, pks = keys

    class FixedController:
        brownout_level = 0

        def plan(self):
            return BatchPlan(batch_size=16, flush_deadline_s=0.0,
                             brownout_level=0, mode="latency")

    async def main():
        bls.set_implementation(single_impl)
        try:
            svc = AggregatingSignatureVerificationService(
                num_workers=1, registry=MetricsRegistry(),
                name="ledger_ann", controller=FixedController())
            await svc.start()
            triples = _grid_batch(pure, sks, pks)
            futs = [svc.verify(*t) for t in triples[:4]]
            assert all(await asyncio.gather(*futs))
            await svc.stop()
        finally:
            bls.reset_implementation()

    mark = dispatchledger.LEDGER.recorded_total
    asyncio.run(main())
    recs = [r for r in dispatchledger.LEDGER.snapshot()
            if r["seq"] > mark]
    assert recs
    ann = recs[-1]["admission"]
    assert ann["plan_mode"] == "latency"
    assert ann["brownout_level"] == 0
    assert ann["service"] == "ledger_ann"
    assert sum(ann["classes"].values()) >= 1
    assert set(ann["classes"]) <= {c.label for c in VerifyClass}


# --------------------------------------------------------------------------
# review hardening: idempotent publication, eviction flag, live brownout
# --------------------------------------------------------------------------

def test_sync_error_retry_publishes_record_once():
    """A raising sync publishes the record (verdict null); a retry
    that succeeds must UPDATE that record in place — a second
    record() would double-count its waste/decision metrics and give
    one trace id two ring entries."""
    import time

    import numpy as np

    from teku_tpu.ops.provider import _DispatchHandle

    class _FlakyLaneOk:
        def __init__(self):
            self.calls = 0

        def __array__(self, *a, **k):
            self.calls += 1
            if self.calls == 1:
                raise RuntimeError("wedged sync")
            return np.ones(4, dtype=bool)

    led = dispatchledger.LEDGER
    base = led.recorded_total
    rec = dispatchledger.open_record(
        shape="4x1", trace_ids=["retry-1"],
        waste={"lane": {"real": 3, "padded": 4}})
    handle = _DispatchHandle(
        np.asarray(True), _FlakyLaneOk(), 4, (), "4x1", "vpu",
        time.perf_counter(), rec=rec)
    with pytest.raises(RuntimeError):
        handle.result()
    assert led.recorded_total == base + 1
    wedged = led.snapshot(trace_id="retry-1")[-1]
    assert wedged["device"]["sync_error"] is True
    assert wedged["verdict"] is None
    assert handle.result() is True          # retry succeeds
    assert led.recorded_total == base + 1   # same ring entry, updated
    retried = led.snapshot(trace_id="retry-1")
    assert len(retried) == 1
    assert retried[-1]["verdict"] is True
    assert "busy_s" in retried[-1]["device"]


def test_summary_flags_records_evicted_from_the_ring():
    """A phase window that outgrew the bounded ring must say so —
    bench_diff gates on the per-phase summary and silent truncation
    would read as full coverage."""
    led = dispatchledger.DispatchLedger(
        capacity=4, registry=MetricsRegistry())
    for _ in range(6):
        led.record({"lanes": 4, "unique_messages": 4})
    s = led.summary()
    assert s["records"] == 4
    assert s["evicted"] == 2
    fresh = led.summary(since_seq=4)
    assert fresh["records"] == 2
    assert "evicted" not in fresh


def test_doctor_reports_active_brownout_from_admission_snapshot():
    """The flight ring shows brownout TRANSITIONS; the admission
    snapshot says what is true NOW (the enter event can roll off the
    bounded ring while the brownout is still on)."""
    diagnosis = doctor.diagnose([], admission={
        "plan": {"batch_size": 256, "mode": "throughput"},
        "inputs": {"utilization": 0.95, "burn_rate": 2.4,
                   "queue_depth": 512},
        "brownout": {"level": 1, "shedding": ["optimistic"],
                     "enters": 1, "exits": 0}})
    assert not diagnosis["healthy"]
    by_kind = {f["kind"]: f for f in diagnosis["findings"]}
    f = by_kind["brownout_active"]
    assert "optimistic" in f["title"]
    assert f["metrics"]["level"] == 1
    assert f["metrics"]["plan"]["batch_size"] == 256
    # a calm controller raises nothing
    assert doctor.diagnose([], admission={
        "brownout": {"level": 0}})["healthy"]


def test_dispatch_annotations_carry_the_governing_plan():
    """The record must stamp the plan the batch was ASSEMBLED under:
    re-fetching controller.plan() at dispatch time could tick a
    brownout edge mid-flight and stamp a mode the batch was never
    admitted under.  Without a governing plan (bisect re-dispatch)
    the fallback is a passive last_plan() read — never plan()."""

    class _TickingController:
        def __init__(self):
            self.plan_calls = 0

        def plan(self):
            self.plan_calls += 1
            return BatchPlan(batch_size=256, flush_deadline_s=0.0,
                             brownout_level=1, mode="throughput")

        def last_plan(self):
            return BatchPlan(batch_size=64, flush_deadline_s=0.0,
                             brownout_level=0, mode="latency")

    ctrl = _TickingController()
    # constructed but never start()ed: no worker loop runs, so the
    # only plan()/last_plan() calls are the ones under test
    svc = AggregatingSignatureVerificationService(
        num_workers=1, registry=MetricsRegistry(),
        name="govplan", controller=ctrl)
    task = type("T", (), {"cls": VerifyClass.GOSSIP})()
    governing = BatchPlan(batch_size=32, flush_deadline_s=0.0,
                          brownout_level=0, mode="latency")
    ann = svc._dispatch_annotations([task], governing)
    assert ann["plan_mode"] == "latency"
    assert ann["plan_batch_size"] == 32
    assert ctrl.plan_calls == 0
    fallback = svc._dispatch_annotations([task], None)
    assert fallback["plan_batch_size"] == 64   # last_plan(), no tick
    assert ctrl.plan_calls == 0


def test_doctor_slo_findings_consume_the_real_snapshot_shape():
    """SloEngine.snapshot() is a mapping keyed by objective name (the
    readiness endpoint serves it verbatim) — the analyzer must emit a
    slo_burn finding from that shape, not a phantom 'objectives'
    list."""
    diagnosis = doctor.diagnose([], slo={
        "attestation_verify_p50": {
            "description": "p50 end-to-end verify latency <= 100ms",
            "target_ratio": 0.9, "burn_rate": 5.0,
            "breached": True, "windows": 12},
        "verify_error_rate": {
            "description": "verify errors", "target_ratio": 0.999,
            "burn_rate": 0.2, "breached": False, "windows": 12}})
    burns = [f for f in diagnosis["findings"]
             if f["kind"] == "slo_burn"]
    assert len(burns) == 1
    assert burns[0]["metrics"]["objective"] == "attestation_verify_p50"
    assert burns[0]["metrics"]["burn_rate"] == 5.0
    assert not diagnosis["healthy"]
