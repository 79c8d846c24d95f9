"""Hot-path tracing: spans, trace attribution across threads/tasks,
slow-trace ring, disabled mode, service + endpoint integration."""

import asyncio
import threading
import time

import pytest

from teku_tpu.crypto import bls
from teku_tpu.crypto.bls import keygen
from teku_tpu.infra import tracing
from teku_tpu.infra.metrics import GLOBAL_REGISTRY, MetricsRegistry
from teku_tpu.services.signatures import (
    AggregatingSignatureVerificationService)


@pytest.fixture(autouse=True)
def _tracing_reset():
    tracing.set_enabled(True)
    tracing.clear_slow_traces()
    tracing.set_sampler(None)
    yield
    tracing.set_enabled(True)
    tracing.clear_slow_traces()
    tracing.set_sampler(None)


def test_span_records_stage_and_trace():
    with tracing.trace("t", kind="unit") as tr:
        with tracing.span("host_prep"):
            pass
    assert tr.complete
    assert [s for s, _ in tr.stages] == ["host_prep"]
    assert all(d >= 0 for _, d in tr.stages)
    assert tr.labels == {"kind": "unit"}


def test_worker_thread_and_asyncio_task_land_in_same_trace():
    """The batch pipeline's exact shape: the root span opens in an
    asyncio task, one stage is recorded in the task, another inside a
    worker thread via asyncio.to_thread (contextvar copy), and a third
    from a RAW thread given the trace handle explicitly."""
    async def run():
        with tracing.trace("gossip_verify", topic="attestation") as tr:
            with tracing.span("assembly"):
                await asyncio.sleep(0)

            def thread_stage():
                with tracing.span("device_sync"):
                    time.sleep(0.001)

            await asyncio.to_thread(thread_stage)

            # raw threads drop contextvars: the explicit-handle form
            def raw_thread():
                tracing.record_stage("queue_wait", 0.002, (tr,))
            t = threading.Thread(target=raw_thread)
            t.start()
            t.join()
        return tr

    tr = asyncio.run(run())
    stages = dict(tr.stages)
    assert set(stages) == {"assembly", "device_sync", "queue_wait"}
    assert stages["device_sync"] >= 0.001
    assert tr.complete and tr.total_s >= 0.001


def test_attach_binds_many_traces_per_dispatch():
    a = tracing.new_trace("a")
    b = tracing.new_trace("b")
    with tracing.attach((a, None, b)):
        with tracing.span("dispatch"):
            pass
    assert [s for s, _ in a.stages] == ["dispatch"]
    assert [s for s, _ in b.stages] == ["dispatch"]


def test_slow_ring_keeps_the_slowest():
    tracing.clear_slow_traces()
    for i in range(50):
        tr = tracing.new_trace("t", i=str(i))
        # monotonic fake durations via a real (tiny) sleep would be
        # slow; instead fudge t_start backwards
        tr.t_start -= i * 0.001
        tracing.finish(tr)
    dump = tracing.slow_traces()
    assert len(dump) <= 32
    totals = [t["total_ms"] for t in dump]
    assert totals == sorted(totals, reverse=True)
    # the slowest synthetic trace survived, the fastest did not
    assert dump[0]["labels"]["i"] == "49"
    assert all(t["labels"]["i"] != "0" for t in dump)


def test_disabled_mode_is_noop():
    tracing.set_enabled(False)
    hist = GLOBAL_REGISTRY.labeled_histogram(
        "verify_stage_duration_seconds", labelnames=("stage",))
    before = hist.labels(stage="complete").snapshot()[2]
    assert tracing.new_trace("x") is None
    with tracing.trace("x") as tr:
        assert tr is None
        assert tracing.current_trace() is None
        with tracing.span("dispatch"):
            pass
    tracing.finish(None)   # tolerated
    assert tracing.slow_traces() == []
    after = hist.labels(stage="complete").snapshot()[2]
    assert after == before


def test_sampler_sees_completed_traces():
    seen = []
    tracing.set_sampler(seen.append)
    with tracing.trace("t"):
        pass
    assert len(seen) == 1 and seen[0].complete


SKS = [keygen(bytes([60 + i]) * 32) for i in range(2)]
PKS = [bls.secret_to_public_key(sk) for sk in SKS]


def test_service_attributes_stages_to_caller_trace():
    """End-to-end through the batching service on the pure provider:
    the caller's root trace collects queue_wait, assembly and dispatch,
    and their sum approximates the end-to-end total."""
    async def main():
        svc = AggregatingSignatureVerificationService(
            num_workers=1, registry=MetricsRegistry(), name="tr_svc")
        await svc.start()
        msg = b"traced"
        sig = bls.sign(SKS[0], msg)
        with tracing.trace("gossip_verify", topic="attestation") as tr:
            ok = await svc.verify([PKS[0]], msg, sig)
        await svc.stop()
        return ok, tr

    ok, tr = asyncio.run(main())
    assert ok
    stages = dict(tr.stages)
    assert {"queue_wait", "assembly", "dispatch"} <= set(stages)
    attributed = (stages["queue_wait"] + stages["assembly"]
                  + stages["dispatch"])
    # attribution covers the bulk of the end-to-end time (the remainder
    # is event-loop scheduling of the future resolution)
    assert attributed <= tr.total_s
    assert attributed >= 0.5 * tr.total_s
    # the trace also made it into the slow ring
    assert any(t["name"] == "gossip_verify"
               for t in tracing.slow_traces())


def test_service_batch_latency_and_bisect_metrics():
    """Batch latency (the `dispatch` stage, one sample a dispatch) +
    first_try/bisect split."""
    stage = GLOBAL_REGISTRY.labeled_histogram(
        "verify_stage_duration_seconds", labelnames=("stage",))
    before = stage.labels(stage="dispatch").snapshot()[2]

    async def main():
        reg = MetricsRegistry()
        svc = AggregatingSignatureVerificationService(
            num_workers=1, registry=reg, split_threshold=2,
            name="bisect_svc")
        await svc.start()
        good = [(f"m{i}".encode()) for i in range(3)]
        futs = [svc.verify([PKS[0]], m, bls.sign(SKS[0], m))
                for m in good]
        # one bad task forces the failure path → bisect recursion
        futs.append(svc.verify([PKS[0]], b"bad", bls.sign(SKS[1],
                                                          b"bad")))
        results = await asyncio.gather(*futs)
        await svc.stop()
        return reg, results

    reg, results = asyncio.run(main())
    assert results[:3] == [True, True, True] and results[3] is False
    dispatches = reg.labeled_counter("bisect_svc_dispatch_total")
    assert dispatches.labels(kind="first_try").value >= 1
    assert dispatches.labels(kind="bisect").value >= 1
    assert stage.labels(stage="dispatch").snapshot()[2] - before \
        == (dispatches.labels(kind="first_try").value
            + dispatches.labels(kind="bisect").value)


def test_admin_traces_endpoint():
    from teku_tpu.api import BeaconRestApi

    async def main():
        with tracing.trace("gossip_verify", topic="attestation"):
            pass
        api = BeaconRestApi(None)
        out = await api._admin_traces()
        assert out["tracing_enabled"] is True
        assert out["data"] and out["data"][0]["name"] == "gossip_verify"
        assert "total_ms" in out["data"][0]
        # ?clear=1 empties the ring after the read
        out = await api._admin_traces(query={"clear": "1"})
        assert out["data"]
        out = await api._admin_traces()
        assert out["data"] == []

    asyncio.run(main())
