"""Batching service semantics: drain, bisect-on-fail, overflow, grouping.

Runs against the pure-Python provider (fast enough at these sizes and
identical semantics through the SPI; the TPU provider is exercised by
tests/test_jax_provider.py)."""

import asyncio

import pytest

from teku_tpu.crypto import bls
from teku_tpu.crypto.bls import keygen
from teku_tpu.infra.metrics import MetricsRegistry
from teku_tpu.services.signatures import (
    AggregatingSignatureVerificationService, ServiceCapacityExceededError)

SKS = [keygen(bytes([40 + i]) * 32) for i in range(4)]
PKS = [bls.secret_to_public_key(sk) for sk in SKS]


def run(coro):
    return asyncio.run(coro)


def make_service(**kw):
    kw.setdefault("registry", MetricsRegistry())
    return AggregatingSignatureVerificationService(**kw)


def test_basic_verify_and_metrics():
    async def main():
        reg = MetricsRegistry()
        svc = make_service(num_workers=1, registry=reg)
        await svc.start()
        msg = b"single"
        sig = bls.sign(SKS[0], msg)
        ok = await svc.verify([PKS[0]], msg, sig)
        bad = await svc.verify([PKS[0]], b"other", sig)
        await svc.stop()
        assert ok and not bad
        assert reg.counter("signature_verifications_task_count_total").value >= 2
        assert reg.counter("signature_verifications_batch_count_total").value >= 2
        assert "signature_verifications_batch_size_bucket" in reg.expose()
    run(main())


def test_batching_drains_queue():
    async def main():
        reg = MetricsRegistry()
        svc = make_service(num_workers=1, registry=reg)
        await svc.start()
        futs = []
        msgs = [b"drain-%d" % i for i in range(6)]
        for i, m in enumerate(msgs):
            futs.append(svc.verify([PKS[i % 4]], m, bls.sign(SKS[i % 4], m)))
        results = await asyncio.gather(*futs)
        await svc.stop()
        assert all(results)
        # fewer batches than tasks proves the drain actually batched
        assert (reg.counter("signature_verifications_batch_count_total").value
                < len(msgs))
    run(main())


def test_bad_signature_isolated_by_bisect():
    async def main():
        svc = make_service(num_workers=1, split_threshold=2)
        await svc.start()
        futs = []
        for i in range(5):
            m = b"bisect-%d" % i
            sig = bls.sign(SKS[i % 4], m)
            if i == 2:
                m = b"tampered"
            futs.append(svc.verify([PKS[i % 4]], m, sig))
        results = await asyncio.gather(*futs)
        await svc.stop()
        assert results == [True, True, False, True, True]
    run(main())


def test_multi_triple_task_atomic():
    async def main():
        svc = make_service(num_workers=1)
        await svc.start()
        m1, m2 = b"proof", b"aggregate"
        good = [([PKS[0]], m1, bls.sign(SKS[0], m1)),
                ([PKS[1]], m2, bls.sign(SKS[1], m2))]
        bad = [([PKS[0]], m1, bls.sign(SKS[0], m1)),
               ([PKS[1]], b"wrong", bls.sign(SKS[1], m2))]
        ok = await svc.verify_multi(good)
        not_ok = await svc.verify_multi(bad)
        await svc.stop()
        assert ok and not not_ok  # one bad sig fails the whole task
    run(main())


@pytest.mark.parametrize("triples,moved", [
    (1, (1, 1, 0)),      # `verify`: one task, one triple, no multi task
    (3, (1, 3, 1)),      # `verify_multi` of an aggregate-and-proof
])
def test_tasks_triples_and_multi_tasks_are_counted_apart(triples, moved):
    """A dispatch's lanes are triples: `_task_count_total` alone
    under-counts an aggregate topic threefold.  Each counter moves at
    completion, whatever the verdict."""
    async def main():
        reg = MetricsRegistry()
        svc = make_service(num_workers=1, registry=reg, name="counted")
        await svc.start()
        task = [([PKS[i]], b"counted-%d" % i,
                 bls.sign(SKS[i], b"counted-%d" % i))
                for i in range(triples)]
        names = ("counted_task_count_total", "counted_triple_count_total",
                 "counted_multi_task_count_total")
        before = [reg.counter(n).value for n in names]
        if triples == 1:
            ok = await svc.verify(*task[0])
        else:
            ok = await svc.verify_multi(task)
        after = [reg.counter(n).value for n in names]
        assert ok is True
        assert tuple(a - b for a, b in zip(after, before)) == moved
        # a false task is a completed task too
        forged = [(pks, msg + b"!", sig) for pks, msg, sig in task]
        bad = (await svc.verify(*forged[0]) if triples == 1
               else await svc.verify_multi(forged))
        await svc.stop()
        assert bad is False
        assert tuple(reg.counter(n).value - b
                     for n, b in zip(names, before)) \
            == tuple(2 * m for m in moved)
    run(main())


def test_queue_overflow():
    async def main():
        svc = make_service(num_workers=1, queue_capacity=2)
        await svc.start()
        # DISTINCT messages: identical pending triples would coalesce
        # onto one queued task and never overflow the queue
        msgs = [b"overflow-%d" % i for i in range(52)]
        sigs = [bls.sign(SKS[0], m) for m in msgs]
        futs = [svc.verify([PKS[0]], msgs[i], sigs[i]) for i in range(2)]
        with pytest.raises(ServiceCapacityExceededError):
            for i in range(2, 52):
                futs.append(svc.verify([PKS[0]], msgs[i], sigs[i]))
        await asyncio.gather(*futs)
        await svc.stop()
    run(main())


def test_identical_inflight_triples_coalesce():
    async def main():
        reg = MetricsRegistry()
        svc = make_service(num_workers=1, registry=reg)
        await svc.start()
        msg = b"coalesce"
        sig = bls.sign(SKS[0], msg)
        bad_sig = bls.sign(SKS[0], b"wrong message")
        # gossip re-delivery: the same triple submitted 5x while queued
        futs = [svc.verify([PKS[0]], msg, sig) for _ in range(5)]
        bad = [svc.verify([PKS[0]], msg, bad_sig) for _ in range(3)]
        results = await asyncio.gather(*futs)
        bad_results = await asyncio.gather(*bad)
        assert results == [True] * 5       # verdict fans out to waiters
        assert bad_results == [False] * 3
        coalesced = reg.counter(
            "signature_verifications_coalesced_total").value
        assert coalesced == 4 + 2
        # pending map drains once verdicts land
        assert not svc._pending
        # a RE-submission after completion is a fresh task (the dedup
        # is in-flight only — a later identical request must re-verify)
        assert await svc.verify([PKS[0]], msg, sig) is True
        await svc.stop()
    run(main())


def test_multi_triple_tasks_coalesce_by_full_key():
    async def main():
        reg = MetricsRegistry()
        svc = make_service(num_workers=1, registry=reg)
        await svc.start()
        m1, m2 = b"agg-1", b"agg-2"
        task = [([PKS[0]], m1, bls.sign(SKS[0], m1)),
                ([PKS[1]], m2, bls.sign(SKS[1], m2))]
        f1 = svc.verify_multi(task)
        f2 = svc.verify_multi(list(task))      # identical -> coalesces
        f3 = svc.verify_multi(task[:1])        # different key: own task
        assert await asyncio.gather(f1, f2, f3) == [True, True, True]
        assert reg.counter(
            "signature_verifications_coalesced_total").value == 1
        await svc.stop()
    run(main())


def test_cancelled_primary_promotes_live_waiter():
    async def main():
        svc = make_service(num_workers=1)
        await svc.start()
        msg = b"promote"
        sig = bls.sign(SKS[0], msg)
        f1 = svc.verify([PKS[0]], msg, sig)
        f2 = svc.verify([PKS[0]], msg, sig)  # coalesce onto f1's task
        f3 = svc.verify([PKS[0]], msg, sig)
        # the original submitter bails while the task is still queued:
        # the waiters' callers still want the verdict — the first live
        # waiter is promoted to primary, nobody else gets cancelled
        f1.cancel()
        assert await asyncio.gather(f2, f3) == [True, True]
        assert f1.cancelled()
        assert not svc._pending
        await svc.stop()
    run(main())


class _AsyncHandle:
    def __init__(self, verdict):
        self._verdict = verdict

    def result(self):
        return self._verdict


class _AsyncFakeImpl:
    """Minimal BLS impl exposing the async begin seam: records the
    call interleaving so the overlap test can prove begin(N+1) runs
    BEFORE result(N) is read."""

    def __init__(self):
        self.calls = []

    def _verdict(self, triples):
        return all(sig == b"good" for _pks, _msg, sig in triples)

    def begin_batch_verify(self, triples):
        self.calls.append(("begin", len(triples)))
        verdict = self._verdict(triples)

        class H(_AsyncHandle):
            def result(h):
                self.calls.append(("result", len(triples)))
                return verdict

        return H(verdict)

    def batch_verify(self, triples):
        self.calls.append(("sync", len(triples)))
        return self._verdict(triples)

    def fast_aggregate_verify(self, pks, msg, sig):
        self.calls.append(("sync", 1))
        return sig == b"good"


def test_async_overlap_begins_next_batch_before_retiring_previous():
    async def main():
        impl = _AsyncFakeImpl()
        bls.set_implementation(impl)
        try:
            svc = make_service(num_workers=1, overlap=True)
            await svc.start()
            futs = [svc.verify([PKS[i % 4]], b"msg-%d" % i, b"good")
                    for i in range(6)]
            assert all(await asyncio.gather(*futs))
            await svc.stop()
        finally:
            bls.reset_implementation()
        begins = [c for c in impl.calls if c[0] == "begin"]
        assert begins, "async seam never engaged"
        # if more than one batch formed, the worker must have begun a
        # later batch before reading an earlier batch's result
        if len(begins) > 1:
            first_result = impl.calls.index(("result", begins[0][1]))
            second_begin = impl.calls.index(begins[1])
            assert second_begin < first_result
    run(main())


def test_async_overlap_failure_still_bisects():
    async def main():
        impl = _AsyncFakeImpl()
        bls.set_implementation(impl)
        try:
            svc = make_service(num_workers=1, overlap=True,
                               split_threshold=2)
            await svc.start()
            futs = []
            for i in range(5):
                sig = b"bad" if i == 2 else b"good"
                futs.append(svc.verify([PKS[i % 4]], b"bis-%d" % i, sig))
            results = await asyncio.gather(*futs)
            await svc.stop()
        finally:
            bls.reset_implementation()
        assert results == [True, True, False, True, True]
    run(main())


def test_overlap_disabled_stays_sync():
    async def main():
        impl = _AsyncFakeImpl()
        bls.set_implementation(impl)
        try:
            svc = make_service(num_workers=1, overlap=False)
            await svc.start()
            assert await svc.verify([PKS[0]], b"m", b"good")
            await svc.stop()
        finally:
            bls.reset_implementation()
        assert all(c[0] == "sync" for c in impl.calls)
    run(main())


def test_not_started_raises():
    async def main():
        svc = make_service()
        with pytest.raises(RuntimeError):
            svc.verify([PKS[0]], b"x", b"y" * 96)
    run(main())


# --------------------------------------------------------------------------
# Priority classes: strict-priority drain, VIP lane, shed-by-class,
# coalescing promotion (ISSUE 7)
# --------------------------------------------------------------------------

from teku_tpu.services.admission import VerifyClass  # noqa: E402


class _OrderRecordingImpl(_AsyncFakeImpl):
    """Records the message order batches are dispatched in (the facade
    routes single-triple batches through fast_aggregate_verify, so
    both seams record).  The FIRST dispatch blocks on a gate so a test
    can pile classed tasks up behind a busy worker deterministically."""

    def __init__(self, gate_first: bool = False):
        super().__init__()
        import threading
        self.batches = []
        self.gate = threading.Event()
        self._gates_left = 1 if gate_first else 0

    def _record(self, triples):
        if self._gates_left:
            self._gates_left -= 1
            self.gate.wait(10)
        self.batches.append([msg for _pks, msg, _sig in triples])
        return self._verdict(triples)

    def batch_verify(self, triples):
        return self._record(triples)

    def fast_aggregate_verify(self, pks, msg, sig):
        return self._record([(pks, msg, sig)])


def test_strict_priority_drain_order():
    """With every class queued while the worker is busy, the next
    batch drains VIP > BLOCK_IMPORT > SYNC_CRITICAL > GOSSIP >
    OPTIMISTIC — and the VIP dispatch carries no lower-class lanes."""
    async def main():
        impl = _OrderRecordingImpl(gate_first=True)
        bls.set_implementation(impl)
        try:
            svc = make_service(num_workers=1, overlap=False)
            await svc.start()
            # the gated first dispatch occupies the single worker
            # while the classed tasks pile up behind it
            futs = [svc.verify([PKS[0]], b"blocker", b"good")]
            await asyncio.sleep(0.05)       # worker inside the gate
            order = [(VerifyClass.OPTIMISTIC, b"opt"),
                     (VerifyClass.GOSSIP, b"gossip"),
                     (VerifyClass.SYNC_CRITICAL, b"sync"),
                     (VerifyClass.BLOCK_IMPORT, b"block"),
                     (VerifyClass.VIP, b"vip")]
            for cls, msg in order:          # submitted WORST first
                futs.append(svc.verify([PKS[0]], msg, b"good",
                                       cls=cls))
            impl.gate.set()
            assert all(await asyncio.gather(*futs))
            await svc.stop()
        finally:
            bls.reset_implementation()
        # first batch: the blocker alone.  The VIP task dispatches in
        # its own batch (bypass), then the rest in priority order.
        assert impl.batches[0] == [b"blocker"]
        assert impl.batches[1] == [b"vip"]
        flat = [m for b in impl.batches[2:] for m in b]
        assert flat == [b"block", b"sync", b"gossip", b"opt"]
    run(main())


def test_vip_is_single_signature_only():
    async def main():
        svc = make_service(num_workers=1)
        await svc.start()
        m1, m2 = b"v1", b"v2"
        with pytest.raises(ValueError):
            svc.verify_multi(
                [([PKS[0]], m1, bls.sign(SKS[0], m1)),
                 ([PKS[1]], m2, bls.sign(SKS[1], m2))],
                cls=VerifyClass.VIP)
        await svc.stop()
    run(main())


def test_full_queue_evicts_lower_class_for_higher_arrival():
    """Shed-by-class at the bound: a BLOCK_IMPORT arrival on a full
    queue evicts a queued OPTIMISTIC task (never the reverse), the
    victim's future fails with the capacity error, and both the
    labeled counter and the flight-recorder event name the class."""
    async def main():
        from teku_tpu.infra import flightrecorder
        from teku_tpu.infra.metrics import MetricsRegistry
        from teku_tpu.services.signatures import (
            ServiceCapacityExceededError)
        reg = MetricsRegistry()
        svc = make_service(num_workers=1, queue_capacity=2,
                           registry=reg)
        await svc.start()
        blocker = svc.verify([PKS[0]], b"blk", b"x")   # worker takes it
        await asyncio.sleep(0.05)                       # worker busy
        opt = svc.verify([PKS[0]], b"opt-victim", b"x",
                         cls=VerifyClass.OPTIMISTIC)
        gos = svc.verify([PKS[0]], b"gos", b"x",
                         cls=VerifyClass.GOSSIP)
        # queue now full (2): a BLOCK_IMPORT arrival evicts the
        # OPTIMISTIC task
        ring_before = len(flightrecorder.RECORDER.snapshot())
        blk = svc.verify([PKS[1]], b"import", b"x",
                         cls=VerifyClass.BLOCK_IMPORT)
        with pytest.raises(ServiceCapacityExceededError):
            await opt
        # an OPTIMISTIC arrival on the still-full queue cannot evict
        # anyone (nothing queued ranks below it) -> rejected outright
        with pytest.raises(ServiceCapacityExceededError):
            svc.verify([PKS[0]], b"opt-2", b"x",
                       cls=VerifyClass.OPTIMISTIC)
        for fut in (blocker, gos, blk):
            with pytest.raises(Exception):
                # fake signatures: verdicts are False, not errors —
                # consume them; only the verdicts matter elsewhere
                if not await fut:
                    raise RuntimeError("expected-false")
        await svc.stop()
        rejected = reg.metrics()[
            "signature_verifications_rejected_total"]
        assert rejected.labels(**{"class": "optimistic"}).value == 2
        assert rejected.labels(**{"class": "block_import"}).value == 0
        sheds = [e for e in flightrecorder.RECORDER.snapshot()
                 [ring_before:] if e["kind"] == "queue_shed"]
        assert {e["class"] for e in sheds} == {"optimistic"}
        assert {e["reason"] for e in sheds} == {"preempted",
                                                "overflow"}
    run(main())


def test_coalesced_higher_class_waiter_promotes_task():
    """Satellite: a VIP duplicate of a queued GOSSIP verify promotes
    the shared lane — it drains ahead of higher-priority-by-default
    traffic queued after it."""
    async def main():
        impl = _OrderRecordingImpl(gate_first=True)
        bls.set_implementation(impl)
        try:
            svc = make_service(num_workers=1, overlap=False)
            await svc.start()
            blocker = svc.verify([PKS[0]], b"blocker", b"good")
            await asyncio.sleep(0.05)       # worker inside the gate
            shared = svc.verify([PKS[0]], b"shared", b"good",
                                cls=VerifyClass.GOSSIP)
            ahead = svc.verify([PKS[0]], b"sync", b"good",
                               cls=VerifyClass.SYNC_CRITICAL)
            # the duplicate arrives with VIP urgency: the SHARED lane
            # must inherit it (one lane, highest waiter's class)
            dup = svc.verify([PKS[0]], b"shared", b"good",
                             cls=VerifyClass.VIP)
            impl.gate.set()
            assert all(await asyncio.gather(blocker, shared, ahead,
                                            dup))
            await svc.stop()
        finally:
            bls.reset_implementation()
        # the promoted task dispatched as the VIP express batch,
        # BEFORE the sync-critical task that outranked its old class
        assert impl.batches[1] == [b"shared"]
        assert impl.batches[2] == [b"sync"]
    run(main())


def test_cancelled_vip_primary_does_not_strand_gossip_waiters():
    """Satellite: the VIP submitter bails while coalesced GOSSIP
    waiters still want the verdict — the first live waiter is
    promoted to primary, every waiter resolves, and the task's
    effective class falls back to the survivors' (GOSSIP), releasing
    the express lane."""
    async def main():
        impl = _OrderRecordingImpl(gate_first=True)
        bls.set_implementation(impl)
        try:
            svc = make_service(num_workers=1, overlap=False)
            await svc.start()
            blocker = svc.verify([PKS[0]], b"blocker", b"good")
            await asyncio.sleep(0.05)       # worker inside the gate
            vip = svc.verify([PKS[0]], b"shared", b"good",
                             cls=VerifyClass.VIP)
            w1 = svc.verify([PKS[0]], b"shared", b"good",
                            cls=VerifyClass.GOSSIP)
            w2 = svc.verify([PKS[0]], b"shared", b"good",
                            cls=VerifyClass.GOSSIP)
            vip.cancel()
            impl.gate.set()
            assert await asyncio.gather(blocker, w1, w2) \
                == [True, True, True]
            assert vip.cancelled()
            assert not svc._pending
            await svc.stop()
        finally:
            bls.reset_implementation()
        # the demoted task no longer rides the VIP express batch: it
        # dispatched as an ordinary (non-solo or solo-by-idle) batch
        # AND nobody was stranded (gathers above resolved)
        assert any(b"shared" in b for b in impl.batches)
    run(main())


def test_per_class_depth_metrics_and_queue_snapshot():
    async def main():
        from teku_tpu.infra.metrics import MetricsRegistry
        reg = MetricsRegistry()
        svc = make_service(num_workers=1, registry=reg)
        await svc.start()
        blocker = svc.verify([PKS[0]], b"blocker", b"x")
        await asyncio.sleep(0.05)
        futs = [svc.verify([PKS[0]], b"g%d" % i, b"x",
                           cls=VerifyClass.GOSSIP) for i in range(3)]
        futs.append(svc.verify([PKS[0]], b"o1", b"x",
                               cls=VerifyClass.OPTIMISTIC))
        snap = svc.queue_snapshot()
        assert snap["classes"]["gossip"]["depth"] == 3
        assert snap["classes"]["optimistic"]["depth"] == 1
        assert snap["classes"]["vip"]["depth"] == 0
        assert snap["total"] == 4
        depth = reg.metrics()[
            "signature_verifications_class_queue_depth"]
        assert depth.labels(**{"class": "gossip"}).value == 3
        await asyncio.gather(blocker, *futs)
        await svc.stop()
        assert svc.queue_snapshot()["total"] == 0
    run(main())


def test_brownout_sheds_queued_optimistic_and_rejects_arrivals():
    """A controller-declared brownout trims queued OPTIMISTIC tasks
    (class-labeled shed events) and rejects new OPTIMISTIC arrivals
    at admission, while GOSSIP flows at level 1."""
    async def main():
        from teku_tpu.services.admission import BatchPlan
        from teku_tpu.services.signatures import (
            ServiceCapacityExceededError)

        class FixedController:
            brownout_level = 1

            def plan(self):
                return BatchPlan(batch_size=64, flush_deadline_s=0.0,
                                 brownout_level=1)

        svc = make_service(num_workers=1,
                           controller=FixedController())
        await svc.start()
        blocker = svc.verify([PKS[0]], b"blocker", b"x")
        await asyncio.sleep(0.05)
        # admission control: OPTIMISTIC rejected outright
        with pytest.raises(ServiceCapacityExceededError):
            svc.verify([PKS[0]], b"o", b"x",
                       cls=VerifyClass.OPTIMISTIC)
        # GOSSIP still admitted at level 1
        g = svc.verify([PKS[0]], b"g", b"x", cls=VerifyClass.GOSSIP)
        assert (await asyncio.gather(blocker, g)) == [False, False]
        await svc.stop()
    run(main())
