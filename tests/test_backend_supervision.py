"""Backend supervisor: bring-up state machine, hot-swap, breaker.

The acceptance criterion of the supervision issue, asserted end to end
with injected faults and NO accelerator: a slow-ramp backend (init far
longer than the old probe deadline) must not stall boot — the node
serves the oracle immediately, the supervisor reaches READY in the
background, the facade hot-swaps with zero failed in-flight
verifications, and an injected dispatch-hang afterwards trips the
breaker back to the oracle, all visible as metric/heartbeat state
transitions.
"""

import asyncio
import time

import pytest

from teku_tpu.crypto import bls
from teku_tpu.crypto.bls import keygen, loader
from teku_tpu.crypto.bls.pure_impl import PureBls12381
from teku_tpu.infra import faults
from teku_tpu.infra.metrics import MetricsRegistry
from teku_tpu.infra.supervisor import (BackendState, BackendSupervisor,
                                       CircuitBreaker, CircuitOpenError,
                                       DispatchTimeoutError)

pytestmark = pytest.mark.faults


@pytest.fixture(autouse=True)
def _clean():
    yield
    faults.clear()
    bls.reset_implementation()


class FakeDevice(PureBls12381):
    """'Device' provider: oracle math behind the `bls.dispatch` fault
    site, so hang/raise/wrong-result injection hits it exactly like the
    real JaxBls12381._dispatch."""

    name = "fake-device"

    def __init__(self):
        super().__init__()
        self.dispatch_count = 0

    def _site(self):
        self.dispatch_count += 1
        faults.check("bls.dispatch")

    def fast_aggregate_verify(self, pks, msg, sig):
        self._site()
        return faults.transform(
            "bls.dispatch", super().fast_aggregate_verify(pks, msg, sig))

    def batch_verify(self, triples):
        self._site()
        return faults.transform(
            "bls.dispatch", super().batch_verify(triples))

    def verify(self, pk, msg, sig):
        self._site()
        return faults.transform(
            "bls.dispatch", super().verify(pk, msg, sig))

    def public_key_is_valid(self, pk):
        self._site()
        return super().public_key_is_valid(pk)


def make_fake_supervisor(registry=None, *, ramp_s=0.0, breaker=None,
                         fail_times=0, with_reprobe=False, **kw):
    """Supervisor over FakeDevice with a SlowRamp/Raise-able probe."""
    registry = registry or MetricsRegistry()
    # default deadline is generous: pure-oracle batch dispatches in
    # these tests take tens of ms and must never trip spuriously
    breaker = breaker or CircuitBreaker(
        failure_threshold=2, deadline_s=2.0, cooldown_s=0.2,
        name="t", registry=registry)
    if ramp_s:
        faults.inject("backend.init", faults.SlowRamp(ramp_s))
    if fail_times:
        faults.inject("backend.init", faults.Raise(
            RuntimeError("device wedged"), times=fail_times))
    installed = {}

    def probe():
        return FakeDevice()

    def install(backend):
        installed["impl"] = backend
        bls.set_implementation(
            loader.GuardedBls12381(backend, breaker))

    def reprobe():
        if not installed["impl"].fast_aggregate_verify([PK], MSG, SIG):
            raise RuntimeError("reprobe wrong verdict")

    kw.setdefault("probe_attempts_per_round", 2)
    kw.setdefault("probe_base_delay_s", 0.01)
    kw.setdefault("round_delay_s", 0.01)
    return BackendSupervisor(
        probe=probe, install=install,
        reprobe=reprobe if with_reprobe else None,
        uninstall=bls.reset_implementation, breaker=breaker,
        name="t", registry=registry, **kw), registry


SK = keygen(b"\x07" * 32)
PK = bls.secret_to_public_key(SK)
MSG = b"supervised"
SIG = bls.sign(SK, MSG)


# --------------------------------------------------------------------------
# state machine
# --------------------------------------------------------------------------

def test_slow_ramp_boots_oracle_then_hot_swaps():
    """Init slower than the OLD probe deadline: boot is instant on the
    oracle, READY arrives in the background, facade hot-swaps."""
    async def main():
        sup, reg = make_fake_supervisor(ramp_s=0.3)
        old_probe_deadline = 0.05          # the legacy blocking budget
        t0 = time.monotonic()
        await sup.start()
        boot_s = time.monotonic() - t0
        assert boot_s < old_probe_deadline  # start() never blocks
        # the node is serving NOW, on the oracle
        assert isinstance(bls.get_implementation(), PureBls12381)
        assert bls.verify(PK, MSG, SIG)
        assert sup.backend_state in ("cold", "probing", "warming",
                                     "ready")
        assert await sup.wait_ready(5.0)
        impl = bls.get_implementation()
        assert isinstance(impl, loader.GuardedBls12381)
        assert impl.name == "fake-device"
        assert bls.verify(PK, MSG, SIG)     # now via the device
        states = [s for s, _ in sup.transitions]
        assert states == ["cold", "probing", "warming", "ready"]
        # transitions carry timestamps and the metrics agree
        assert all(t > 0 for _, t in sup.transitions)
        assert reg.state_gauge("t_state").state == "ready"
        assert 'state="ready"} 1.0' in reg.expose()
        await sup.stop()
    asyncio.run(main())


def test_probe_failures_back_off_then_succeed():
    async def main():
        # 3 raise-faults > one 2-attempt round: forces a round of
        # backoff before the probe lands
        sup, reg = make_fake_supervisor(fail_times=3)
        await sup.start()
        assert await sup.wait_ready(5.0)
        assert reg.counter("t_probe_failures_total").value >= 1
        assert faults.fired_count("backend.init") == 3
        await sup.stop()
    asyncio.run(main())


def test_non_retryable_probe_degrades():
    async def main():
        registry = MetricsRegistry()

        def probe():
            raise ImportError("no accelerator runtime in this image")

        sup = BackendSupervisor(
            probe=probe, install=lambda b: None, name="t",
            registry=registry, probe_attempts_per_round=2,
            probe_base_delay_s=0.01, round_delay_s=0.01)
        await sup.start()
        for _ in range(200):
            if sup.backend_state == "degraded":
                break
            await asyncio.sleep(0.02)
        assert sup.backend_state == "degraded"
        assert "abandoned" in sup.backend_detail
        # the oracle still serves: DEGRADED costs speed, not liveness
        assert bls.verify(PK, MSG, SIG)
        await sup.stop()
    asyncio.run(main())


def test_warmup_veto_degrades_instead_of_installing():
    """A device that returns a wrong verdict on a KNOWN-good input
    during warmup must never be hot-swapped in: correctness over
    speed, so the supervisor goes DEGRADED on the oracle."""
    from teku_tpu.infra.supervisor import WarmupVetoError

    async def main():
        def warmup(backend):
            raise WarmupVetoError("warmup batch did not verify")

        sup = BackendSupervisor(
            probe=FakeDevice, warmup=warmup,
            install=lambda b: bls.set_implementation(b),
            name="t", registry=MetricsRegistry(),
            probe_base_delay_s=0.01, round_delay_s=0.01)
        await sup.start()
        for _ in range(200):
            if sup.backend_state == "degraded":
                break
            await asyncio.sleep(0.02)
        assert sup.backend_state == "degraded"
        assert "veto" in sup.backend_detail
        # the untrusted device was NOT installed
        assert isinstance(bls.get_implementation(), PureBls12381)
        assert not sup._ready_event.is_set()
        await sup.stop()
    asyncio.run(main())


def test_warmup_ordinary_failure_still_installs():
    """A non-veto warmup hiccup (e.g. compile error) installs anyway:
    the first real batch compiles lazily."""
    async def main():
        def warmup(backend):
            raise RuntimeError("compile hiccup")

        breaker = CircuitBreaker(name="t", registry=MetricsRegistry())
        sup = BackendSupervisor(
            probe=FakeDevice, warmup=warmup,
            install=lambda b: bls.set_implementation(
                loader.GuardedBls12381(b, breaker)),
            name="t", registry=MetricsRegistry(),
            probe_base_delay_s=0.01, round_delay_s=0.01)
        await sup.start()
        assert await sup.wait_ready(5.0)
        assert isinstance(bls.get_implementation(),
                          loader.GuardedBls12381)
        await sup.stop()
    asyncio.run(main())


def test_kzg_error_does_not_trip_breaker():
    """Malformed-input KzgErrors from the device backend are verdicts:
    they propagate but never count toward the trip threshold."""
    from teku_tpu.crypto import kzg

    class VerdictKzg:
        name = "verdict"

        def g1_lincomb(self, setup, scalars):
            raise kzg.KzgError("scalar count must match basis size")

    br = CircuitBreaker(failure_threshold=1, deadline_s=1.0,
                        cooldown_s=60.0, name="vk",
                        registry=MetricsRegistry())
    guarded = loader.GuardedKzgBackend(VerdictKzg(), br)
    for _ in range(3):
        with pytest.raises(kzg.KzgError):
            guarded.g1_lincomb(None, [])
    assert br.state == CircuitBreaker.CLOSED   # never tripped


def test_max_rounds_degrades():
    async def main():
        sup, _ = make_fake_supervisor(fail_times=100, max_rounds=2)
        await sup.start()
        for _ in range(200):
            if sup.backend_state == "degraded":
                break
            await asyncio.sleep(0.02)
        assert sup.backend_state == "degraded"
        await sup.stop()
    asyncio.run(main())


# --------------------------------------------------------------------------
# hot-swap under concurrent load
# --------------------------------------------------------------------------

def test_hot_swap_zero_failed_inflight_verifications():
    """Continuous verification traffic across the oracle→device swap:
    every single verdict stays correct."""
    from teku_tpu.infra.metrics import MetricsRegistry as MR
    from teku_tpu.services.signatures import (
        AggregatingSignatureVerificationService)

    async def main():
        sup, _ = make_fake_supervisor(ramp_s=0.1)
        svc = AggregatingSignatureVerificationService(
            num_workers=2, registry=MR())
        await svc.start()
        await sup.start()
        results = []
        bad_sig = bls.sign(SK, b"other-message")
        # traffic spans the swap: supervisor goes READY ~0.1s in
        for burst in range(12):
            futs = [svc.verify([PK], MSG, SIG) for _ in range(6)]
            with_bad = burst % 5 == 0
            if with_bad:
                futs.append(svc.verify([PK], MSG, bad_sig))
            got = await asyncio.gather(*futs)
            results.append((with_bad, got))
            await asyncio.sleep(0.01)
        assert await sup.wait_ready(5.0)
        for with_bad, got in results:
            assert got[:6] == [True] * 6     # zero failed verifications
            if with_bad:
                assert got[6] is False       # bad sig still rejected
        # the device actually served part of the traffic
        assert sup.backend.dispatch_count > 0
        await svc.stop()
        await sup.stop()
    asyncio.run(main())


# --------------------------------------------------------------------------
# circuit breaker
# --------------------------------------------------------------------------

def test_breaker_trips_on_consecutive_failures_and_recloses():
    reg = MetricsRegistry()
    br = CircuitBreaker(failure_threshold=2, deadline_s=1.0,
                        cooldown_s=0.1, name="cb", registry=reg)

    def boom():
        raise RuntimeError("device fault")

    for _ in range(2):
        with pytest.raises(RuntimeError):
            br.call(boom)
    assert br.state == CircuitBreaker.OPEN
    assert reg.counter("cb_circuit_trips_total").value == 1
    # open: dispatch refused without touching the device
    with pytest.raises(CircuitOpenError):
        br.call(lambda: True)
    time.sleep(0.15)
    # half-open probe succeeds -> re-closed
    assert br.call(lambda: "ok") == "ok"
    assert br.state == CircuitBreaker.CLOSED


def test_breaker_half_open_failure_reopens_with_longer_cooldown():
    br = CircuitBreaker(failure_threshold=1, deadline_s=1.0,
                        cooldown_s=0.1, name="cb2",
                        registry=MetricsRegistry())
    with pytest.raises(RuntimeError):
        br.call(lambda: (_ for _ in ()).throw(RuntimeError("x")))
    assert br.state == CircuitBreaker.OPEN
    first_open_until = br._open_until
    time.sleep(0.12)
    with pytest.raises(RuntimeError):      # half-open probe fails
        br.call(lambda: (_ for _ in ()).throw(RuntimeError("y")))
    assert br.state == CircuitBreaker.OPEN
    # cooldown doubled: second window is longer than the first
    assert br._open_until - br._clock() > 0.15
    assert br._open_until > first_open_until


def test_breaker_deadline_counts_as_failure():
    br = CircuitBreaker(failure_threshold=1, deadline_s=0.05,
                        cooldown_s=10.0, name="cb3",
                        registry=MetricsRegistry())
    with pytest.raises(DispatchTimeoutError):
        br.call(time.sleep, 0.5)
    assert br.state == CircuitBreaker.OPEN


def test_guarded_bls_falls_back_to_oracle_per_call():
    """A raising device never corrupts a verdict: the SAME call is
    re-served by the oracle."""
    reg = MetricsRegistry()
    br = CircuitBreaker(failure_threshold=3, deadline_s=1.0,
                        cooldown_s=60.0, name="g", registry=reg)
    device = FakeDevice()
    guarded = loader.GuardedBls12381(device, br)
    faults.inject("bls.dispatch", faults.Raise(
        RuntimeError("device fault"), times=1))
    assert guarded.verify(PK, MSG, SIG) is True     # oracle served it
    assert guarded.verify(PK, MSG, SIG) is True     # device again
    assert br.state == CircuitBreaker.CLOSED


def test_dispatch_hang_trips_breaker_back_to_oracle_then_recloses():
    """The acceptance scenario's second half: after READY, an injected
    dispatch hang trips the breaker; verdicts keep flowing from the
    oracle (TRIPPED state), and once the fault clears the half-open
    probe re-closes the circuit back to READY."""
    async def main():
        reg = MetricsRegistry()
        br = CircuitBreaker(failure_threshold=2, deadline_s=0.5,
                            cooldown_s=0.2, name="t", registry=reg)
        sup, _ = make_fake_supervisor(registry=reg, breaker=br)
        await sup.start()
        assert await sup.wait_ready(5.0)
        impl = bls.get_implementation()
        # hang longer than the 0.5s per-dispatch deadline, every time
        faults.inject("bls.dispatch", faults.Hang(1.0))
        for _ in range(2):                 # threshold=2 -> trip
            assert await asyncio.to_thread(
                bls.verify, PK, MSG, SIG)  # correct, via oracle
        assert impl.breaker.state == CircuitBreaker.OPEN
        assert impl.serving == "oracle"
        assert sup.backend_state == "tripped"
        assert "tripped" in [s for s, _ in sup.transitions]
        # while open: no device calls, instant oracle service
        n_before = sup.backend.dispatch_count
        assert bls.verify(PK, MSG, SIG)
        assert sup.backend.dispatch_count == n_before
        # clear the fault; after cooldown a half-open probe re-closes.
        # Orphaned hang threads may still hold the device lock for a
        # while (by design: a busy device reads as busy), so retry
        # until they drain
        faults.clear("bls.dispatch")
        deadline = time.monotonic() + 8.0
        while time.monotonic() < deadline:
            assert await asyncio.to_thread(bls.verify, PK, MSG, SIG)
            if impl.breaker.state == CircuitBreaker.CLOSED:
                break
            await asyncio.sleep(0.3)
        assert impl.breaker.state == CircuitBreaker.CLOSED
        assert sup.backend_state == "ready"
        snap = sup.snapshot()
        assert snap["circuit"] == "closed"
        assert [t["state"] for t in snap["transitions"]][-2:] == \
            ["tripped", "ready"]
        await sup.stop()
    asyncio.run(main())


# --------------------------------------------------------------------------
# lifecycle / wiring
# --------------------------------------------------------------------------

def test_node_owns_supervisor_lifecycle():
    """BeaconNode.do_start starts the supervisor, do_stop stops it and
    restores the oracle."""
    from teku_tpu.node import Devnet

    async def main():
        sup, _ = make_fake_supervisor()
        net = Devnet(n_nodes=1, n_validators=8)
        net.nodes[0].supervisor = sup
        await net.start()
        assert sup.is_running
        assert await sup.wait_ready(5.0)
        assert isinstance(bls.get_implementation(),
                          loader.GuardedBls12381)
        await net.run_slot(1)
        await net.stop()
        assert not sup.is_running
        # uninstall restored the oracle
        assert isinstance(bls.get_implementation(), PureBls12381)
    asyncio.run(main())


def test_stop_before_ready_cancels_cleanly():
    async def main():
        sup, _ = make_fake_supervisor(ramp_s=0.6)
        await sup.start()
        await asyncio.sleep(0.05)
        await sup.stop()                   # mid-probe cancel
        assert sup.backend_state in ("probing", "cold")
        assert isinstance(bls.get_implementation(), PureBls12381)
    asyncio.run(main())


def test_probe_reserved_keeps_live_traffic_off_half_open():
    """With a supervisor-owned reprobe, a live call arriving after the
    cooldown must NOT be drafted as the half-open probe."""
    br = CircuitBreaker(failure_threshold=1, deadline_s=1.0,
                        cooldown_s=0.05, name="pr",
                        registry=MetricsRegistry())
    br.probe_reserved = True
    with pytest.raises(RuntimeError):
        br.call(lambda: (_ for _ in ()).throw(RuntimeError("x")))
    assert br.state == CircuitBreaker.OPEN
    time.sleep(0.08)
    # cooldown elapsed: a live (non-probe) call is still refused...
    with pytest.raises(CircuitOpenError):
        br.call(lambda: True)
    assert br.state == CircuitBreaker.OPEN
    # ...and only the probe call may re-close
    assert br.call(lambda: "ok", probe=True) == "ok"
    assert br.state == CircuitBreaker.CLOSED


def test_kzg_dispatch_faults_feed_the_breaker():
    """Hang/raise injection at kzg.dispatch runs INSIDE the guarded
    call: deadlines contain hangs and raises count toward the trip."""
    from teku_tpu.crypto import kzg

    class IdleKzg:
        name = "idle"

        def g1_lincomb(self, setup, scalars):
            return b"\x00" * 48

    br = CircuitBreaker(failure_threshold=2, deadline_s=0.2,
                        cooldown_s=60.0, name="kd",
                        registry=MetricsRegistry())
    guarded = loader.GuardedKzgBackend(IdleKzg(), br)
    faults.inject("kzg.dispatch", faults.Raise(RuntimeError("boom")))
    with pytest.raises(kzg.BackendUnavailable):
        guarded.g1_lincomb(None, [])
    faults.clear("kzg.dispatch")
    faults.inject("kzg.dispatch", faults.Hang(1.0, times=1))
    with pytest.raises(kzg.BackendUnavailable):   # deadline, not 1.0s
        t0 = time.monotonic()
        guarded.g1_lincomb(None, [])
    assert time.monotonic() - t0 < 0.8
    assert br.state == CircuitBreaker.OPEN        # 2 failures tripped


def test_background_reprobe_recloses_without_live_traffic():
    """After a trip, the SUPERVISOR's synthetic reprobe re-closes the
    circuit — no live verification pays the probe's deadline wait."""
    async def main():
        reg = MetricsRegistry()
        br = CircuitBreaker(failure_threshold=1, deadline_s=0.3,
                            cooldown_s=0.2, name="t", registry=reg)
        sup, _ = make_fake_supervisor(registry=reg, breaker=br,
                                      with_reprobe=True)
        await sup.start()
        assert await sup.wait_ready(5.0)
        faults.inject("bls.dispatch", faults.Hang(1.0, times=1))
        assert await asyncio.to_thread(bls.verify, PK, MSG, SIG)
        assert sup.backend_state == "tripped"
        # NO further traffic: the background reprobe must recover alone
        for _ in range(100):
            if sup.backend_state == "ready":
                break
            await asyncio.sleep(0.05)
        assert sup.backend_state == "ready"
        assert br.state == CircuitBreaker.CLOSED
        await sup.stop()
    asyncio.run(main())


def test_complete_batch_verify_across_hot_swap():
    """A prepare/complete pair split across the oracle→device swap
    completes on the implementation family it started with."""
    semi = bls.prepare_batch_verify(([PK], MSG, SIG))     # oracle semi
    bad_semi = bls.prepare_batch_verify(([PK], b"x", SIG))
    br = CircuitBreaker(failure_threshold=2, deadline_s=2.0,
                        cooldown_s=0.2, name="x",
                        registry=MetricsRegistry())
    guarded = loader.GuardedBls12381(FakeDevice(), br)
    bls.set_implementation(guarded)                       # hot-swap
    assert bls.complete_batch_verify([semi]) is True
    assert bls.complete_batch_verify([bad_semi]) is False
    # and mixed old/new semis in one completion
    new_semi = bls.prepare_batch_verify(([PK], MSG, SIG))
    assert bls.complete_batch_verify([semi, new_semi]) is True


def test_configure_supervised_boots_pure():
    assert loader.configure("supervised") == "pure"
    assert isinstance(bls.get_implementation(), PureBls12381)


@pytest.mark.slow
def test_supervised_bringup_real_jax_provider():
    """End-to-end on the real device provider (CPU backend): probe,
    warmup compile, hot-swap, and a guarded verification that actually
    dispatches the staged kernel."""
    async def main():
        sup = loader.make_supervisor(registry=MetricsRegistry(),
                                     probe_base_delay_s=0.1,
                                     round_delay_s=0.1)
        await sup.start()
        assert await sup.wait_ready(1200.0)
        impl = bls.get_implementation()
        assert isinstance(impl, loader.GuardedBls12381)
        assert impl.name == "jax-tpu"
        # generous deadline: a cold staged compile is minutes on CPU
        impl.breaker.deadline_s = 900.0
        assert await asyncio.to_thread(bls.verify, PK, MSG, SIG)
        assert not bls.verify(PK, b"other", SIG)
        assert sup.backend[0].dispatch_count > 0
        await sup.stop()
    asyncio.run(main())


def test_guarded_kzg_backend_unavailable_falls_through():
    """A tripped device KZG backend must cost latency, not verdicts:
    the facade falls through to the host path."""
    from teku_tpu.crypto import kzg

    class BoomKzg:
        name = "boom"

        def verify_blob_kzg_proof_batch(self, *a):
            raise RuntimeError("device fault")

        def g1_lincomb(self, *a):
            raise RuntimeError("device fault")

        def verify_blob_kzg_proof(self, *a):
            raise RuntimeError("device fault")

    br = CircuitBreaker(failure_threshold=1, deadline_s=1.0,
                        cooldown_s=60.0, name="gk",
                        registry=MetricsRegistry())
    kzg.set_backend(loader.GuardedKzgBackend(BoomKzg(), br))
    try:
        setup = kzg.insecure_setup()
        # nonzero polynomial: keeps commitment/proof off the infinity
        # point so the host pairing path is exercised for real
        blob = ((7).to_bytes(32, "big")
                + b"\x00" * (kzg.BYTES_PER_BLOB - 32))
        commitment = kzg.blob_to_kzg_commitment(blob, setup)
        proof = kzg.compute_blob_kzg_proof(blob, commitment, setup)
        # device raises -> breaker opens -> host path still verifies
        assert kzg.verify_blob_kzg_proof_batch(
            [blob], [commitment], [proof], setup)
        assert br.state == CircuitBreaker.OPEN
    finally:
        kzg.set_backend(None)
