"""Mesh self-healing: shard-level fault isolation, device ejection,
reshape-with-RTO instead of the whole-mesh oracle cliff.

Three layers:

- host-side unit tests of the keyed fault site, the per-device health
  ledger and the MeshHealer state machine over a FAKE backend world
  (eject/reshape/readmit, veto, unattributed failures, shrink-to-zero);
- the doctor's ``mesh_degraded`` / ``mesh_flap`` findings and the
  capacity model's topology retirement;
- the CHAOS ACCEPTANCE test on the real 8-virtual-device mesh: a
  timed ``bls.mesh_shard`` fault kills one chip mid-serving; the REAL
  loader wiring (GuardedBls12381 + make_mesh_healer) must eject
  exactly that device, reshape to 4, keep serving on-device with zero
  failed in-flight verifications and verdicts bit-identical to the
  oracle, then readmit and grow back to 8 — with the whole cycle
  visible in flight events, ``bls_mesh_reshape_total``, the
  supervisor/readiness mesh snapshot, the dispatch ledger's epoch
  stamps, and a doctor finding citing the killing dispatch.

Compile budget: the acceptance test reuses the SAME committee grid
shape (16 lanes, min_bucket 8) as tests/test_mesh_grouped.py for the
8-shard kernel, and pays one small 4-shard serving shape plus the
tiny reshape-warm shape (TEKU_TPU_MESH_WARM_BATCH=1).
"""

import threading
import time

import pytest

import jax

from teku_tpu import parallel
from teku_tpu.crypto.bls import keygen
from teku_tpu.crypto.bls.pure_impl import PureBls12381
from teku_tpu.infra import capacity, dispatchledger, doctor, faults
from teku_tpu.infra import flightrecorder
from teku_tpu.infra.metrics import GLOBAL_REGISTRY, MetricsRegistry
from teku_tpu.infra.supervisor import BackendSupervisor, CircuitBreaker
from teku_tpu.parallel import selfheal
from teku_tpu.parallel.selfheal import (DeviceHealthLedger,
                                        InstallVetoError, MeshHealer)

pytest_plugins: list = []


@pytest.fixture(autouse=True)
def _restore_global_topology_filter():
    """The chaos tests drive the REAL self-heal path, which retires
    latency series on the process-global capacity model and installs
    its live-topology filter.  Left in place, the filter silently
    drops every later non-mesh test's capacity samples in the same
    process."""
    yield
    capacity.TELEMETRY.latency.clear_topology_filter()


def _wait(predicate, timeout_s=10.0, what="condition"):
    t0 = time.monotonic()
    while not predicate():
        if time.monotonic() - t0 > timeout_s:
            raise AssertionError(f"timed out waiting for {what}")
        time.sleep(0.02)


# --------------------------------------------------------------------------
# keyed fault site
# --------------------------------------------------------------------------

def test_keyed_faults_scope_to_named_members():
    f = faults.inject("t.keyed", faults.Raise(RuntimeError("sick"),
                                              key="dev3"))
    try:
        faults.check("t.keyed")                  # keyless call: no fire
        faults.check("t.keyed", keys=("dev1",))  # wrong member: no fire
        assert f.fired == 0
        with pytest.raises(RuntimeError):
            faults.check("t.keyed", keys=("dev1", "dev3"))
        assert f.fired == 1
    finally:
        faults.clear("t.keyed")
    # keyless faults keep firing everywhere (backward compatibility)
    f2 = faults.inject("t.keyed", faults.Raise(RuntimeError("x")))
    try:
        with pytest.raises(RuntimeError):
            faults.check("t.keyed")
        with pytest.raises(RuntimeError):
            faults.check("t.keyed", keys=("anything",))
        assert f2.fired == 2
    finally:
        faults.clear("t.keyed")


# --------------------------------------------------------------------------
# per-device health ledger
# --------------------------------------------------------------------------

def test_device_health_ledger_trip_and_readmit():
    led = DeviceHealthLedger(["d0", "d1", "d2"], trip_threshold=2)
    assert led.record_failure(1, "err") is False   # 1 < threshold
    assert led.record_failure(1, "err") is True    # trips
    led.eject(1)
    assert led.live() == [0, 2]
    assert led.ejected() == [1]
    # success resets the consecutive count for live devices
    led.record_failure(0, "blip")
    led.record_success(0)
    assert led.record_failure(0, "blip") is False
    # readmit restores and clears the streak
    assert led.readmit(1) is True
    assert led.live() == [0, 1, 2]
    assert led.record_failure(1, "err") is False
    snap = led.snapshot()
    assert snap["trip_threshold"] == 2
    assert snap["devices"][1]["ejects_total"] == 1


# --------------------------------------------------------------------------
# MeshHealer over a fake world
# --------------------------------------------------------------------------

def _fake_world(n=8, **healer_kw):
    installs: list = []
    recorder = flightrecorder.FlightRecorder(
        capacity=256, registry=MetricsRegistry())

    def probe(i):
        faults.check(selfheal.FAULT_SITE, keys=(f"fd{i}",))

    kw = dict(trip_threshold=1, probe_deadline_s=1.0, reprobe_s=0.05)
    kw.update(healer_kw)
    healer = MeshHealer(
        [f"fd{i}" for i in range(n)], probe=probe,
        make_backend=lambda live: ("backend", live) if live else None,
        install=lambda be, live, epoch: installs.append(
            (be, live, epoch)),
        recorder=recorder, **kw)
    return healer, installs, recorder


def test_healer_ejects_reshapes_and_grows_back():
    healer, installs, recorder = _fake_world()
    faults.inject(selfheal.FAULT_SITE,
                  faults.Raise(RuntimeError("sick"), key="fd3"))
    try:
        healer.on_dispatch_failure(error="dispatch died",
                                   timeout=True, trace_id="tr-kill")
        _wait(lambda: len(installs) >= 1, what="shrink install")
        be, live, epoch = installs[-1]
        # largest surviving pow-2 subset in original device order
        assert live == (0, 1, 2, 4)
        assert epoch == 1
        assert healer.last_recovery_s is not None
        events = {e["kind"]: e for e in recorder.snapshot()}
        assert events["mesh_eject"]["device"] == "fd3"
        # the triggering dispatch's trace id rides the events
        assert events["mesh_eject"]["trace_id"] == "tr-kill"
        assert events["mesh_reshape"]["direction"] == "shrink"
        assert events["mesh_reshape"]["to_devices"] == 4
        assert events["mesh_reshape"]["configured"] == 8
    finally:
        faults.clear(selfheal.FAULT_SITE)
    # fault cleared: the background reprobe readmits and grows back
    _wait(lambda: len(installs) >= 2, what="grow install")
    be, live, epoch = installs[-1]
    assert live == tuple(range(8))
    assert healer.reshapes == {"shrink": 1, "grow": 1}
    kinds = [e["kind"] for e in recorder.snapshot()]
    assert "mesh_readmit" in kinds
    healer.close()


def test_healer_unattributed_failure_does_not_eject():
    healer, installs, recorder = _fake_world()
    # no fault armed: every isolation probe passes — the collective
    # failure stays the backend breaker's problem
    healer.on_dispatch_failure(error="host-side blip")
    time.sleep(0.3)
    assert installs == []
    assert healer.live_devices == tuple(range(8))
    kinds = [e["kind"] for e in recorder.snapshot()]
    assert "mesh_heal_unattributed" in kinds
    assert "mesh_eject" not in kinds
    healer.close()


def test_healer_warm_veto_blocks_install():
    def veto(_backend, _live):
        raise InstallVetoError("wrong verdict on known input")

    healer, installs, recorder = _fake_world(warm=veto)
    faults.inject(selfheal.FAULT_SITE,
                  faults.Raise(RuntimeError("sick"), key="fd0"))
    try:
        healer.on_dispatch_failure(error="x")
        _wait(lambda: any(e["kind"] == "mesh_reshape_vetoed"
                          for e in recorder.snapshot()),
              what="veto event")
        assert installs == []          # never installed
        assert healer.live_devices == tuple(range(8))
    finally:
        faults.clear(selfheal.FAULT_SITE)
        healer.close()


def test_healer_failed_grow_rolls_readmit_back():
    """A readmitted device whose grow reshape VETOES must go back to
    EJECTED — no install happened, so exiting the reprobe loop there
    would leave the mesh silently stuck below width while the ledger
    claims recovery.  The rollback is not a new flap (eject count
    unchanged), and once the veto clears the retry grows back."""
    state = {"grow_veto": True}

    def warm(_backend, live):
        if len(live) == 8 and state["grow_veto"]:
            raise InstallVetoError("grow verdicts untrusted")

    healer, installs, recorder = _fake_world(warm=warm)
    faults.inject(selfheal.FAULT_SITE,
                  faults.Raise(RuntimeError("sick"), key="fd3"))
    try:
        healer.on_dispatch_failure(error="x")
        _wait(lambda: len(installs) >= 1, what="shrink install")
    finally:
        faults.clear(selfheal.FAULT_SITE)
    # reprobe readmits -> grow warm VETOES -> readmit rolled back
    _wait(lambda: any(e["kind"] == "mesh_reshape_vetoed"
                      for e in recorder.snapshot()), what="grow veto")
    _wait(lambda: healer.ledger.ejected() == [3], timeout_s=5.0,
          what="readmit rollback")
    assert len(installs) == 1              # the grow never installed
    assert healer.ledger.eject_count(3) == 1   # rollback != new flap
    # veto clears: the NEXT reprobe retries and the mesh recovers
    state["grow_veto"] = False
    _wait(lambda: len(installs) >= 2
          and installs[-1][1] == tuple(range(8)), what="grow retry")
    healer.close()


def test_healer_reconciles_failed_shrink_install():
    """A shrink whose INSTALL raised must be retried by the reprobe
    loop's reconcile pass: the heal path alone would strand the
    wedged full-width mesh (later sweeps find the sick device already
    ejected and report unattributed, and nothing else retries)."""
    calls = {"n": 0}
    installs: list = []
    recorder = flightrecorder.FlightRecorder(
        capacity=256, registry=MetricsRegistry())

    def probe(i):
        faults.check(selfheal.FAULT_SITE, keys=(f"fd{i}",))

    def install(be, live, epoch):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient install failure")
        installs.append((be, live, epoch))

    healer = MeshHealer(
        [f"fd{i}" for i in range(8)], probe=probe,
        make_backend=lambda live: ("backend", live) if live else None,
        install=install, trip_threshold=1, probe_deadline_s=1.0,
        reprobe_s=0.05, recorder=recorder)
    faults.inject(selfheal.FAULT_SITE,
                  faults.Raise(RuntimeError("sick"), key="fd3"))
    try:
        healer.on_dispatch_failure(error="x")
        _wait(lambda: any(i[1] == (0, 1, 2, 4) for i in installs),
              what="reconciled shrink install")
        assert healer.live_devices == (0, 1, 2, 4)
    finally:
        faults.clear(selfheal.FAULT_SITE)
        healer.close()


def test_healer_shrinks_through_one_to_zero():
    """4 -> 2 -> 1 (single-device) -> 0 (oracle last resort): the
    capacity steps down pow-2 at a time and install(None) marks the
    end of the device road."""
    healer, installs, recorder = _fake_world(n=2)
    faults.inject(selfheal.FAULT_SITE,
                  faults.Raise(RuntimeError("s0"), key="fd0"))
    try:
        healer.on_dispatch_failure(error="x")
        _wait(lambda: len(installs) >= 1, what="shrink to 1")
        assert installs[-1][0] == ("backend", (1,))
        assert installs[-1][1] == (1,)
        faults.inject(selfheal.FAULT_SITE,
                      faults.Raise(RuntimeError("s1"), key="fd1"))
        healer.on_dispatch_failure(error="y")
        _wait(lambda: len(installs) >= 2, what="shrink to 0")
        assert installs[-1][0] is None
        assert installs[-1][1] == ()
    finally:
        faults.clear(selfheal.FAULT_SITE)
        healer.close()


def test_healer_probe_deadline_catches_hangs():
    healer, installs, recorder = _fake_world(
        probe_deadline_s=0.3)
    faults.inject(selfheal.FAULT_SITE,
                  faults.Hang(5.0, key="fd2"))
    try:
        healer.on_dispatch_failure(error="wedge", timeout=True)
        _wait(lambda: len(installs) >= 1, timeout_s=5.0,
              what="hang-attributed shrink")
        assert 2 not in installs[-1][1]
        ev = [e for e in recorder.snapshot()
              if e["kind"] == "mesh_eject"][0]
        assert "deadline" in ev["probe_error"]
    finally:
        faults.clear(selfheal.FAULT_SITE)
        healer.close()


# --------------------------------------------------------------------------
# doctor findings + capacity topology retirement
# --------------------------------------------------------------------------

def test_doctor_mesh_degraded_and_flap_findings():
    events = [
        {"seq": 1, "kind": "mesh_eject", "device": "d3",
         "trace_id": "tr-kill"},
        {"seq": 2, "kind": "mesh_reshape", "direction": "shrink",
         "from_devices": 8, "to_devices": 4, "configured": 8,
         "epoch": 1, "recovery_s": 2.5, "trace_id": "tr-kill"},
        {"seq": 3, "kind": "mesh_readmit", "device": "d3"},
        {"seq": 4, "kind": "mesh_reshape", "direction": "grow",
         "from_devices": 4, "to_devices": 8, "configured": 8,
         "epoch": 2},
        {"seq": 5, "kind": "mesh_eject", "device": "d3",
         "trace_id": "tr-kill2"},
        {"seq": 6, "kind": "mesh_reshape", "direction": "shrink",
         "from_devices": 8, "to_devices": 4, "configured": 8,
         "epoch": 3, "recovery_s": 2.1, "trace_id": "tr-kill2"},
    ]
    records = [{"seq": 9, "trace_ids": ["tr-kill2"],
                "shape": "16x1@m8", "mesh": {"devices": 8}}]
    diag = doctor.diagnose(records, flight_events=events)
    by_kind = {f["kind"]: f for f in diag["findings"]}
    deg = by_kind["mesh_degraded"]
    assert deg["metrics"]["live_devices"] == 4
    assert deg["metrics"]["configured_devices"] == 8
    # the finding cites the ejection event AND the killing dispatch
    cited_kinds = {e.get("kind") for e in deg["evidence"]
                   if e["type"] == "flight_event"}
    assert "mesh_eject" in cited_kinds
    assert any(e["type"] == "dispatch" and e["seq"] == 9
               for e in deg["evidence"])
    flap = by_kind["mesh_flap"]
    assert flap["metrics"]["by_device"] == {"d3": 2}
    assert not diag["healthy"]
    # text rendering never crashes on the new finding kinds
    assert "mesh_degraded" in doctor.render_text(diag)


def test_doctor_mesh_degraded_survives_flight_ring_eviction():
    """A long-degraded mesh must stay diagnosable after its
    eject/reshape events rolled off the bounded flight ring: the
    supervisor's mesh snapshot (readiness ``backend.mesh.self_heal``)
    is the authoritative CURRENT state — same bug class PR 11 fixed
    for brownout with the admission snapshot."""
    mesh = {"devices": ["d0", "d1", "d2", "d4"], "n_devices": 4,
            "axis": "dp",
            "self_heal": {"configured": 8, "live": 4, "epoch": 3,
                          "ejected": ["d3"]}}
    diag = doctor.diagnose([], flight_events=[], mesh=mesh)
    deg = [f for f in diag["findings"] if f["kind"] == "mesh_degraded"]
    assert deg, "snapshot-only degradation missed"
    assert deg[0]["metrics"]["live_devices"] == 4
    assert deg[0]["metrics"]["configured_devices"] == 8
    # and a full-width snapshot is healthy
    mesh["self_heal"] = {"configured": 8, "live": 8, "epoch": 4,
                         "ejected": []}
    diag = doctor.diagnose([], flight_events=[], mesh=mesh)
    assert not any(f["kind"] == "mesh_degraded"
                   for f in diag["findings"])


def test_doctor_full_width_mesh_is_not_degraded():
    events = [
        {"seq": 1, "kind": "mesh_reshape", "direction": "grow",
         "from_devices": 4, "to_devices": 8, "configured": 8,
         "epoch": 2},
    ]
    diag = doctor.diagnose([], flight_events=events)
    assert "mesh_degraded" not in {f["kind"] for f in diag["findings"]}
    assert "mesh_flap" not in {f["kind"] for f in diag["findings"]}


def test_capacity_retires_dead_topology_series():
    model = capacity.ShapeLatencyModel(registry=MetricsRegistry())
    model.observe("32x1@m8", "vpu", 0.004)
    model.observe("16x1@m8", "vpu", 0.003)
    model.observe("32x1", "vpu", 0.010)
    # mesh shrank to 4: the old @m8 series and the single-device
    # series must stop informing the admission planner
    model.observe("32x1@m4", "vpu", 0.008)
    dropped = model.retire_mesh_shapes(4)
    assert dropped == 3
    assert set(model.snapshot()) == {"32x1@m4"}
    assert model.latency_for_lanes(32) == pytest.approx(0.008)
    # a LATE observe from a dispatch that completed on the old plan
    # (the hot-swap lets old-pair dispatches finish after the swap)
    # must NOT resurrect the retired series
    model.observe("32x1@m8", "vpu", 0.004)
    model.observe("32x1", "vpu", 0.010)
    assert set(model.snapshot()) == {"32x1@m4"}
    assert model.latency_for_lanes(32) == pytest.approx(0.008)
    # shrink to single-device: every mesh family goes, and the
    # single-device family records again
    assert model.retire_mesh_shapes(0) == 1
    model.observe("32x1", "vpu", 0.010)
    model.observe("32x1@m4", "vpu", 0.008)     # late m4 straggler
    assert set(model.snapshot()) == {"32x1"}
    # retired shapes freed their slot in the bounded shape set
    assert model.latency_for_lanes(16) is None


# --------------------------------------------------------------------------
# chaos acceptance: the real mesh, the real loader wiring
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def chaos_world(request):
    """One 8-virtual-device mesh provider under the REAL guarded +
    healer wiring, with keys and a committee-grid batch maker shared
    by the acceptance test (one 8-shard kernel shape, matching
    tests/test_mesh_grouped.py's grid)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices (see conftest XLA_FLAGS)")
    import os

    from teku_tpu.crypto.bls.loader import (GuardedBls12381,
                                            make_mesh_healer)
    from teku_tpu.ops.provider import JaxBls12381

    prev_wb = os.environ.get("TEKU_TPU_MESH_WARM_BATCH")
    os.environ["TEKU_TPU_MESH_WARM_BATCH"] = "1"
    request.addfinalizer(lambda: (
        os.environ.pop("TEKU_TPU_MESH_WARM_BATCH", None)
        if prev_wb is None else
        os.environ.__setitem__("TEKU_TPU_MESH_WARM_BATCH", prev_wb)))

    impl = JaxBls12381(mesh=parallel.make_mesh(8), min_bucket=8)
    # deadline far above a cold XLA compile of the reshaped kernel: a
    # first-ever run pays it inside a guarded dispatch (the persistent
    # .jax_cache makes every later run hit disk), and a compile must
    # read as slow, never as a wedge
    breaker = CircuitBreaker(failure_threshold=3, deadline_s=900.0,
                             cooldown_s=60.0, name="selfheal_t",
                             registry=MetricsRegistry())
    guarded = GuardedBls12381(impl, breaker)
    # a REAL (unstarted) supervisor: its snapshot() IS the readiness
    # endpoint's "backend" body, so asserting on it proves the
    # /teku/v1/admin/readiness surface tracks the live mesh
    sup = BackendSupervisor(probe=lambda: None, install=lambda b: None,
                            name="selfheal_sup",
                            registry=MetricsRegistry())
    sup.mesh = dict(impl.mesh_info)
    healer = make_mesh_healer(
        guarded, breaker, max_batch=64, min_bucket=8, supervisor=sup,
        trip_threshold=1, probe_deadline_s=10.0, reprobe_s=0.2)
    assert healer is not None
    pure = PureBls12381()
    sks = [keygen(bytes([91 + i]) * 32) for i in range(8)]
    pks = [pure.secret_key_to_public_key(sk) for sk in sks]
    request.addfinalizer(healer.close)
    return {"impl": impl, "guarded": guarded, "breaker": breaker,
            "healer": healer, "sup": sup, "pure": pure, "sks": sks,
            "pks": pks}


_seq = [0]
_U_MAP = [0, 0, 0, 0, 1, 1, 1, 1, 2, 2, 3, 3, 4, 5, 6, 7]


def _grid_batch(world):
    """Committee-shaped 16-lane / 8-unique grid (the
    test_mesh_grouped shape) with fresh messages per call."""
    pure, sks, pks = world["pure"], world["sks"], world["pks"]
    _seq[0] += 1
    msgs = [b"heal-%d-%d" % (_seq[0], u) for u in range(8)]
    sig_cache: dict = {}
    triples = []
    for lane in range(16):
        u = _U_MAP[lane]
        k = lane % 8
        if (k, u) not in sig_cache:
            sig_cache[(k, u)] = pure.sign(sks[k], msgs[u])
        triples.append(([pks[k]], msgs[u], sig_cache[(k, u)]))
    return triples


def _tamper_sig(world, batch, lane=2):
    """Flip one lane's signature WITHOUT changing the message set (the
    batch keeps its compiled shape)."""
    bad = list(batch)
    pure, sks = world["pure"], world["sks"]
    bad[lane] = (batch[lane][0], batch[lane][1],
                 pure.sign(sks[0], b"wrong-message"))
    return bad


def test_chaos_eject_reshape_readmit_cycle(chaos_world):
    """THE acceptance cycle: 8 -> wedge -> eject device 3 -> 4-device
    mesh keeps serving on-device, verdicts bit-identical -> readmit
    -> 8, everything observable."""
    from teku_tpu.infra import tracing

    world = chaos_world
    impl, guarded = world["impl"], world["guarded"]
    healer, sup, breaker = (world["healer"], world["sup"],
                            world["breaker"])
    mesh_gauge = GLOBAL_REGISTRY.gauge("bls_mesh_devices")
    reshape_fam = GLOBAL_REGISTRY.labeled_counter(
        "bls_mesh_reshape_total")
    flight0 = len(flightrecorder.RECORDER.snapshot())
    led0 = dispatchledger.LEDGER.recorded_total

    # ---- healthy serving at 8 devices --------------------------------
    batch = _grid_batch(world)
    assert guarded.batch_verify(batch) is True
    assert guarded.batch_verify(_tamper_sig(world, batch)) is False
    assert breaker.state == CircuitBreaker.CLOSED
    assert mesh_gauge.value == 8.0
    assert sup.snapshot()["mesh"]["n_devices"] == 8

    # ---- the wedge: device 3 goes sick -------------------------------
    sick = impl.mesh_info["devices"][3]
    shrink_before = reshape_fam.labels(direction="shrink",
                                       devices="4").value
    faults.inject(selfheal.FAULT_SITE,
                  faults.Raise(RuntimeError("chaos: shard wedged"),
                               key=sick))
    # the fault stays ARMED through the degraded-phase assertions: it
    # is keyed to the ejected device, so the shrunken collective never
    # passes its key again (serving is clean) while the background
    # reprobe keeps failing against it (the mesh HOLDS at 4 instead of
    # racing the assertions with an instant readmit)
    try:
        tr = tracing.new_trace("chaos_kill")
        with tracing.attach((tr,)):
            # the wedged dispatch: the oracle serves THIS call — the
            # in-flight verification still gets the correct verdict
            assert guarded.batch_verify(_grid_batch(world)) is True
        tracing.finish(tr)
        # the healer attributes, ejects, reshapes, AOT-warms, swaps
        _wait(lambda: guarded.device is not impl, timeout_s=600.0,
              what="reshape swap")
        _assert_degraded_phase(world, impl, sick, tr, reshape_fam,
                               shrink_before, mesh_gauge, led0,
                               flight0)
    finally:
        faults.clear(selfheal.FAULT_SITE)

    # ---- recovery: the device comes back, the mesh grows -------------
    _wait(lambda: not healer.ledger.ejected(), timeout_s=600.0,
          what="readmit")
    _wait(lambda: len(healer.live_devices) == 8, timeout_s=600.0,
          what="grow reshape")
    assert mesh_gauge.value == 8.0
    assert sup.snapshot()["mesh"]["n_devices"] == 8
    assert reshape_fam.labels(direction="grow", devices="8").value >= 1
    events = flightrecorder.RECORDER.snapshot()
    assert any(e["kind"] == "mesh_readmit" and e["device"] == sick
               for e in events)
    # and the regrown mesh serves (lazily recompiles its 8-shard
    # kernel: a fresh provider instance, same cached XLA program)
    batch = _grid_batch(world)
    assert guarded.batch_verify(batch) is True
    assert guarded.device.mesh_info["n_devices"] == 8


def _assert_degraded_phase(world, impl, sick, tr, reshape_fam,
                           shrink_before, mesh_gauge, led0, flight0):
    """Everything that must be true while the mesh is held at 4."""
    guarded, healer = world["guarded"], world["healer"]
    sup, breaker = world["sup"], world["breaker"]
    assert len(healer.live_devices) == 4
    assert healer.ledger.device_names[3] == sick
    assert healer.ledger.ejected() == [3]
    new_impl = guarded.device
    assert new_impl.mesh_info["n_devices"] == 4
    assert sick not in new_impl.mesh_info["devices"]
    assert new_impl.mesh_epoch >= 1
    batch = _grid_batch(world)
    assert guarded.batch_verify(batch) is True
    assert guarded.batch_verify(_tamper_sig(world, batch)) is False
    assert breaker.state == CircuitBreaker.CLOSED
    assert guarded.serving == "device"
    # readiness surfaces follow the LIVE mesh
    assert mesh_gauge.value == 4.0
    sup_mesh = sup.snapshot()["mesh"]
    assert sup_mesh["n_devices"] == 4
    assert sup_mesh["self_heal"]["ejected"] == [sick]
    assert reshape_fam.labels(direction="shrink",
                              devices="4").value == shrink_before + 1
    assert healer.last_recovery_s is not None
    assert GLOBAL_REGISTRY.gauge(
        "bls_mesh_recovery_seconds").value > 0
    # the dispatch ledger stamped the live device set + epoch
    mesh_recs = [r for r in dispatchledger.LEDGER.snapshot()
                 if r.get("seq", 0) > led0
                 and (r.get("mesh") or {}).get("devices") == 4]
    assert mesh_recs, "no @m4 ledger records"
    assert mesh_recs[-1]["mesh"]["epoch"] >= 1
    assert sick not in mesh_recs[-1]["mesh"]["live"]

    # ---- flight events + doctor finding ------------------------------
    events = flightrecorder.RECORDER.snapshot()[flight0:]
    by_kind = {}
    for e in events:
        by_kind.setdefault(e["kind"], []).append(e)
    assert by_kind["mesh_eject"][0]["device"] == sick
    # the eject names the dispatch that killed the chip
    assert by_kind["mesh_eject"][0]["trace_id"] == tr.trace_id
    assert by_kind["mesh_reshape"][0]["to_devices"] == 4
    diag = doctor.diagnose(
        dispatchledger.LEDGER.snapshot(), flight_events=events)
    degraded = [f for f in diag["findings"]
                if f["kind"] == "mesh_degraded"]
    assert degraded and degraded[0]["metrics"]["live_devices"] == 4
    assert any(e.get("trace_id") == tr.trace_id
               for e in degraded[0]["evidence"])
