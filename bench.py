"""North-star benchmark: BLS signatures verified per second per chip.

Measures the batched verification kernel (teku_tpu/ops/verify.py) on the
real device at the BASELINE.md batch sizes (1 / 64 / 512 / 4096), end to
end per dispatch: host arrays in, verdict out, device synchronized; plus
a bursty-arrival latency phase (BASELINE.md measurement config 5)
reporting attestation-verify p50/p99 through the batching service.

Prints ONE JSON line:
  {"metric": "bls_verify_sigs_per_sec", "value": <best>, "unit":
   "sigs/sec/chip", "vs_baseline": <value / 50_000>, "p50_ms": ...,
   ...detail...}

The device numbers come from a TPU or not at all: JAX is initialised
once, in this process, on whatever the machine offers; every result
names `platform`, `device_kind` and `device_count`; and where the
platform is not `tpu` the device phases are refused and the run exits
non-zero — nothing measured on a CPU is ever written under `unit:
"sigs/sec/chip"`.  (The virtual-clock control-plane phases need no
device kernel: `BENCH_THROUGHPUT=0` with the kernel phases off still
runs them anywhere.)  No child process is started: one process holds
the chip.  A phase that fails fails the run — its error is recorded in
the JSON AND the exit code is non-zero.  Around that:
- a watchdog thread force-emits the JSON and exits non-zero if any
  armed phase wedges inside the runtime where signal handlers cannot
  run;
- every phase transition appends to BENCH_HEARTBEAT.json and stderr so
  even a SIGKILL leaves evidence of where time went;
- a wall-clock budget (BENCH_BUDGET_S) gates each extra compile.

vs_baseline is against the project target (>= 50k attestation sigs/sec
on one TPU v5e-1, BASELINE.md; the reference's CPU blst does ~1-2k
verifies/sec/core).  The reference measures the same surface with JMH
(reference: eth-benchmark-tests/src/jmh/java/tech/pegasys/teku/
benchmarks/BLSBenchmark.java:37-80 and ethereum/statetransition/src/jmh/
.../AggregatingSignatureVerificationServiceBenchmark.java).
"""

import json
import os
import signal
import sys
import threading
import time
import traceback

import numpy as np

OUT = {
    "metric": "bls_verify_sigs_per_sec",
    "value": 0.0,
    "unit": "sigs/sec/chip",
    "vs_baseline": 0.0,
}

_HEARTBEAT_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "BENCH_HEARTBEAT.json")

_emitted = False


def _emit():
    global _emitted
    if _emitted:
        return
    _emitted = True
    try:
        # even a signal/watchdog exit carries the health verdict
        _final_health()
    except Exception:
        pass
    print(json.dumps(OUT))
    sys.stdout.flush()


def _beat(stage: str, **extra) -> None:
    """Progress evidence that survives ANY exit: a heartbeat file beside
    the repo root plus a stderr JSON line (stdout stays reserved for the
    ONE result line the driver parses): every phase transition is
    observable post-mortem."""
    beat = {"stage": stage, "t": round(time.time(), 1), **extra,
            "out_so_far": {k: OUT[k] for k in
                           ("value", "device", "error")
                           if k in OUT}}
    line = json.dumps(beat)
    try:
        with open(_HEARTBEAT_PATH, "a") as fh:
            fh.write(line + "\n")
    except OSError:
        pass
    print(line, file=sys.stderr)
    sys.stderr.flush()


def _ledger_mark() -> int:
    """Dispatch-ledger high-water mark (record seq) at a phase start."""
    try:
        from teku_tpu.infra import dispatchledger
        return dispatchledger.LEDGER.recorded_total
    except Exception:
        return 0


def _ledger_phase_summary(phase: str, since: int, **extra) -> None:
    """Per-phase dispatch-ledger summary into OUT["ledger"][phase]:
    padding-waste per stage bucket (and per lane bucket), dedup ratio,
    mesh shard imbalance, and the decision/compile histograms — so the
    perf trajectory records WHY each phase performed as it did, not
    just how fast it went (tools/bench_diff.py gates on the waste and
    imbalance ratios).  ``extra`` annotates the summary — e.g.
    ``pinned_min_bucket`` when the phase deliberately pins the
    dispatch bucket for compile budget (waste then reflects the pin,
    not the planner, and the diff gate skips it)."""
    try:
        from teku_tpu.infra import dispatchledger
        summary = dispatchledger.LEDGER.summary(since_seq=since)
        if summary.get("records"):
            summary.update(extra)
            OUT.setdefault("ledger", {})[phase] = summary
    except Exception:
        pass


def _on_term(signum, frame):  # pragma: no cover - signal path
    """An external timeout (driver harness) must still get the JSON
    line: a TPU-side compile can block past any soft deadline."""
    OUT["error"] = OUT.get("error", f"killed by signal {signum} "
                                    "(budget exceeded mid-compile)")
    _emit()
    os._exit(1)


class _Watchdog:
    """A hung runtime call blocks the main thread inside C, where
    Python signal handlers cannot run.  This daemon thread force-emits
    the JSON and exits the process (non-zero) when an armed phase
    overruns its deadline."""

    def __init__(self):
        self._deadline = None
        self._label = ""
        t = threading.Thread(target=self._run, daemon=True)
        t.start()

    def arm(self, seconds: float, label: str) -> None:
        self._label = label
        self._deadline = time.time() + seconds

    def disarm(self) -> None:
        self._deadline = None

    def _run(self):  # pragma: no cover - failure path
        while True:
            time.sleep(1.0)
            d = self._deadline
            if d is not None and time.time() > d:
                OUT["error"] = (f"watchdog: {self._label} exceeded "
                                "deadline (backend hang)")
                _beat("watchdog_fired", label=self._label)
                _emit()
                os._exit(1)


# initialized by main(): importing this module (tests do) must not
# install process-wide signal handlers or spawn the watchdog thread
WD = None


def _arm_process_guards() -> None:
    global WD
    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)
    if WD is None:
        WD = _Watchdog()


_BACKEND_STATES: list = []


def _backend_state(state: str, **extra) -> None:
    """Record a supervisor-style backend state transition (COLD →
    PROBING → READY/DEGRADED) with a timestamp, into BOTH the heartbeat
    stream and the final JSON — so BENCH_*.json shows WHY this run
    served the backend it served (`infra/supervisor.py:BackendState`
    names; the node's supervisor emits the same vocabulary)."""
    _BACKEND_STATES.append({"state": state, "t": round(time.time(), 1),
                            **extra})
    OUT["backend_states"] = _BACKEND_STATES
    try:
        # same ring the node uses: breaker trips / sheds from the
        # latency phase interleave with bring-up in one timeline
        from teku_tpu.infra import flightrecorder
        flightrecorder.record("backend_state", supervisor="bench",
                              state=state, **extra)
    except Exception:
        pass
    _beat("backend_state", state=state, **extra)


def _final_health() -> None:
    """A last health snapshot + the flight-recorder tail into the
    result JSON and heartbeat, so a failed run explains itself without
    log archaeology."""
    status, detail = "up", ""
    if OUT.get("error"):
        status, detail = "down", OUT["error"]
    OUT["health"] = {
        "status": status, "detail": detail,
        "device": OUT.get("device", "unknown"),
        "last_backend_state": (_BACKEND_STATES[-1]["state"]
                               if _BACKEND_STATES else "unknown")}
    try:
        from teku_tpu.infra import flightrecorder
        OUT["flight_recorder"] = flightrecorder.RECORDER.tail(20)
    except Exception:
        pass
    _beat("final_health", health=OUT["health"],
          flight_recorder_events=len(OUT.get("flight_recorder", [])))


class NoTpuError(RuntimeError):
    """The device phases were asked to run on something else than a
    TPU: refused, never measured."""


def _init_device(need_tpu: bool):
    """Initialise JAX once, in this process, on what the machine
    offers, and say what that is.  No probe child (a chip belongs to
    one process), no CPU fallback, no virtual devices: where the
    device phases are on and the platform is not `tpu`, refuse."""
    _backend_state("probing")
    WD.arm(600, "backend init")
    import jax

    # persistent compile cache: repeat bench invocations skip the
    # multi-minute per-bucket XLA compiles (one definition in
    # infra/compilecache, shared with the CLI and chip_smoke.py);
    # hit/miss counters feed the compile vs cache_load accounting below
    from teku_tpu.infra import compilecache
    OUT["compile_cache"] = {"dir": compilecache.configure()}
    devs = jax.devices()
    WD.disarm()
    OUT["device"] = str(devs[0])
    OUT["platform"] = devs[0].platform
    OUT["device_kind"] = devs[0].device_kind
    OUT["device_count"] = len(devs)
    if need_tpu and devs[0].platform != "tpu":
        raise NoTpuError(
            f"the device phases need a TPU, jax found "
            f"{devs[0].platform!r} ({OUT['device']}); nothing is "
            f"written under unit {OUT['unit']!r} from it")
    _backend_state("ready", device=OUT["device"])
    _beat("device_ready", device=OUT["device"],
          platform=OUT["platform"], device_kind=OUT["device_kind"],
          device_count=OUT["device_count"])
    return jax


def _throughput_phase(jax, deadline, batches, detail):
    """Batches are tried IN ORDER and each fresh compile is gated on
    the remaining budget: cold compiles of the staged programs take
    minutes (PERF.md "On the chip"), so one measured number at the
    primary shape beats four JSON-less timeouts.  The
    persistent compile cache makes warm reruns cheap.  `detail` is the
    shared accumulator across calls (main() runs this phase twice:
    primary shape first, the rest only after p50/epoch landed)."""
    from teku_tpu.infra import compilecache
    from teku_tpu.ops import examples
    from teku_tpu.ops import verify as V

    kernel = V.verify_staged     # staged bounded compiles, not one
                                 # monolith (dedup-aware: h2c + miller
                                 # run at unique-message width)
    best = float(OUT.get("value") or 0.0)
    best_batch = OUT.get("best_batch")
    compiled_once = any(
        isinstance(v, dict) and ("compile_s" in v or "cache_load_s" in v)
        for v in detail.values())
    for n in batches:
        remaining = deadline - time.time()
        # a cold compile needs a wide margin; after one shape compiled
        # (cache siblings share most of the work server-side) be braver
        need = 120 if compiled_once else 600
        if remaining < need and detail:
            detail[str(n)] = "skipped: budget"
            continue
        try:
            args = examples.example_batch(n)
            stage_s = {}

            def _on_stage(nm, s, _n=n, _st=stage_s):
                _st[nm] = round(s, 1)
                _beat("stage_done", batch=_n, stage_name=nm,
                      s=round(s, 1))

            # stage-by-stage warm/compile, watchdogged: each of the five
            # staged programs must land within the phase's own margin
            _beat("compile_start", batch=n)
            WD.arm(max(remaining, need) + 120, f"compile batch {n}")
            cache_before = compilecache.stats()
            t0 = time.time()
            ok, lane_ok = kernel(*args, on_stage=_on_stage)
            ok = bool(np.asarray(ok))
            WD.disarm()
            compile_s = time.time() - t0
            compiled_once = True
            # compile_s vs cache_load_s: a post-cache (warm-boot) run
            # must not report disk loads as "compile" time — the two
            # differ by orders of magnitude and drivers compare them
            moved = compilecache.delta(cache_before)
            kind = ("cache_load_s"
                    if compilecache.classify_first_dispatch(moved)
                    == "cache_load" else "compile_s")
            entry = {kind: round(compile_s, 1),
                     "cache_hits": moved["hits"],
                     "cache_misses": moved["misses"],
                     "stage_s": stage_s}
            detail[str(n)] = entry
            if not (ok and np.asarray(lane_ok).all()):
                entry["error"] = "batch did not verify"
                continue
            iters = max(1, min(30, int(200 / max(n / 64, 1))))
            WD.arm(max(deadline - time.time(), 60) + 120,
                   f"measure batch {n}")
            t0 = time.time()
            for _ in range(iters):
                ok, lane_ok = kernel(*args)
            jax.block_until_ready((ok, lane_ok))
            WD.disarm()
            dt = (time.time() - t0) / iters
            rate = n / dt
            entry["sigs_per_sec"] = round(rate, 1)
            entry["dispatch_ms"] = round(dt * 1e3, 2)
            _beat("batch_measured", batch=n,
                  sigs_per_sec=entry["sigs_per_sec"])
            if rate > best:
                best, best_batch = rate, n
            # keep the headline current so even a SIGTERM mid-phase
            # reports the best number measured so far
            OUT["detail"] = detail
            OUT["best_batch"] = best_batch
            OUT["value"] = round(best, 1)
            OUT["vs_baseline"] = round(best / 50_000, 4)
        except Exception as exc:
            detail[str(n)] = {"error": f"{type(exc).__name__}: {exc}"}
    OUT["detail"] = detail
    OUT["best_batch"] = best_batch
    OUT["value"] = round(best, 1)
    OUT["vs_baseline"] = round(best / 50_000, 4)
    bad = [b for b, v in detail.items()
           if isinstance(v, dict) and "error" in v]
    if bad:
        # the other batches were still measured; the run still fails
        raise RuntimeError(
            f"batch(es) {', '.join(bad)}: "
            + "; ".join(detail[b]["error"] for b in bad))


def _latency_phase(jax, deadline):
    """Slot-burst replay through AggregatingSignatureVerificationService:
    Poisson-bursty single-attestation tasks, p50/p99 task latency PLUS
    per-stage attribution (queue_wait / assembly / dispatch / host_prep /
    device_enqueue / device_sync / complete p50/p95/p99) from the tracing
    layer — so a
    future p50 regression in BENCH_*.json names its guilty stage."""
    import asyncio
    import secrets
    from collections import defaultdict

    from teku_tpu.crypto import bls
    from teku_tpu.crypto.bls import keygen
    from teku_tpu.infra import tracing
    from teku_tpu.ops.provider import JaxBls12381
    from teku_tpu.services.signatures import (
        AggregatingSignatureVerificationService)

    trace_on = os.environ.get("BENCH_TRACING", "1") != "0"
    tracing.set_enabled(trace_on)
    OUT["tracing"] = "on" if trace_on else "off"
    stage_samples: dict = defaultdict(list)

    def _sampler(tr):
        # raw per-trace samples beat histogram-bucket percentiles:
        # dedupe repeated stage entries (bisect retries) by summing
        per_stage: dict = defaultdict(float)
        for stage, dur in tr.stages:
            per_stage[stage] += dur
        for stage, dur in per_stage.items():
            stage_samples[stage].append(dur)
        stage_samples["complete"].append(tr.total_s)

    if trace_on:
        tracing.set_sampler(_sampler)

    led0 = _ledger_mark()
    # min_bucket=256 pins EVERY service dispatch to the one 256-lane
    # shape the throughput phase already compiled — no extra kernel
    # compiles in this phase (only the small pubkey-validation program)
    impl = JaxBls12381(max_batch=256, min_bucket=256)
    bls.set_implementation(impl)
    try:
        sks = [keygen(bytes([i + 1]) * 32) for i in range(16)]
        pks = [impl.secret_key_to_public_key(sk) for sk in sks]
        msgs = [b"att-%d" % i for i in range(16)]
        sigs = [impl.sign(sk, m) for sk, m in zip(sks, msgs)]
        # one warm dispatch (256-lane bucket + pk validation compile);
        # same compile vs cache_load split as the throughput phase so
        # a post-cache run doesn't report a misleading "warm_compile_s"
        from teku_tpu.infra import compilecache
        triples = [([pks[i % 16]], msgs[i % 16], sigs[i % 16])
                   for i in range(256)]
        cache_before = compilecache.stats()
        t0 = time.time()
        if not impl.batch_verify(triples):
            raise RuntimeError("warmup batch failed")
        warm_s = round(time.time() - t0, 1)
        moved = compilecache.delta(cache_before)
        if (moved["hits"] or moved["misses"]) and \
                compilecache.classify_first_dispatch(moved) == "cache_load":
            OUT["warm_cache_load_s"] = warm_s
        else:
            OUT["warm_compile_s"] = warm_s

        lat: list = []

        async def run():
            svc = AggregatingSignatureVerificationService(
                num_workers=2, max_batch_size=256)
            await svc.start()
            rng = np.random.default_rng(3)
            # one slot-boundary burst: ~500 attestations arriving in
            # ~200ms (BASELINE config 5 scaled to bench budget)
            n_msgs = 500
            pending = []
            for i in range(n_msgs):
                j = i % 16
                t_submit = time.perf_counter()
                # one root trace per attestation, submit → verdict
                # (the service + provider attribute their stages to it)
                tr = tracing.new_trace("bench_verify")
                with tracing.attach((tr,)):
                    fut = svc.verify([pks[j]], msgs[j], sigs[j])
                pending.append((t_submit, fut, tr))
                await asyncio.sleep(float(rng.exponential(0.0004)))
            for t_submit, fut, tr in pending:
                okv = await fut
                tracing.finish(tr)
                assert okv
                lat.append(time.perf_counter() - t_submit)
            await svc.stop()

        from teku_tpu.infra import timeline
        ring0 = timeline.RING.mark()
        t_tl0 = time.perf_counter()
        asyncio.run(run())
        t_tl1 = time.perf_counter()
        lat_ms = np.asarray(sorted(lat)) * 1e3
        OUT["p50_ms"] = round(float(np.percentile(lat_ms, 50)), 2)
        OUT["p99_ms"] = round(float(np.percentile(lat_ms, 99)), 2)
        OUT["latency_tasks"] = len(lat_ms)
        if stage_samples:
            stages = {}
            for stage, samples in sorted(stage_samples.items()):
                arr = np.asarray(samples) * 1e3
                stages[stage] = {
                    "p50_ms": round(float(np.percentile(arr, 50)), 3),
                    "p95_ms": round(float(np.percentile(arr, 95)), 3),
                    "p99_ms": round(float(np.percentile(arr, 99)), 3),
                    "n": len(samples)}
            OUT["latency_stages"] = stages
            # attribution coverage: the named stages' p50s should
            # account for the end-to-end p50 (driver checks ±20%).
            # device time is enqueue + sync since the attribution
            # split (device_sync excludes host-prep overlap, so the
            # sum no longer double-counts under TEKU_TPU_ASYNC_OVERLAP)
            attributed = sum(
                stages[s]["p50_ms"] for s in
                ("queue_wait", "assembly", "host_prep",
                 "device_enqueue", "device_sync")
                if s in stages)
            OUT["latency_p50_attributed_ms"] = round(attributed, 3)
        # causal-timeline attribution over the burst window: what share
        # of wall the device actually worked while the queue held tasks
        # (overlap_efficiency) and how much host_prep stayed serial
        # outside device-busy (host_prep_serial_share) — None when the
        # ring is off, and tools/bench_diff.py skips its gate then
        from teku_tpu.infra import dispatchledger
        tl_events = timeline.RING.snapshot(since_seq=ring0)
        attr = timeline.attribution(
            tl_events, t_tl0, t_tl1,
            stage_sums={s: sum(v) for s, v in stage_samples.items()},
            compile_s=dispatchledger.LEDGER.summary(
                since_seq=led0).get("compile_s"))
        OUT["attribution"] = attr
        OUT["overlap_efficiency"] = attr.get("overlap_efficiency")
        OUT["host_prep_serial_share"] = attr.get(
            "host_prep_serial_share")
        # the instrumentation measures itself: ring-append cost times
        # the events this phase actually emitted, as a share of the
        # burst wall (the ≤2% budget the timeline PR promises)
        ovh = timeline.measure_overhead()
        OUT["timeline_overhead"] = {
            "per_event_us": ovh["per_event_us"],
            "events": len(tl_events),
            "share": round(len(tl_events) * ovh["per_event_us"] * 1e-6
                           / max(t_tl1 - t_tl0, 1e-9), 6)}
        # capacity evidence: the same derived signals the node's
        # /teku/v1/admin/capacity serves, measured over this phase's
        # live dispatches (per-shape latency model + occupancy)
        from teku_tpu.infra import capacity
        cap = capacity.snapshot()
        OUT["capacity"] = {
            "derived": cap["derived"],
            "occupancy_ratio": cap["device"]["occupancy_ratio"],
            "shapes": {shape: {path: {k: stats[k] for k in
                                      ("ewma_s", "p50_s", "samples")}
                               for path, stats in paths.items()}
                       for shape, paths in cap["shapes"].items()}}
        # min_bucket is PINNED to 256 above (compile budget): the lane
        # waste in this summary measures the pin + the burst's
        # coalescing, not the production planner — flagged so the
        # bench_diff waste gate skips this phase
        _ledger_phase_summary("latency", led0, pinned_min_bucket=256)
    finally:
        tracing.set_sampler(None)
        bls.reset_implementation()


def _mont_phase(jax, deadline):
    """Kernel-level A/B microbench: mont_muls/sec on the vpu
    (elementwise int64 pad-and-sum) vs mxu (int8 digit-split matmul)
    path at the service's primary batch shapes — so BENCH_*.json shows
    the multiplier-level speedup INDEPENDENT of end-to-end pipeline
    noise (the whole verify pipeline is ~11k mont_muls/signature, so
    this ratio bounds the pipeline win the MXU path can deliver)."""
    import secrets as _secrets

    import jax.numpy as jnp
    from jax import lax

    from teku_tpu.ops import limbs as fp
    from teku_tpu.ops import mxu

    batches = [int(b) for b in os.environ.get(
        "BENCH_MONT_BATCHES", "256,4096").split(",")]
    chain = int(os.environ.get("BENCH_MONT_CHAIN", "16"))
    _beat("mont_phase_start", batches=batches, chain=chain)
    out: dict = {"chain": chain, "unit": "mont_muls/sec"}

    def make_chain(mul):
        # a scan-chained multiply measures steady-state kernel cost,
        # not per-dispatch overhead: chain * batch mont_muls per call
        def run(a, b):
            def step(c, _):
                return mul(c, b), None
            c, _ = lax.scan(step, a, None, length=chain)
            return c
        return jax.jit(run)

    kernels = {"vpu": make_chain(fp.mont_mul_vpu),
               "mxu": make_chain(fp.mont_mul_mxu)}
    for n in batches:
        if time.time() > deadline - 60:
            out[str(n)] = "skipped: budget"
            continue
        a = np.stack([fp.int_to_mont(int.from_bytes(
            _secrets.token_bytes(47), "big")) for _ in range(n)])
        b = np.roll(a, 1, axis=0)
        entry: dict = {}
        for path, fn in kernels.items():
            try:
                WD.arm(max(deadline - time.time(), 60) + 120,
                       f"mont_mul {path} batch {n}")
                jax.block_until_ready(fn(a, b))      # warm/compile
                iters = max(3, min(50, int(2e6 / (n * chain))))
                t0 = time.time()
                for _ in range(iters):
                    r = fn(a, b)
                jax.block_until_ready(r)
                WD.disarm()
                dt = (time.time() - t0) / iters
                entry[path] = {
                    "mont_muls_per_sec": round(n * chain / dt, 1),
                    "dispatch_ms": round(dt * 1e3, 3)}
            except Exception as exc:
                entry[path] = {"error": f"{type(exc).__name__}: {exc}"}
        if all("mont_muls_per_sec" in entry.get(p, {})
               for p in ("vpu", "mxu")):
            entry["mxu_speedup"] = round(
                entry["mxu"]["mont_muls_per_sec"]
                / entry["vpu"]["mont_muls_per_sec"], 3)
        out[str(n)] = entry
        _beat("mont_batch_done", batch=n,
              **{p: entry[p].get("mont_muls_per_sec")
                 for p in ("vpu", "mxu") if p in entry})
    out["active_path"] = mxu.resolve()
    OUT["mont_mul"] = out
    _beat("mont_phase_done")


def _dedup_phase(jax, deadline):
    """Duplication sweep: fixed batch, dup factor 1x/8x/64x — the
    committee-gossip shape ("Performance of EdDSA and BLS Signatures in
    Committee-Based Consensus" measures exactly this batch mix).  The
    dedup-aware pipeline runs h2c AND the Miller loops at unique-message
    width, so sigs/sec must rise MONOTONICALLY with the duplication
    factor; a final fully-warm pass (same messages again) proves a warm
    H(m) cache makes ZERO h2c dispatches.  Per-factor rates + dedup/
    cache evidence land in OUT["h2c_dedup"]."""
    from teku_tpu.crypto.bls import keygen
    from teku_tpu.ops import provider as pv
    from teku_tpu.ops.provider import JaxBls12381

    batch = int(os.environ.get("BENCH_DEDUP_BATCH", "256"))
    factors = [int(f) for f in os.environ.get(
        "BENCH_DEDUP_FACTORS", "1,8,64").split(",")]
    iters = int(os.environ.get("BENCH_DEDUP_ITERS", "3"))
    led0 = _ledger_mark()
    impl = JaxBls12381(max_batch=batch, min_bucket=batch)
    out: dict = {"batch": batch, "factors": {}}
    OUT["h2c_dedup"] = out
    _beat("dedup_phase_start", batch=batch, factors=factors)
    sks = [keygen(bytes([17 + i]) * 32) for i in range(16)]
    pks = [impl.secret_key_to_public_key(sk) for sk in sks]
    seq = [0]

    def fresh_triples(d):
        """One batch at duplication factor d: batch/d FRESH unique
        messages (cold H(m) path), each signed by d committee members
        cycling over 16 keys."""
        uniq = max(batch // d, 1)
        msgs = [b"dedup-%d-%d" % (seq[0], u) for u in range(uniq)]
        seq[0] += 1
        sig_cache: dict = {}
        triples = []
        for lane in range(batch):
            m = msgs[lane % uniq]
            k = lane % 16
            if (k, m) not in sig_cache:
                sig_cache[(k, m)] = impl.sign(sks[k], m)
            triples.append(([pks[k]], m, sig_cache[(k, m)]))
        return triples

    rate_1x = None
    last_triples = None
    for d in factors:
        remaining = deadline - time.time()
        if remaining < 120 and out["factors"]:
            out["factors"][str(d)] = "skipped: budget"
            continue
        try:
            WD.arm(max(remaining, 60) + 300, f"dedup factor {d}")
            t0 = time.time()
            if not impl.batch_verify(fresh_triples(d)):  # warm/compile
                raise RuntimeError("dedup warmup batch failed")
            warm_s = round(time.time() - t0, 1)
            best = 0.0
            h2c_d0 = impl.h2c_dispatch_count
            for _ in range(iters):
                triples = fresh_triples(d)   # fresh: cold H(m) cache
                t0 = time.time()
                okv = impl.batch_verify(triples)
                dt = time.time() - t0
                if not okv:
                    raise RuntimeError("dedup batch did not verify")
                best = max(best, batch / dt)
            WD.disarm()
            last_triples = triples
            entry = {"sigs_per_sec": round(best, 1),
                     "compile_s": warm_s,
                     "unique_per_batch": max(batch // d, 1),
                     "h2c_dispatches": impl.h2c_dispatch_count - h2c_d0}
            if d == 1:
                rate_1x = best
            elif rate_1x:
                entry["speedup_vs_1x"] = round(best / rate_1x, 3)
            out["factors"][str(d)] = entry
            _beat("dedup_factor_done", factor=d,
                  sigs_per_sec=entry["sigs_per_sec"],
                  speedup=entry.get("speedup_vs_1x"))
        except Exception as exc:
            out["factors"][str(d)] = {
                "error": f"{type(exc).__name__}: {exc}"}
    # fully-warm pass: the SAME messages again — steady-state gossip
    # (every AttestationData already mapped this slot)
    if last_triples is not None and time.time() < deadline:
        try:
            WD.arm(max(deadline - time.time(), 60) + 120, "dedup warm")
            h2c_d0 = impl.h2c_dispatch_count
            t0 = time.time()
            okv = impl.batch_verify(last_triples)
            dt = time.time() - t0
            WD.disarm()
            out["warm"] = {
                "sigs_per_sec": round(batch / dt, 1) if okv else 0.0,
                "h2c_dispatches": impl.h2c_dispatch_count - h2c_d0}
            if rate_1x and okv:
                out["warm"]["speedup_vs_1x"] = round(
                    batch / dt / rate_1x, 3)
        except Exception as exc:
            out["warm"] = {"error": f"{type(exc).__name__}: {exc}"}
    out["dedup_ratio"] = round(pv._dedup_ratio(), 4)
    out["cache"] = impl._h2c_cache.stats()
    _ledger_phase_summary("dedup", led0)
    _beat("dedup_phase_done", **{k: out.get(k) for k in
                                 ("dedup_ratio", "warm")})


def _mesh_phase(jax, deadline):
    """Device-count sweep of the GROUP-ALIGNED sharded verify path
    (ROADMAP item 1): the committee-shaped dup-8 batch dispatched
    through JaxBls12381(mesh=make_mesh(n)) at n = 1/2/4/8 devices,
    per-count sigs/sec + scaling efficiency into OUT["mesh"].

    On virtual CPU devices (xla_force_host_platform_device_count over
    ONE host) the shards execute SERIALIZED, so measured wall rates
    cannot rise with n; the phase additionally reports the per-device
    projection — wall_n/n per-dispatch latency, i.e. what concurrent
    shards would deliver, including the replicated finish and gather
    overhead the mesh really adds (PERF.md "Multi-chip mesh" derives
    why this equals real-mesh scaling up to ICI latency).  The
    monotonicity/efficiency gates in tools/bench_diff.py key on the
    ``series`` field: "measured" on real parallel hardware,
    "projected_serialized_virtual" here."""
    from teku_tpu import parallel
    from teku_tpu.crypto.bls import keygen
    from teku_tpu.ops.provider import JaxBls12381

    batch = int(os.environ.get("BENCH_MESH_BATCH", "256"))
    dup = int(os.environ.get("BENCH_MESH_DUP", "8"))
    iters = int(os.environ.get("BENCH_MESH_ITERS", "2"))
    counts = [int(c) for c in os.environ.get(
        "BENCH_MESH_COUNTS", "1,2,4,8").split(",")]
    avail = len(jax.devices())
    virtual = jax.devices()[0].platform == "cpu"
    led0 = _ledger_mark()
    out: dict = {"batch": batch, "dup": dup,
                 "available_devices": avail,
                 "series": ("projected_serialized_virtual" if virtual
                            else "measured"),
                 "devices": {}}
    OUT["mesh"] = out
    _beat("mesh_phase_start", batch=batch, dup=dup, counts=counts,
          available=avail, virtual=virtual)
    pure_sks = [keygen(bytes([41 + i]) * 32) for i in range(16)]
    seq = [0]

    def fresh_triples(impl, pks):
        """One committee-shaped batch: batch/dup FRESH unique messages
        (cold H(m) path), each signed by dup committee members."""
        uniq = max(batch // dup, 1)
        msgs = [b"mesh-%d-%d" % (seq[0], u) for u in range(uniq)]
        seq[0] += 1
        sig_cache: dict = {}
        triples = []
        for lane in range(batch):
            m = msgs[lane % uniq]
            k = lane % 16
            if (k, m) not in sig_cache:
                sig_cache[(k, m)] = impl.sign(pure_sks[k], m)
            triples.append(([pks[k]], m, sig_cache[(k, m)]))
        return triples

    wall: dict = {}
    for c in counts:
        if c > avail:
            out["devices"][str(c)] = "skipped: devices"
            continue
        remaining = deadline - time.time()
        if remaining < 120 and wall:
            out["devices"][str(c)] = "skipped: budget"
            continue
        try:
            WD.arm(max(remaining, 60) + 600, f"mesh {c} devices")
            mesh = None if c == 1 else parallel.make_mesh(c)
            impl = JaxBls12381(max_batch=batch, min_bucket=batch,
                               mesh=mesh)
            pks = [impl.secret_key_to_public_key(sk)
                   for sk in pure_sks]
            t0 = time.time()
            if not impl.batch_verify(fresh_triples(impl, pks)):
                raise RuntimeError("mesh warmup batch failed")
            compile_s = round(time.time() - t0, 1)
            best_wall = None
            for _ in range(iters):
                triples = fresh_triples(impl, pks)
                t0 = time.time()
                okv = impl.batch_verify(triples)
                dt = time.time() - t0
                if not okv:
                    raise RuntimeError("mesh batch did not verify")
                best_wall = dt if best_wall is None \
                    else min(best_wall, dt)
            WD.disarm()
            wall[c] = best_wall
            entry = {"sigs_per_sec": round(batch / best_wall, 2),
                     "wall_s": round(best_wall, 3),
                     "compile_s": compile_s,
                     "mesh_dispatches":
                         impl.dispatch_count if mesh else 0}
            # the scaling series: measured on real parallel devices,
            # the wall/n per-device projection on serialized virtual
            entry["mesh_sigs_per_sec"] = round(
                batch * c / best_wall if virtual
                else batch / best_wall, 2)
            out["devices"][str(c)] = entry
            _beat("mesh_count_done", devices=c, **{
                k: entry[k] for k in ("sigs_per_sec",
                                      "mesh_sigs_per_sec",
                                      "compile_s")})
        except Exception as exc:
            out["devices"][str(c)] = {
                "error": f"{type(exc).__name__}: {exc}"}
    rates = [(c, out["devices"][str(c)]["mesh_sigs_per_sec"])
             for c in counts
             if isinstance(out["devices"].get(str(c)), dict)
             and "mesh_sigs_per_sec" in out["devices"][str(c)]]
    if len(rates) >= 2:
        out["monotonic"] = all(b[1] >= a[1] for a, b in
                               zip(rates, rates[1:]))
        base_c, base_r = rates[0]
        max_c, max_r = rates[-1]
        out["max_devices"] = max_c
        # efficiency vs linear scaling from the smallest count
        out["scaling_efficiency_at_max"] = round(
            (max_r / base_r) / (max_c / base_c), 4)
    _ledger_phase_summary("mesh", led0)
    _beat("mesh_phase_done",
          monotonic=out.get("monotonic"),
          efficiency=out.get("scaling_efficiency_at_max"))


def _chaos_phase(jax, deadline):
    """Mesh self-healing recovery-time objective (RTO) on the REAL
    8-virtual-device mesh: serve committee batches through a
    breaker-guarded mesh provider with the self-healer wired
    (`parallel/selfheal.py` + `loader.make_mesh_healer`), wedge one
    shard mid-serving via the keyed ``bls.mesh_shard`` fault, and
    measure the full cycle — eject exactly the sick device, reshape
    to the surviving pow-2 subset, AOT-warm, atomic swap, keep
    serving on-device — then clear the fault and measure the readmit
    grow-back.  Every verdict along the way is checked against the
    expected truth (valid batches True, a tampered batch False):
    ``wrong_verdicts`` must be ZERO in every run.

    On virtual (serialized CPU) devices wall recovery time is
    dominated by XLA compiles of the smaller sharded shape and by the
    serialized shards, so ``series="virtual"`` and tools/bench_diff.py
    gates only the correctness properties; real parallel hardware
    reports ``series="measured"`` and must also beat
    ``mesh_recovery_s_max``.  The fault kind defaults to a fast Raise
    on virtual (wall-cheap) and a true Hang (deadline overrun) on
    hardware; BENCH_CHAOS_FAULT={raise,hang} overrides."""
    from teku_tpu import parallel
    from teku_tpu.crypto.bls import keygen
    from teku_tpu.crypto.bls.loader import (GuardedBls12381,
                                            make_mesh_healer)
    import contextlib
    from teku_tpu.infra import faults
    from teku_tpu.infra.env import env_override
    from teku_tpu.infra.supervisor import CircuitBreaker
    from teku_tpu.ops.provider import JaxBls12381

    from teku_tpu.infra.pow2 import floor_pow2
    n_dev = floor_pow2(min(8, len(jax.devices())))
    if n_dev < 4:
        OUT["chaos"] = "skipped: needs >= 4 devices"
        return
    batch = int(os.environ.get("BENCH_CHAOS_BATCH", "64"))
    dup = 8
    virtual = jax.devices()[0].platform == "cpu"
    fault_kind = os.environ.get(
        "BENCH_CHAOS_FAULT", "raise" if virtual else "hang")
    deadline_s = float(os.environ.get("BENCH_CHAOS_DEADLINE_S",
                                      "5" if virtual else "20"))
    led0 = _ledger_mark()
    out: dict = {"devices": n_dev, "batch": batch, "dup": dup,
                 "series": "virtual" if virtual else "measured",
                 "fault": fault_kind}
    OUT["chaos"] = out
    _beat("chaos_phase_start", devices=n_dev, batch=batch,
          fault=fault_kind)
    # reshape warm = the serving shape set: the first post-swap
    # dispatch must hit the jit cache, so recovery time includes the
    # real AOT cost and nothing compiles on the serving path.  The
    # operator's value restores in the finally (env_override owns the
    # None-means-unset dance; the try body is too far from a `with`).
    warm_override = contextlib.ExitStack()
    warm_override.enter_context(
        env_override("TEKU_TPU_MESH_WARM_BATCH", str(batch)))
    healer = None
    try:
        impl = JaxBls12381(max_batch=batch, min_bucket=batch,
                           mesh=parallel.make_mesh(n_dev))
        sick = impl.mesh_info["devices"][n_dev // 2 - 1]
        breaker = CircuitBreaker(
            failure_threshold=3, deadline_s=deadline_s,
            cooldown_s=5.0, name="bench_chaos_device")
        guarded = GuardedBls12381(impl, breaker)
        healer = make_mesh_healer(
            guarded, breaker, max_batch=batch, min_bucket=batch,
            trip_threshold=1, probe_deadline_s=max(deadline_s, 2.0),
            reprobe_s=1.0)
        sks = [keygen(bytes([71 + i]) * 32) for i in range(16)]
        pks = [impl.secret_key_to_public_key(sk) for sk in sks]
        seq = [0]

        def fresh():
            uniq = max(batch // dup, 1)
            seq[0] += 1
            msgs = [b"chaos-%d-%d" % (seq[0], u) for u in range(uniq)]
            sig_cache: dict = {}
            triples = []
            for lane in range(batch):
                m = msgs[lane % uniq]
                k = lane % 16
                if (k, m) not in sig_cache:
                    sig_cache[(k, m)] = impl.sign(sks[k], m)
                triples.append(([pks[k]], m, sig_cache[(k, m)]))
            return triples

        wrong = 0

        def check_serving(tag):
            """One valid + one tampered batch; verdicts must match
            the oracle truth exactly."""
            nonlocal wrong
            good = fresh()
            if guarded.batch_verify(good) is not True:
                wrong += 1
            bad = list(good)
            bad[3] = (bad[3][0], b"chaos-tampered", bad[3][2])
            if guarded.batch_verify(bad) is not False:
                wrong += 1
            _beat("chaos_check", stage_name=tag, wrong=wrong)

        WD.arm(max(deadline - time.time(), 60) + 900, "chaos warmup")
        t0 = time.time()
        if not impl.batch_verify(fresh()):
            raise RuntimeError("chaos warmup batch failed")
        out["warm_s"] = round(time.time() - t0, 1)
        check_serving("before_fault")
        # ---- the wedge: one shard of the live mesh goes sick -------
        # times=None on BOTH kinds: the fault must keep firing for the
        # sick device's ISOLATION PROBE after the collective dispatch
        # consumed a firing — a budgeted fault would make the probe
        # pass and attribution impossible (the probe deadline bounds
        # each hang; the collective stops matching once ejected)
        if fault_kind == "hang":
            faults.inject("bls.mesh_shard", faults.Hang(
                deadline_s + 10, key=sick))
        else:
            faults.inject("bls.mesh_shard", faults.Raise(
                RuntimeError("bench chaos: shard wedged"), key=sick))
        t_fault = time.time()
        # this dispatch fails/overruns; the ORACLE serves it (correct
        # verdict, zero failed in-flight) and the healer starts
        if guarded.batch_verify(fresh()) is not True:
            wrong += 1
        # wait for the eject+reshape swap (includes the m{n/2} kernel
        # compile on a cold cache); bounded by the REMAINING budget so
        # a starved run records chaos_error and moves on instead of
        # eating the phases behind it
        swap_bound = max(120.0, min(900.0, deadline - time.time()))
        while guarded.device is impl \
                and time.time() - t_fault < swap_bound:
            time.sleep(0.2)
        if guarded.device is impl:
            raise RuntimeError("healer never swapped the provider")
        out["recovery_s"] = healer.last_recovery_s
        out["recovery_wall_s"] = round(time.time() - t_fault, 1)
        out["ejected_device"] = sick
        out["live_after_eject"] = len(healer.live_devices)
        faults.clear("bls.mesh_shard")
        check_serving("on_shrunken_mesh")
        out["serving_after_eject"] = guarded.serving
        _beat("chaos_recovered", recovery_s=out["recovery_s"],
              live=out["live_after_eject"])
        # ---- readmit: the device recovered; the mesh grows back ----
        # the grow completes at the INSTALL, not the ledger readmit —
        # wait for the live width, bounded by the remaining budget
        t_clear = time.time()
        grow_bound = max(120.0, min(600.0, deadline - time.time()))
        while (healer.ledger.ejected()
               or len(healer.live_devices) < n_dev) \
                and time.time() - t_clear < grow_bound:
            time.sleep(0.2)
        regrown = (not healer.ledger.ejected()
                   and len(healer.live_devices) == n_dev)
        out["regrow_s"] = (round(time.time() - t_clear, 1)
                           if regrown else None)
        out["live_after_readmit"] = len(healer.live_devices)
        out["recovered"] = regrown
        check_serving("after_readmit")
        out["wrong_verdicts"] = wrong
        out["reshapes"] = dict(healer.reshapes)
        out["mesh"] = healer.snapshot()
        _ledger_phase_summary("chaos", led0)
        _beat("chaos_phase_done", recovery_s=out.get("recovery_s"),
              regrow_s=out.get("regrow_s"), wrong=wrong,
              recovered=out.get("recovered"))
    finally:
        # a raising phase must not leak a live reprobe daemon (it
        # would keep probing/reshaping under the LATER bench phases)
        # or leave the watchdog armed
        if healer is not None:
            healer.close()
        WD.disarm()
        faults.clear("bls.mesh_shard")
        warm_override.close()


def _epoch_transition_phase(deadline):
    """Altair epoch transition on a synthetic large-validator state —
    the reference's EpochTransitionBenchmark surface (eth-benchmark-
    tests/.../EpochTransitionBenchmark.java runs the same measurement
    against generated 300k+ validator states).  Pure host-side state
    math: independent of the accelerator backend."""
    from teku_tpu.spec import perf as P
    from teku_tpu.spec.altair import epoch as AE

    n = int(os.environ.get("BENCH_EPOCH_VALIDATORS", "300000"))
    cfg = P.perf_config()
    _beat("epoch_phase_start", validators=n)
    state = P.make_synthetic_altair_state(cfg, n)
    best = None
    runs = 0
    for _ in range(3):
        if time.time() > deadline:
            break
        t0 = time.time()
        AE.process_epoch(cfg, state)
        dt = (time.time() - t0) * 1e3
        best = dt if best is None else min(best, dt)
        runs += 1
    if best is not None:
        OUT["epoch_transition_ms"] = round(best, 1)
        OUT["epoch_transition_validators"] = n
        OUT["epoch_transition_runs"] = runs
        _beat("epoch_phase_done", ms=round(best, 1))
    # the latest fork's epoch transition (pending queues, compounding
    # credentials) on the same registry size
    if time.time() < deadline:
        from teku_tpu.spec.electra import epoch as EE
        cfg_e = P.perf_config_electra()
        state_e = P.make_synthetic_electra_state(cfg_e, n)
        best_e = None
        for _ in range(2):
            if time.time() > deadline:
                break
            t0 = time.time()
            EE.process_epoch(cfg_e, state_e)
            best_e = ((time.time() - t0) * 1e3 if best_e is None
                      else min(best_e, (time.time() - t0) * 1e3))
        if best_e is not None:
            OUT["epoch_transition_electra_ms"] = round(best_e, 1)
            _beat("epoch_electra_done", ms=round(best_e, 1))


def _kzg_phase(deadline):
    """Blob-verification throughput (deneb DA check): batch of 6 blobs
    (mainnet MAX_BLOBS_PER_BLOCK) verified per dispatch, REAL ceremony
    setup (the vendored public KZG ceremony artifact), device path when
    available (reference surface: CKZG4844.java:104-122
    verifyBlobKzgProofBatch)."""
    import secrets as _secrets

    from teku_tpu.crypto import kzg
    from teku_tpu.ops.kzg import JaxKzg

    kzg.set_backend(JaxKzg())
    setup = kzg.get_setup()   # the real 4096-point ceremony file
    n_blobs = int(os.environ.get("BENCH_KZG_BLOBS", "6"))
    _beat("kzg_phase_start", blobs=n_blobs)
    rng = np.random.default_rng(11)
    blobs = []
    for _ in range(n_blobs):
        fes = [int.from_bytes(_secrets.token_bytes(31), "big")
               for _ in range(kzg.FIELD_ELEMENTS_PER_BLOB)]
        blobs.append(b"".join(v.to_bytes(32, "big") for v in fes))
    t0 = time.time()
    commitments = []
    proofs = []
    for b in blobs:
        # every commitment/proof is one 4096-lane device MSM — gate
        # each on the remaining budget so this phase can't overshoot
        if time.time() > deadline - 60 and commitments:
            break
        commitments.append(kzg.blob_to_kzg_commitment(b, setup))
        proofs.append(kzg.compute_blob_kzg_proof(b, commitments[-1],
                                                 setup))
    blobs = blobs[:len(proofs)]
    commitments = commitments[:len(proofs)]
    if not blobs:
        return
    n_blobs = len(blobs)
    # commit + proof are one MSM each: the recorded figure is the
    # prover-side cost per blob (both MSMs)
    OUT["kzg_commit_proof_s_per_blob"] = round(
        (time.time() - t0) / n_blobs, 2)
    _beat("kzg_proofs_ready", blobs=n_blobs)
    # warm (compiles the verification kernel when the device backend is
    # installed), then measure
    t_warm = time.time()
    assert kzg.verify_blob_kzg_proof_batch(blobs, commitments, proofs,
                                           setup)
    warm_s = time.time() - t_warm
    iters = 0
    t0 = time.time()
    while iters < 5 and time.time() < deadline:
        assert kzg.verify_blob_kzg_proof_batch(blobs, commitments,
                                               proofs, setup)
        iters += 1
    if iters:
        dt = (time.time() - t0) / iters
    else:
        dt = warm_s          # budget-starved: the warm dispatch (incl.
        OUT["kzg_warm_only"] = True   # compile) is still evidence
    OUT["kzg_blobs_per_sec"] = round(n_blobs / dt, 2)
    OUT["kzg_backend"] = kzg.backend_name()
    _beat("kzg_phase_done", blobs_per_sec=OUT["kzg_blobs_per_sec"])


def _overload_phase(deadline):
    """Closed-loop overload control: the REAL service + admission
    controller (priority classes, adaptive pow-2 batching, brownout
    shed-by-class) driven at several offered-load factors on a virtual
    clock (`teku_tpu/services/overload_sim.py`).  The device model is
    nominal (BENCH_OVERLOAD_CAPACITY sigs/sec) because the property
    under test is the CONTROL PLANE — does the node hold the 100 ms
    attestation-verify p50 at 10x sustained offered load by shedding
    OPTIMISTIC/GOSSIP and never BLOCK_IMPORT — which is independent of
    this host's absolute BLS speed (virtual time also makes the phase
    budget-proof: each factor runs in a few wall seconds).  The
    measured per-factor curve + the 10x acceptance evidence land in
    OUT["overload"]; tools/bench_diff.py gates on them."""
    from teku_tpu.services import overload_sim

    cap = float(os.environ.get("BENCH_OVERLOAD_CAPACITY", "2000"))
    duration = float(os.environ.get("BENCH_OVERLOAD_DURATION_S", "4"))
    factors = [float(f) for f in os.environ.get(
        "BENCH_OVERLOAD_FACTORS", "1,2,5,10").split(",")]
    _beat("overload_phase_start", capacity=cap, factors=factors)
    out: dict = {"capacity_sigs_per_sec": cap, "duration_s": duration,
                 "slo_p50_ms": 100.0, "curve": {}}
    OUT["overload"] = out
    for x in factors:
        if time.time() > deadline - 30 and out["curve"]:
            out["curve"][str(x)] = "skipped: budget"
            continue
        try:
            WD.arm(max(deadline - time.time(), 60) + 120,
                   f"overload factor {x}")
            res = overload_sim.run(
                offered_x=x, duration_s=duration,
                capacity_sigs_per_sec=cap)
            WD.disarm()
            out["curve"][str(x)] = {
                "p50_ms": res["p50_ms"], "p95_ms": res["p95_ms"],
                "completed_share": res["completed_share"],
                "shed_total": res["shed_total"],
                "brownout_enters": res["brownout"]["enters"]}
            if x == max(factors):
                # the acceptance point: full shed breakdown + brownout
                # edge evidence for the 10x run
                res.pop("final_inputs", None)
                out["at_max"] = res
            _beat("overload_factor_done", factor=x,
                  p50_ms=res["p50_ms"],
                  sheds=res["sheds"])
        except Exception as exc:
            out["curve"][str(x)] = {
                "error": f"{type(exc).__name__}: {exc}"}
    _beat("overload_phase_done",
          p50_at_max=(out.get("at_max") or {}).get("p50_ms"))


def _mainnet_phase(deadline):
    """Mainnet-shape traffic replay (`teku_tpu/loadgen`): seeded
    gossip-replay scenarios — committee-duplicated subnets, aggregation
    waves, sync committee, blob waves, epoch-boundary storms, and
    adversarial shapes (invalid-sig flood exercising bisect,
    equivocation replay exercising coalescing, dup-collapse starving
    the H(m) cache) — against the REAL signature service + admission
    controller on a virtual clock.  Per-scenario sigs/sec, per-class
    p50/p99, shed counts, dedup ratio and brownout transitions land in
    OUT["mainnet"]; tools/bench_diff.py gates BLOCK_IMPORT sheds == 0
    under every scenario, the critical-class p50 bound, and the
    dedup-ratio floor on committee-shaped mixes."""
    from teku_tpu.loadgen import driver, scenarios

    seed = int(os.environ.get("BENCH_MAINNET_SEED", "1"))
    slots = int(os.environ.get("BENCH_MAINNET_SLOTS", "2"))
    names = [s for s in os.environ.get(
        "BENCH_MAINNET_SCENARIOS",
        ",".join(scenarios.DEFAULT_SWEEP)).split(",") if s]
    _beat("mainnet_phase_start", scenarios=names, seed=seed,
          slots=slots)
    out: dict = {"seed": seed, "slots": slots, "scenarios": {}}
    OUT["mainnet"] = out
    for name in names:
        if time.time() > deadline - 30 and out["scenarios"]:
            out["scenarios"][name] = "skipped: budget"
            continue
        try:
            WD.arm(max(deadline - time.time(), 60) + 120,
                   f"mainnet scenario {name}")
            rep = driver.run_scenario(name, seed=seed, slots=slots)
            WD.disarm()
            out["scenarios"][name] = rep
            _beat("mainnet_scenario_done", scenario=name,
                  sigs_per_sec=rep["sigs_per_sec"],
                  p50_ms=rep["p50_ms"], sheds=rep["shed_total"],
                  dedup_ratio=rep["dedup_ratio"],
                  bisect=rep["bisect_dispatches"],
                  brownout_enters=rep["brownout"]["enters"])
        except Exception as exc:
            out["scenarios"][name] = {
                "error": f"{type(exc).__name__}: {exc}"}
    out["summary"] = driver.summarize(out["scenarios"])
    _beat("mainnet_phase_done", **out["summary"])


_TRAJECTORY_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "BENCH_TRAJECTORY.json")


def trajectory_entry(out: dict, run_id: str) -> dict:
    """Flatten one bench result into the compact trajectory record
    tools/bench_diff.py and future perf PRs compare against."""
    entry = {"run_id": run_id, "t_wall": round(time.time(), 1),
             "sigs_per_sec": out.get("value"),
             "best_batch": out.get("best_batch"),
             "device": out.get("device"),
             "mont_path": out.get("mont_path"),
             "p50_ms": out.get("p50_ms"), "p99_ms": out.get("p99_ms")}
    stages = out.get("latency_stages") or {}
    entry["stage_p50_ms"] = {s: v.get("p50_ms")
                             for s, v in stages.items()
                             if isinstance(v, dict)}
    compile_s, cache_load_s = 0.0, 0.0
    for v in (out.get("detail") or {}).values():
        if isinstance(v, dict):
            compile_s += v.get("compile_s", 0.0)
            cache_load_s += v.get("cache_load_s", 0.0)
    entry["compile_s"] = round(compile_s, 1)
    entry["cache_load_s"] = round(cache_load_s, 1)
    dedup = out.get("h2c_dedup") or {}
    f8 = (dedup.get("factors") or {}).get("8")
    entry["dedup_speedup_8x"] = (f8.get("speedup_vs_1x")
                                 if isinstance(f8, dict) else None)
    warm = dedup.get("warm")
    entry["warm_h2c_dispatches"] = (warm.get("h2c_dispatches")
                                    if isinstance(warm, dict) else None)
    cap = out.get("capacity") or {}
    entry["occupancy_ratio"] = cap.get("occupancy_ratio")
    entry["overlap_efficiency"] = out.get("overlap_efficiency")
    entry["host_prep_serial_share"] = out.get("host_prep_serial_share")
    at_max = (out.get("overload") or {}).get("at_max") or {}
    entry["overload_p50_ms"] = at_max.get("p50_ms")
    entry["overload_block_import_sheds"] = (
        at_max.get("sheds") or {}).get("block_import")
    mainnet = (out.get("mainnet") or {}).get("summary") or {}
    entry["mainnet_block_import_sheds"] = mainnet.get(
        "block_import_sheds_worst")
    entry["mainnet_critical_p50_ms"] = mainnet.get(
        "critical_p50_ms_worst")
    entry["mainnet_dedup_ratio_min"] = mainnet.get(
        "committee_dedup_ratio_min")
    mesh_block = out.get("mesh") or {}
    entry["mesh_monotonic"] = mesh_block.get("monotonic")
    entry["mesh_series"] = mesh_block.get("series")
    entry["mesh_scaling_efficiency"] = mesh_block.get(
        "scaling_efficiency_at_max")
    chaos = out.get("chaos") or {}
    if isinstance(chaos, dict):
        entry["chaos_recovery_s"] = chaos.get("recovery_s")
        entry["chaos_wrong_verdicts"] = chaos.get("wrong_verdicts")
        entry["chaos_series"] = chaos.get("series")
        entry["chaos_recovered"] = chaos.get("recovered")
    lint = out.get("lint") or {}
    if isinstance(lint, dict) and "error" not in lint:
        entry["lint_unsuppressed"] = lint.get("unsuppressed")
        entry["lint_suppressed"] = lint.get("suppressed")
    return entry


def append_trajectory(out: dict, path: str = _TRAJECTORY_PATH,
                      run_id: str = None, max_entries: int = 50) -> str:
    """Append this run to the rolling BENCH_TRAJECTORY.json.

    REFUSES to overwrite an existing entry for the same run id — a
    re-run under the same id must not silently rewrite the historical
    record a regression gate already cited (re-measure under a fresh
    id instead).  Returns "appended" | "duplicate_run_id" | an error
    string; never raises (bench's result line must always come out)."""
    run_id = run_id or os.environ.get("BENCH_RUN_ID") \
        or f"run_{int(time.time())}"
    try:
        beat = _beat if path == _TRAJECTORY_PATH else (
            lambda *a, **k: None)        # tests use scratch paths
        try:
            with open(path) as fh:
                doc = json.load(fh)
        except FileNotFoundError:
            doc = {"entries": []}        # first run: fresh history
        except (OSError, ValueError) as exc:
            # an EXISTING but unreadable/corrupt trajectory must abort
            # the append — restarting history here would overwrite the
            # record a regression gate already cited
            beat("trajectory_error", run_id=run_id,
                 why=f"unreadable trajectory: {exc}")
            return f"error: unreadable trajectory: {exc}"
        entries = doc.get("entries") or []
        if any(e.get("run_id") == run_id for e in entries):
            beat("trajectory_skipped", run_id=run_id,
                 why="duplicate run id (entries are append-only)")
            return "duplicate_run_id"
        entries.append(trajectory_entry(out, run_id))
        doc["entries"] = entries[-max_entries:]
        tmp = path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(doc, fh, indent=1)
        os.replace(tmp, path)
        beat("trajectory_appended", run_id=run_id,
             entries=len(doc["entries"]))
        return "appended"
    except Exception as exc:  # noqa: BLE001 - evidence, not the result
        return f"error: {type(exc).__name__}: {exc}"


# the switches of the phases that dispatch kernels on the device (mesh
# and chaos ride on BENCH_THROUGHPUT): with any of them on, the run
# needs a TPU
_DEVICE_PHASES = ("BENCH_THROUGHPUT", "BENCH_P50", "BENCH_MONT",
                  "BENCH_DEDUP", "BENCH_KZG")


def _on(var: str) -> bool:
    return os.environ.get(var, "1") != "0"


def main() -> int:
    t_start = time.time()
    budget_s = float(os.environ.get("BENCH_BUDGET_S", "1500"))
    deadline = t_start + budget_s
    _arm_process_guards()
    try:
        os.unlink(_HEARTBEAT_PATH)   # fresh evidence trail per run
    except OSError:
        pass
    _beat("bench_start", budget_s=budget_s)
    _backend_state("cold")
    # 256 first: it doubles as the latency phase's service bucket.
    # 512 is BASELINE.md measurement config 2's missing size;
    # 1/64/512/4096 are the advertised batch points.
    batches = [int(b) for b in
               os.environ.get("BENCH_BATCHES",
                              "256,512,64,4096,1").split(",")]
    # BENCH_THROUGHPUT=0 skips the kernel-compile phases entirely: the
    # virtual-clock phases (overload, mainnet) need no device kernel,
    # so a control-plane-focused run should not pay minutes of XLA
    run_throughput = _on("BENCH_THROUGHPUT")
    need_tpu = any(_on(var) for var in _DEVICE_PHASES)
    try:
        jax = _init_device(need_tpu)
    except Exception as exc:
        OUT["error"] = f"device init: {type(exc).__name__}: {exc}"
        _emit()
        return 1
    failed = []

    def phase(name, fn, *args, margin_s=300):
        """One fenced phase: its failure is recorded under
        `<name>_error` AND fails the run (the remaining phases still
        get their chance to report)."""
        try:
            WD.arm(max(deadline - time.time(), 60) + margin_s,
                   f"{name} phase")
            fn(*args)
        except Exception as exc:
            failed.append(name)
            OUT[f"{name}_error"] = f"{type(exc).__name__}: {exc}"
            OUT.setdefault("trace", traceback.format_exc(limit=3))
        finally:
            WD.disarm()

    # Phase order is budget-priority order: primary shape -> p50
    # latency (reuses the warm 256 bucket) -> epoch transition
    # (host-side, cheap) -> the remaining batch shapes.
    detail: dict = {}
    if run_throughput:
        phase("throughput", _throughput_phase, jax, deadline,
              batches[:1], detail)
    if _on("BENCH_P50") and time.time() < deadline:
        _beat("latency_phase_start")
        phase("p50", _latency_phase, jax, deadline)
    if _on("BENCH_MONT") and time.time() < deadline:
        phase("mont", _mont_phase, jax, deadline)
    if _on("BENCH_DEDUP") and time.time() < deadline:
        phase("dedup", _dedup_phase, jax, deadline)
    if _on("BENCH_MESH") and run_throughput and time.time() < deadline:
        phase("mesh", _mesh_phase, jax, deadline, margin_s=600)
    # virtual-clock phases: a few wall seconds each, so they run even
    # on budget-starved rounds
    if _on("BENCH_OVERLOAD"):
        phase("overload", _overload_phase, deadline)
    if _on("BENCH_MAINNET"):
        phase("mainnet", _mainnet_phase, deadline)
    if _on("BENCH_EPOCH"):
        phase("epoch", _epoch_transition_phase, deadline)
    # chaos AFTER the wall-cheap virtual phases: its compiles (the
    # reshaped kernel + warm shapes) must never starve them, and its
    # own floor keeps a budget-tight run recording "skipped" instead
    # of a watchdog kill
    chaos_floor = float(os.environ.get("BENCH_CHAOS_MIN_BUDGET_S",
                                       "600"))
    if _on("BENCH_CHAOS") and run_throughput:
        if time.time() < deadline - chaos_floor:
            phase("chaos", _chaos_phase, jax, deadline, margin_s=900)
        else:
            OUT["chaos"] = "skipped: budget"
    if run_throughput:
        phase("throughput2", _throughput_phase, jax, deadline,
              batches[1:], detail)
    if _on("BENCH_KZG") and time.time() < deadline:
        phase("kzg", _kzg_phase, deadline)
    # hit/miss evidence for the whole run: a warm (second) run shows
    # hits>0 and per-shape cache_load_s instead of compile_s
    from teku_tpu.infra import compilecache
    OUT.setdefault("compile_cache", {}).update(compilecache.stats())
    from teku_tpu.ops import mxu
    OUT["mont_path"] = mxu.resolve()
    try:
        # static-analysis state of the tree this run measured: finding
        # counts per checker (all zero on a clean tree) so the
        # trajectory shows the tree STAYING clean PR over PR.  Pure
        # AST, ~a second; never the reason a bench run fails.
        from teku_tpu.analysis import run_lint
        lint_report = run_lint()
        OUT["lint"] = {
            "files": lint_report.files_scanned,
            "unsuppressed": len(lint_report.unsuppressed),
            "suppressed": (len(lint_report.findings)
                           - len(lint_report.unsuppressed)),
            "unused_suppressions": len(
                lint_report.unused_suppressions),
            "by_checker": lint_report.counts(),
        }
    except Exception as exc:  # noqa: BLE001 - evidence, not the result
        OUT["lint"] = {"error": f"{type(exc).__name__}: {exc}"}
    OUT["total_s"] = round(time.time() - t_start, 1)
    if failed:
        OUT["error"] = "failed phases: " + ", ".join(failed)
        OUT["trajectory"] = "skipped: failed run"
    else:
        # rolling trajectory: the regression gate (tools/bench_diff.py)
        # compares the latest entries across PRs — measurements only
        OUT["trajectory"] = append_trajectory(OUT)
    _beat("bench_done", total_s=OUT["total_s"], failed=failed)
    _emit()
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
