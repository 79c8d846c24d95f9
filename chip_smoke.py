"""The served BLS verify path, end to end, on one TPU chip.

Run through the chip tool from the root of a checkout:

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --mesh4    # four chips: the --mesh path only

It drives what a `cli node` user gets: oracle first, the backend
supervisor PROBING -> WARMING -> READY, the hot-swap to the
breaker-guarded device provider, then an
AggregatingSignatureVerificationService at Teku's batcher defaults
answering seeded attestation traffic — and it holds the run to "the
device did the work": every counted dispatch served by the device,
none by the oracle, the breaker never open, no AOT-store error.  A
second, fresh process then boots from what the first left on disk with
zero kernel-grade compiles.

One process at a time holds the chip, so this file is two programs:
the PARENT (no argument) imports nothing that imports JAX and runs each
boot as a child; the CHILD (`--child <phase>`) is the process that
touches the device.  The last stdout line is the result the driver
reads; it says `"ok": true` only when a TPU ran every phase.
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
RESULT_TAG = "SMOKE_RESULT="

# ---- the smoke's serving configuration; every cut is printed ----------
MAX_BATCH = 256
# CUT 1: min_bucket 256 (cli node: 16) folds every lane bucket into the
# one 256-lane shape, as the bench's latency phase already runs
MIN_BUCKET = 256
# CUT 2: the unique-message bucket floor (TEKU_TPU_H2C_MIN_BUCKET,
# default 8) is raised to the lane width, so every dispatch shares ONE
# set of h2c / miller / finish programs: unique buckets {8,16,32,256}
# -> {256}.  The committee batches then run their Miller loops over 256
# rows of which 32 are live.
H2C_MIN_BUCKET = 256
SERVICE_BATCH = 250          # Teku's batcher default (queue 15,000)
COMMITTEE = 8                # signers per message in batches (a)/(e)
VALIDATORS = 64              # distinct signers of the single-key batches
CUTS = [
    f"min_bucket={MIN_BUCKET} (cli node default 16): one lane shape",
    f"TEKU_TPU_H2C_MIN_BUCKET={H2C_MIN_BUCKET} (default 8): one unique "
    "bucket, so one h2c, miller and finish program instead of four "
    "each (a fresh 32-message committee batch would also need an h2c "
    "program no warm profile covers)",
    f"batches are {SERVICE_BATCH} tasks (the batcher's size), not 256: "
    "the 6-task straggler dispatch would compile a shape of its own",
    "(c) 488-key aggregates and (d) the 512-key sync-committee verify "
    "are not served: the smoke's supervisor is given no key bucket "
    "(`cli node` gives its network's, and then warms kmax 512), so "
    "their first dispatch would compile under the 30 s dispatch "
    "deadline — outside what a cold run can afford",
]
RUN_BUDGET_S = 1150          # both boots; the whole run must end in 1200
MESH4_BUDGET_S = 1750        # --mesh4 compiles one-chip AND mesh programs
READY_TIMEOUT_S = 900
PLATFORM = "tpu"             # what jax must find; nothing else passes


def log(msg: str) -> None:
    print(f"[smoke +{time.monotonic() - T0:7.1f}s] {msg}", flush=True)


T0 = time.monotonic()


# =======================================================================
# PARENT: stdlib only — it must never hold the chip
# =======================================================================

def run_child(phase: str, seed: int, timeout_s: float) -> dict:
    """One child process holding the chip; relays its lines, returns
    its result record.  The child's whole process group dies with it,
    at `timeout_s` at the latest."""
    env = dict(os.environ)
    env["TEKU_TPU_H2C_MIN_BUCKET"] = str(H2C_MIN_BUCKET)
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child", phase,
         "--seed", str(seed)],
        stdout=subprocess.PIPE, text=True, env=env, cwd=HERE,
        start_new_session=True)

    def kill():
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except OSError:
            pass

    result = None
    timer = threading.Timer(timeout_s, kill)
    timer.start()
    try:
        for line in proc.stdout:      # ends when the child's group dies
            if line.startswith(RESULT_TAG):
                result = json.loads(line[len(RESULT_TAG):])
            else:
                print(line, end="", flush=True)
        rc = proc.wait()
    finally:
        overran = not timer.is_alive()
        timer.cancel()
        kill()                        # whatever the child left behind
        proc.wait()
    if overran:
        rc = None
    if rc is None:
        return {"ok": False,
                "error": f"{phase}: no end within {timeout_s:.0f}s"}
    if result is None:
        return {"ok": False, "error": f"{phase}: exit {rc}, no result"}
    if rc != 0:
        result["ok"] = False
        result.setdefault("error", f"{phase}: exit {rc}")
    return result


def parent(args) -> int:
    for cut in CUTS:
        log(f"cut: {cut}")
    phases = ["mesh4"] if args.mesh4 else ["boot1", "boot2"]
    deadline = T0 + (MESH4_BUDGET_S if args.mesh4 else RUN_BUDGET_S)
    device = None
    error = None
    results = {}
    for phase in phases:
        log(f"---- {phase}: starting a fresh process ----")
        res = run_child(phase, args.seed,
                        max(deadline - time.monotonic(), 1.0))
        results[phase] = res
        device = res.get("device") or device
        if not res.get("ok"):
            error = res.get("error", f"{phase} failed")
            break
    if error is None and not args.mesh4:
        b1, b2 = results["boot1"], results["boot2"]
        log(f"seconds to READY: cold {b1['ready_s']}, warm "
            f"{b2['ready_s']}; warm boot kernel-grade compiles "
            f"{b2['kernel_compiles']}")
    final = {"ok": error is None, "device": device}
    if error is not None:
        final["error"] = error
    print(json.dumps(final), flush=True)
    return 0 if error is None else 1


# =======================================================================
# CHILD: the process that holds the chip
# =======================================================================

class Failed(Exception):
    """A phase did not meet its contract."""


def require(cond, what: str) -> None:
    if not cond:
        raise Failed(what)
    log(f"ok: {what}")


def device_record():
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def series(name: str) -> dict:
    """{label string: value} of one metric family, from the same
    exposition text a scrape reads."""
    from teku_tpu.infra.metrics import GLOBAL_REGISTRY
    out = {}
    for line in GLOBAL_REGISTRY.expose().splitlines():
        if line.startswith(name) and line[len(name)] in " {":
            key, _, value = line[len(name):].rpartition(" ")
            out[key] = float(value)
    return out


def served_by() -> dict:
    """bls_verify_requests_total split into device / oracle."""
    out = {"device": 0.0, "oracle": 0.0}
    for labels, value in series("bls_verify_requests_total").items():
        out["oracle" if 'backend="oracle"' in labels else "device"] += value
    return out


class CompileLog:
    """Per-program backend compile seconds, from jax's own duration
    events (they carry the jitted function's name)."""

    def __init__(self):
        import jax
        self.rows = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event.endswith("backend_compile_duration") and duration >= 1.0:
            self.rows.append((kw.get("fun_name", "?"), round(duration, 1)))

    def report(self, since: int = 0) -> None:
        for name, s in self.rows[since:]:
            log(f"  compiled {name}: {s}s")


# ---- seeded traffic ----------------------------------------------------

class Traffic:
    """Triples with verdicts known by construction, from --seed."""

    def __init__(self, seed: int):
        import hashlib
        import random
        from teku_tpu.crypto.bls import curve as C
        from teku_tpu.crypto.bls.constants import R
        from teku_tpu.crypto.bls.hash_to_curve import hash_to_g2
        from teku_tpu.crypto.bls.pure_impl import PureBls12381
        self._C, self._R, self._h2g = C, R, hash_to_g2
        self._sha = hashlib.sha256
        self.oracle = PureBls12381()
        self.rng = random.Random(seed)
        self.seed = seed
        self.sks = [self.rng.randrange(1, R) for _ in range(VALIDATORS)]
        self.pks = [self.oracle.secret_key_to_public_key(sk)
                    for sk in self.sks]

    def message(self, tag: str, i: int) -> bytes:
        # 32 bytes, like the signing root an attestation carries
        return self._sha(f"{self.seed}/{tag}/{i}".encode()).digest()

    def _sign_point(self, sk: int, hm) -> bytes:
        C = self._C
        return C.g2_compress(C.point_mul(C.FQ2_OPS, sk % self._R, hm))

    def sign(self, sk: int, msg: bytes) -> bytes:
        return self._sign_point(sk, self._h2g(msg))

    def committee_batch(self, tag: str):
        """SERVICE_BATCH single-key triples, COMMITTEE signers per
        message (H(m) is hashed once per message)."""
        out = []
        for m in range(-(-SERVICE_BATCH // COMMITTEE)):
            msg = self.message(tag, m)
            hm = self._h2g(msg)
            for j in range(COMMITTEE):
                v = (m * COMMITTEE + j) % VALIDATORS
                out.append(([self.pks[v]], msg,
                            self._sign_point(self.sks[v], hm)))
        return out[:SERVICE_BATCH]

    def tampered_batch(self, tag: str):
        """A committee batch with ONE signature made under another key:
        (batch, index of the bad task)."""
        batch = self.committee_batch(tag)
        bad = self.rng.randrange(SERVICE_BATCH)
        pks, msg, _ = batch[bad]
        batch[bad] = (pks, msg, self.sign(self.sks[0] + 1, msg))
        return batch, bad

    def unique_batch(self, tag: str):
        return [([self.pks[i % VALIDATORS]], msg,
                 self.sign(self.sks[i % VALIDATORS], msg))
                for i, msg in enumerate(self.message(tag, i)
                                        for i in range(SERVICE_BATCH))]


async def serve(service, triples):
    import asyncio
    futs = [service.verify(pks, msg, sig) for pks, msg, sig in triples]
    return list(await asyncio.gather(*futs))


async def counted(service, breaker, name: str, triples, expect):
    """Serve one batch that must be answered by the device alone."""
    before = served_by()
    t0 = time.monotonic()
    got = await serve(service, triples)
    dt = time.monotonic() - t0
    after = served_by()
    moved = {k: after[k] - before[k] for k in before}
    log(f"{name}: {len(triples)} tasks in {dt:.2f}s, dispatches "
        f"device={moved['device']:.0f} oracle={moved['oracle']:.0f}")
    require(got == expect, f"{name}: verdicts as constructed")
    require(moved["device"] > 0 and moved["oracle"] == 0,
            f"{name}: served by the device, the oracle answered nothing")
    require(breaker.state == breaker.CLOSED, f"{name}: breaker closed")


async def boot(seed: int, warm_boot: bool) -> dict:
    import asyncio

    dev = device_record()
    out = {"device": dev}
    log(f"device: {dev}")
    if dev["platform"] != PLATFORM:
        raise Failed(f"no TPU: jax found platform {dev['platform']!r}")
    clog = CompileLog()

    from teku_tpu.crypto import bls
    from teku_tpu.crypto.bls import loader
    from teku_tpu.infra import aotstore, compilecache
    from teku_tpu.ops import mxu
    from teku_tpu.services.signatures import (
        AggregatingSignatureVerificationService)

    log(f"compile cache: {compilecache.configure()}; AOT store: "
        f"{aotstore.store_dir()}")
    # the way `cli node` does it: oracle now, device in the background
    loader.configure("supervised")
    t_boot = time.monotonic()
    sup = loader.make_supervisor(max_batch=MAX_BATCH,
                                 min_bucket=MIN_BUCKET, max_rounds=1,
                                 warmup_deadline_s=READY_TIMEOUT_S)
    await sup.start()
    # the traffic is made while the device warms (host-only work)
    traffic_task = asyncio.create_task(asyncio.to_thread(Traffic, seed))
    ready = False
    while (not ready and sup.backend_state != "degraded"
           and time.monotonic() - t_boot < READY_TIMEOUT_S):
        ready = await sup.wait_ready(2.0)
    out["ready_s"] = round(time.monotonic() - t_boot, 1)
    snap = sup.snapshot()
    log(f"supervisor: {[t['state'] for t in snap['transitions']]} in "
        f"{out['ready_s']}s on {snap['detail']}")
    log(f"warmup: {snap.get('warmup_cache')}")
    clog.report()
    require(ready and snap["state"] == "ready", "supervisor READY")
    require(snap["warmup_cache"].get("finished") is True,
            "READY with warmup FINISHED (no overrun, no failure)")
    log(f"paths: mont_mul {mxu.resolve()}")
    log(f"compilecache {compilecache.stats()}")
    log(f"aotstore {aotstore.stats()}")
    log(f"first dispatches: {series('bls_jit_dispatch_total')}")
    guarded = bls.get_implementation()
    require(isinstance(guarded, loader.GuardedBls12381),
            "facade hot-swapped to the guarded device provider")
    probe = guarded.device.begin_batch_verify(
        [([loader._PROBE_PK], b"teku-tpu warmup",
          guarded.oracle.sign(1, b"teku-tpu warmup"))])
    placed = {d.platform for d in probe._lane_ok.devices()}
    require(probe.result() and placed == {PLATFORM},
            f"a verdict array lives on {placed}")

    traffic = await traffic_task
    breaker = sup.breaker
    service = AggregatingSignatureVerificationService(
        queue_capacity=15_000, max_batch_size=SERVICE_BATCH)
    await service.start()
    try:
        yes = [True] * SERVICE_BATCH
        if warm_boot:
            batch = traffic.committee_batch("a0")
            await counted(service, breaker, "(a) committee batch",
                          batch, yes)
        else:
            await serve_all(service, breaker, traffic, clog, yes)
    finally:
        await service.stop()
        await sup.stop()
    log(f"bls_verify_requests_total: {series('bls_verify_requests_total')}")
    require(series("bls_device_circuit_trips_total").get("", 0.0) == 0,
            "the breaker never opened")
    aot = aotstore.stats()
    log(f"aotstore at exit {aot}; compilecache {compilecache.stats()}")
    require(aot["errors"] == 0, 'aot_store_total{outcome="error"} == 0')
    out["kernel_compiles"] = compilecache.stats()["kernel_compiles"]
    if warm_boot:
        require(out["kernel_compiles"] == 0 and aot["loads"] > 0,
                f"warm boot: 0 kernel-grade compiles, {aot['loads']} AOT "
                "loads")
    return out


async def serve_all(service, breaker, traffic, clog, yes) -> None:
    n_compiled = len(clog.rows)
    # (a) the committee shape the warm-up compiled: >= 4 batches
    for k in range(4):
        await counted(service, breaker, f"(a{k}) committee batch",
                      traffic.committee_batch(f"a{k}"), yes)
    # (b) all-unique, also a warm profile
    await counted(service, breaker, "(b) all-unique batch",
                  traffic.unique_batch("b"), yes)
    # (e) one tampered signature: the batch fails and is bisected down
    # to the one bad task.  The halves reuse the warm programs except
    # for one small group program (a 31-lane half takes the ladder),
    # which compiles well inside the dispatch deadline
    batch, bad = traffic.tampered_batch("e")
    bisects = series("signature_verifications_dispatch_total")
    await counted(service, breaker, "(e) tampered batch", batch,
                  [i != bad for i in range(SERVICE_BATCH)])
    clog.report(n_compiled)
    n_bisect = (series("signature_verifications_dispatch_total")
                .get('{kind="bisect"}', 0)
                - bisects.get('{kind="bisect"}', 0))
    require(n_bisect > 0, f"(e) bisected ({n_bisect:.0f} bisect dispatches)"
            f" to the one bad task, index {bad}")
    # oracle parity on a seeded sample, outside any timing
    sample = traffic.committee_batch("a0")
    picks = traffic.rng.sample(range(SERVICE_BATCH), 8)
    for i in picks:
        pks, msg, sig = sample[i]
        if not traffic.oracle.fast_aggregate_verify(pks, msg, sig):
            raise Failed(f"oracle disagrees on sample task {i}")
    bad = (sample[0][0], sample[0][1], sample[1][2])
    require(not traffic.oracle.fast_aggregate_verify(*bad)
            and not (await serve(service, [bad]))[0],
            f"oracle parity on {len(picks)} sampled tasks + 1 forged")


# ---- four chips: the --mesh path against the single-chip provider -----

def mesh4(seed: int) -> dict:
    import numpy as np

    dev = device_record()
    out = {"device": dev}
    log(f"device: {dev}")
    if dev["platform"] != PLATFORM or dev["count"] != 4:
        raise Failed(f"--mesh4 needs 4 TPU chips, jax found {dev}")
    clog = CompileLog()
    from teku_tpu.crypto.bls import loader
    from teku_tpu.infra import compilecache
    log(f"compile cache: {compilecache.configure()}")
    traffic = Traffic(seed)
    good = traffic.committee_batch("a0")
    tampered, _ = traffic.tampered_batch("a0")

    t0 = time.monotonic()
    single, where = loader._probe_jax(MAX_BATCH, MIN_BUCKET, mesh="off")
    want = [single.batch_verify(good), single.batch_verify(tampered)]
    log(f"single-chip provider on {where}: verdicts {want} in "
        f"{time.monotonic() - t0:.1f}s")
    clog.report()
    n = len(clog.rows)
    t0 = time.monotonic()
    meshed, where = loader._probe_jax(MAX_BATCH, MIN_BUCKET, mesh="4")
    info = meshed.mesh_info or {}
    log(f"mesh provider on {where}: {info}")
    require(info.get("n_devices") == 4, "mesh of 4 devices, no demotion")
    handle = meshed.begin_batch_verify(good)
    shards = {s.device for s in handle._lane_ok.addressable_shards}
    got = [handle.result(), meshed.batch_verify(tampered)]
    log(f"mesh verdicts {got} in {time.monotonic() - t0:.1f}s")
    clog.report(n)
    require(want == [True, False] and got == want,
            "verdict parity with the single-chip provider")
    lanes = np.prod(handle._lane_ok.shape)
    require(len(shards) == 4
            and {d.platform for d in shards} == {PLATFORM},
            f"each of the 4 chips holds a shard of the {lanes}-lane array")
    return out


def child(args) -> int:
    import asyncio
    out = {"ok": False}
    try:
        if args.child == "mesh4":
            out.update(mesh4(args.seed))
        else:
            out.update(asyncio.run(boot(args.seed, args.child == "boot2")))
        out["ok"] = True
    except Failed as exc:
        log(f"FAILED: {exc}")
        out["error"] = f"{args.child}: {exc}"
    except Exception as exc:  # the phase fails, and says where
        import traceback
        traceback.print_exc()
        out["error"] = f"{args.child}: {type(exc).__name__}: {exc}"
    if "device" not in out:
        try:
            out["device"] = device_record()
        except Exception:
            out["device"] = None
    print(RESULT_TAG + json.dumps(out), flush=True)
    sys.stdout.flush()
    # daemon threads may still hold an orphaned compile: do not wait
    os._exit(0 if out["ok"] else 1)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=24)
    ap.add_argument("--mesh4", action="store_true",
                    help="four chips: only the --mesh path and the "
                         "single-chip provider it is compared with")
    ap.add_argument("--child", choices=("boot1", "boot2", "mesh4"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    return child(args) if args.child else parent(args)


if __name__ == "__main__":
    sys.exit(main())
