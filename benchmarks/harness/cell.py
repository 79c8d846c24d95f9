"""One run of one cell: set-up, the measured window, the check, the
result line.

The process that runs this holds the chip; the only other processes
are the traffic workers, which never import JAX.  Set-up is everything
from process start to the window's first task: traffic made beside the
boot, supervisor PROBING -> READY on the probe alone (`warm=False`),
the signer set's keys resolved, and this cell's own shapes warmed
straight on the device provider (outside the breaker's 30 s deadline)
by whole batches of the traffic's own kind and by every distinct shape
the probe batch's bisection will dispatch.  After the window: in a
traced run the dispatches under the profiler, then the probe batch,
then the comparison.
"""

import asyncio
import importlib.util
import json
import os
import statistics
import sys
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

from . import boot, check, profile, traffic, window, work

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
SPLIT_THRESHOLD = 25        # the service's default: below it, one by one
HOST_STAGES = ("assembly", "host_prep", "device_enqueue", "device_sync")


PLATFORM = "tpu"            # no stand-in: a run on anything else exits


class NoDevice(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclass
class Seams:
    """What `benchmarks/tests` replaces to drive a run at a size a CPU
    holds.  The benchmark's own runs use the defaults."""
    bench: Optional[dict] = None            # in place of BENCHMARK.json
    traffic_dir: Optional[str] = None       # in place of traffic/
    tamper: Optional[Callable] = None       # a fault of harness/tamper.py
    look_for_chip: bool = True


_T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def bisect_ranges(n: int, bad: int, split: int = SPLIT_THRESHOLD):
    """The (lo, hi) task ranges the service dispatches, in order, when
    a batch of n tasks holds one bad task: the whole, then halves of
    what failed, then below `split` tasks one by one."""
    out = [(0, n)]

    def failed(lo: int, hi: int) -> None:
        size = hi - lo
        if size == 1:
            return
        if size >= split:
            mid = lo + size // 2
            parts = [(lo, mid), (mid, hi)]
        else:
            parts = [(i, i + 1) for i in range(lo, hi)]
        for a, b in parts:
            out.append((a, b))
            if a <= bad < b and b - a > 1:
                failed(a, b)

    failed(0, n)
    return out


def shape_signature(specs, knobs: dict, arena_warm: bool) -> tuple:
    """What decides which compiled programs a dispatch of these tasks
    runs, by the program's own bucket rule."""
    from teku_tpu.ops import shapeset
    groups: Dict[bytes, int] = {}
    for s in specs:
        groups[s.message] = groups.get(s.message, 0) + 1
    env = knobs["env"]
    arena_off = str(env.get("TEKU_TPU_H2C_CACHE_CAP", "")).lower() == "off"
    plan = shapeset.batch_plan(
        list(groups.values()), min_bucket=knobs["min_bucket"],
        h2c_min_bucket=int(env["TEKU_TPU_H2C_MIN_BUCKET"]),
        group_cap=int(env["TEKU_TPU_H2C_GROUP_CAP"]),
        h2c_missing=0 if (arena_warm and not arena_off) else None)
    return (len(specs) == 1, plan["shape"], plan["u_hm"],
            plan["group_bucket"], plan["msm_path"], plan["h2c_bucket"])


def reports(metric: dict, cell: dict) -> bool:
    """Whether a metric of BENCHMARK.json is this cell's to report: one
    without a `workloads` key is every cell's."""
    return cell["name"] in metric.get("workloads", [cell["name"]])


def load_reader(name: str):
    path = os.path.join(BENCH, "layer_metrics", f"{name}.py")
    if not os.path.exists(path):
        raise SystemExit(f"no reader {path} for per-layer metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        "bench_reader_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile of all the values."""
    ordered = sorted(values)
    rank = max(1, int(-(-q * len(ordered) // 1)))
    return ordered[min(rank, len(ordered)) - 1]


class StageSampler:
    """Raw stage samples of the traced run, through the program's
    `tracing.set_sampler`: per task `queue_wait`; per dispatch (the 250
    traces of one batch carry the same span) the host stages."""

    def __init__(self):
        self.queue_wait: List[tuple] = []        # (t0, seconds)
        self.spans: Dict[tuple, float] = {}      # (stage, t0) -> seconds
        self.host_prep: Dict[float, float] = {}  # dispatch t0 -> seconds

    def __call__(self, trace) -> None:
        prep, dispatch_t0 = 0.0, None
        for stage, t0, secs in list(trace.spans):
            if stage == "queue_wait":
                self.queue_wait.append((t0, secs))
            elif stage in HOST_STAGES:
                self.spans[(stage, round(t0, 6))] = secs
            if stage == "host_prep":
                prep += secs
            elif stage == "device_enqueue" and dispatch_t0 is None:
                dispatch_t0 = round(t0, 6)
        if dispatch_t0 is not None:
            # a task of a bisected batch is dispatched again; its first
            # dispatch names the sample
            self.host_prep.setdefault(dispatch_t0, prep)

    def host_spans(self, offset: float):
        return [(st, t0 + offset, secs)
                for (st, t0), secs in self.spans.items()]


class Tracer:
    """The profiler over a traced run's own dispatches.

    The device line of this program holds some 2.4 million events a
    dispatch (every iteration of every loop of the staged programs):
    handing them over costs the profiler 75 s a traced dispatch in an
    idle process, and took ten minutes for one second of trace while
    the window's dispatches went on (PR 25).  So a traced run lets the
    window's backlog drain, starts the profiler, queues the mix's
    `trace_batches` further service batches of the same traffic in one
    turn (the same shapes through the same entry) and stops the
    profiler once they are answered."""

    def __init__(self):
        self.out: dict = {}
        self.session = None

    def start(self) -> None:
        import jax
        from jax._src.lib import _profiler
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.devices()       # the backend before the profiler's session
        # the session itself, not `jax.profiler.start_trace`: its
        # `stop()` hands the trace over in memory, where `stop_trace`
        # also writes it out and converts its millions of events for
        # the trace viewer
        self.session = _profiler.ProfilerSession(opts)
        with jax.profiler.TraceAnnotation(profile.SYNC_MARK):
            self.out["sync_pc"] = time.perf_counter()
            time.sleep(0.001)
        self.out["lo_pc"] = time.perf_counter()

    async def stop(self, hi_pc: float) -> None:
        """`hi_pc`: the instant of the last traced verdict, where the
        traced window ends."""
        self.out["hi_pc"] = hi_pc
        await asyncio.sleep(0.02)
        t0 = time.monotonic()
        self.out["xspace"] = await asyncio.to_thread(self.session.stop)
        log(f"profiler traced {self.out['hi_pc'] - self.out['lo_pc']:.2f}s;"
            f" stopping it took {time.monotonic() - t0:.1f}s, "
            f"{len(self.out['xspace']) / 1e6:.1f} MB")


def _reduce_trace(prof: dict, sampler: StageSampler) -> Optional[dict]:
    if "xspace" not in prof:
        return None
    trace = profile.load_xspace(prof["xspace"])
    span = profile.traced_span(trace)
    if span is None:
        return None
    if trace["sync_s"] is not None:
        offset = trace["sync_s"] - prof["sync_pc"]
        lo, hi = prof["lo_pc"] + offset, prof["hi_pc"] + offset
    else:
        offset = span[0] - prof["lo_pc"]
        lo, hi = span
    return {"trace": trace, "lo": lo, "hi": hi, "offset": offset,
            "busy_s": profile.busy_seconds(trace, lo, hi),
            "window_s": hi - lo,
            "host_spans": sampler.host_spans(offset)}


async def run(bench: dict, args, t_start: float, seams: Seams) -> int:
    cell = find_cell(bench, args.workload)
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    config = load_json(ROOT, cfg_entry["file"])
    mix = load_json(seams.traffic_dir or os.path.join(BENCH, "traffic"),
                    f"{cell['traffic']}.json")
    traced = bool(args.trace)
    boot.apply_knobs(config)
    plan = traffic.plan(config, mix, args.seed, args.seconds)
    log(f"{cell['name']} seed {args.seed}: pool {len(plan.pool)} tasks, "
        f"{plan.signers} signers, {plan.arrivals}")

    signer = traffic.Signer(args.seed)
    try:
        return await _run(bench, cell, config, mix, plan, signer, args,
                          t_start, traced, seams)
    finally:
        signer.close()


async def _run(bench, cell, config, mix, plan, signer, args, t_start,
               traced, seams) -> int:
    key_futs = signer.public_keys(plan.signers)
    warm_futs = [signer.sign(b) for b in plan.warm]
    # the probe's twin: same structure, own messages, warms the shapes
    # the probe's bisection will dispatch
    twin = [traffic.TaskSpec(s.signer, b"twin" + s.message[4:], s.forged)
            for s in plan.probe]
    twin_futs = signer.sign(twin)
    probe_futs = signer.sign(plan.probe)
    traced_futs = signer.sign(plan.traced) if traced else []
    pool_futs = signer.sign(plan.pool)

    dev = boot.device_record()
    if seams.look_for_chip and (dev["platform"] != PLATFORM
                                or dev["count"] < cell["chips"]):
        raise NoDevice(f"{cell['name']} needs {cell['chips']} "
                       f"{PLATFORM} chip(s); jax found {dev}")
    clog = boot.CompileLog()
    program = boot.Program(config)
    knobs = config["knobs"]
    await program.boot()
    log(f"supervisor {program.transitions} in {program.ready_s:.1f}s")
    from teku_tpu.infra import tracing
    seq_setup = boot.Counters().ledger_seq

    pks = await asyncio.to_thread(traffic.gather_keys, key_futs)
    t = time.monotonic()
    await asyncio.to_thread(program.resolve_public_keys, pks)
    log(f"{len(pks)} public keys resolved in {time.monotonic() - t:.1f}s")

    def warm_dispatch(triples, want: bool, what: str) -> None:
        t0 = time.monotonic()
        got = program.dispatch_direct(triples)
        log(f"warm {what}: {len(triples)} tasks, verdict {got}, "
            f"{time.monotonic() - t0:.2f}s")
        if got is not want:
            raise boot.BootError(f"warm {what}: verdict {got}, the "
                                 f"reference says {want}")

    for k, (specs, futs) in enumerate(zip(plan.warm, warm_futs)):
        triples = await asyncio.to_thread(
            traffic.gather_triples, specs, futs, pks)
        await asyncio.to_thread(warm_dispatch, triples, True, f"batch {k}")
    twin_triples = await asyncio.to_thread(
        traffic.gather_triples, twin, twin_futs, pks)
    seen = set()
    bad = plan.meta["forged_at"]
    for n, (lo, hi) in enumerate(bisect_ranges(len(twin), bad)):
        sig = shape_signature(twin[lo:hi], knobs, arena_warm=n > 0)
        if n > 0 and sig in seen:
            continue
        seen.add(sig)
        await asyncio.to_thread(
            warm_dispatch, twin_triples[lo:hi], not lo <= bad < hi,
            f"probe shape {sig}")
    setup_ledger = boot.ledger_since(seq_setup)

    probe_triples = await asyncio.to_thread(
        traffic.gather_triples, plan.probe, probe_futs, pks)
    traced_triples = await asyncio.to_thread(
        traffic.gather_triples, plan.traced, traced_futs, pks)
    pool_triples = await asyncio.to_thread(
        traffic.gather_triples, plan.pool, pool_futs, pks)
    await program.start_service()
    if seams.tamper is not None:
        seams.tamper(program)
    sampler = StageSampler()
    if traced:
        tracing.set_sampler(sampler)
    offer = window.Offer(program.service, traced)
    n_setup_compiles = len(clog.rows)
    before = boot.Counters()
    setup_s = time.monotonic() - t_start
    log(f"set-up {setup_s:.1f}s; window of {args.seconds}s opens")

    if plan.arrivals == "backlog":
        res = await window.run_backlog(offer, pool_triples, plan.backlog,
                                       plan.topup, args.seconds)
    else:
        res = await window.run_poisson(offer, pool_triples, plan.due_s,
                                       args.seconds)
    after = boot.Counters()
    memory_peak = boot.memory_peak_bytes()
    win_ledger = boot.ledger_since(before.ledger_seq)
    done = res.in_window()
    log(f"window of {res.seconds:.3f}s closed: {len(res.answers)} offered, "
        f"{len(done)} answered inside it, {len(win_ledger)} dispatches")
    if len(win_ledger) > 2:
        def med(values):
            return statistics.median(values) * 1e3
        stamps = sorted(r["t_mono"] for r in win_ledger)
        log("a dispatch of the window, medians by the program's ledger: "
            f"enqueue {med(r['compile']['enqueue_s'] for r in win_ledger):.1f}"
            f" ms, sync {med(r['device']['sync_s'] for r in win_ledger):.1f}"
            f" ms, device busy "
            f"{med(r['device']['busy_s'] for r in win_ledger):.1f} ms, one "
            f"every {med(b - a for a, b in zip(stamps, stamps[1:])):.1f} ms")
    if res.late_s:
        log(f"generator lateness: median "
            f"{statistics.median(res.late_s) * 1e3:.3f} ms, max "
            f"{max(res.late_s) * 1e3:.3f} ms over {len(res.late_s)} tasks")
        # a backlog that grows shows as a later half that waits longer
        mid = res.t_open + res.seconds / 2
        halves = [[a.done - a.due for a in res.answers
                   if a.done is not None and (a.due < mid) == first]
                  for first in (True, False)]
        log("median latency of tasks due in the first / second half: "
            + " / ".join(f"{statistics.median(h) * 1e3:.0f} ms"
                         for h in halves if h))

    tracer, traced_answers, traced_ledger = Tracer(), [], []
    if traced:
        tracer.start()
        traced_answers = await window.run_batch(offer, traced_triples)
        await tracer.stop(offer.last_done)
        traced_ledger = boot.ledger_since(after.ledger_seq)
    prof = tracer.out
    seq_probe = boot.Counters().ledger_seq
    probe_answers = await window.run_batch(offer, probe_triples)
    final = boot.Counters()
    log(f"probe: {len(boot.ledger_since(seq_probe))} dispatches, forged "
        f"task {bad} answered {probe_answers[bad].verdict}")
    tracing.set_sampler(None)
    all_ledger = boot.ledger_since(before.ledger_seq)

    # ---- the comparison -------------------------------------------------
    import random
    rng = random.Random(args.seed ^ 0x5EED)
    win_idx, probe_idx = check.sample_indices(
        rng, len(res.answers), bad, len(probe_answers))
    sample = ([pool_triples[i] for i in win_idx]
              + [probe_triples[i] for i in probe_idx])
    sample_want = ([not plan.pool[i].forged for i in win_idx]
                   + [not plan.probe[i].forged for i in probe_idx])
    ref_futs = signer.reference_verdicts(sample)
    ref = await asyncio.to_thread(
        lambda: [v for f in ref_futs for v in f.result()])
    await program.stop()

    w = check.count_wrong(res.answers,
                          check.expected(plan.pool[:len(res.answers)]))
    p = check.count_wrong(probe_answers + traced_answers,
                          check.expected(plan.probe)
                          + [True] * len(traced_answers))
    compiled = len(clog.rows) - n_setup_compiles
    true_answers = sum(1 for a in (res.answers + probe_answers
                                   + traced_answers)
                       if a.verdict is True)
    lanes_ok = sum(r.get("lanes", 0) for r in all_ledger
                   if r.get("verdict") is True)
    numbers = {
        "wrong_verdicts": w["wrong"] + p["wrong"],
        "missing_verdicts": w["missing"] + p["missing"],
        "reference_disagrees": sum(1 for got, want in
                                   zip(ref, sample_want) if got != want),
        "oracle_dispatches": final.served["oracle"]
        - before.served["oracle"],
        "breaker_trips": (final.trips - before.trips)
        + (0 if program.breaker_closed else 1),
        "aot_errors": final.aot["errors"],
        "window_compiles": compiled,
        "unwarmed_dispatches": sum(
            1 for r in all_ledger
            if (r.get("compile") or {}).get("outcome") != "cache_hit"),
        "undispatched_tasks": max(true_answers - lanes_ok, 0),
        "pool_drained": int(res.pool_drained),
    }
    checks = check.decide(numbers)
    correct = check.is_correct(checks)
    if compiled:
        log(f"compiled inside the window: {clog.rows[n_setup_compiles:]}")

    # ---- metrics --------------------------------------------------------
    attempted = (len(res.answers) + len(probe_answers)
                 + len(traced_answers))
    failed = (w["wrong"] + w["missing"] + p["wrong"] + p["missing"]
              + int(numbers["oracle_dispatches"]))
    lat_ms = [(a.done - a.due) * 1e3 for a in res.answers
              if a.done is not None and a.verdict is not None]
    end_to_end = {
        "sigs_per_s": len(done) / res.seconds,
        "setup_s": setup_s,
    }
    if plan.arrivals == "poisson" and lat_ms:
        end_to_end["verify_p50_ms"] = percentile(lat_ms, 0.50)
        end_to_end["verify_p95_ms"] = percentile(lat_ms, 0.95)
    device = dict(dev, memory_peak_bytes=memory_peak)
    out = {"correct": correct, "attempted": attempted, "failed": failed}
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}

    if not traced:
        metrics = {m["name"]: end_to_end[m["name"]]
                   for m in bench["end_to_end"]
                   if reports(m, cell) and m["name"] in end_to_end}
    else:
        reduced = await asyncio.to_thread(_reduce_trace, prof, sampler)
        ctx = {
            "cell": cell, "config": config, "mix": mix,
            "setup_s": setup_s, "ready_s": program.ready_s,
            "probe_s": boot.series("bls_backend_last_probe_seconds")
            .get("", 0.0),
            "setup_ledger": setup_ledger, "window_ledger": win_ledger,
            "traced_ledger": traced_ledger,
            "before": before, "after": after, "window": res,
            "sampler": sampler, "reduced": reduced,
            "table": work.load_table(config["roofline"]),
            "peak": work.load_peak(dev["kind"]) if reduced else None,
        }
        firsts = [r for r in setup_ledger
                  if (r.get("compile") or {}).get("outcome") != "cache_hit"]
        log(f"set-up paid {len(firsts)} first dispatches: "
            + ", ".join(f"{r['shape']}/{r['msm']['path']}:"
                        f"{r['compile']['outcome']} "
                        f"{r['compile']['enqueue_s']:.1f}s"
                        for r in firsts)
            + f"; {n_setup_compiles} backend compiles, "
            f"{sum(s for _, s in clog.rows[:n_setup_compiles]):.1f}s; "
            f"aot {final.aot}")
        metrics = {}
        for m in bench["per_layer"]:
            if not reports(m, cell):
                continue
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = value
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            out["breakdown"] = {
                "device_ops": profile.top_modules(reduced["trace"]),
                "idle_gaps": profile.idle_gaps(
                    reduced["trace"], reduced["lo"], reduced["hi"],
                    reduced["host_spans"]),
            }
            log(f"trace: busy {reduced['busy_s']:.3f}s of "
                f"{reduced['window_s']:.3f}s")
    out["metrics"] = {name: {"value": value, "unit": units[name]}
                      for name, value in metrics.items()}
    out["device"] = device
    if "breakdown" in out:
        out["breakdown"] = out.pop("breakdown")
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
