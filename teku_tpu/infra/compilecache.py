"""Persistent XLA compile cache: wire-up + hit/miss observability.

Cold XLA compiles of the staged verify kernel cost 314-357 s PER
BUCKET SHAPE on CPU (tens of minutes projected on TPU) and were paid
again on every boot.  The compiles are deterministic in (program,
shape, flags), so JAX's persistent compilation cache
(``jax_compilation_cache_dir``) turns every boot after the first into
cache LOADS — warm boots skip the compile entirely.

``configure()`` is called by ``cli node`` / ``cli devnet``, bench.py,
chip_smoke.py and the tests.  The directory is placed from OUTSIDE:
where ``JAX_COMPILATION_CACHE_DIR`` is set jax reads it itself and
nothing here names a directory; where it is not, the cache goes to the
fixed ``<checkout>/.jax_cache`` (the path is part of jax's cache key,
so a directory that moves never hits).  jax's own
``JAX_ENABLE_COMPILATION_CACHE=0`` switches the cache off.  Safe in
both import orders: before jax is imported it sets the JAX_* env vars
the config reads at definition time; after, it updates jax.config
directly.  Nothing here initializes a backend — boot stays O(1).

Observability: a jax.monitoring listener counts the runtime's
``/jax/compilation_cache/cache_hits|cache_misses`` events into
``xla_compile_cache_total{outcome="hit"|"miss"}`` and a process-local
snapshot API — ``ops/provider.py`` diffs snapshots around the first
dispatch of a bucket shape to split its jit outcome into ``compile``
(fresh XLA work) vs ``cache_load`` (served from disk), and the backend
supervisor's WARMING stage reports how much of the warmup was cache
hits vs fresh compiles.
"""

import logging
import os
import sys
import threading

from . import clock
from .env import env_float
from .metrics import GLOBAL_REGISTRY

_LOG = logging.getLogger(__name__)

JAX_ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
ENV_MIN_COMPILE_S = "TEKU_TPU_XLA_CACHE_MIN_COMPILE_S"
ENV_KERNEL_COMPILE_S = "TEKU_TPU_KERNEL_COMPILE_MIN_S"
ENV_COMPILE_SPAN_MIN_S = "TEKU_TPU_COMPILE_SPAN_MIN_S"

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_counts = {"hit": 0, "miss": 0}
# backend compiles: EVERY XLA backend_compile this process performed,
# with durations.  `kernel` counts only compiles >= the kernel-grade
# threshold — a fresh process op-by-op-dispatches a handful of
# millisecond micro programs (jnp.asarray, arena scatter) no store
# can eliminate, so "zero fresh compiles at warm boot" is defined over
# KERNEL-grade compiles (PERF.md documents the definition); raw counts
# stay visible alongside.
_compiles = {"count": 0, "seconds": 0.0, "kernel": 0}
_installed = {"listener": False, "dir": None}
# clock-spine stamp of the most recent cache event: the timeline
# orders "which dispatch paid that cache load" against trace spans
_last_event = {"outcome": None, "t_wall": None, "t_mono": None}

_M_CACHE = GLOBAL_REGISTRY.labeled_counter(
    "xla_compile_cache_total",
    "persistent XLA compile cache lookups by outcome",
    labelnames=("outcome",))
_M_BACKEND = GLOBAL_REGISTRY.labeled_counter(
    "xla_backend_compile_total",
    "XLA backend compiles this process performed, by grade "
    "(kernel = duration >= TEKU_TPU_KERNEL_COMPILE_MIN_S, micro = "
    "op-by-op dispatch of trivial host programs)",
    labelnames=("grade",))


def default_dir() -> str:
    """The fixed repo-adjacent default."""
    here = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(os.path.dirname(here))
    return os.path.join(repo, ".jax_cache")


def _on_event(event: str, **_kw) -> None:
    if event == _HIT_EVENT:
        key = "hit"
    elif event == _MISS_EVENT:
        key = "miss"
    else:
        return
    stamp = clock.stamp({"outcome": key})
    with _lock:
        _counts[key] += 1
        _last_event.update(stamp)
    _M_CACHE.labels(outcome=key).inc()


_cfg: dict = {}


def _compile_cfg() -> dict:
    """Lazy knob reads (memoized; tests clear _cfg around
    env_override).  kernel_s splits kernel-grade compiles from
    micro-op dispatch; span_s floors timeline compile spans so
    micro compiles don't flood the ring."""
    if not _cfg:
        _cfg["kernel_s"] = env_float(ENV_KERNEL_COMPILE_S, 1.0,
                                     lo=0.0)
        _cfg["span_s"] = env_float(ENV_COMPILE_SPAN_MIN_S, 0.05,
                                   lo=0.0)
    return _cfg


def _on_compile_duration(event: str, duration: float, **_kw) -> None:
    if event != _COMPILE_EVENT:
        return
    cfg = _compile_cfg()
    kernel = duration >= cfg["kernel_s"]
    with _lock:
        _compiles["count"] += 1
        _compiles["seconds"] += duration
        if kernel:
            _compiles["kernel"] += 1
    _M_BACKEND.labels(grade="kernel" if kernel else "micro").inc()
    if duration >= cfg["span_s"]:
        # first-class compile span on the shared clock spine: the
        # attribution window sees the TRUE in-window compile overlap
        # instead of clamping ledger-side enqueue seconds at 1.0
        from . import timeline, tracing
        # emit-at-completion: the listener fires when the backend
        # compile returns, so the interval ends NOW
        timeline.interval("worker", "compile", duration,
                          trace_id=tracing.current_trace_id())


def ensure_instrumented() -> bool:
    """Register the monitoring listeners (idempotent).  Imports jax,
    so callers on the boot path defer this until jax is loaded
    anyway."""
    with _lock:
        if _installed["listener"]:
            return True
    from jax import monitoring
    with _lock:
        if not _installed["listener"]:
            monitoring.register_event_listener(_on_event)
            monitoring.register_event_duration_secs_listener(
                _on_compile_duration)
            _installed["listener"] = True
    return True


def configure(min_compile_s=None) -> str:
    """Wire the persistent cache; returns the cache dir.

    The directory is ``JAX_COMPILATION_CACHE_DIR`` where that is set —
    jax reads it itself, no directory is set in code — and the fixed
    repo-adjacent ``.jax_cache`` otherwise.  `min_compile_s` (arg >
    TEKU_TPU_XLA_CACHE_MIN_COMPILE_S > 1 s, the kernel-grade
    threshold) keeps trivial programs from churning the disk.
    """
    placed = os.environ.get(JAX_ENV_DIR)
    if min_compile_s is None:
        # default = the kernel-grade threshold: whatever a boot counts
        # as a kernel-grade compile is persisted, so a warm boot never
        # repeats one (a 1-2 s program used to fall between the two)
        min_compile_s = env_float(ENV_MIN_COMPILE_S, 1.0, lo=0.0)
    if "jax" in sys.modules:
        import jax
        if not placed and (jax.config.jax_compilation_cache_dir
                           != default_dir()):
            jax.config.update("jax_compilation_cache_dir", default_dir())
            # jax binds its cache OBJECT to the dir at first use; a
            # config update alone leaves reads/writes on the old dir
            from jax.experimental.compilation_cache import (
                compilation_cache)
            compilation_cache.reset_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_compile_s)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        ensure_instrumented()
    else:
        # jax not imported yet (cli boot path): the env vars are read
        # when jax.config defines these options, so this wires the
        # cache without paying the jax import here.  The listener is
        # installed by whichever component imports jax first and asks
        # for stats (provider module import / bench / supervisor).
        if not placed:
            os.environ[JAX_ENV_DIR] = default_dir()
        os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = \
            str(min_compile_s)
        os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    _installed["dir"] = placed or default_dir()
    _LOG.info("persistent XLA compile cache: %s", _installed["dir"])
    return _installed["dir"]


def cache_dir():
    """The configured dir (None before configure())."""
    return _installed["dir"]


def stats() -> dict:
    """Process-local cache counters (one JSON-able dict)."""
    if "jax" in sys.modules:
        ensure_instrumented()
    with _lock:
        return {"dir": _installed["dir"], "hits": _counts["hit"],
                "misses": _counts["miss"],
                "backend_compiles": _compiles["count"],
                "backend_compile_s": round(_compiles["seconds"], 6),
                "kernel_compiles": _compiles["kernel"],
                "last_event": dict(_last_event)}


def delta(before: dict, after=None) -> dict:
    """Counter movement between two stats() snapshots (``.get`` so
    pre-existing snapshots without the backend-compile keys diff)."""
    if after is None:
        after = stats()
    out = {"hits": after["hits"] - before["hits"],
           "misses": after["misses"] - before["misses"]}
    for key in ("backend_compiles", "kernel_compiles"):
        out[key] = after.get(key, 0) - before.get(key, 0)
    out["backend_compile_s"] = round(
        after.get("backend_compile_s", 0.0)
        - before.get("backend_compile_s", 0.0), 6)
    return out


def classify_first_dispatch(d: dict, aot=None) -> str:
    """Jit outcome for the FIRST dispatch of a shape, from the cache
    delta observed around it (and optionally the AOT-store delta):
    pure disk hits -> ``cache_load``; serialized-executable loads
    with NO persistent-cache traffic at all -> ``aot_load``; any
    fresh XLA work (or no cache/store) -> ``compile``."""
    if d["hits"] > 0 and d["misses"] == 0:
        return "cache_load"
    if (aot and aot.get("loads", 0) > 0 and d["hits"] == 0
            and d["misses"] == 0):
        return "aot_load"
    return "compile"
