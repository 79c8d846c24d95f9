"""Fault-injection harness: deterministic failure modes at named sites.

The robustness counterpart of the reference's acceptance-test chaos
hooks (reference: acceptance-tests/.../dsl/TekuNode.java restart/kill
semantics): production code calls `check(site)` / `transform(site, v)`
at its dispatch seams, and tests install faults keyed by site to prove
the supervisor/breaker state machine end to end — dispatch hangs,
dispatch exceptions, wrong results, slow-ramp backend init, and queue
overflow — without ever touching a real accelerator.

The site vocabulary is CLOSED: ``SITES`` below declares every legal
site string, and the static analyzer (`cli lint`, closed-registry
checker) verifies both directions — no undeclared call site, no dead
member.  Keyed sites: ``bls.mesh_shard`` faults may carry a ``key``
(a device name) — the collective dispatch passes the LIVE device set
(a wedged shard wedges the whole collective) while the self-healing
mesh's per-device isolation probes pass one name, so a keyed fault
models exactly one sick chip (teku_tpu/parallel/selfheal.py).
``h2c.cache`` WrongResult(value=slot) poisons a cache hit; the cache
must re-verify by digest and recompute, never flip a verdict
(ops/h2c_cache.py).

The no-fault fast path is one module-global bool check, so production
traffic pays nothing for the instrumentation.  The registry is
process-global on purpose: dispatch sites run inside worker threads and
jitted call stacks where plumbing a context object through would leak
test concerns into kernel signatures.
"""

import threading
import time
from typing import Dict, List, Optional

__all__ = ["Fault", "Hang", "Raise", "WrongResult", "SlowRamp",
           "Overflow", "SITES", "inject", "clear", "active", "check",
           "transform", "fired_count"]

# The CLOSED site vocabulary: every `check(site)` / `transform(site)`
# string in the tree must be declared here, and every member must have
# a live call site — enforced statically by `cli lint`'s
# closed-registry checker (teku_tpu/analysis/registries.py), replacing
# the grep-maintained list this docstring used to carry.  A typo'd
# site would otherwise silently never fire its fault.
SITES = frozenset({
    "backend.init",         # device bring-up probe (SlowRamp/Raise/Hang)
    "bls.dispatch",         # JaxBls12381 device dispatch (begin+result)
    "bls.mesh_shard",       # sharded mesh dispatch; faults may carry a
                            # device-name key (selfheal.FAULT_SITE)
    "bls.batch_verify",     # BLS facade batch entry (WrongResult)
    "h2c.cache",            # H(m) device-cache slot resolution
    "kzg.dispatch",         # device KZG backend calls
    "sigservice.enqueue",   # batching-service queue admission (Overflow)
    "verifiers.dispatch",   # spec-level verifier seam
})


class Fault:
    """One injectable failure.  `times` bounds how often it fires
    (None = every time until cleared).  `kind` decides whether the
    fault spends its budget at check() (entry) or transform() (result)
    — a WrongResult must not be consumed by the entry hook.  `key`
    scopes the fault to one member of a keyed site (e.g. a mesh device
    index): it fires only when the site's check() names that key in
    its ``keys`` — a keyless fault fires on every call, and a keyed
    fault never fires at a call that passes no keys (the caller is
    not key-aware, so a device-scoped fault cannot leak into it)."""

    kind = "check"

    def __init__(self, times: Optional[int] = None, key=None):
        self.times = times
        self.key = key
        self.fired = 0

    def _matches(self, keys) -> bool:
        if self.key is None:
            return True
        return keys is not None and self.key in keys

    def _consume(self) -> bool:
        if self.times is not None and self.fired >= self.times:
            return False
        self.fired += 1
        return True

    # subclasses override exactly one of these
    def on_check(self) -> None:  # pragma: no cover - interface
        pass

    def on_transform(self, value):
        return value


class Hang(Fault):
    """Dispatch hang: the call blocks for `seconds` (long enough to
    overrun a breaker deadline, short enough for tests)."""

    def __init__(self, seconds: float, times: Optional[int] = None,
                 key=None):
        super().__init__(times, key=key)
        self.seconds = seconds

    def on_check(self) -> None:
        time.sleep(self.seconds)


class Raise(Fault):
    """Dispatch exception: the call raises `exc` (an instance or a
    zero-arg factory)."""

    def __init__(self, exc, times: Optional[int] = None, key=None):
        super().__init__(times, key=key)
        self.exc = exc

    def on_check(self) -> None:
        exc = self.exc() if callable(self.exc) else self.exc
        raise exc


class WrongResult(Fault):
    """Wrong-result: boolean results are inverted (or forced to `value`
    when given) — the fault class the bisect-on-fail path must isolate."""

    kind = "transform"

    def __init__(self, value=None, times: Optional[int] = None,
                 key=None):
        super().__init__(times, key=key)
        self.value = value

    def on_transform(self, result):
        if self.value is not None:
            return self.value
        if isinstance(result, bool):
            return not result
        return result


class SlowRamp(Hang):
    """Slow-ramp init: the site takes `seconds` before succeeding —
    models a minutes-long device bring-up at test timescales.
    Mechanically a Hang; the distinct name marks *bring-up* slowness
    (site succeeds afterwards) vs a *dispatch* wedge."""


class Overflow(Fault):
    """Queue overflow: admission raises the overflow error class the
    site's shed path handles (default asyncio.QueueFull)."""

    def __init__(self, exc=None, times: Optional[int] = None,
                 key=None):
        super().__init__(times, key=key)
        self.exc = exc

    def on_check(self) -> None:
        if self.exc is not None:
            raise self.exc() if callable(self.exc) else self.exc
        import asyncio
        raise asyncio.QueueFull()


_LOCK = threading.Lock()
_FAULTS: Dict[str, List[Fault]] = {}
_ACTIVE = False       # fast-path guard: no dict lookup when quiescent


def inject(site: str, fault: Fault) -> Fault:
    """Install `fault` at `site`; returns it (so tests can read
    .fired)."""
    global _ACTIVE
    with _LOCK:
        _FAULTS.setdefault(site, []).append(fault)
        _ACTIVE = True
    return fault


def clear(site: Optional[str] = None) -> None:
    """Remove faults at `site` (all sites when None)."""
    global _ACTIVE
    with _LOCK:
        if site is None:
            _FAULTS.clear()
        else:
            _FAULTS.pop(site, None)
        _ACTIVE = bool(_FAULTS)


def active() -> bool:
    return _ACTIVE


def fired_count(site: str) -> int:
    with _LOCK:
        return sum(f.fired for f in _FAULTS.get(site, ()))


def check(site: str, keys=None) -> None:
    """Call at a dispatch site BEFORE the real work: installed faults
    may sleep (Hang/SlowRamp) or raise (Raise/Overflow).  ``keys``
    names the site members this call touches (e.g. the live mesh
    device indices): keyed faults fire only when their key is named,
    so a per-device fault wedges the collective dispatch AND that one
    device's isolation probe, and nothing else."""
    if not _ACTIVE:
        return
    with _LOCK:
        faults = [f for f in _FAULTS.get(site, ())
                  if f.kind == "check" and f._matches(keys)
                  and f._consume()]
    for f in faults:
        f.on_check()


def transform(site: str, value, keys=None):
    """Call at a dispatch site on the RESULT: WrongResult faults
    corrupt the value on its way out (same ``keys`` scoping as
    check())."""
    if not _ACTIVE:
        return value
    with _LOCK:
        faults = [f for f in _FAULTS.get(site, ())
                  if f.kind == "transform" and f._matches(keys)
                  and f._consume()]
    for f in faults:
        value = f.on_transform(value)
    return value
