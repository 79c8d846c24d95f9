"""Bring the program up the way `cli node` does, and read its counters.

Copies of `chip_smoke.py`'s boot sequence, `series`, `served_by` and
`CompileLog` (PR 24), kept here because later PRs may change the
program and not the yardstick.  Everything in this file touches the
program only through what a deployment touches: `loader.configure`,
`loader.make_supervisor`, the facade, the metrics exposition, the
dispatch ledger, `compilecache.stats` and `aotstore.stats`.
"""

import os
import time
from typing import Dict, List, Sequence

READY_TIMEOUT_S = 1100


class BootError(Exception):
    """The program did not reach the state a run needs."""


def apply_knobs(config: dict) -> None:
    """The configuration's environment knobs, set BEFORE the program is
    imported (its modules read them at import or construction)."""
    for key, value in config["knobs"]["env"].items():
        os.environ[key] = str(value)
    # the dispatch ledger is a ring; a window must fit into it whole
    os.environ.setdefault("TEKU_TPU_DISPATCH_LEDGER_CAPACITY", "8192")


def device_record() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax
    peak = 0
    for dev in jax.devices():
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def series(name: str) -> Dict[str, float]:
    """{label string: value} of one metric family, from the same
    exposition text a scrape reads."""
    from teku_tpu.infra.metrics import GLOBAL_REGISTRY
    out = {}
    for line in GLOBAL_REGISTRY.expose().splitlines():
        if line.startswith(name) and line[len(name)] in " {":
            key, _, value = line[len(name):].rpartition(" ")
            out[key] = float(value)
    return out


def served_by() -> Dict[str, float]:
    """bls_verify_requests_total split into device / oracle."""
    out = {"device": 0.0, "oracle": 0.0}
    for labels, value in series("bls_verify_requests_total").items():
        out["oracle" if 'backend="oracle"' in labels else "device"] += value
    return out


class CompileLog:
    """Every backend compile with its seconds, from jax's own duration
    events (they carry the jitted function's name)."""

    def __init__(self):
        import jax
        self.rows: List[tuple] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            self.rows.append((kw.get("fun_name", "?"), duration))


class Counters:
    """One reading of every counter a window is judged by."""

    def __init__(self):
        from teku_tpu.infra import aotstore, dispatchledger
        self.served = served_by()
        self.aot = aotstore.stats()
        self.ledger_seq = dispatchledger.LEDGER.recorded_total
        self.trips = series("bls_device_circuit_trips_total").get("", 0.0)
        self.sig = {name: series(f"signature_verifications_{name}")
                    for name in ("batch_count_total", "task_count_total")}


def ledger_since(seq: int) -> List[dict]:
    from teku_tpu.infra import dispatchledger
    return [r for r in dispatchledger.LEDGER.snapshot()
            if r.get("seq", 0) > seq]


class Program:
    """The booted system under test: supervisor READY, the facade
    hot-swapped to the guarded device provider, the service started."""

    def __init__(self, config: dict):
        self.config = config
        self.sup = None
        self.guarded = None
        self.service = None
        self.ready_s = None
        self.transitions: Sequence = ()

    async def boot(self) -> None:
        from teku_tpu.crypto import bls
        from teku_tpu.crypto.bls import loader
        from teku_tpu.infra import compilecache
        knobs = self.config["knobs"]
        compilecache.configure()
        # the way `cli node` does it: oracle now, device in the background
        loader.configure("supervised")
        t0 = time.monotonic()
        self.sup = loader.make_supervisor(
            max_batch=knobs["max_batch"], min_bucket=knobs["min_bucket"],
            warm=False, max_rounds=1)
        await self.sup.start()
        ready = False
        while (not ready and self.sup.backend_state != "degraded"
               and time.monotonic() - t0 < READY_TIMEOUT_S):
            ready = await self.sup.wait_ready(1.0)
        self.ready_s = time.monotonic() - t0
        snap = self.sup.snapshot()
        self.transitions = [t["state"] for t in snap["transitions"]]
        if not ready or snap["state"] != "ready":
            raise BootError(f"supervisor {self.transitions}: "
                            f"{snap.get('detail')}")
        self.guarded = bls.get_implementation()
        if not isinstance(self.guarded, loader.GuardedBls12381):
            raise BootError("facade was not hot-swapped to the guarded "
                            "device provider")

    async def start_service(self) -> None:
        from teku_tpu.services.signatures import (
            AggregatingSignatureVerificationService)
        svc = self.config["knobs"]["service"]
        self.service = AggregatingSignatureVerificationService(
            num_workers=svc["workers"], queue_capacity=svc["queue"],
            max_batch_size=svc["max_batch"])
        await self.service.start()

    def resolve_public_keys(self, pks: Sequence[bytes]) -> None:
        """Fill the provider's pubkey cache, as a node's validator-key
        cache is at start, in dispatches of the probe's own program
        (16 keys): no `pk_validate` dispatch falls in the window."""
        device = self.guarded.device
        for i in range(0, len(pks), 16):
            resolved = device._resolve_pks(pks[i:i + 16])
            if any(entry[0] != "ok" for entry in resolved.values()):
                raise BootError("the device rejected a signer's key")

    def dispatch_direct(self, triples) -> bool:
        """One batch straight on the device provider, outside the
        breaker's 30 s deadline: how set-up warms the cell's shapes.
        Mirrors the facade: a single triple goes the non-batch way."""
        device = self.guarded.device
        if len(triples) == 1:
            return device.fast_aggregate_verify(*triples[0])
        return device.batch_verify(triples)

    async def stop(self) -> None:
        if self.service is not None:
            await self.service.stop()
        if self.sup is not None:
            await self.sup.stop()

    @property
    def breaker_closed(self) -> bool:
        breaker = self.sup.breaker
        return breaker.state == breaker.CLOSED
