"""The one general traffic generator.

A traffic mix is a data file (`benchmarks/traffic/<name>.json`) of
parameters; a configuration (`benchmarks/configs/<name>.json`) says how
tasks share messages and how many signers the node holds.  From
(configuration, mix, seed, seconds) `plan()` derives, as a pure
function and without any cryptography, every task of a run: the pool
the window draws from, the warm-up batches, the probe batch with its
forged task, the batches a traced run puts under the profiler, and
(open loop) each task's due instant.  `Signer` then
turns task specs into signed triples in worker processes that import
the plain reference and nothing of the program or of JAX.

Nothing is ever replayed: a task is (signer, message) with a message
unique to its group, so neither the provider's H(m) arena nor the
service's in-flight coalescing sees a triple twice.
"""

import hashlib
import math
import multiprocessing
import os
import random
from concurrent.futures import ProcessPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

# group order of BLS12-381 (the plain reference's constants.R; repeated
# here so that planning needs no import at all)
_R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001

CHUNK = 125             # tasks signed per worker call


@dataclass(frozen=True)
class TaskSpec:
    """One verification task before it is signed."""
    signer: int          # index into the signer set
    message: bytes       # the 32-byte signing root
    forged: bool = False  # signed under another key: verdict False


@dataclass
class Plan:
    seed: int
    signers: int
    pool: List[TaskSpec]
    warm: List[List[TaskSpec]]          # batches dispatched in set-up
    probe: List[TaskSpec]               # after the window, one forged
    traced: List[TaskSpec]              # a traced run's own dispatches
    due_s: Optional[List[float]] = None  # open loop: offset of each task
    arrivals: str = "backlog"
    backlog: int = 0
    topup: int = 0
    rate_per_s: float = 0.0
    meta: Dict[str, object] = field(default_factory=dict)


def _rng(seed: int, tag: str) -> random.Random:
    digest = hashlib.sha256(f"{seed}/{tag}".encode()).digest()
    return random.Random(int.from_bytes(digest[:8], "big"))


def secret_key(seed: int, signer: int) -> int:
    digest = hashlib.sha512(f"{seed}/sk/{signer}".encode()).digest()
    return int.from_bytes(digest, "big") % (_R - 1) + 1


def _message(seed: int, tag: str, group: int) -> bytes:
    # 32 bytes, as the signing root of an AttestationData or a block
    return hashlib.sha256(f"{seed}/{tag}/{group}".encode()).digest()


def _tasks(seed: int, tag: str, n: int, per_message: int, signers: int,
           first_signer: int) -> List[TaskSpec]:
    """n tasks; consecutive runs of `per_message` share one message,
    signers walk round the signer set."""
    out = []
    for i in range(n):
        out.append(TaskSpec((first_signer + i) % signers,
                            _message(seed, tag, i // per_message)))
    return out


def pool_size(mix: dict, seconds: float, batch: int) -> int:
    """Tasks made for a window of `seconds`: a backlog's pool in whole
    service batches, an open loop's every task of its schedule."""
    if mix["arrivals"] == "poisson":
        return int(mix["rate_per_s"] * seconds)
    want = (mix["pool_tasks_per_s"] * seconds
            + mix["backlog_batches"] * batch)
    return int(math.ceil(want / batch)) * batch


def poisson_due(rate_per_s: float, n: int, rng: random.Random
                ) -> List[float]:
    """Due instants of n Poisson arrivals inside n / rate seconds.  The
    gaps are the exponential distribution's own quantiles, the same n
    numbers for every seed, and the seed only orders them: every run is
    offered the same work, so two seeds differ no more than two runs of
    one.  They are scaled so that the last task is due half a mean gap
    before the window closes."""
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = (n - 0.5) / rate_per_s / sum(gaps)
    rng.shuffle(gaps)
    t, due = 0.0, []
    for gap in gaps:
        t += gap * scale
        due.append(t)
    return due


def plan(config: dict, mix: dict, seed: int, seconds: float) -> Plan:
    shape = config["traffic_shape"]
    per_message = int(shape["tasks_per_message"])
    signers = int(config["signer_set"])
    # a service batch: what one drain of Teku's batcher takes
    batch = int(config["knobs"]["service"]["max_batch"])
    rng = _rng(seed, "plan")
    # every seed draws the same sizes; only keys, messages, the order
    # of signers and (open loop) the gaps differ
    first = rng.randrange(signers)
    n_pool = pool_size(mix, seconds, batch)
    pool = _tasks(seed, "pool", n_pool, per_message, signers, first)
    warm = [_tasks(seed, f"warm{k}", batch, per_message, signers,
                   first + k)
            for k in range(int(mix.get("warm_batches", 2)))]
    probe = _tasks(seed, "probe", batch, per_message, signers,
                   first + 7)
    bad = rng.randrange(batch)
    probe[bad] = TaskSpec(probe[bad].signer, probe[bad].message, True)
    traced = _tasks(seed, "traced", int(mix["trace_batches"]) * batch,
                    per_message, signers, first + 11)
    out = Plan(seed=seed, signers=signers, pool=pool, warm=warm,
               probe=probe, traced=traced, arrivals=mix["arrivals"],
               meta={"forged_at": bad, "tasks_per_message": per_message})
    if mix["arrivals"] == "backlog":
        out.backlog = int(mix["backlog_batches"]) * batch
        out.topup = int(mix.get("topup_batches", 1)) * batch
    elif mix["arrivals"] == "poisson":
        out.rate_per_s = float(mix["rate_per_s"])
        out.due_s = poisson_due(out.rate_per_s, n_pool, _rng(seed, "gaps"))
    else:
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    return out


# ---- signing, in worker processes ------------------------------------

Triple = Tuple[List[bytes], bytes, bytes]


def _sign_chunk(seed: int, specs: Sequence[Tuple[int, bytes, bool]]
                ) -> List[bytes]:
    """The signature of each spec; one hash-to-curve per run of equal
    messages."""
    from benchmarks.reference import bls
    from benchmarks.reference.h2c import hash_to_g2
    out = []
    last_msg, hm = None, None
    for signer, msg, forged in specs:
        if msg != last_msg:
            last_msg, hm = msg, hash_to_g2(msg)
        sk = secret_key(seed, signer)
        # a forged task carries a well-formed signature of the same
        # message under ANOTHER key: only the pairing can tell
        out.append(bls.sign_point(sk + 1 if forged else sk, hm))
    return out


def _public_keys(seed: int, signers: Sequence[int]) -> List[bytes]:
    from benchmarks.reference import bls
    return [bls.public_key(secret_key(seed, s)) for s in signers]


def _reference_verdicts(triples: Sequence[Triple]) -> List[bool]:
    from benchmarks.reference import bls
    return [bls.fast_aggregate_verify(pks, msg, sig)
            for pks, msg, sig in triples]


def worker_count() -> int:
    # the boot (program loads, compiles) wants cores of its own
    return max(2, min(10, (os.cpu_count() or 4) - 3))


class Signer:
    """A pool of worker processes that make keys and signatures."""

    def __init__(self, seed: int):
        self.seed = seed
        self._pool = ProcessPoolExecutor(
            max_workers=worker_count(),
            mp_context=multiprocessing.get_context("spawn"))

    def close(self) -> None:
        self._pool.shutdown(wait=True, cancel_futures=True)

    def public_keys(self, n_signers: int):
        """Future of the signer set's public keys, in order."""
        step = 64
        futs = [self._pool.submit(_public_keys, self.seed,
                                  list(range(i, min(i + step, n_signers))))
                for i in range(0, n_signers, step)]
        return futs

    def sign(self, specs: Sequence[TaskSpec]):
        """Futures (one a chunk) of the signatures of `specs`."""
        futs = []
        for i in range(0, len(specs), CHUNK):
            chunk = [(s.signer, s.message, s.forged)
                     for s in specs[i:i + CHUNK]]
            futs.append(self._pool.submit(_sign_chunk, self.seed, chunk))
        return futs

    def reference_verdicts(self, triples: Sequence[Triple]):
        step = 4
        return [self._pool.submit(_reference_verdicts,
                                  list(triples[i:i + step]))
                for i in range(0, len(triples), step)]


def gather_keys(futs) -> List[bytes]:
    return [pk for f in futs for pk in f.result()]


def gather_triples(specs: Sequence[TaskSpec], futs,
                   pks: Sequence[bytes]) -> List[Triple]:
    sigs = [sig for f in futs for sig in f.result()]
    return [([pks[s.signer]], s.message, sig)
            for s, sig in zip(specs, sigs)]
