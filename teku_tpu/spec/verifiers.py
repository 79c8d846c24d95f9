"""Signature-verifier seams between the spec engine and the BLS backend.

All spec-level verification code is written against these interfaces,
never against the BLS facade directly — the reference's second SPI seam
(reference: infrastructure/bls/src/main/java/tech/pegasys/teku/bls/
BLSSignatureVerifier.java:1-87; ethereum/spec/src/main/java/tech/pegasys/
teku/spec/logic/common/util/AsyncBLSSignatureVerifier.java:24-60;
AsyncBatchBLSSignatureVerifier.java:24-60), so block import can swap in
the collect-then-batch verifier and gossip validation can swap in the
TPU batching service without the spec logic knowing.
"""

import asyncio
import contextlib
import contextvars
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

from ..crypto import bls
from ..infra import faults, tracing
from ..services.admission import VerifyClass

Triple = Tuple[Sequence[bytes], bytes, bytes]

# ambient class override: lets a call site that does not own the
# verifier (e.g. the node's deferred-gossip retry loop re-running a
# validator) demote everything submitted inside the block to a lower
# class without threading a parameter through every layer.  ContextVars
# propagate through awaits within the task, so the whole validate()
# coroutine inherits it.
_CLASS_OVERRIDE: contextvars.ContextVar = contextvars.ContextVar(
    "verify_class_override", default=None)


@contextlib.contextmanager
def verify_class(cls: VerifyClass):
    """Run the enclosed (possibly async) code with every service-bound
    verification submitted at `cls` — e.g. OPTIMISTIC for speculative
    re-validation of deferred gossip."""
    token = _CLASS_OVERRIDE.set(cls)
    try:
        yield
    finally:
        _CLASS_OVERRIDE.reset(token)


def effective_class(cls: Optional[VerifyClass]
                    ) -> Optional[VerifyClass]:
    """The ambient override beats the call-site default: a retry loop
    demoting to OPTIMISTIC wins over a validator's GOSSIP."""
    override = _CLASS_OVERRIDE.get()
    return override if override is not None else cls


class SignatureVerifier:
    """Sync seam: verify one (pubkeys, message, signature) triple."""

    def verify(self, public_keys: Sequence[bytes], message: bytes,
               signature: bytes) -> bool:
        raise NotImplementedError


class SimpleSignatureVerifier(SignatureVerifier):
    """Immediate verification straight through the BLS facade (the
    reference's BLSSignatureVerifier.SIMPLE)."""

    def verify(self, public_keys, message, signature) -> bool:
        # `verifiers.dispatch` fault site: the spec-level seam, so
        # injected faults reach block import exactly where a sick
        # backend would
        faults.check("verifiers.dispatch")
        # root span: SIMPLE serves cold paths (no batching service in
        # front), so the trace is opened here and the dispatch IS it
        with tracing.trace("verify", kind="simple"):
            with tracing.span("dispatch"):
                if len(public_keys) == 1:
                    ok = bls.verify(public_keys[0], message, signature)
                else:
                    ok = bls.fast_aggregate_verify(
                        list(public_keys), message, signature)
        return faults.transform("verifiers.dispatch", ok)


SIMPLE = SimpleSignatureVerifier()


class BatchSignatureVerifier(SignatureVerifier):
    """Disposable collect-then-verify verifier for block import.

    verify() only records the triple and optimistically returns True;
    batch_verify() submits everything as ONE random-multiplier batch
    (reference: ethereum/spec/.../statetransition/blockvalidator/
    BatchSignatureVerifier.java:38-108 — there prepareBatchVerify over a
    parallel stream + one completeBatchVerify; here one padded device
    dispatch via bls.batch_verify).  Use once per imported block; a
    False batch_verify invalidates every optimistic True.

    A lane's keys: since Electra (EIP-7549) one on-chain attestation
    aggregates every committee of its slot that voted the same
    `AttestationData`, so a block's majority vote carries up to
    `MAX_COMMITTEES_PER_SLOT` x the largest committee (~31,300 keys at
    1,000,000 validators; `shapeset.block_key_bucket` is the bucket the
    node warms for it), and an attester slashing's indexed attestation
    up to `MAX_COMMITTEES_PER_SLOT` x `MAX_VALIDATORS_PER_COMMITTEE`
    (131,072 on mainnet), the provider's `max_keys_per_lane`, beyond
    which it refuses a lane (`shapeset.MAX_KEYS_PER_LANE`).

    Class: BLOCK_IMPORT — this path bypasses the batching queue
    entirely (one direct dispatch), which IS the strongest priority:
    it never waits behind gossip and can never be shed.  The class is
    stamped on the trace and the capacity model's arrival accounting
    so overload attribution still sees block-import demand.
    """

    cls = VerifyClass.BLOCK_IMPORT

    def __init__(self):
        self._jobs: List[Triple] = []
        self._complete = False

    def verify(self, public_keys, message, signature) -> bool:
        assert not self._complete, "verifier already completed"
        if not public_keys:
            return False
        self._jobs.append((list(public_keys), message, signature))
        return True

    def batch_verify(self) -> bool:
        assert not self._complete, "verifier already completed"
        self._complete = True
        if not self._jobs:
            return True
        faults.check("verifiers.dispatch")
        # offered-load accounting: block-import verifies are demand on
        # the same device the gossip queue shares — the capacity
        # model's utilization must see them or brownout reads low
        from ..infra import capacity
        capacity.record_arrival(self.cls.label, len(self._jobs))
        # root span per imported block's signature batch — the
        # provider's host_prep/device_enqueue/device_sync spans nest
        # inside
        with tracing.trace("verify", kind="block_import",
                           cls=self.cls.label,
                           jobs=str(len(self._jobs))):
            with tracing.span("dispatch"):
                ok = bls.batch_verify(self._jobs)
        return faults.transform("verifiers.dispatch", ok)


class AsyncSignatureVerifier:
    """Async seam: the gossip-side interface the batching service
    implements (reference AsyncBLSSignatureVerifier).  ``cls`` is the
    submitting call site's ``VerifyClass``; ``source`` names the
    arrival's demand stream in the capacity model (the sync-committee
    verbs carry their own) — implementations without a priority queue
    or capacity accounting ignore both."""

    async def verify(self, public_keys: Sequence[bytes], message: bytes,
                     signature: bytes,
                     cls: Optional[VerifyClass] = None,
                     source: Optional[str] = None) -> bool:
        raise NotImplementedError

    @staticmethod
    def wrap(sync_verifier: SignatureVerifier) -> "AsyncSignatureVerifier":
        return _WrappedAsync(sync_verifier)


class _WrappedAsync(AsyncSignatureVerifier):
    def __init__(self, inner: SignatureVerifier):
        self._inner = inner

    async def verify(self, public_keys, message, signature,
                     cls: Optional[VerifyClass] = None,
                     source: Optional[str] = None) -> bool:
        return self._inner.verify(public_keys, message, signature)


class ServiceAsyncSignatureVerifier(AsyncSignatureVerifier):
    """Adapter onto AggregatingSignatureVerificationService (the TPU
    batcher) — futures resolve when the device batch lands.  Threads
    the caller's priority class (validator default or the ambient
    ``verify_class`` override) and arrival source into the service's
    per-class queue and capacity accounting."""

    def __init__(self, service):
        self._service = service

    async def verify(self, public_keys, message, signature,
                     cls: Optional[VerifyClass] = None,
                     source: Optional[str] = None) -> bool:
        return await self._service.verify(
            list(public_keys), message, signature,
            cls=effective_class(cls), source=source)

    async def verify_multi(self, triples: Sequence[Triple],
                           cls: Optional[VerifyClass] = None,
                           source: Optional[str] = None) -> bool:
        return await self._service.verify_multi(
            list(triples), cls=effective_class(cls), source=source)


class AsyncBatchSignatureVerifier:
    """Collect-then-submit adapter: verify() records triples and returns
    True; batch_verify() submits ALL collected triples as ONE atomic
    task to the async delegate, so e.g. a SignedAggregateAndProof's
    three signatures verify together or not at all (reference:
    AsyncBatchBLSSignatureVerifier.java:24-60, used at
    AggregateAttestationValidator.java:124-126,242).  The constructing
    validator stamps its priority class on the whole atomic task.
    """

    def __init__(self, delegate: AsyncSignatureVerifier,
                 cls: Optional[VerifyClass] = None,
                 source: Optional[str] = None):
        self._delegate = delegate
        self._cls = cls
        self._source = source
        self._jobs: List[Triple] = []

    def verify(self, public_keys, message, signature) -> bool:
        self._jobs.append((list(public_keys), message, signature))
        return True

    async def batch_verify(self) -> bool:
        if not self._jobs:
            return True
        if isinstance(self._delegate, ServiceAsyncSignatureVerifier):
            return await self._delegate.verify_multi(
                self._jobs, cls=self._cls, source=self._source)
        for pks, msg, sig in self._jobs:
            if not await self._delegate.verify(pks, msg, sig,
                                               cls=self._cls,
                                               source=self._source):
                return False
        return True
