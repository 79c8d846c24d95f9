"""The guarded provider's device-entry lock covers only what enters the
device: a provider's host half (`prepare_dispatch`) runs BEFORE the lock
is taken, so one worker packs its batch while the other one's runs.

Deterministic: the fake provider's halves block on events, no sleeps.
The real provider's halves are pinned in `tests/test_jax_provider.py`
(no device program or transfer in the host half, a missed key and the
H(m) arena under the lock), the phase order in
`tests/test_dispatch_phases.py`."""

import threading

import numpy as np
import pytest

from teku_tpu.crypto.bls import loader
from teku_tpu.crypto.bls.spi import PreparedDispatch, ResolvedHandle
from teku_tpu.infra import tracing
from teku_tpu.infra.metrics import MetricsRegistry
from teku_tpu.infra.supervisor import CircuitBreaker

WAIT_S = 10.0


class _Batch(PreparedDispatch):
    __slots__ = ("tag",)

    def __init__(self, tag, verdict=None):
        super().__init__(verdict)
        self.tag = tag


class TwoHalves:
    """Its device half blocks until the test releases it; every step
    lands in `events` in the order it happened."""

    name = "two-halves"

    def __init__(self):
        self.events = []
        self.in_device = threading.Event()
        self.release = {}
        self._order = threading.Lock()

    def _note(self, *event):
        with self._order:
            self.events.append(event)

    def prepare_dispatch(self, op, triples):
        tag = triples[0][1]
        self._note("prep", tag)
        if tag == b"host-false":
            return _Batch(tag, verdict=False)
        return _Batch(tag)

    def launch_dispatch(self, prepared):
        self._note("launch", prepared.tag)
        self.in_device.set()
        gate = self.release.get(prepared.tag)
        if gate is not None:
            assert gate.wait(WAIT_S)
        self._note("done", prepared.tag)
        return ResolvedHandle(True)

    def batch_verify(self, triples):      # the guard never calls it
        raise AssertionError("the guard bypassed the two halves")


class Oracle:
    def __init__(self):
        self.served = []

    def batch_verify(self, triples):
        self.served.append(triples[0][1])
        return True


def _guarded(device, deadline_s=WAIT_S, threshold=3):
    breaker = CircuitBreaker(failure_threshold=threshold,
                             deadline_s=deadline_s, cooldown_s=60.0,
                             name="prep_outside",
                             registry=MetricsRegistry())
    return loader.GuardedBls12381(device, breaker, oracle=Oracle(),
                                  registry=MetricsRegistry())


def _call(guarded, tag, out):
    """One guarded dispatch on a thread of its own, under marks of its
    own; `out[tag]` gets (verdict, marks)."""
    def run():
        with tracing.dispatch_marks("thread_hop") as marks:
            verdict = guarded.batch_verify([([b"pk"], tag, b"sig")])
        out[tag] = (verdict, marks)

    thread = threading.Thread(target=run, daemon=True)
    thread.start()
    return thread


class _WaitSeen:
    """`tracing.DispatchMarks.mark`, announcing each `lock_wait`."""

    def __init__(self, monkeypatch):
        self.seen = threading.Event()
        real = tracing.DispatchMarks.mark
        seen = self.seen

        def mark(marks, name):
            now = real(marks, name)
            if name == "lock_wait" and seen.armed:
                seen.set()
            return now

        seen.armed = False
        monkeypatch.setattr(tracing.DispatchMarks, "mark", mark)


def test_second_dispatch_preps_while_the_first_holds_the_lock(monkeypatch):
    device = TwoHalves()
    device.release[b"first"] = threading.Event()
    guarded = _guarded(device)
    _device, lock = guarded._serving
    waiting = _WaitSeen(monkeypatch)
    out = {}
    first = _call(guarded, b"first", out)
    assert device.in_device.wait(WAIT_S)
    assert lock.locked()
    # the first is on the device, under the lock: the second one's host
    # half runs to its end and its wait for the lock begins
    waiting.seen.armed = True
    second = _call(guarded, b"second", out)
    assert waiting.seen.wait(WAIT_S)
    assert lock.locked()
    assert device.events == [("prep", b"first"), ("launch", b"first"),
                             ("prep", b"second")]
    device.release[b"first"].set()
    first.join(WAIT_S)
    second.join(WAIT_S)
    assert not first.is_alive() and not second.is_alive()
    assert device.events[3:] == [("done", b"first"), ("launch", b"second"),
                                 ("done", b"second")]
    assert out[b"first"][0] is True and out[b"second"][0] is True
    assert not lock.locked()
    for tag in (b"first", b"second"):
        marks = out[tag][1]
        names = [name for name, _t0, _secs in marks.phases]
        assert names[:3] == ["thread_hop", "host_prep", "lock_wait"]
        prep_end = marks.phases[1][1] + marks.phases[1][2]
        assert prep_end <= marks.lock["acquired"] + 2.5e-6
    # the second one waited from its prep's end to the first's release
    one, two = out[b"first"][1], out[b"second"][1]
    assert one.lock["acquired"] < two.phases[2][1] < one.lock["released"]
    assert two.lock["acquired"] >= one.lock["released"]


def test_host_verdict_never_waits_at_the_lock():
    device = TwoHalves()
    device.release[b"first"] = threading.Event()
    guarded = _guarded(device)
    out = {}
    first = _call(guarded, b"first", out)
    assert device.in_device.wait(WAIT_S)
    # the lock is held; a dispatch the host half can answer returns
    assert guarded.batch_verify([([b"pk"], b"host-false", b"sig")]) is False
    assert ("launch", b"host-false") not in device.events
    device.release[b"first"].set()
    first.join(WAIT_S)
    assert not first.is_alive() and out[b"first"][0] is True


def test_orphan_keeps_the_lock_and_a_later_dispatch_times_out():
    """A dispatch that overran its deadline is still on the device and
    keeps the lock.  A later dispatch preps, blocks at the lock and is
    accounted a timeout at the breaker's deadline (the oracle serves
    it); once the orphan drains, the device serves again."""
    device = TwoHalves()
    device.release[b"wedged"] = threading.Event()
    guarded = _guarded(device, deadline_s=0.2)
    _device, lock = guarded._serving
    timeouts = guarded.breaker._m_timeouts
    assert guarded.batch_verify([([b"pk"], b"wedged", b"sig")]) is True
    assert guarded.oracle.served == [b"wedged"]
    assert timeouts.value == 1
    assert lock.locked()                  # the orphan, still in launch
    assert guarded.batch_verify([([b"pk"], b"later", b"sig")]) is True
    assert guarded.oracle.served == [b"wedged", b"later"]
    assert timeouts.value == 2
    # it prepped, and never reached the device
    assert ("prep", b"later") in device.events
    assert ("launch", b"later") not in device.events
    assert guarded.breaker.state == CircuitBreaker.CLOSED
    # the orphan drains; the blocked dispatch's own orphan then takes
    # the lock, runs and lets go: a busy device read as a busy device
    device.release[b"wedged"].set()
    tick = threading.Event()
    for _ in range(int(WAIT_S / 0.005)):
        if ("done", b"later") in device.events and not lock.locked():
            break
        tick.wait(0.005)
    assert ("done", b"later") in device.events and not lock.locked()
    assert guarded.batch_verify([([b"pk"], b"after", b"sig")]) is True
    assert guarded.oracle.served == [b"wedged", b"later"]
    assert device.events[-3:] == [("prep", b"after"), ("launch", b"after"),
                                  ("done", b"after")]


def test_a_swap_mid_prep_keeps_the_batch_on_its_own_provider():
    """`device, lock = self._serving` is ONE read: a batch prepared for
    a provider runs on that provider, under that provider's lock."""
    old, new = TwoHalves(), TwoHalves()
    guarded = _guarded(old)
    _old, old_lock = guarded._serving
    swapped = []
    real_prepare = old.prepare_dispatch

    def prepare_then_swap(op, triples):
        prepared = real_prepare(op, triples)
        guarded.swap_device(new)
        swapped.append(old_lock.locked())
        return prepared

    old.prepare_dispatch = prepare_then_swap
    assert guarded.batch_verify([([b"pk"], b"mid-swap", b"sig")]) is True
    assert swapped == [False]             # the host half ran off the lock
    assert old.events == [("prep", b"mid-swap"), ("launch", b"mid-swap"),
                          ("done", b"mid-swap")]
    assert new.events == []
    assert guarded.batch_verify([([b"pk"], b"next", b"sig")]) is True
    assert [e[0] for e in new.events] == ["prep", "launch", "done"]


def test_a_provider_without_halves_runs_whole_under_the_lock():
    """The oracle family and the models have no host half to run ahead:
    their verb is the device half."""
    held = []

    class Whole:
        name = "whole"

        def batch_verify(self, triples):
            held.append(lock.locked())
            return True

    guarded = _guarded(Whole())
    _device, lock = guarded._serving
    with tracing.dispatch_marks("thread_hop") as marks:
        assert guarded.batch_verify([([b"pk"], b"m", b"sig")]) is True
    assert held == [True]
    assert [name for name, _t0, _s in marks.phases] == ["thread_hop",
                                                         "lock_wait"]


# --------------------------------------------------------------------------
# the multipliers' bits on the host
# --------------------------------------------------------------------------

@pytest.mark.parametrize("vals", [
    [0, 1, 2, 3],
    [2**64 - 1, 2**63, 2**63 - 1, 2**32, 2**32 - 1],
    "random",
    "nudged",
])
def test_numpy_bit_expansion_equals_the_device_one(vals):
    from teku_tpu.ops import points as PT
    if vals == "random":
        raw = np.random.default_rng(27).integers(
            0, 2**64, size=256, dtype=np.uint64)
    elif vals == "nudged":
        # what the provider does with a zero multiplier
        raw = np.array([0, 5, 0, 2**64 - 1], dtype=np.uint64)
        raw[raw == 0] = 1
    else:
        raw = np.array(vals, dtype=np.uint64)
    host = PT.scalar_bits_np(raw)
    device = np.asarray(PT.scalar_from_uint64(raw))
    assert type(host) is np.ndarray
    assert host.dtype == device.dtype == np.int64
    assert host.shape == device.shape == raw.shape + (64,)
    assert (host == device).all()
    # MSB first: the bits spell the value
    weights = [1 << (63 - i) for i in range(64)]
    assert [sum(int(b) * w for b, w in zip(row, weights))
            for row in host] == [int(v) for v in raw]


# --------------------------------------------------------------------------
# the benchmark's reader of the `prep` field
# --------------------------------------------------------------------------

def _reader():
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "layer_metrics",
        "guard.prep_outside_share.py")
    spec = importlib.util.spec_from_file_location("prep_share", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.mark.parametrize("ledger,share", [
    ([{"prep": "outside_lock"}] * 3, 100.0),
    ([{"prep": "outside_lock"}] * 3
     + [{"prep": "under_lock", "prep_reason": "pk_miss"}], 75.0),
    ([{"prep": "under_lock", "prep_reason": "arena"}], 0.0),
    # records without the field (a program from before it) do not count
    ([{"lanes": 250}, {"prep": "outside_lock"}], 100.0),
    ([{"lanes": 250}, {"lanes": 250}], None),
    ([], None),
])
def test_prep_outside_share_reads_the_ledgers_prep_field(ledger, share):
    assert _reader()({"window_ledger": ledger}) == share
