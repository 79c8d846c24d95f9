"""The guarded provider's device-entry lock covers only what enters the
device: a provider's host half (`prepare_dispatch`) runs BEFORE the lock
is taken, so one worker packs its batch while the other one's runs.
Host halves take turns (`prep_wait`: two at once convoy on the
interpreter lock), and a dispatch keeps the turn through its wait for
the lock and its launches: it gives it back once `launch_dispatch` has
returned, before the sync, so the other worker packs while this one's
programs run and not while it launches.

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


class _Sync:
    """A handle whose sync stands until the test opens its gate."""

    def __init__(self, device, tag, gate):
        self.device, self.tag, self.gate = device, tag, gate

    def result(self):
        self.device._note("sync", self.tag)
        self.device.in_sync.set()
        assert self.gate.wait(WAIT_S)
        self.device._note("synced", self.tag)
        return True


class TwoHalves:
    """Its launches block until the test opens `release[tag]`, its sync
    until it opens `sync_gate[tag]`; every step lands in `events` in
    the order it happened."""

    name = "two-halves"

    def __init__(self):
        self.events = []
        self.in_device = threading.Event()
        self.in_sync = threading.Event()
        self.release = {}
        self.sync_gate = {}
        self._order = threading.Lock()

    def _note(self, *event):
        with self._order:
            self.events.append(event)

    def prepare_dispatch(self, op, triples):
        tag = triples[0][1]
        self._note("prep", tag)
        if tag == b"prep-raises":
            raise RuntimeError("the host half failed")
        if tag == b"host-false":
            return _Batch(tag, verdict=False)
        return _Batch(tag)

    def launch_dispatch(self, prepared):
        self._note("launch", prepared.tag)
        self.in_device.set()
        if prepared.tag == b"launch-raises":
            raise RuntimeError("a launch failed")
        gate = self.release.get(prepared.tag)
        if gate is not None:
            assert gate.wait(WAIT_S)
        self._note("done", prepared.tag)
        gate = self.sync_gate.get(prepared.tag)
        if gate is not None:
            return _Sync(self, prepared.tag, gate)
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


def _turns(guarded):
    """`bls_prep_turn_total` of this guard, by `released`."""
    return {key[0]: child.value for key, child in guarded._m_turn._items()}


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


def _until(done):
    tick = threading.Event()
    for _ in range(int(WAIT_S / 0.005)):
        if done():
            return True
        tick.wait(0.005)
    return done()


class _MarkSeen:
    """`tracing.DispatchMarks.mark`, announcing each mark of `name`
    made once `armed` is set."""

    def __init__(self, monkeypatch, name):
        self.seen = threading.Event()
        self.armed = threading.Event()
        real = tracing.DispatchMarks.mark

        def mark(marks, phase):
            now = real(marks, phase)
            if phase == name and self.armed.is_set():
                self.seen.set()
            return now

        monkeypatch.setattr(tracing.DispatchMarks, "mark", mark)


def test_second_dispatch_preps_while_the_first_holds_the_lock(monkeypatch):
    device = TwoHalves()
    device.sync_gate[b"first"] = threading.Event()
    guarded = _guarded(device)
    _device, lock, turn = guarded._serving
    waiting = _MarkSeen(monkeypatch, "lock_wait")
    out = {}
    first = _call(guarded, b"first", out)
    assert device.in_sync.wait(WAIT_S)
    assert lock.locked()
    # the first is on the device, under the lock, its launches out: the
    # second one's host half runs to its end and its wait for the lock
    # begins
    waiting.armed.set()
    second = _call(guarded, b"second", out)
    assert waiting.seen.wait(WAIT_S)
    assert lock.locked() and turn.locked()      # the second's turn
    assert device.events == [("prep", b"first"), ("launch", b"first"),
                             ("done", b"first"), ("sync", b"first"),
                             ("prep", b"second")]
    device.sync_gate[b"first"].set()
    first.join(WAIT_S)
    second.join(WAIT_S)
    assert not first.is_alive() and not second.is_alive()
    assert device.events[5:] == [("synced", b"first"),
                                 ("launch", b"second"),
                                 ("done", b"second")]
    assert out[b"first"][0] is True and out[b"second"][0] is True
    assert not lock.locked() and not turn.locked()
    for tag in (b"first", b"second"):
        marks = out[tag][1]
        names = [name for name, _t0, _secs in marks.phases]
        assert names[:4] == ["thread_hop", "prep_wait", "host_prep",
                             "lock_wait"]
        prep_end = marks.phases[2][1] + marks.phases[2][2]
        assert prep_end <= marks.lock["acquired"] + 2.5e-6
    # the second one waited from its prep's end to the first's release
    one, two = out[b"first"][1], out[b"second"][1]
    assert one.lock["acquired"] < two.phases[3][1] < one.lock["released"]
    assert two.lock["acquired"] >= one.lock["released"]


def test_the_turn_is_given_back_before_the_sync(monkeypatch):
    """The turn is held through `launch_dispatch` and given back before
    `result()`: a second host half waits while the first launches, and
    packs while the first's handle blocks in its sync."""
    device = TwoHalves()
    device.release[b"first"] = threading.Event()
    device.sync_gate[b"first"] = threading.Event()
    guarded = _guarded(device)
    _device, lock, turn = guarded._serving
    turn_wait = _MarkSeen(monkeypatch, "prep_wait")
    at_lock = _MarkSeen(monkeypatch, "lock_wait")
    out = {}
    first = _call(guarded, b"first", out)
    assert device.in_device.wait(WAIT_S)        # inside its launches
    turn_wait.armed.set()
    second = _call(guarded, b"second", out)
    assert turn_wait.seen.wait(WAIT_S)
    second.join(0.05)
    assert second.is_alive() and turn.locked() and lock.locked()
    assert device.events == [("prep", b"first"), ("launch", b"first")]
    assert _turns(guarded) == {}
    at_lock.armed.set()
    device.release[b"first"].set()
    # the first's launches returned: the second packs and stands at the
    # lock while the first's sync still blocks
    assert at_lock.seen.wait(WAIT_S)
    assert ("synced", b"first") not in device.events
    assert device.events[:3] == [("prep", b"first"), ("launch", b"first"),
                                 ("done", b"first")]
    assert ("prep", b"second") in device.events[3:]
    assert ("launch", b"second") not in device.events
    assert _turns(guarded) == {"launched": 1}
    device.sync_gate[b"first"].set()
    first.join(WAIT_S)
    second.join(WAIT_S)
    assert not first.is_alive() and not second.is_alive()
    assert out[b"first"][0] is True and out[b"second"][0] is True
    assert device.events[-2:] == [("launch", b"second"), ("done", b"second")]
    assert _turns(guarded) == {"launched": 2}
    assert not lock.locked() and not turn.locked()
    waited = dict((n, s) for n, _t, s in out[b"second"][1].phases)
    assert waited["prep_wait"] >= 0.04
    _assert_tiles(out[b"second"][1].phases)


def _gated_prepare(device, tag, entered, gate):
    """`device.prepare_dispatch`, standing inside the host half of
    the batch `tag` until the test opens `gate`."""
    real_prepare = device.prepare_dispatch

    def prepare(op, triples):
        prepared = real_prepare(op, triples)
        if prepared.tag == tag:
            entered.set()
            assert gate.wait(WAIT_S)
        return prepared

    device.prepare_dispatch = prepare


def _assert_tiles(phases):
    for (_n0, t0, secs), (_n1, t1, _s1) in zip(phases, phases[1:]):
        assert t0 + secs == pytest.approx(t1, abs=2.5e-6)
    assert all(secs >= 0 for _n, _t, secs in phases)


def test_host_halves_take_turns(monkeypatch):
    """Two dispatches that arrive together pack one after the other:
    the second's host half starts when the first has launched (two at
    once convoy on the interpreter lock), its wait for the turn is
    `prep_wait` and not `host_prep`, and neither waits for the
    device-entry lock to pack."""
    device = TwoHalves()
    in_prep, gate = threading.Event(), threading.Event()
    _gated_prepare(device, b"first", in_prep, gate)
    guarded = _guarded(device)
    _device, lock, turn = guarded._serving
    waiting = _MarkSeen(monkeypatch, "prep_wait")
    out = {}
    first = _call(guarded, b"first", out)
    assert in_prep.wait(WAIT_S)
    waiting.armed.set()
    second = _call(guarded, b"second", out)
    assert waiting.seen.wait(WAIT_S)
    # the first still packs: the second stands at the packers' turn,
    # and nobody holds the device's lock
    assert turn.locked() and not lock.locked()
    assert device.events == [("prep", b"first")]
    gate.set()
    first.join(WAIT_S)
    second.join(WAIT_S)
    assert not first.is_alive() and not second.is_alive()
    assert [e for e in device.events if e[0] == "prep"] \
        == [("prep", b"first"), ("prep", b"second")]
    assert out[b"first"][0] is True and out[b"second"][0] is True
    assert not turn.locked() and not lock.locked()
    one, two = out[b"first"][1].phases, out[b"second"][1].phases
    for phases in (one, two):
        assert [name for name, _t0, _s in phases][:4] == [
            "thread_hop", "prep_wait", "host_prep", "lock_wait"]
        _assert_tiles(phases)
    # the second's wait ended, and its own packing began, when the
    # first's packing had ended: the host halves never overlap
    first_prep_end = one[2][1] + one[2][2]
    assert two[2][1] >= first_prep_end - 2.5e-6
    # and it began while the first was inside its host half
    assert one[2][1] <= two[1][1] + 2.5e-6
    assert two[1][1] + two[1][2] == pytest.approx(two[2][1], abs=2.5e-6)


def test_many_dispatches_at_once_never_share_a_host_half():
    """More dispatch threads than cores, the interpreter switching
    every 10 us: at no instant are two host halves inside
    `prepare_dispatch`, nor two device halves under the lock, nor a
    host half beside a launch, and every dispatch is served by the
    device."""
    import os
    import sys

    class Counting(TwoHalves):
        def __init__(self):
            super().__init__()
            self.inside = {"prep": 0, "launch": 0}
            self.worst = {"prep": 0, "launch": 0, "turn": 0}

        def _enter(self, half):
            with self._order:
                self.inside[half] += 1
                self.worst[half] = max(self.worst[half],
                                       self.inside[half])
                self.worst["turn"] = max(self.worst["turn"],
                                         sum(self.inside.values()))

        def _leave(self, half):
            with self._order:
                self.inside[half] -= 1

        def prepare_dispatch(self, op, triples):
            self._enter("prep")
            try:
                sum(range(200))           # lets the interpreter switch
                return _Batch(triples[0][1])
            finally:
                self._leave("prep")

        def launch_dispatch(self, prepared):
            self._enter("launch")
            try:
                sum(range(200))
                return ResolvedHandle(True)
            finally:
                self._leave("launch")

    device = Counting()
    guarded = _guarded(device)
    _device, _lock, turn = guarded._serving
    threads_n, each = min(32, 2 * (os.cpu_count() or 4)), 25
    verdicts = []

    def run(i):
        for j in range(each):
            verdicts.append(guarded.batch_verify(
                [([b"pk"], b"t%d-%d" % (i, j), b"sig")]))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=run, args=(i,), daemon=True)
                   for i in range(threads_n)]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(6 * WAIT_S)
    finally:
        sys.setswitchinterval(interval)
    assert not any(thread.is_alive() for thread in threads)
    assert verdicts == [True] * (threads_n * each)
    assert guarded.oracle.served == []
    assert device.worst == {"prep": 1, "launch": 1, "turn": 1}
    assert not turn.locked()
    assert _turns(guarded) == {"launched": threads_n * each}


def test_a_worker_waiting_for_the_device_does_not_hold_the_turn(
        monkeypatch):
    """A worker in `lock_wait` holds the turn: with the device-entry
    lock held by somebody else, a second host half starts only after
    the first's `launch_dispatch` has returned.  Once its launches are
    out and it waits for the device, in its sync, the turn is the
    other worker's."""
    device = TwoHalves()
    device.sync_gate[b"first"] = threading.Event()
    guarded = _guarded(device)
    _device, lock, turn = guarded._serving
    at_lock = _MarkSeen(monkeypatch, "lock_wait")
    turn_wait = _MarkSeen(monkeypatch, "prep_wait")
    out = {}
    assert lock.acquire(timeout=WAIT_S)       # a third party's hold
    try:
        at_lock.armed.set()
        first = _call(guarded, b"first", out)
        assert at_lock.seen.wait(WAIT_S)
        assert turn.locked() and device.events == [("prep", b"first")]
        at_lock.seen.clear()
        turn_wait.armed.set()
        second = _call(guarded, b"second", out)
        assert turn_wait.seen.wait(WAIT_S)
        second.join(0.05)
        # the second stands at the turn: nothing of it has packed
        assert second.is_alive() and device.events == [("prep", b"first")]
        assert not at_lock.seen.is_set()
    finally:
        lock.release()
    # the first launches, gives the turn back and waits for the device;
    # the second packs meanwhile and stands at the lock
    assert at_lock.seen.wait(WAIT_S)
    assert device.events[:3] == [("prep", b"first"), ("launch", b"first"),
                                 ("done", b"first")]
    assert ("prep", b"second") in device.events[3:]
    assert ("synced", b"first") not in device.events
    device.sync_gate[b"first"].set()
    first.join(WAIT_S)
    second.join(WAIT_S)
    assert not first.is_alive() and not second.is_alive()
    assert out[b"first"][0] is True and out[b"second"][0] is True
    assert device.events[-2:] == [("launch", b"second"), ("done", b"second")]
    assert not lock.locked() and not turn.locked()
    for tag in (b"first", b"second"):
        _assert_tiles(out[tag][1].phases)
    # the second's wait for the turn held the first's wait at the lock
    # and its launches
    waited = dict((n, s) for n, _t, s in out[b"second"][1].phases)
    assert waited["prep_wait"] >= 0.04


def test_a_swap_leaves_the_turn_where_it_is(monkeypatch):
    """A reshape swaps the whole (provider, device-entry lock, turn)
    triple, and leaves the old turn with the dispatch that holds it:
    one waiting at the old lock keeps it, and a dispatch on the new
    triple takes the new turn and packs, launches and completes
    meanwhile (no new dispatch queues behind a wedged orphan)."""
    old, new = TwoHalves(), TwoHalves()
    guarded = _guarded(old)
    _old, old_lock, old_turn = guarded._serving
    at_lock = _MarkSeen(monkeypatch, "lock_wait")
    out = {}
    assert old_lock.acquire(timeout=WAIT_S)   # a wedged orphan's hold
    try:
        at_lock.armed.set()
        first = _call(guarded, b"on-old", out)
        assert at_lock.seen.wait(WAIT_S)
        assert old_turn.locked()
        guarded.swap_device(new)
        _new, new_lock, new_turn = guarded._serving
        assert new_lock is not old_lock and new_turn is not old_turn
        second = _call(guarded, b"on-new", out)
        second.join(WAIT_S)
        assert not second.is_alive() and out[b"on-new"][0] is True
        assert [e[0] for e in new.events] == ["prep", "launch", "done"]
        # the old triple's launcher still waits, holding the old turn
        assert first.is_alive() and old_turn.locked()
        assert old.events == [("prep", b"on-old")]
    finally:
        old_lock.release()
    first.join(WAIT_S)
    assert not first.is_alive() and out[b"on-old"][0] is True
    assert [e[0] for e in old.events] == ["prep", "launch", "done"]
    assert not any(held.locked()
                   for held in (old_lock, old_turn, new_lock, new_turn))
    assert _turns(guarded) == {"launched": 2}


def test_a_hung_host_half_gives_the_turn_back_when_it_ends():
    """A host half that outlasts the breaker's deadline keeps the
    turn; the oracle answers its dispatch and the one that waited out
    the deadline behind it (`prep_wait` is under the deadline as
    `lock_wait` is).  When the hung half ends the turn is free, the
    orphans run, and the device serves the next dispatch."""
    device = TwoHalves()
    in_prep, gate = threading.Event(), threading.Event()
    _gated_prepare(device, b"hung", in_prep, gate)
    guarded = _guarded(device, deadline_s=0.2)
    _device, lock, turn = guarded._serving
    timeouts = guarded.breaker._m_timeouts
    assert guarded.batch_verify([([b"pk"], b"hung", b"sig")]) is True
    assert guarded.oracle.served == [b"hung"] and timeouts.value == 1
    assert turn.locked() and not lock.locked()
    with tracing.dispatch_marks("thread_hop") as marks:
        assert guarded.batch_verify([([b"pk"], b"behind", b"sig")]) is True
    assert guarded.oracle.served == [b"hung", b"behind"]
    assert timeouts.value == 2
    # it never packed: it stood in `prep_wait` until the deadline
    assert ("prep", b"behind") not in device.events
    names = [name for name, _t0, _s in marks.phases]
    assert names[:2] == ["thread_hop", "prep_wait"]
    assert "host_prep" not in names and "lock_wait" not in names
    assert dict((n, s) for n, _t, s in marks.phases)["prep_wait"] >= 0.15
    assert guarded.breaker.state == CircuitBreaker.CLOSED
    gate.set()
    assert _until(lambda: ("done", b"behind") in device.events
                  and ("done", b"hung") in device.events)
    assert _until(lambda: not (turn.locked() or lock.locked()))
    assert guarded.batch_verify([([b"pk"], b"after", b"sig")]) is True
    assert guarded.oracle.served == [b"hung", b"behind"]
    assert device.events[-3:] == [("prep", b"after"), ("launch", b"after"),
                                  ("done", b"after")]


def test_host_verdict_never_waits_at_the_lock():
    device = TwoHalves()
    device.sync_gate[b"first"] = threading.Event()
    guarded = _guarded(device)
    out = {}
    first = _call(guarded, b"first", out)
    assert device.in_sync.wait(WAIT_S)
    # the lock is held; a dispatch the host half can answer returns
    assert guarded.batch_verify([([b"pk"], b"host-false", b"sig")]) is False
    assert ("launch", b"host-false") not in device.events
    device.sync_gate[b"first"].set()
    first.join(WAIT_S)
    assert not first.is_alive() and out[b"first"][0] is True


def test_a_host_verdict_gives_the_turn_back_without_the_lock():
    """A verdict the host half gives hands the turn back at once; the
    device-entry lock is never touched, and no lock edge is stamped."""
    device = TwoHalves()
    guarded = _guarded(device)
    _device, _lock, turn = guarded._serving

    class Untouchable:
        def __enter__(self):
            raise AssertionError("a host verdict took the device lock")

        def __exit__(self, *exc):
            return False

    guarded._serving = (device, Untouchable(), turn)
    with tracing.dispatch_marks("thread_hop") as marks:
        assert guarded.batch_verify(
            [([b"pk"], b"host-false", b"sig")]) is False
    assert not turn.locked()
    assert _turns(guarded) == {"host_verdict": 1}
    assert [name for name, _t0, _s in marks.phases] == [
        "thread_hop", "prep_wait", "host_prep"]
    assert marks.lock == {}
    assert device.events == [("prep", b"host-false")]
    assert guarded.oracle.served == []


@pytest.mark.parametrize("tag", [b"prep-raises", b"launch-raises"])
def test_a_raise_before_the_launches_returned_gives_the_turn_back(tag):
    """A raising host half or launch gives the turn back (and the lock,
    where it was taken): the oracle answers that dispatch, and the
    device serves the next one."""
    device = TwoHalves()
    guarded = _guarded(device)
    _device, lock, turn = guarded._serving
    assert guarded.batch_verify([([b"pk"], tag, b"sig")]) is True
    assert guarded.oracle.served == [tag]
    assert not turn.locked() and not lock.locked()
    assert _turns(guarded) == {"error": 1}
    launched = ("launch", tag) in device.events
    assert launched is (tag == b"launch-raises")
    assert guarded.breaker.state == CircuitBreaker.CLOSED
    assert guarded.batch_verify([([b"pk"], b"after", b"sig")]) is True
    assert guarded.oracle.served == [tag]
    assert device.events[-3:] == [("prep", b"after"), ("launch", b"after"),
                                  ("done", b"after")]
    assert _turns(guarded) == {"error": 1, "launched": 1}


def test_orphan_keeps_the_lock_and_a_later_dispatch_times_out():
    """A dispatch that overran its deadline is still on the device and
    keeps the lock.  A later dispatch preps, blocks at the lock and is
    accounted a timeout at the breaker's deadline (the oracle serves
    it); once the orphan drains, the device serves again."""
    device = TwoHalves()
    device.sync_gate[b"wedged"] = threading.Event()
    guarded = _guarded(device, deadline_s=0.2)
    _device, lock, turn = guarded._serving
    timeouts = guarded.breaker._m_timeouts
    assert guarded.batch_verify([([b"pk"], b"wedged", b"sig")]) is True
    assert guarded.oracle.served == [b"wedged"]
    assert timeouts.value == 1
    assert lock.locked()                  # the orphan, still in its sync
    assert guarded.batch_verify([([b"pk"], b"later", b"sig")]) is True
    assert guarded.oracle.served == [b"wedged", b"later"]
    assert timeouts.value == 2
    # it prepped, and never reached the device: its orphan stands at
    # the lock, holding the turn
    assert ("prep", b"later") in device.events
    assert ("launch", b"later") not in device.events
    assert turn.locked()
    assert guarded.breaker.state == CircuitBreaker.CLOSED
    # the orphan drains; the blocked dispatch's own orphan then takes
    # the lock, runs and lets go: a busy device read as a busy device
    device.sync_gate[b"wedged"].set()
    assert _until(lambda: ("done", b"later") in device.events
                  and not lock.locked() and not turn.locked())
    assert guarded.batch_verify([([b"pk"], b"after", b"sig")]) is True
    assert guarded.oracle.served == [b"wedged", b"later"]
    assert device.events[-3:] == [("prep", b"after"), ("launch", b"after"),
                                  ("done", b"after")]


def test_an_orphan_at_the_lock_keeps_the_turn_until_it_launches():
    """A dispatch that overran its deadline while it waited at the
    lock still holds the turn: the next dispatch on the same triple
    waits for the turn, is answered by the oracle at its deadline and
    never packs.  The turn comes free when the orphan has launched;
    the second's orphan then packs and runs, and the device serves
    again."""
    device = TwoHalves()
    guarded = _guarded(device, deadline_s=0.2)
    _device, lock, turn = guarded._serving
    timeouts = guarded.breaker._m_timeouts
    assert lock.acquire(timeout=WAIT_S)       # a wedged dispatch's hold
    try:
        assert guarded.batch_verify(
            [([b"pk"], b"orphan", b"sig")]) is True
        assert guarded.oracle.served == [b"orphan"] and timeouts.value == 1
        assert turn.locked() and device.events == [("prep", b"orphan")]
        with tracing.dispatch_marks("thread_hop") as marks:
            assert guarded.batch_verify(
                [([b"pk"], b"behind", b"sig")]) is True
        assert guarded.oracle.served == [b"orphan", b"behind"]
        assert timeouts.value == 2
        assert device.events == [("prep", b"orphan")]
        names = [name for name, _t0, _s in marks.phases]
        assert names[:2] == ["thread_hop", "prep_wait"]
        assert "host_prep" not in names and "lock_wait" not in names
        assert guarded.breaker.state == CircuitBreaker.CLOSED
    finally:
        lock.release()
    assert _until(lambda: ("done", b"behind") in device.events
                  and not turn.locked() and not lock.locked())
    assert device.events == [
        ("prep", b"orphan"), ("launch", b"orphan"), ("done", b"orphan"),
        ("prep", b"behind"), ("launch", b"behind"), ("done", b"behind")]
    assert _until(lambda: _turns(guarded) == {"launched": 2})
    assert guarded.batch_verify([([b"pk"], b"after", b"sig")]) is True
    assert guarded.oracle.served == [b"orphan", b"behind"]
    assert device.events[-3:] == [("prep", b"after"), ("launch", b"after"),
                                  ("done", b"after")]


def test_a_swap_mid_prep_keeps_the_batch_on_its_own_provider():
    """`device, lock, turn = self._serving` is ONE read: a batch
    prepared for a provider runs on that provider, under that
    provider's lock."""
    old, new = TwoHalves(), TwoHalves()
    guarded = _guarded(old)
    _old, old_lock, _old_turn = guarded._serving
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


@pytest.mark.parametrize("kind", ["no_halves", "wrapped_verb"])
def test_a_provider_without_halves_runs_whole_under_the_lock(kind):
    """The oracle family and the models have no host half to run ahead:
    their verb is the device half.  So is a verb replaced on the
    instance (a fault harness: the halves would go around it).  They
    take no turn: neither `prep_wait` nor `host_prep` is marked, and
    the packers' turn is never touched.  Their hold is `launch_head`
    to its end: they launch no program through the seam."""
    held = []

    def whole(triples):
        held.append((turn.locked(), lock.locked()))
        return True

    if kind == "no_halves":
        class Whole:
            name = "whole"
            batch_verify = staticmethod(whole)
        device = Whole()
    else:
        device = TwoHalves()
        device.batch_verify = whole
    guarded = _guarded(device)
    _device, lock, turn = guarded._serving
    with tracing.dispatch_marks("thread_hop") as marks:
        assert guarded.batch_verify([([b"pk"], b"m", b"sig")]) is True
    assert held == [(False, True)]
    names = [name for name, _t0, _s in marks.phases]
    assert names == ["thread_hop", "lock_wait", "launch_head"]
    assert getattr(device, "events", []) == []
    assert _turns(guarded) == {}


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

def _reader(name="guard.prep_outside_share"):
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "layer_metrics",
        name + ".py")
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), path)
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


def _dispatch(wait_s=None, prep_s=0.11):
    """A ledger record's `phases` as the guard marks them; without
    `wait_s`, as the parent of the PR that brought `prep_wait` did."""
    phases = [["thread_hop", 10.0, 0.002]]
    t = 10.002
    if wait_s is not None:
        phases.append(["prep_wait", t, wait_s])
        t += wait_s
    phases += [["host_prep", t, prep_s], ["lock_wait", t + prep_s, 0.5]]
    return {"lanes": 252, "phases": phases}


@pytest.mark.parametrize("ledger,median_ms", [
    # a burst's pair: the second waits out the first's packing
    ([_dispatch(0.0), _dispatch(0.11)], 55.0),
    ([_dispatch(0.0), _dispatch(0.11), _dispatch(0.0004)], 0.4),
    # records without the phase do not count: a provider without a
    # host half, a program from before the phase, one without phases
    ([_dispatch(0.002), _dispatch(), {"lanes": 1}], 2.0),
    ([_dispatch(), _dispatch(prep_s=1.136)], None),
    ([{"lanes": 250}], None),
    ([], None),
])
def test_prep_wait_reader_reads_the_ledgers_phase(ledger, median_ms):
    got = _reader("guard.prep_wait_ms")({"window_ledger": ledger})
    assert got == (None if median_ms is None
                   else pytest.approx(median_ms))
