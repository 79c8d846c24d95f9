"""The measured window: offer the plan's tasks to the service and keep
every task's due instant, verdict and verdict instant.

Two arrival disciplines, chosen by the traffic file's `arrivals`:

- `backlog`: the tasks in flight are kept at `backlog`; whenever
  `topup` verdicts have come back, `topup` more tasks are queued in ONE
  event-loop turn, so the service always drains whole top-ups.  The
  window closes with the first verdict at or after `seconds`: verdicts
  come a whole dispatch at a time, so a window cut at a fixed instant
  would read its rate in steps of one dispatch (250 tasks of some
  14,000: 1.8 %, whichever side of the cut the last dispatch fell), and
  one that ends with a dispatch holds whole dispatches only.
- `poisson`: open loop; each task is queued at its due instant (drawn
  from the seed) whatever the service is doing, and is timed from that
  instant.  Every task of the plan is due inside the window.  How late
  the generator ran is reported.

A backlog run that drains its pool before the window ends has
`pool_drained` set and is not correct: nothing is replayed.
"""

import asyncio
import time
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

DRAIN_WAIT_S = 60.0     # a minute past the close, for answers in flight


@dataclass
class Answer:
    due: float                      # perf_counter: when it was due
    sent: float = 0.0               # when it was queued
    done: Optional[float] = None    # when its verdict came
    verdict: Optional[bool] = None
    error: Optional[str] = None
    trace: object = None


@dataclass
class WindowResult:
    t_open: float
    t_close: float
    answers: List[Answer] = field(default_factory=list)
    pool_drained: bool = False
    late_s: List[float] = field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def in_window(self) -> List[Answer]:
        return [a for a in self.answers
                if a.done is not None and a.done <= self.t_close]


class Offer:
    """Queues tasks on the service and records their answers."""

    def __init__(self, service, traced: bool):
        self.service = service
        self.traced = traced
        self.done_count = 0
        self.last_done = 0.0        # instant of the newest verdict
        self.moved = asyncio.Event()

    def send(self, triple, due: float, answers: List[Answer]) -> None:
        ans = Answer(due=due, sent=time.perf_counter())
        answers.append(ans)
        try:
            if self.traced:
                from teku_tpu.infra import tracing
                ans.trace = tracing.new_trace("bench_task")
                with tracing.attach([ans.trace]):
                    fut = self.service.verify(*triple)
            else:
                fut = self.service.verify(*triple)
        except Exception as exc:   # shed at admission: a failed task
            ans.error = f"{type(exc).__name__}: {exc}"
            self._count(ans)
            return
        fut.add_done_callback(lambda f, a=ans: self._settle(f, a))

    def _settle(self, fut, ans: Answer) -> None:
        if fut.cancelled():
            ans.error = "cancelled"
        elif fut.exception() is not None:
            exc = fut.exception()
            ans.error = f"{type(exc).__name__}: {exc}"
        else:
            ans.verdict = fut.result()
        if ans.trace is not None:
            from teku_tpu.infra import tracing
            tracing.finish(ans.trace)
        self._count(ans)

    def _count(self, ans: Answer) -> None:
        ans.done = self.last_done = time.perf_counter()
        self.done_count += 1
        self.moved.set()

    async def drain(self, answers: Sequence[Answer],
                    wait_s: float = DRAIN_WAIT_S) -> None:
        """Wait for every answer still in flight; one that never comes
        stays without a verdict."""
        deadline = time.perf_counter() + wait_s
        while (any(a.done is None for a in answers)
               and time.perf_counter() < deadline):
            self.moved.clear()
            try:
                await asyncio.wait_for(self.moved.wait(), 0.5)
            except asyncio.TimeoutError:
                pass


async def run_backlog(offer: Offer, triples, backlog: int, topup: int,
                      seconds: float) -> WindowResult:
    res = WindowResult(t_open=time.perf_counter(), t_close=0.0)
    t_end = res.t_open + seconds
    sent = 0

    def queue(n: int) -> None:
        nonlocal sent
        now = time.perf_counter()
        for triple in triples[sent:sent + n]:
            offer.send(triple, now, res.answers)
        sent += n

    queue(backlog)
    # a dispatch's verdicts all settle in one event-loop turn, ahead of
    # this task's wake-up, so `last_done` is the whole dispatch's
    while offer.last_done < t_end:
        now = time.perf_counter()
        while sent - offer.done_count <= backlog - topup:
            if sent + topup > len(triples):
                res.pool_drained = True
                break
            queue(topup)
        if res.pool_drained:
            break
        offer.moved.clear()
        try:
            await asyncio.wait_for(offer.moved.wait(), 0.05)
        except asyncio.TimeoutError:
            pass
        if now - t_end > DRAIN_WAIT_S:      # the service has stopped
            break
    res.t_close = (offer.last_done if offer.last_done >= t_end
                   else time.perf_counter())
    await offer.drain(res.answers)
    return res


async def run_poisson(offer: Offer, triples, due_s: Sequence[float],
                      seconds: float) -> WindowResult:
    res = WindowResult(t_open=time.perf_counter(), t_close=0.0)
    t_end = res.t_open + seconds
    i = 0
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        while i < len(triples) and res.t_open + due_s[i] <= now:
            due = res.t_open + due_s[i]
            offer.send(triples[i], due, res.answers)
            res.late_s.append(res.answers[-1].sent - due)
            i += 1
        nxt = min(res.t_open + due_s[i], t_end) if i < len(triples) \
            else t_end
        await asyncio.sleep(max(nxt - time.perf_counter(), 0.0))
    res.t_close = t_end
    await offer.drain(res.answers)
    return res


async def run_batch(offer: Offer, triples) -> List[Answer]:
    """Whole service batches after the window, through the same entry,
    all queued in one turn so that the service drains them whole: the
    probe (which the service finds false and bisects down to the forged
    task) and, in a traced run, the dispatches under the profiler."""
    answers: List[Answer] = []
    now = time.perf_counter()
    for triple in triples:
        offer.send(triple, now, answers)
    await offer.drain(answers, wait_s=180.0)
    return answers
