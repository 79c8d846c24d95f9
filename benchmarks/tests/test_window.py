"""The two arrival disciplines against a stand-in service that answers
a whole batch at a time, as the program's does."""

import asyncio

from benchmarks.harness import window

BATCH = 4


class BatchingService:
    """Answers the oldest BATCH queued tasks every `period` seconds, all
    in one event-loop turn."""

    def __init__(self, period: float):
        self.period = period
        self.queue = []
        self.task = None

    def verify(self, *triple):
        fut = asyncio.get_running_loop().create_future()
        self.queue.append(fut)
        if self.task is None:
            self.task = asyncio.ensure_future(self._serve())
        return fut

    async def _serve(self):
        while True:
            await asyncio.sleep(self.period)
            batch, self.queue = self.queue[:BATCH], self.queue[BATCH:]
            for fut in batch:
                fut.set_result(True)


def _run(coro_fn):
    async def main():
        service = BatchingService(0.02)
        try:
            return await coro_fn(window.Offer(service, traced=False))
        finally:
            if service.task is not None:
                service.task.cancel()
    return asyncio.run(main())


def test_a_backlog_window_closes_with_a_whole_dispatch():
    triples = [((b"k",), b"m", b"s")] * 400
    res = _run(lambda offer: window.run_backlog(
        offer, triples, 2 * BATCH, BATCH, 0.25))
    done = res.in_window()
    # whole batches only, the last of them the one that closed the window
    assert len(done) % BATCH == 0 and not res.pool_drained
    assert res.t_close == max(a.done for a in done)
    assert 0.25 <= res.seconds < 0.25 + 3 * 0.02
    # the backlog was kept: everything queued was answered in the drain
    assert len(res.answers) == len(done) + BATCH
    assert all(a.verdict is True for a in res.answers)


def test_a_backlog_window_that_outruns_its_pool_says_so():
    res = _run(lambda offer: window.run_backlog(
        offer, [((b"k",), b"m", b"s")] * 12, 2 * BATCH, BATCH, 0.25))
    assert res.pool_drained


def test_an_open_loop_offers_every_task_at_its_due_instant():
    due = [0.01 * (i + 1) for i in range(20)]
    res = _run(lambda offer: window.run_poisson(
        offer, [((b"k",), b"m", b"s")] * 20, due, 0.25))
    assert len(res.answers) == 20 and not res.pool_drained
    assert all(a.verdict is True and a.done >= a.due for a in res.answers)
    assert res.seconds == 0.25
    assert max(res.late_s) < 0.02
