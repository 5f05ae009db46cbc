"""The open-loop generator times each request from its due time, so a
server's stall and a late send both show in the latency."""
import threading
import time

import numpy as np
import pytest

from perfbench.harness import loadgen


class Pending:
    def __init__(self):
        self.done = False
        self.completed_at = None
        self.error = None

    def result(self, timeout=None):
        if self.error:
            raise self.error
        return "answer"


class StallingScheduler:
    """Serves everything queued in one tick, after sleeping ``stall_s``
    on the first tick that finds work (then ``tick_s`` on each)."""

    def __init__(self, stall_s, tick_s=0.0, refuse_every=0, fail=False):
        self.queue = []
        self.lock = threading.Lock()
        self.stall_s, self.tick_s = stall_s, tick_s
        self.refuse_every, self.fail = refuse_every, fail
        self.n = 0

    def submit(self, item):
        self.n += 1
        if self.refuse_every and self.n % self.refuse_every == 0:
            return None
        p = Pending()
        with self.lock:
            self.queue.append(p)
        return p

    def tick(self):
        with self.lock:
            batch, self.queue = self.queue, []
        if not batch:
            return 0
        time.sleep(self.stall_s + self.tick_s)
        self.stall_s = 0.0
        now = time.perf_counter()
        for p in batch:
            if self.fail:
                p.error = RuntimeError("tick failed")
            p.completed_at = now
            p.done = True
        return len(batch)


def test_schedule_is_fixed_by_the_seed():
    a = loadgen.poisson(500, 2.0, 100, seed=2**33 + 1)
    b = loadgen.poisson(500, 2.0, 100, seed=2**33 + 1)
    c = loadgen.poisson(500, 2.0, 100, seed=2**33 + 2)
    np.testing.assert_array_equal(a.due_s, b.due_s)
    np.testing.assert_array_equal(a.item, b.item)
    assert not np.array_equal(a.item[:50], c.item[:50])
    assert np.all(np.diff(a.due_s) >= 0) and a.due_s[-1] < 2.0
    assert 800 < a.due_s.size < 1200          # about rate x seconds
    assert a.item.min() >= 0 and a.item.max() < 100


def test_latency_includes_the_servers_stall():
    sched = StallingScheduler(stall_s=0.3)
    res = loadgen.run_open_loop(sched.submit, sched.tick,
                                loadgen.poisson(200, 1.0, 10, seed=3))
    lat = res.latency_s()
    assert res.failed == 0 and res.ok.all()
    # arrivals during the 0.3 s stall wait for it, from their due time
    assert lat.max() >= 0.25
    assert np.percentile(lat, 99) >= 0.1
    assert np.all(lat >= res.done - res.sent - 1e-9)
    # sends were not held back by the stall: ticks run in another thread
    assert np.percentile(res.late_s(), 50) < 0.05


def test_a_late_send_is_recorded_and_counted():
    sched = StallingScheduler(stall_s=0.0)
    calls = []

    def slow_submit(item):
        calls.append(item)
        if len(calls) == 5:
            time.sleep(0.2)              # the generator itself stalls
        return sched.submit(item)

    res = loadgen.run_open_loop(slow_submit, sched.tick,
                                loadgen.poisson(200, 1.0, 10, seed=4))
    late = res.late_s()
    assert late.max() >= 0.15
    # a request sent late is timed from when it was due
    assert np.all(res.latency_s() >= late - 1e-9)


@pytest.mark.parametrize("refuse_every,fail", [(4, False), (0, True)])
def test_refused_and_failed_requests_count_as_missing(refuse_every, fail):
    sched = StallingScheduler(stall_s=0.0, refuse_every=refuse_every,
                              fail=fail)
    res = loadgen.run_open_loop(sched.submit, sched.tick,
                                loadgen.poisson(100, 0.5, 10, seed=5))
    missing = ~res.ok
    assert missing.sum() == res.failed > 0
    # a missing request waits until the run's end
    np.testing.assert_allclose(res.latency_s()[missing],
                               res.end - res.due[missing])
