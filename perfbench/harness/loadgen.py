"""Open-loop load generator: requests arrive on a schedule drawn from the
seed, whatever the server is doing, and each is timed from the moment it
was due.

The program's own generator (``serve/loadgen.py``) ticks the scheduler in
the sending thread and times a request from its actual submit, so a long
tick delays every arrival behind it and the wait never shows.  Here the
scheduler ticks in a thread of its own, a send that runs late is recorded
as late, latency runs from the due time to the answer, and the run covers
a window of seconds rather than a count of requests.  A request that is
refused, fails, or never completes counts as missing: its latency is the
time from its due time to the end of the run.
"""
from __future__ import annotations

import dataclasses
import sys
import threading
import time
from typing import Any, Callable, List, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Schedule:
    """Arrivals: due times (seconds after the window opens, ascending) and
    the index of the pooled request each one sends."""

    due_s: np.ndarray
    item: np.ndarray


def poisson(rate_per_s: float, seconds: float, pool: int,
            seed: int) -> Schedule:
    """A Poisson process at ``rate_per_s`` over ``seconds``, each arrival
    sending a request drawn uniformly from a pool of ``pool``."""
    rng = np.random.default_rng(seed)
    times = np.zeros(0)
    t = 0.0
    while t < seconds:
        block = t + np.cumsum(rng.exponential(
            1.0 / rate_per_s, int(rate_per_s * seconds) // 4 + 64))
        times = np.concatenate([times, block])
        t = float(times[-1])
    times = times[times < seconds]
    return Schedule(times, rng.integers(0, pool, times.size))


@dataclasses.dataclass
class LoadResult:
    """One open-loop run, every time on the host's ``perf_counter``."""

    start: float              # the window opened
    end: float                # the last answer came, or the wait ended
    due: np.ndarray           # when each request was due
    sent: np.ndarray          # when its send began
    done: np.ndarray          # when its answer came (nan: none)
    ok: np.ndarray            # answered without error
    handles: List[Any]        # submit()'s handle per request (None: refused)
    item: np.ndarray          # the pooled request each one sent

    @property
    def attempted(self) -> int:
        return int(self.due.size)

    @property
    def failed(self) -> int:
        return int(self.due.size - np.count_nonzero(self.ok))

    def latency_s(self) -> np.ndarray:
        """Due time to answer; to the end of the run where none came."""
        return np.where(self.ok, self.done, self.end) - self.due

    def late_s(self) -> np.ndarray:
        """How late each send began past its due time."""
        return self.sent - self.due


def run_open_loop(submit: Callable[[int], Optional[Any]],
                  tick: Callable[[], int], schedule: Schedule, *,
                  wait_s: float = 60.0, idle_sleep_s: float = 2e-4,
                  switch_s: float = 5e-4) -> LoadResult:
    """Send ``schedule``'s arrivals through ``submit(item)`` (a handle with
    ``done``, ``completed_at`` and ``result()``, or None when refused) while
    a second thread calls ``tick()`` (requests served; 0 when idle).

    After the last arrival it waits for the answers, at most ``wait_s``
    past the close, then stops the ticking thread and waits for it.  The
    interpreter's thread switch interval is ``switch_s`` meanwhile, so a
    send that is due waits less for the ticking thread to yield."""
    n = schedule.due_s.size
    sent = np.full(n, np.nan)
    handles: List[Any] = [None] * n
    stop = threading.Event()
    errors: List[BaseException] = []

    def ticker() -> None:
        try:
            while not stop.is_set():
                if tick() == 0:
                    time.sleep(idle_sleep_s)
        except BaseException as e:  # reported and re-raised below
            errors.append(e)

    thread = threading.Thread(target=ticker, name="perfbench-ticker",
                              daemon=True)
    switch = sys.getswitchinterval()
    sys.setswitchinterval(switch_s)
    thread.start()
    start = time.perf_counter()
    due = start + schedule.due_s
    try:
        for i in range(n):
            wait = due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            sent[i] = time.perf_counter()
            handles[i] = submit(int(schedule.item[i]))
        close = time.perf_counter()
        for h in handles:
            if h is None:
                continue
            while not h.done and time.perf_counter() < close + wait_s \
                    and not errors:
                time.sleep(idle_sleep_s)
        waited = time.perf_counter()
    finally:
        stop.set()
        thread.join(timeout=wait_s)
        sys.setswitchinterval(switch)
    if thread.is_alive():
        raise RuntimeError("the ticking thread did not stop")
    if errors:
        raise errors[0]
    done = np.array([h.completed_at if h is not None and h.done else np.nan
                     for h in handles], np.float64)
    ok = np.array([h is not None and h.done and _answered(h)
                   for h in handles], bool)
    # the run ends with its last answer, or when the wait for a missing
    # one gave up
    end = waited if not ok.all() else float(np.max(done, initial=start))
    return LoadResult(start, end, due, sent, done, ok, handles,
                      schedule.item)


def _answered(handle: Any) -> bool:
    try:
        handle.result(timeout=0)
    except Exception:  # a failed request is counted, not raised
        return False
    return True

