"""Traffic kind ``serve_open``: single-query requests to one tenant of a
``SearchServer``, arriving open-loop as a Poisson process at the traffic's
fixed ``rate_per_s``.

The load generator (``harness/loadgen.py``) sends each request when it is
due, whatever the server is doing, while the scheduler ticks in a thread
of its own; each request is timed from its due time to its answer.
``serve_p99_ms`` is the 99th percentile over every request of the window,
a refused or failed one counting as still waiting at the end of the run.

Set-up builds the tenant and warms every bucket shape of the scheduler.
A sample of answered requests, drawn from the seed, is compared with the
reference once the window has closed: it covers the batching, the padding
and the slicing as well as the search.
"""
from __future__ import annotations

import numpy as np

from perfbench.harness import loadgen, program
from perfbench.harness.runner import Check, Window

TENANT = "t0"


class Driver:
    def __init__(self, cell):
        self.cell = cell

    def setup(self) -> None:
        c, t = self.cell.config, self.cell.traffic
        ref = self.cell.ref
        self.pool = ref.make_queries(c, self.cell.seed, t["pool"])
        corpus = [ref.make_corpus(c, self.cell.seed, self.cell.devices)]
        # the tenant is built once; then the server holds the only copy
        self.server = program.search_server(lambda tenant: corpus.pop(), c, t,
                                            self.cell.devices)
        for bucket in self.server.scheduler.config.bucket_set():
            pending = [self._submit(i) for i in range(bucket)]
            while self.server.tick():
                pass
            for p in pending:
                p.result()

    def _submit(self, item: int):
        return self.server.submit(self.pool[item], k=self.cell.traffic["k"],
                                  tenant=TENANT)

    def load(self, rate_per_s: float, seconds: float,
             seed: int) -> loadgen.LoadResult:
        """One open-loop run at ``rate_per_s`` (the window, or one step of
        the knee sweep)."""
        schedule = loadgen.poisson(rate_per_s, seconds, self.pool.shape[0],
                                   seed)
        return loadgen.run_open_loop(self._submit, self.server.tick,
                                     schedule)

    def window(self, seconds: float) -> Window:
        res = self.load(self.cell.traffic["rate_per_s"], seconds,
                        self.cell.seed)
        self.result = res
        lat = res.latency_s()
        p99 = float(np.percentile(lat, 99)) * 1e3
        # where the tail came from: the seconds of the window in which
        # most requests waited over 100 ms
        slow = np.bincount((res.due[lat > 0.1] - res.start).astype(int),
                           minlength=int(seconds) + 1)
        worst = np.argsort(-slow)[:5]
        self.notes = {
            "p50_ms": float(np.percentile(lat, 50)) * 1e3, "p99_ms": p99,
            "max_ms": float(lat.max(initial=0.0)) * 1e3,
            "late_max_ms": float(res.late_s().max(initial=0.0)) * 1e3,
            "slow_seconds": [[int(w), int(slow[w])] for w in worst
                             if slow[w]]}
        return Window(res.start, res.end, res.attempted, res.failed,
                      {"serve_p99_ms": p99}, {"load": res})

    def release(self) -> None:
        del self.server

    def check(self):
        c, t = self.cell.config, self.cell.traffic
        res = self.result
        answered = np.nonzero(res.ok)[0]
        rng = np.random.default_rng([self.cell.seed, 3])
        pick = rng.choice(answered, min(c["checked_answers"], answered.size),
                          replace=False)
        got = [res.handles[i].result() for i in pick]
        scores = np.stack([g[0] for g in got])
        ids = np.stack([g[1] for g in got])
        queries = self.pool[res.item[pick]]
        ref = self.cell.ref
        corpus = ref.make_corpus(c, self.cell.seed, self.cell.devices)
        numbers = ref.compare(c, corpus, queries, ids, scores, t["k"])
        numbers["unanswered"] = float(res.failed)
        return [Check(name, numbers[name], limit)
                for name, limit in {**c["limits"],
                                    **t["limits"]}.items()]
