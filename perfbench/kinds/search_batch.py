"""Traffic kind ``search_batch``: the experiment grid's evaluation pass.

Set-up builds one ``SearchSession`` over the configuration's corpus, made
on the cell's devices (over several, row-sharded from birth).  Each
pass of the window sends the traffic's whole query set through
``SearchSession.search_scored`` in chunks of ``query_chunk`` at top-``k``,
pass after pass (a closed loop of one client).  ``search_qps`` is the
queries answered over the window, which closes at the end of the last
whole pass.

A sample of answers, (pass, query) pairs drawn from the seed, is compared
with the reference once the window has closed.
"""
from __future__ import annotations

import time

import numpy as np

from perfbench.harness import program
from perfbench.harness.runner import Check, Window


class Driver:
    def __init__(self, cell):
        self.cell = cell

    def setup(self) -> None:
        from repro.retrieval.search_core import SearchSession
        c, t = self.cell.config, self.cell.traffic
        ref = self.cell.ref
        self.queries = ref.make_queries(c, self.cell.seed, t["queries"])
        devices = self.cell.devices
        corpus = program.search_corpus(
            ref.make_corpus(c, self.cell.seed, devices), c, devices)
        self.session = SearchSession(corpus, program.search_config(
            c, t["query_chunk"], devices))
        del corpus
        self._pass()                 # warms every chunk shape of a pass

    def _pass(self):
        return self.session.search_scored(self.queries,
                                          k=self.cell.traffic["k"])

    def window(self, seconds: float) -> Window:
        t = self.cell.traffic
        passes = []
        start = time.perf_counter()
        while True:
            passes.append(self._pass())
            end = time.perf_counter()
            if end - start >= seconds:
                break
        self.passes = passes
        n = len(passes) * t["queries"]
        chunks = [min(t["query_chunk"], t["queries"] - lo)
                  for lo in range(0, t["queries"], t["query_chunk"])]
        return Window(start, end, n, 0, {"search_qps": n / (end - start)},
                      {"passes": len(passes), "chunk_rows": chunks})

    def release(self) -> None:
        del self.session

    def check(self):
        c, t = self.cell.config, self.cell.traffic
        rng = np.random.default_rng([self.cell.seed, 3])
        size = min(c["checked_answers"], len(self.passes) * t["queries"])
        pick = rng.choice(len(self.passes) * t["queries"], size,
                          replace=False)
        p, q = np.divmod(pick, t["queries"])
        scores = np.stack([self.passes[a][0][b] for a, b in zip(p, q)])
        ids = np.stack([self.passes[a][1][b] for a, b in zip(p, q)])
        ref = self.cell.ref
        corpus = ref.make_corpus(c, self.cell.seed, self.cell.devices)
        numbers = ref.compare(c, corpus, self.queries[q], ids, scores, t["k"])
        return [Check(name, numbers[name], limit)
                for name, limit in c["limits"].items()]
