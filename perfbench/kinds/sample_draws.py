"""Traffic kind ``sample_draws``: repeated draws from one staged session.

Set-up stages the graph and the labels once (the draws need them); each
iteration of the window is then one ``SamplerSession.draw`` at a fresh seed
and the traffic's ``target_size`` — the repeated draws an IR team makes to
see a sample's variance.  Graph build and label propagation are bypassed.
``sample_s`` is the window over the draws completed in it.

The session caches every draw it has made; the window leaves that cache
as a user's loop would meet it, so its memory shows in the run's peak.
A sample of the draws, chosen by the seed, is kept and compared with the
reference, with the staged graph and labels.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from perfbench.harness import program
from perfbench.harness.runner import Check, Window


class Driver:
    def __init__(self, cell):
        self.cell = cell

    def setup(self) -> None:
        c = self.cell.config
        t0 = time.perf_counter()
        self.inputs = self.cell.ref.make_inputs(c, self.cell.seed)
        self.table = program.qrel_table(self.inputs)
        self.session = program.sampler_session(
            self.table, c, program.draw_seed(self.cell.seed, 0))
        t1 = time.perf_counter()
        edges, self.degrees = self.session.graph()
        self.edges = tuple(edges)
        self.labels = self.session.labels()[0]
        self._draw(program.draw_seed(self.cell.seed, 0))   # warm the draw
        self.notes = {"inputs_s": t1 - t0,
                      "stage_s": time.perf_counter() - t1}

    def _draw(self, seed: int):
        d = self.session.draw(target_size=self.cell.traffic["target_size"],
                              seed=seed)
        jax.block_until_ready((d.entity_mask, d.reconstructed.query_mask))
        return d

    def window(self, seconds: float) -> Window:
        ref = self.cell.ref
        t = self.cell.traffic
        pick = np.random.default_rng([self.cell.seed, 2])
        kept, n = [], 0
        start = time.perf_counter()
        while True:
            n += 1
            seed = program.draw_seed(self.cell.seed, n)
            d = self._draw(seed)
            if len(kept) < t["checked_draws"] and \
                    pick.random() < t["checked_share"]:
                kept.append(ref.Draw(seed, d.entity_mask, d.sample.keep_prob,
                                     d.reconstructed.query_mask))
            end = time.perf_counter()
            if end - start >= seconds:
                break
        if not kept:                       # always check the last draw
            kept.append(ref.Draw(seed, d.entity_mask, d.sample.keep_prob,
                                 d.reconstructed.query_mask))
        self.kept = kept
        return Window(start, end, n, 0, {"sample_s": (end - start) / n},
                      {"draws": n})

    def release(self) -> None:
        del self.session, self.table

    def check(self):
        ref = self.cell.ref
        run = ref.Run(self.edges, self.degrees, self.labels, self.kept)
        numbers = ref.Reference(self.cell.config, self.inputs).compare(
            [run], self.cell.traffic["target_size"])
        return [Check(name, numbers[name], limit)
                for name, limit in self.cell.config["limits"].items()]
