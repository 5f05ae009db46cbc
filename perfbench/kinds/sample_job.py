"""Traffic kind ``sample_job``: whole sampling jobs, one after another.

Each job goes through the front door as a user's would: a new
``SamplerSession`` over the judgments, ``labels()`` (graph build and label
propagation), then one ``draw()`` at the configuration's sample fraction.
A closed loop of one client: the next job starts when the last has ended.
``sample_s`` is the window over the jobs completed in it; the window closes
at the end of the last job, so no partial job is counted.

Every job's degrees, labels and draw are compared with the reference, and
one job's edges (chosen by the seed).
"""
from __future__ import annotations

import time

import jax

from perfbench.harness import program
from perfbench.harness.runner import Check, Window


class Driver:
    def __init__(self, cell):
        self.cell = cell
        self.runs = []

    def setup(self) -> None:
        c = self.cell.config
        t0 = time.perf_counter()
        self.inputs = self.cell.ref.make_inputs(c, self.cell.seed)
        self.table = program.qrel_table(self.inputs)
        self.draw_seed = program.draw_seed(self.cell.seed, 0)
        t1 = time.perf_counter()
        self._job(keep_edges=False)       # loads or compiles every program
        self.notes = {"inputs_s": t1 - t0,
                      "warm_job_s": time.perf_counter() - t1}

    def _job(self, keep_edges: bool):
        ref = self.cell.ref
        session = program.sampler_session(self.table, self.cell.config,
                                          self.draw_seed)
        labels = session.labels()[0]
        draw = session.draw()
        jax.block_until_ready((labels, draw.entity_mask,
                               draw.reconstructed.query_mask))
        edges, degrees = session.graph()
        return ref.Run(tuple(edges) if keep_edges else None, degrees, labels,
                       [ref.Draw(self.draw_seed, draw.entity_mask,
                                 draw.sample.keep_prob,
                                 draw.reconstructed.query_mask)])

    def window(self, seconds: float) -> Window:
        pick = self.cell.seed % 3          # the job whose edges are kept
        runs = []
        start = last = time.perf_counter()
        while True:
            run = self._job(keep_edges=len(runs) <= pick)
            if run.edges is not None and runs:
                runs[-1] = runs[-1]._replace(edges=None)
            runs.append(run)
            end = time.perf_counter()
            self.notes.setdefault("job_s", []).append(end - last)
            last = end
            if end - start >= seconds:
                break
        self.runs = runs
        return Window(start, end, len(runs), 0,
                      {"sample_s": (end - start) / len(runs)},
                      {"jobs": len(runs)})

    def release(self) -> None:
        del self.table

    def check(self):
        ref = self.cell.ref.Reference(self.cell.config, self.inputs)
        numbers = ref.compare(self.runs, self.cell.config["sample_fraction"])
        return [Check(name, numbers[name], limit)
                for name, limit in self.cell.config["limits"].items()]
