"""With the timed path broken underneath, a run through the harness comes
out not correct: once for each fault a cell can have (a step that returns
its state unchanged, half of the batch left out, an answer altered where it
is produced, and on the four-device search cell the exchange between chips
left out)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.tests.conftest import run_small


@pytest.fixture(autouse=True)
def fresh_programs():
    """Faults are planted in traced Python: drop compiled programs so the
    broken path is traced, and again so it does not outlive the test."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _failed(line, *names):
    assert line["correct"] is False
    bad = {n for n, c in line["checks"].items() if c["value"] > c["limit"]}
    assert bad & set(names), line["checks"]


def test_lp_round_returning_its_labels_unchanged(small_root, monkeypatch):
    from repro.core import engines
    monkeypatch.setattr(engines.PallasEngine, "round",
                        lambda self, labels, state: labels)
    _failed(run_small(small_root, "sample.msmarco.job"), "labels_diff")


def test_half_the_judgments_left_out(small_root, monkeypatch):
    from repro.core import sampling_core
    real = sampling_core._graph_stage

    def half(qrels, **kw):
        n = qrels.valid.shape[0]
        keep = qrels.valid & (jnp.arange(n) < n // 2)
        return real(qrels._replace(valid=keep), **kw)

    monkeypatch.setattr(sampling_core, "_graph_stage", half)
    _failed(run_small(small_root, "sample.msmarco.job"), "edges_diff",
            "degrees_diff")


@pytest.mark.parametrize("cell", ["sample.msmarco.job",
                                  "sample.msmarco.draws"])
def test_a_drawn_entity_altered(small_root, monkeypatch, cell):
    from repro.core import sampling_core
    real = sampling_core._draw_stage

    def altered(qrels, labels, degrees, seed, **kw):
        out = real(qrels, labels, degrees, seed, **kw)
        e = int(jnp.argmax(degrees))               # a node of the graph
        mask = out.entity_mask.at[e].set(~out.entity_mask[e])
        return out._replace(entity_mask=mask)

    monkeypatch.setattr(sampling_core, "_draw_stage", altered)
    _failed(run_small(small_root, cell), "mask_diff")


def _break_search(monkeypatch, how):
    from repro.retrieval import search_core
    real = search_core.SearchSession._search_chunk

    def broken(self, queries, k):
        scores, ids = real(self, queries, k)
        scores, ids = scores.copy(), ids.copy()
        if how == "half":            # the batch's second half left out
            h = (queries.shape[0] + 1) // 2
            scores[h:] = scores[:queries.shape[0] - h]
            ids[h:] = ids[:queries.shape[0] - h]
        else:                        # each query's best answer altered
            ids[:, 0] = (ids[:, 0] + 1) % self.corpus_size
        return scores, ids

    monkeypatch.setattr(search_core.SearchSession, "_search_chunk", broken)


@pytest.mark.parametrize("how", ["half", "altered"])
@pytest.mark.parametrize("cell", ["search.dense768.batch",
                                  "serve.dense768.open"])
def test_search_answers_broken(small_root, monkeypatch, cell, how):
    _break_search(monkeypatch, how)
    _failed(run_small(small_root, cell), "topk_miss", "score_gap")


def drop_one_shard():
    """Planted in the child run: the merge of the chips' partial top-k
    lists leaves the last chip's list out."""
    from jax import lax
    from repro.retrieval import sharded

    def merge(s, i, axes, k):
        w = s.shape[1]
        s = lax.all_gather(s, axes, axis=1, tiled=True)[:, :-w]
        i = lax.all_gather(i, axes, axis=1, tiled=True)[:, :-w]
        top_s, pos = lax.top_k(s, min(k, s.shape[1]))
        return top_s, jnp.take_along_axis(i, pos, axis=1)

    sharded._merge = merge


def test_exchange_between_chips_left_out(small_root):
    _failed(run_small(small_root, "search.dense768.mesh4",
                      plant=f"{__name__}:drop_one_shard"), "topk_miss")
