"""Retrieval substrate + training substrate tests: index recall, metrics,
optimizers, checkpoint/restore (incl. elastic re-shard), compression."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.compression import (compress_leaf, dequantize_int8,
                                           ef_init, quantize_int8)
from repro.retrieval.exact import exact_topk
from repro.retrieval.ivfflat import build_ivfflat, search_ivfflat
from repro.retrieval.lsh import build_lsh, search_lsh, popcount32
from repro.retrieval.metrics import precision_at_k, qrel_set
from repro.train.checkpoint import (AsyncCheckpointer, latest_step,
                                    restore_checkpoint, save_checkpoint)
from repro.train.optimizer import (AdamWConfig, AdafactorConfig, adamw_init,
                                   adamw_update, adafactor_init,
                                   adafactor_update)


@pytest.fixture(scope="module")
def vectors():
    key = jax.random.PRNGKey(0)
    corpus = jax.random.normal(key, (1500, 32))
    corpus = corpus / jnp.linalg.norm(corpus, axis=1, keepdims=True)
    queries = corpus[:40] + 0.05 * jax.random.normal(jax.random.PRNGKey(1),
                                                     (40, 32))
    full = np.asarray(queries @ corpus.T)
    gt = np.argsort(-full, axis=1)[:, :5]
    return corpus, queries, gt


def test_exact_topk_is_exact(vectors):
    corpus, queries, gt = vectors
    _, ids = exact_topk(queries, corpus, k=5, block=256)
    assert (np.asarray(ids) == gt).all()


def test_ivfflat_recall(vectors):
    corpus, queries, gt = vectors
    idx = build_ivfflat(jax.random.PRNGKey(0), corpus, n_lists=32)
    _, ids = search_ivfflat(idx, queries, k=5, nprobe=16)
    rec = np.mean([len(set(a.tolist()) & set(b.tolist())) / 5
                   for a, b in zip(np.asarray(ids), gt)])
    assert rec > 0.7


def test_ivfflat_full_probe_is_exact(vectors):
    corpus, queries, gt = vectors
    idx = build_ivfflat(jax.random.PRNGKey(0), corpus, n_lists=8)
    _, ids = search_ivfflat(idx, queries, k=5, nprobe=8)
    assert (np.sort(np.asarray(ids), 1) == np.sort(gt, 1)).all()


def test_lsh_rerank_recall(vectors):
    corpus, queries, gt = vectors
    idx = build_lsh(jax.random.PRNGKey(0), corpus, n_bits=128)
    _, ids = search_lsh(idx, queries, k=5, rerank=80)
    rec = np.mean([len(set(a.tolist()) & set(b.tolist())) / 5
                   for a, b in zip(np.asarray(ids), gt)])
    assert rec > 0.6


def test_popcount():
    x = jnp.asarray([0, 1, 3, -1, 2**30], jnp.int32)
    assert popcount32(x).tolist() == [0, 1, 2, 32, 1]


def test_precision_at_k():
    qrels = {(0, 10), (0, 11), (1, 20)}
    retrieved = np.array([[10, 11, 99], [20, 21, 22]])
    p = precision_at_k(retrieved, np.array([0, 1]), qrels, k=3)
    assert abs(p - 3 / 6) < 1e-9


def _toy_params(key):
    k1, k2 = jax.random.split(key)
    return {"w": jax.random.normal(k1, (8, 4)), "b": jnp.zeros((4,))}


def test_adamw_descends():
    params = _toy_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    y = jax.random.normal(jax.random.PRNGKey(2), (16, 4))
    loss = lambda p: jnp.mean((x @ p["w"] + p["b"] - y) ** 2)
    cfg = AdamWConfig(lr=3e-2, warmup_steps=1, total_steps=200,
                      weight_decay=0.0)
    state = adamw_init(params)
    l0 = float(loss(params))
    for _ in range(100):
        g = jax.grad(loss)(params)
        params, state, _ = adamw_update(g, state, params, cfg)
    assert float(loss(params)) < 0.3 * l0


def test_adafactor_descends_and_is_factored():
    params = _toy_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    y = jax.random.normal(jax.random.PRNGKey(2), (16, 4))
    loss = lambda p: jnp.mean((x @ p["w"] + p["b"] - y) ** 2)
    cfg = AdafactorConfig(lr=2e-1, warmup_steps=1, total_steps=300)
    state = adafactor_init(params)
    assert state["slots"]["w"]["vr"].shape == (8,)    # factored moments
    assert state["slots"]["w"]["vc"].shape == (4,)
    l0 = float(loss(params))
    for _ in range(150):
        g = jax.grad(loss)(params)
        params, state, _ = adafactor_update(g, state, params, cfg)
    assert float(loss(params)) < 0.5 * l0


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "nested": {"b": jnp.ones((4,), jnp.int32)}}
    save_checkpoint(str(tmp_path), 7, tree)
    assert latest_step(str(tmp_path)) == 7
    restored, step = restore_checkpoint(str(tmp_path), tree)
    assert step == 7
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree["a"]))


def test_checkpoint_atomicity(tmp_path):
    """A crashed writer must never corrupt the published checkpoint."""
    tree = {"a": jnp.ones((3,))}
    save_checkpoint(str(tmp_path), 1, tree)
    # simulate a stale tmp dir from a crashed writer
    os.makedirs(os.path.join(str(tmp_path), "step_0000000002.tmp"))
    assert latest_step(str(tmp_path)) == 1
    restored, _ = restore_checkpoint(str(tmp_path), tree)
    assert float(restored["a"].sum()) == 3.0


def test_async_checkpointer(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path), keep=2)
    tree = {"a": jnp.ones((3,))}
    for step in (1, 2, 3):
        ck.save(step, jax.tree.map(lambda x: x * step, tree))
    ck.close()
    assert latest_step(str(tmp_path)) == 3
    restored, _ = restore_checkpoint(str(tmp_path), tree, step=3)
    assert float(restored["a"][0]) == 3.0


def test_elastic_reshard_roundtrip(tmp_path):
    """Checkpoint written under one mesh restores under a different mesh
    (elastic re-mesh resume): values identical, shardings re-applied."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh1 = jax.make_mesh((1,), ("data",))
    tree = {"w": jnp.arange(8.0).reshape(4, 2)}
    with mesh1:
        sharded = jax.device_put(tree["w"], NamedSharding(mesh1, P("data")))
    save_checkpoint(str(tmp_path), 5, {"w": sharded})
    mesh2 = jax.make_mesh((1, 1), ("data", "model"))
    shardings = {"w": NamedSharding(mesh2, P("model", None))}
    restored, _ = restore_checkpoint(str(tmp_path), tree, shardings=shardings)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))


def test_int8_error_feedback_compression():
    g = jnp.asarray(np.random.default_rng(0).normal(size=(64,)) * 1e-3,
                    jnp.float32)
    err = jnp.zeros_like(g)
    # accumulated dequantized updates converge to the true gradient sum
    total_sent = jnp.zeros_like(g)
    for _ in range(50):
        q, scale, err = compress_leaf(g, err)
        total_sent = total_sent + dequantize_int8(q, scale)
    np.testing.assert_allclose(np.asarray(total_sent / 50), np.asarray(g),
                               atol=float(jnp.abs(g).max()) * 0.02)


def test_quantize_int8_bounds():
    x = jnp.asarray([-3.0, 0.0, 5.0])
    q, scale = quantize_int8(x)
    assert q.dtype == jnp.int8
    np.testing.assert_allclose(np.asarray(dequantize_int8(q, scale)),
                               np.asarray(x), atol=float(scale))
