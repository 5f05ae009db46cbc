"""Per-kernel shape/dtype sweeps: Pallas (interpret mode on CPU) vs the
pure-jnp ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.label_prop.ops import label_prop_round
from repro.kernels.label_prop.ref import label_prop_round_ref
from repro.kernels.lsh_hamming.ops import hamming_topk
from repro.kernels.lsh_hamming.ref import hamming_topk_ref
from repro.kernels.topk_scoring.ops import (gathered_topk, topk_scores,
                                            topk_scores_int8)
from repro.kernels.topk_scoring.ref import (gathered_topk_ref,
                                            topk_scores_int8_ref,
                                            topk_scores_ref)
from repro.core.label_prop import ell_round


@pytest.mark.parametrize("q,n,d,k", [
    (16, 256, 32, 3), (64, 1000, 64, 8), (7, 513, 16, 5), (128, 4096, 128, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_scoring(q, n, d, k, dtype):
    key = jax.random.PRNGKey(q * n)
    qs = (jax.random.normal(key, (q, d)) - 0.3).astype(dtype)
    cs = (jax.random.normal(jax.random.PRNGKey(1), (n, d)) - 0.3).astype(dtype)
    s1, i1 = topk_scores(qs, cs, k=k, block_q=32, block_n=256)
    s2, i2 = topk_scores_ref(qs.astype(jnp.float32), cs.astype(jnp.float32), k=k)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-5)
    if dtype == jnp.float32:
        assert (np.asarray(i1) == np.asarray(i2)).all()


@pytest.mark.parametrize("q,n,d,k,use_kernel", [
    (3, 50, 16, 7, True),     # q below block_q floor, n below block_n floor
    (5, 40, 8, 60, True),     # k > 32 (kernel widens its tile), k > n
    (4, 8, 8, 33, True),      # k > 32 with k > n
    (3, 5, 8, 9, True),       # kernel path with k > n
    (3, 5, 8, 9, False),      # forced ref with k > n
])
def test_topk_scoring_odd_shapes(q, n, d, k, use_kernel):
    """Satellite: non-block-multiple k/N never crash the dispatch wrapper;
    the valid prefix matches the oracle and the k > N tail is -inf/-1."""
    key = jax.random.PRNGKey(q * n + k)
    qs = jax.random.normal(key, (q, d))
    cs = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    s, i = topk_scores(qs, cs, k=k, use_kernel=use_kernel)
    k_eff = min(k, n)
    s_ref, i_ref = topk_scores_ref(qs, cs, k=k_eff)
    assert s.shape == (q, k) and i.shape == (q, k)
    np.testing.assert_allclose(np.asarray(s)[:, :k_eff],
                               np.asarray(s_ref), rtol=1e-5, atol=1e-5)
    assert (np.asarray(i)[:, :k_eff] == np.asarray(i_ref)).all()
    assert (np.asarray(i)[:, k_eff:] == -1).all()
    assert np.isneginf(np.asarray(s)[:, k_eff:]).all()


@pytest.mark.parametrize("n,n_valid", [(1000, None), (3001, None),
                                         (3001, 2500), (3001, 7)])
def test_topk_scoring_ragged_and_row_bound(n, n_valid):
    """No corpus copy: N need not be a multiple of block_n (the kernel masks
    its ragged last block) and rows at or past ``n_valid`` are not
    candidates — the result is the oracle's over the first n_valid rows."""
    q, d, k = 19, 32, 10
    qs = jax.random.normal(jax.random.PRNGKey(n), (q, d))
    cs = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    s, i = topk_scores(qs, cs, k=k, n_valid=n_valid, block_q=8,
                       block_n=1024)
    m = n if n_valid is None else n_valid
    k_eff = min(k, m)
    s_ref, i_ref = topk_scores_ref(qs, cs[:m], k=k_eff)
    np.testing.assert_allclose(np.asarray(s)[:, :k_eff], np.asarray(s_ref),
                               rtol=1e-5, atol=1e-5)
    assert (np.asarray(i)[:, :k_eff] == np.asarray(i_ref)).all()
    assert (np.asarray(i)[:, k_eff:] == -1).all()
    assert np.isneginf(np.asarray(s)[:, k_eff:]).all()


@pytest.mark.parametrize("use_kernel", [True, False])
def test_topk_scoring_row_bound_all_negative(use_kernel):
    """Every real score negative and the rows past ``n_valid`` zero, as a
    row-sharded corpus pads its last shard: the zero rows would score 0 and
    win, so the bound alone keeps them out (a traced bound, as a shard
    computes it from its axis index)."""
    qs = jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (5, 16))) + 0.1
    real = -jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (2900, 16)))
    cs = jnp.concatenate([real, jnp.zeros((101, 16))])     # 3,001 rows
    s, i = jax.jit(lambda q, c, nv: topk_scores(
        q, c, k=10, n_valid=nv, block_n=1024, use_kernel=use_kernel))(
        qs, cs, jnp.int32(2900))
    s_ref, i_ref = topk_scores_ref(qs, real, k=10)
    assert (np.asarray(s) < 0).all()
    assert (np.asarray(i) == np.asarray(i_ref)).all()
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("q,n,d,k", [
    (16, 256, 32, 3), (64, 1000, 64, 8), (7, 513, 16, 5),
    (3, 50, 16, 7),           # q and n below the block floors
    (3, 5, 8, 9),             # odd-small shape, k > n (pad-row hazard)
    (5, 40, 8, 70),           # k > 64: a wider partial tile
])
def test_topk_scoring_int8(q, n, d, k):
    """int8 scoring kernel vs the int32-accumulate oracle.  Codes are drawn
    all-negative-capable so a zero-valued pad row would win without the
    kernel's n_valid masking (the same hazard as the sharded pad test)."""
    key = jax.random.PRNGKey(q * n + k)
    qc = jax.random.randint(key, (q, d), -127, 128, dtype=jnp.int8)
    cc = jax.random.randint(jax.random.PRNGKey(1), (n, d), -127, 128,
                            dtype=jnp.int8)
    s, i = topk_scores_int8(qc, cc, k=k)
    k_eff = min(k, n)
    s_ref, i_ref = topk_scores_int8_ref(qc, cc, k=k_eff)
    assert s.shape == (q, k) and i.shape == (q, k)
    np.testing.assert_allclose(np.asarray(s)[:, :k_eff], np.asarray(s_ref))
    assert (np.asarray(i)[:, :k_eff] == np.asarray(i_ref)).all()
    assert (np.asarray(i)[:, k_eff:] == -1).all()
    assert np.isneginf(np.asarray(s)[:, k_eff:]).all()


def test_topk_scoring_int8_all_negative():
    """Every true score negative: the padded tail must never be selected."""
    qc = -jnp.ones((4, 16), jnp.int8) * 3
    cc = jnp.abs(jax.random.randint(jax.random.PRNGKey(0), (37, 16), 1, 100)
                 ).astype(jnp.int8)
    s, i = topk_scores_int8(qc, cc, k=5)
    assert (np.asarray(s) < 0).all()
    assert (np.asarray(i) >= 0).all() and (np.asarray(i) < 37).all()


@pytest.mark.parametrize("q,n,w,k", [(5, 40, 2, 60), (3, 5, 2, 9),
                                     (37, 130, 3, 11)])
def test_lsh_hamming_odd_shapes(q, n, w, k):
    kq = jax.random.PRNGKey(q + k)
    qc = jax.random.randint(kq, (q, w), -2**31, 2**31 - 1, dtype=jnp.int32)
    cc = jax.random.randint(jax.random.PRNGKey(7), (n, w), -2**31,
                            2**31 - 1, dtype=jnp.int32)
    s, i = hamming_topk(qc, cc, k=k, block_q=32, block_n=256)
    k_eff = min(k, n)
    s_ref, _ = hamming_topk_ref(qc, cc, k=k_eff)
    assert i.shape == (q, k)
    np.testing.assert_allclose(np.asarray(s)[:, :k_eff], np.asarray(s_ref))
    assert (np.asarray(i)[:, k_eff:] == -1).all()


@pytest.mark.parametrize("q,c,d,k", [
    (7, 100, 16, 5), (3, 513, 8, 10), (1, 40, 4, 45), (9, 257, 8, 32),
])
def test_gathered_topk(q, c, d, k):
    """Per-query candidate kernel (the ivfflat probe-scoring step) vs the
    jnp oracle, with -1 holes in the candidate lists and odd shapes."""
    key = jax.random.PRNGKey(q * c)
    qs = jax.random.normal(key, (q, d))
    cv = jax.random.normal(jax.random.PRNGKey(2), (q, c, d))
    ci = jax.random.randint(jax.random.PRNGKey(3), (q, c), -1, 10_000,
                            dtype=jnp.int32)
    s, i = gathered_topk(qs, cv, ci, k=k)
    k_eff = min(k, c)
    s_ref, i_ref = gathered_topk_ref(qs, cv, ci, k=k_eff)
    np.testing.assert_allclose(np.asarray(s)[:, :k_eff], np.asarray(s_ref),
                               rtol=1e-5, atol=1e-5)
    assert (np.asarray(i)[:, :k_eff] == np.asarray(i_ref)).all()
    assert (np.asarray(i)[:, k_eff:] == -1).all()


@pytest.mark.parametrize("b,s,h,hkv,d", [
    (2, 64, 4, 2, 32), (1, 128, 8, 8, 64), (2, 96, 4, 1, 32), (1, 200, 4, 2, 16),
])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 40), (False, None)])
def test_flash_attention(b, s, h, hkv, d, causal, window):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, s, h, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, hkv, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, hkv, d))
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=32, block_kv=32)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (2, 64, 4, 32)).astype(dtype)
    k = jax.random.normal(jax.random.PRNGKey(4), (2, 64, 2, 32)).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(5), (2, 64, 2, 32)).astype(dtype)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_kv=32)
    ref = flash_attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32), causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("n,kdeg", [(64, 4), (300, 12), (1000, 7)])
def test_label_prop_kernel(n, kdeg):
    key = jax.random.PRNGKey(n)
    nbr = jax.random.randint(key, (n, kdeg), -1, n)
    wgt = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (n, kdeg)))
    labels = jnp.arange(n, dtype=jnp.int32)
    out_k = label_prop_round(labels, nbr, wgt, block_n=64)
    lab = jnp.where(nbr >= 0, labels[jnp.maximum(nbr, 0)], -1)
    out_r = label_prop_round_ref(lab, wgt, labels)
    out_c = ell_round(labels, nbr, wgt)
    assert (np.asarray(out_k) == np.asarray(out_r)).all()
    assert (np.asarray(out_k) == np.asarray(out_c)).all()


@pytest.mark.parametrize("q,n,w,k", [(16, 512, 4, 3), (37, 1111, 8, 5),
                                     (128, 2048, 2, 10)])
def test_lsh_hamming(q, n, w, k):
    kq = jax.random.PRNGKey(q)
    qc = jax.random.randint(kq, (q, w), -2**31, 2**31 - 1, dtype=jnp.int32)
    cc = jax.random.randint(jax.random.PRNGKey(7), (n, w), -2**31, 2**31 - 1,
                            dtype=jnp.int32)
    s1, i1 = hamming_topk(qc, cc, k=k, block_q=32, block_n=256)
    s2, i2 = hamming_topk_ref(qc, cc, k=k)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2))
    # distances equal => id sets equal per query (ties may reorder)
    for a, b in zip(np.asarray(s1), np.asarray(s2)):
        assert (a == b).all()
