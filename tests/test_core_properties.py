"""Property-based tests (hypothesis) for the WindTunnel core invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis",
                    reason="hypothesis not installed; see requirements.txt")
from hypothesis import given, settings, strategies as st

from repro.core import graph_builder as gb
from repro.core import label_prop as lp
from repro.core.yule_simon import fit_em

settings.register_profile("ci", max_examples=20, deadline=None)
settings.load_profile("ci")


@st.composite
def qrel_tables(draw):
    n = draw(st.integers(8, 64))
    nq = draw(st.integers(2, 10))
    ne = draw(st.integers(2, 20))
    rng = np.random.default_rng(draw(st.integers(0, 2**31)))
    q = rng.integers(0, nq, n).astype(np.int32)
    e = rng.integers(0, ne, n).astype(np.int32)
    s = rng.random(n).astype(np.float32)
    valid = rng.random(n) < 0.9
    return gb.QRelTable(jnp.asarray(q), jnp.asarray(e), jnp.asarray(s),
                        jnp.asarray(valid)), nq, ne


@given(qrel_tables())
def test_affinity_graph_invariants(data):
    """Alg. 1 invariants: canonical orientation, dedup, affinity = min rule,
    affinity bounded by the member scores."""
    qrels, nq, ne = data
    edges = gb.build_affinity_graph(qrels, num_queries=nq,
                                    tau_quantile=0.0, fanout=8)
    u = np.asarray(edges.u)[np.asarray(edges.valid)]
    v = np.asarray(edges.v)[np.asarray(edges.valid)]
    w = np.asarray(edges.w)[np.asarray(edges.valid)]
    assert (u < v).all()                       # canonical orientation
    pairs = list(zip(u.tolist(), v.tolist()))
    assert len(pairs) == len(set(pairs))       # dedup
    assert (w >= 0).all() and (u >= 0).all() and (v.max(initial=-1) < ne)

    # brute-force oracle over the same (thresholded, fanout-capped) table
    q = np.asarray(qrels.query_ids)
    e = np.asarray(qrels.entity_ids)
    s = np.asarray(qrels.scores)
    val = np.asarray(qrels.valid)
    if val.any():   # the paper's strict 's > tau' drops the minimum too
        tau = np.quantile(s[val], 0.0)
        val = val & (s > tau)
    best = {}
    for qi in range(nq):
        rows = np.nonzero(val & (q == qi))[0]
        rows = rows[np.argsort(-s[rows], kind="stable")][:8]
        for i in range(len(rows)):
            for j in range(len(rows)):
                if i == j:
                    continue
                e1, e2 = e[rows[i]], e[rows[j]]
                if e1 == e2:
                    continue
                key = (min(e1, e2), max(e1, e2))
                aff = min(s[rows[i]], s[rows[j]])
                best[key] = max(best.get(key, -1.0), aff)
    got = dict(zip(pairs, w.tolist()))
    assert set(got) == set(best)
    for k in best:
        assert abs(got[k] - best[k]) < 1e-5


@given(st.integers(0, 2**31), st.integers(2, 6), st.integers(1, 12),
       st.booleans())
def test_dedup_keeps_valid_edges_within_the_row_bound(seed, fanout, nq,
                                                      full):
    """``dedup_edges`` puts every valid edge in the first
    rows (fanout - 1) // 2 slots, the prefix label propagation slices to
    (``lp.lp_caps``); ``full`` gives each query exactly ``fanout``
    distinct entities, where the bound is all but tight."""
    rng = np.random.default_rng(seed)
    if full:
        q = np.repeat(np.arange(nq), fanout)
        e = np.concatenate([rng.choice(3 * fanout, fanout, replace=False)
                            for _ in range(nq)])
    else:
        n = int(rng.integers(1, 8 * nq + 1))
        q = rng.integers(0, nq, n)
        e = rng.integers(0, 4 * fanout, n)
    s = rng.random(q.size).astype(np.float32)
    qrels = gb.QRelTable(jnp.asarray(q, jnp.int32), jnp.asarray(e, jnp.int32),
                         jnp.asarray(s), jnp.asarray(rng.random(q.size) < 0.9))
    edges = gb.build_affinity_graph(qrels, num_queries=nq,
                                    tau_quantile=0.0, fanout=fanout)
    bound = q.size * (fanout - 1) // 2
    valid = np.asarray(edges.valid)
    assert not valid[bound:].any()
    _, edge_cap = lp.lp_caps(rows=q.size, fanout=fanout,
                             num_nodes=4 * fanout, edge_slots=valid.size)
    assert not valid[edge_cap:].any()
    assert valid.sum() <= edge_cap


@given(st.integers(0, 2**31), st.integers(10, 60), st.integers(2, 6))
def test_label_prop_engines_agree(seed, n_edges, max_deg):
    """Sort-based and ELL label propagation agree when no edges are dropped
    by the degree cap."""
    rng = np.random.default_rng(seed)
    n_nodes = 16
    u = rng.integers(0, n_nodes, n_edges).astype(np.int32)
    v = rng.integers(0, n_nodes, n_edges).astype(np.int32)
    keep = u != v
    u, v = u[keep], v[keep]
    # dedup so degree cap can be exact
    pairs = sorted({(min(a, b), max(a, b)) for a, b in zip(u, v)})
    if not pairs:
        return
    u = np.array([p[0] for p in pairs], np.int32)
    v = np.array([p[1] for p in pairs], np.int32)
    w = rng.random(u.size).astype(np.float32) + 0.1
    edges = gb.EdgeList(jnp.asarray(u), jnp.asarray(v), jnp.asarray(w),
                        jnp.ones(u.size, bool))
    src, dst, ww, valid = gb.symmetrize(edges)
    res_sort = lp.propagate(src, dst, ww, valid, num_nodes=n_nodes, rounds=3)
    nbr, wgt = lp.edges_to_ell(src, dst, ww, valid, num_nodes=n_nodes,
                               max_degree=n_nodes)
    res_ell = lp.propagate_ell(nbr, wgt, rounds=3)
    assert (np.asarray(res_sort.labels) == np.asarray(res_ell.labels)).all()


@given(st.floats(0.8, 3.0), st.integers(0, 2**31))
def test_yule_simon_em_recovers_rho(rho, seed):
    rng = np.random.default_rng(seed)
    wts = rng.exponential(1.0 / rho, 20000)
    k = rng.geometric(np.exp(-wts))
    fit = fit_em(jnp.asarray(k), max_iters=300)
    assert abs(float(fit.rho) - rho) / rho < 0.15
    assert float(fit.stderr) > 0
