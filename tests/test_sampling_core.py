"""Sampling-core tests (DESIGN.md §10): strategy registry, the
build-once/draw-many SamplerSession, sweep stage counters, bit-parity with
the legacy one-shot entry points (single-device and 1-device mesh), the
associated-queries / reconstructor cross-check, and the CLI registry-error
contract shared by launch/sample.py and launch/evaluate.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (QRelTable, SamplerSession, SamplerSpec,
                        WindTunnelConfig, associated_queries,
                        available_samplers, get_sampler, reconstruct,
                        run_uniform_baseline, run_windtunnel,
                        run_windtunnel_sharded)
from repro.core import engines as eng
from repro.core import graph_builder as gb
from repro.core import sampler as sm
from repro.core.samplers import SamplerStrategy, judged_entities
from repro.data.synthetic import generate_corpus
from repro.launch.mesh import make_host_mesh


@pytest.fixture(scope="module")
def corpus():
    return generate_corpus(num_queries=96, qrels_per_query=8, num_topics=10,
                           aux_fraction=0.3, seed=0, vocab_size=256)


@pytest.fixture(scope="module")
def qrels(corpus):
    return QRelTable(*(jnp.asarray(x) for x in corpus.qrels))


def _spec(corpus, **kw):
    kw.setdefault("fanout", 8)
    kw.setdefault("lp_rounds", 4)
    kw.setdefault("max_degree", corpus.num_entities)
    kw.setdefault("target_size", 0.3 * corpus.num_primary)
    return SamplerSpec(**kw)


def _session(corpus, qrels, **kw):
    return SamplerSession(qrels, num_queries=corpus.num_queries,
                          num_entities=corpus.num_entities,
                          spec=_spec(corpus, **kw))


# ---------------------------------------------------------------------------
# strategy registry
# ---------------------------------------------------------------------------

def test_registry_contents():
    assert {"full", "uniform", "windtunnel",
            "degree_stratified"} <= set(available_samplers())
    for name in available_samplers():
        assert isinstance(get_sampler(name), SamplerStrategy)


def test_unknown_strategy_raises_with_registered_names():
    with pytest.raises(ValueError, match="registered strategies"):
        get_sampler("stratified-by-vibes")


def test_session_validates_registries_up_front(corpus, qrels):
    with pytest.raises(ValueError, match="registered strategies"):
        _session(corpus, qrels, strategy="nope")
    with pytest.raises(ValueError, match="registered engines"):
        _session(corpus, qrels, engine="spark")
    with pytest.raises(ValueError, match="needs a mesh"):
        _session(corpus, qrels, engine="ell", sharded=True)
    with pytest.raises(ValueError, match="ELL-family"):
        _session(corpus, qrels, sharded=True, mesh=make_host_mesh())


# ---------------------------------------------------------------------------
# sweep cache: graph + LP execute exactly once for an S x R sweep
# ---------------------------------------------------------------------------

def test_sweep_stages_graph_and_lp_exactly_once(corpus, qrels):
    session = _session(corpus, qrels)
    sizes = [0.2 * corpus.num_primary, 0.3 * corpus.num_primary,
             0.4 * corpus.num_primary]
    sweep = session.sweep(sizes, [0, 1, 2])
    assert len(sweep.draws) == 9
    counts = session.stage_counts()
    assert counts["graph"][0] == 1
    assert counts["labels"][0] == 1
    assert counts["draw"] == (9, 9)
    # every draw requested the staged prefixes (the PlanTrie reading)
    assert counts["graph"][1] >= 9 and counts["labels"][1] >= 9
    js = sweep.to_json()
    assert js["stage_counts"]["labels"]["executions"] == 1
    assert len(js["draws"]) == 9


def test_draws_distinct_seeds_differ_and_cache_hits(corpus, qrels):
    session = _session(corpus, qrels)
    d0 = session.draw(seed=0)
    d1 = session.draw(seed=1)
    assert (np.asarray(d0.entity_mask) != np.asarray(d1.entity_mask)).any()
    assert session.draw(seed=0) is d0          # cached, not recomputed
    assert session.stage_counts()["draw"] == (2, 3)


def test_identical_sessions_are_bit_equal(corpus, qrels):
    a = _session(corpus, qrels).draw(seed=5)
    b = _session(corpus, qrels).draw(seed=5)
    assert (np.asarray(a.entity_mask) == np.asarray(b.entity_mask)).all()
    assert (np.asarray(a.reconstructed.qrels.valid) ==
            np.asarray(b.reconstructed.qrels.valid)).all()


# ---------------------------------------------------------------------------
# parity with the legacy entry points (the acceptance criterion)
# ---------------------------------------------------------------------------

def test_sweep_draws_bit_equal_fresh_run_windtunnel(corpus, qrels):
    """Each (size, seed) cell of a 3x3 sweep matches a fresh one-shot
    run_windtunnel at the same config bit-for-bit."""
    session = _session(corpus, qrels)
    sizes = [0.2 * corpus.num_primary, 0.3 * corpus.num_primary,
             0.4 * corpus.num_primary]
    seeds = [0, 1, 2]
    sweep = session.sweep(sizes, seeds)
    for size in sizes:
        for seed in seeds:
            cfg = WindTunnelConfig(fanout=8, lp_rounds=4,
                                   max_degree=corpus.num_entities,
                                   target_size=size, seed=seed)
            ref = jax.jit(lambda q, cfg=cfg: run_windtunnel(
                q, num_queries=corpus.num_queries,
                num_entities=corpus.num_entities, config=cfg))(qrels)
            draw = sweep.draws[(float(size), seed)]
            assert (np.asarray(draw.entity_mask) ==
                    np.asarray(ref.sample.entity_mask)).all(), (size, seed)
            assert (np.asarray(draw.reconstructed.query_mask) ==
                    np.asarray(ref.reconstructed.query_mask)).all()
    assert session.stage_counts()["labels"][0] == 1


def test_run_windtunnel_wrapper_matches_manual_pipeline(corpus, qrels):
    """Wrapper parity: run_windtunnel equals the historical inline
    composition graph -> LP -> cluster_sample -> reconstruct bit-for-bit."""
    cfg = WindTunnelConfig(fanout=8, lp_rounds=4,
                           max_degree=corpus.num_entities,
                           target_size=0.3 * corpus.num_primary, seed=0)

    def manual(q):
        edges = gb.build_affinity_graph(
            q, num_queries=corpus.num_queries,
            tau_quantile=cfg.tau_quantile, fanout=cfg.fanout)
        degrees = gb.node_degrees(edges, corpus.num_entities)
        src, dst, w, valid = gb.symmetrize(edges)
        lp_res = eng.run_engine(eng.get_engine(cfg.engine), src, dst, w,
                                valid, num_nodes=corpus.num_entities,
                                max_degree=cfg.max_degree,
                                rounds=cfg.lp_rounds)
        sample = sm.cluster_sample(lp_res.labels,
                                   jax.random.PRNGKey(cfg.seed),
                                   num_nodes=corpus.num_entities,
                                   target_size=cfg.target_size,
                                   eligible=degrees > 0)
        return lp_res.labels, sample.entity_mask

    labels_ref, mask_ref = jax.jit(manual)(qrels)
    res = jax.jit(lambda q: run_windtunnel(
        q, num_queries=corpus.num_queries,
        num_entities=corpus.num_entities, config=cfg))(qrels)
    assert (np.asarray(res.labels) == np.asarray(labels_ref)).all()
    assert (np.asarray(res.sample.entity_mask) == np.asarray(mask_ref)).all()
    assert "deprecated" in run_windtunnel.__doc__


def test_run_uniform_baseline_wrapper_matches_legacy_draw(corpus, qrels):
    """Wrapper parity: the uniform baseline reproduces the legacy
    whole-corpus Bernoulli mask bit-exactly for the same (rate, seed)."""
    for rate, seed in [(0.2, 3), (0.45, 7)]:
        res = run_uniform_baseline(qrels, num_queries=corpus.num_queries,
                                   num_entities=corpus.num_entities,
                                   rate=rate, seed=seed)
        legacy = sm.uniform_sample(corpus.num_entities,
                                   jax.random.PRNGKey(seed), rate=rate)
        assert (np.asarray(res.entity_mask) == np.asarray(legacy)).all()
        ref = reconstruct(qrels, legacy, num_queries=corpus.num_queries)
        assert (np.asarray(res.query_mask) == np.asarray(ref.query_mask)).all()
    assert "deprecated" in run_uniform_baseline.__doc__


@pytest.mark.parametrize("engine", ["ell", "pallas"])
def test_sharded_session_bit_equal_on_host_mesh(corpus, qrels, engine):
    """One config, mesh in the spec: the sharded session reproduces the
    unsharded session AND both legacy entry points on a 1-device mesh."""
    mesh = make_host_mesh()
    sh = _session(corpus, qrels, engine=engine, sharded=True, mesh=mesh)
    ref = _session(corpus, qrels, engine=engine)
    d_sh, d_ref = sh.draw(), ref.draw()
    assert (np.asarray(d_sh.entity_mask) ==
            np.asarray(d_ref.entity_mask)).all()
    assert (np.asarray(sh.labels()[0]) == np.asarray(ref.labels()[0])).all()
    # both stage slots were filled by ONE shard_map region
    assert sh.stage_counts()["graph"][0] == 1
    assert sh.stage_counts()["labels"][0] == 1
    cfg = _spec(corpus, engine=engine).to_config()
    legacy = run_windtunnel_sharded(
        qrels, num_queries=corpus.num_queries,
        num_entities=corpus.num_entities, config=cfg, mesh=mesh)
    assert (np.asarray(legacy.sample.entity_mask) ==
            np.asarray(d_sh.entity_mask)).all()


# ---------------------------------------------------------------------------
# strategies: fraction targets, universes, degree stratification
# ---------------------------------------------------------------------------

def test_labels_span_and_active_nodes_gauge(tmp_path):
    """The ``sampling.labels`` span carries LP's static caps, and in a
    traced run the ``sampling.labels.active_nodes`` gauge counts the nodes
    with an edge; an untraced session reads nothing back for it, and a
    session traced under ``jax.jit`` sets no gauge from a tracer."""
    from repro.core import label_prop as lp
    from repro.launch import trace as trace_cli
    from repro.obs import REGISTRY, trace
    n, nq, rows = 20_000, 64, 256
    rng = np.random.default_rng(0)
    pool = rng.choice(n, 160, replace=False)
    qrels = QRelTable(jnp.asarray(rng.integers(0, nq, rows), jnp.int32),
                      jnp.asarray(pool[rng.integers(0, 160, rows)], jnp.int32),
                      jnp.asarray(rng.random(rows), jnp.float32),
                      jnp.ones(rows, bool))
    spec = SamplerSpec(engine="ell", fanout=4, lp_rounds=3, max_degree=8,
                       target_size=0.3)
    gauge = REGISTRY.gauge("sampling.labels.active_nodes")
    gauge.set(-1)
    untraced = SamplerSession(qrels, num_queries=nq, num_entities=n,
                              spec=spec).labels()[0]
    assert gauge.value == -1
    sink = tmp_path / "trace.jsonl"
    cfg = spec.to_config()
    trace.enable(str(sink))
    try:
        session = SamplerSession(qrels, num_queries=nq, num_entities=n,
                                 spec=spec)
        session.labels()
        active = gauge.value
        gauge.set(-1)
        jitted = jax.jit(lambda q: run_windtunnel(
            q, num_queries=nq, num_entities=n, config=cfg).labels)(qrels)
        assert gauge.value == -1
    finally:
        trace.disable()
    edges, degrees = session.graph()
    node_cap, edge_cap = lp.lp_caps(rows=rows, fanout=4, num_nodes=n,
                                    edge_slots=edges.u.shape[0])
    assert node_cap < n
    recs = [r for r in trace_cli.load_spans(str(sink))
            if r["name"] == "sampling.labels"]
    assert len(recs) == 2
    for rec in recs:
        assert (rec["attrs"]["node_cap"], rec["attrs"]["edge_cap"]) == (
            node_cap, edge_cap)
    assert active == int((np.asarray(degrees) > 0).sum()) > 0
    for labels in (untraced, jitted):
        assert np.array_equal(np.asarray(labels),
                              np.asarray(session.labels()[0]))


def test_fraction_target_matches_absolute_target(corpus, qrels):
    session = _session(corpus, qrels)
    deg = np.asarray(session.graph()[1])
    n_elig = int((deg > 0).sum())
    frac = session.draw(target_size=0.3, seed=0)
    absolute = session.draw(target_size=float(0.3 * n_elig), seed=0)
    assert (np.asarray(frac.entity_mask) ==
            np.asarray(absolute.entity_mask)).all()


def test_uniform_judged_universe_excludes_aux(corpus, qrels):
    session = _session(corpus, qrels, strategy="uniform")
    mask = np.asarray(session.draw(target_size=0.4, seed=0).entity_mask)
    assert mask[:corpus.num_primary].any()
    assert not mask[corpus.num_primary:].any()
    judged = np.asarray(judged_entities(qrels, corpus.num_entities))
    assert judged.sum() == corpus.num_primary
    # no graph/LP staged for a Bernoulli baseline
    assert session.stage_counts()["graph"] == (0, 0)
    assert session.stage_counts()["labels"] == (0, 0)


def test_uniform_requires_target(corpus, qrels):
    with pytest.raises(ValueError, match="target_size"):
        _session(corpus, qrels, strategy="uniform",
                 target_size=None).draw()


def test_degree_stratified_preserves_degree_distribution(corpus, qrels):
    session = _session(corpus, qrels, strategy="degree_stratified")
    deg = np.asarray(session.graph()[1])
    strat = get_sampler("degree_stratified")
    d0 = session.draw(target_size=0.4, seed=0)
    mask = np.asarray(d0.entity_mask)
    eligible = deg > 0
    assert eligible[mask].all()               # only affinity-graph nodes
    # quota per stratum -> realized size within rounding of the target
    target = 0.4 * eligible.sum()
    assert abs(mask.sum() - target) <= strat.num_strata
    # per-stratum keep fraction ~ rate for every populated bucket
    buckets = np.clip(np.floor(np.log2(np.maximum(deg, 1))), 0,
                      strat.num_strata - 1).astype(int)
    for b in np.unique(buckets[eligible]):
        members = eligible & (buckets == b)
        kept = (mask & members).sum()
        assert abs(kept - 0.4 * members.sum()) <= 1.0, b
    # distinct seeds pick different members at the same per-bucket quota
    d1 = session.draw(target_size=0.4, seed=1)
    assert (np.asarray(d1.entity_mask) != mask).any()
    assert np.asarray(d1.entity_mask).sum() == mask.sum()


def test_same_seed_strategies_are_decorrelated(corpus, qrels):
    """Per-strategy key salts: baselines drawn at the SAME seed must not
    consume the same uniform array (else uniform and degree_stratified keep
    near-identical sets and the grid compares a sampler with itself)."""
    session = _session(corpus, qrels)
    uni = np.asarray(session.draw(target_size=0.4, seed=0,
                                  strategy="uniform").entity_mask)
    ds = np.asarray(session.draw(target_size=0.4, seed=0,
                                 strategy="degree_stratified").entity_mask)
    both = uni.sum() + ds.sum()
    overlap = (uni & ds).sum()
    # independent 0.4-rate draws overlap ~0.16 of the universe; identical
    # draws would overlap ~min(|uni|, |ds|). Require clearly-below-identical.
    assert overlap < 0.75 * min(uni.sum(), ds.sum()), (overlap, both)


def test_sweep_stage_counts_are_per_sweep_deltas(corpus, qrels):
    session = _session(corpus, qrels)
    first = session.sweep([0.2, 0.3], [0, 1])
    again = session.sweep([0.2, 0.3], [0, 1])     # fully cache-served
    assert first.stage_counts["draw"] == (4, 4)
    assert first.stage_counts["labels"][0] == 1
    assert again.stage_counts["draw"] == (0, 4)   # no re-execution
    assert again.stage_counts["labels"][0] == 0
    fresh = session.sweep([0.2, 0.3], [2, 3])
    assert fresh.stage_counts["draw"] == (4, 4)
    assert fresh.stage_counts["graph"][0] == 0    # staged before this sweep


def test_full_strategy_and_result_guard(corpus, qrels):
    session = _session(corpus, qrels, strategy="full")
    mask = np.asarray(session.draw().entity_mask)
    assert mask.all()
    with pytest.raises(ValueError, match="cluster-sample"):
        session.result()


# ---------------------------------------------------------------------------
# associated_queries <-> reconstructor cross-check (moved from eval/runner)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(3))
def test_associated_queries_matches_reconstruct_rule(corpus, qrels, seed):
    rng = np.random.default_rng(seed)
    mask = rng.random(corpus.num_entities) < 0.35
    assoc, qids = associated_queries(corpus.qrels, mask,
                                     num_queries=corpus.num_queries)
    ref = reconstruct(qrels, jnp.asarray(mask),
                      num_queries=corpus.num_queries)
    assert (assoc == np.asarray(ref.query_mask)).all()
    assert (assoc[qids]).all() and qids.size == assoc.sum()


def test_associated_queries_subsample_cap(corpus):
    mask = np.ones(corpus.num_entities, bool)
    assoc, qids = associated_queries(corpus.qrels, mask,
                                     num_queries=corpus.num_queries,
                                     max_queries=10, seed=1)
    assert qids.size == 10
    assert assoc[qids].all()
    assert (np.diff(qids) > 0).all()       # sorted, unique
    _, again = associated_queries(corpus.qrels, mask,
                                  num_queries=corpus.num_queries,
                                  max_queries=10, seed=1)
    assert (qids == again).all()           # deterministic in the seed


# ---------------------------------------------------------------------------
# CLI registry-error contract (launch/sample.py and launch/evaluate.py)
# ---------------------------------------------------------------------------

def test_sample_cli_unknown_strategy_lists_registered():
    from repro.launch import sample
    with pytest.raises(ValueError, match="registered strategies"):
        sample.main(["--strategy", "bogus", "--queries", "32"])


def test_sample_cli_unknown_engine_lists_registered():
    from repro.launch import sample
    with pytest.raises(ValueError, match="registered engines"):
        sample.main(["--engine", "spark", "--queries", "32"])


def test_evaluate_cli_unknown_sampler_lists_registered():
    from repro.launch import evaluate
    with pytest.raises(ValueError, match="registered strategies"):
        evaluate.main(["--grid", "smoke", "--samplers", "bogus",
                       "--queries", "32"])


def test_evaluate_cli_unknown_engine_lists_registered():
    from repro.launch import evaluate
    with pytest.raises(ValueError, match="registered engines"):
        evaluate.main(["--grid", "smoke", "--engines", "faiss",
                       "--queries", "32"])


def test_sample_cli_sweep_smoke(tmp_path, capsys):
    from repro.launch import sample
    sample.main(["--queries", "48", "--qrels-per-query", "4",
                 "--topics", "4", "--aux-fraction", "0.2",
                 "--fanout", "4", "--lp-rounds", "2",
                 "--sweep-sizes", "0.2,0.4", "--sweep-seeds", "0,1",
                 "--out", str(tmp_path)])
    out = capsys.readouterr().out
    assert "graph" in out and "sweep: 2 sizes x 2 seeds" in out
    import json
    stats = json.loads((tmp_path / "stats.json").read_text())
    assert len(stats["sweep"]["draws"]) == 4
    assert stats["sweep"]["stage_counts"]["labels"]["executions"] == 1
