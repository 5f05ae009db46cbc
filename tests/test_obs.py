"""Observability layer tests (DESIGN.md §12): the span tracer's disabled
no-op fast path, JSONL round-trip, buffered sink, garbage-collection spans
and profiler-trace mirror, histogram percentiles against
hand-computed fixtures, serve latency percentiles end-to-end, PlanTrie
counter parity with the legacy per-node sums, the drain step-bound guard,
draw-cache hit/miss counters, and the launch/trace.py aggregator."""
import gc
import glob
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import trace as trace_cli
from repro.obs import REGISTRY, Histogram, Registry, trace
from repro.obs.timing import provenance, timeit


@pytest.fixture(autouse=True)
def _tracer_disabled():
    """Every test starts and ends with the tracer off (process-global).
    Automatic collection is off meanwhile, so a test's sink holds
    ``python.gc`` spans only where the test calls ``gc.collect()``."""
    trace.disable()
    gc.disable()
    yield
    gc.enable()
    trace.disable()


# --------------------------------------------------------------------------
# tracer: disabled no-op fast path
# --------------------------------------------------------------------------

def test_disabled_tracer_is_strict_noop(tmp_path):
    assert not trace.is_enabled()
    before = trace._STATE.records_written
    s = trace.span("anything", attr=1)
    j = trace.jax_span("anything.jax", compile_key="k", attr=2)
    # no span objects allocated: both return the one shared singleton
    assert s is trace.NOOP and j is trace.NOOP
    with trace.span("outer") as sp:
        sp.set(x=1).declare(jnp.zeros(3))   # chainable, retains nothing
        with trace.jax_span("inner") as inner:
            inner.declare(jnp.ones(2))
    assert trace._STATE.records_written == before   # nothing written
    assert trace.enabled_path() is None


def test_env_configure_blank_and_off_values(monkeypatch, tmp_path):
    for off in ("", "0", "off", "none", "  "):
        monkeypatch.setenv(trace.ENV_VAR, off)
        trace.configure_from_env()
        assert not trace.is_enabled()
    sink = tmp_path / "t.jsonl"
    monkeypatch.setenv(trace.ENV_VAR, str(sink))
    trace.configure_from_env()
    assert trace.is_enabled() and trace.enabled_path() == str(sink)
    trace.disable()
    assert not trace.is_enabled()


# --------------------------------------------------------------------------
# tracer: JSONL round-trip, nesting, attrs, first/steady, block_s
# --------------------------------------------------------------------------

def test_span_nesting_and_attrs_roundtrip(tmp_path):
    sink = tmp_path / "spans.jsonl"
    trace.enable(str(sink))
    with trace.span("outer", stage="build") as outer:
        with trace.span("inner", i=3) as inner:
            inner.set(found=True)
        outer.set(n=7)
    trace.disable()

    recs = trace_cli.load_spans(str(sink))
    assert [r["name"] for r in recs] == ["inner", "outer"]  # close order
    inner_r, outer_r = recs
    assert inner_r["parent"] == outer_r["id"]
    assert outer_r["parent"] is None
    assert inner_r["attrs"] == {"i": 3, "found": True}
    assert outer_r["attrs"] == {"stage": "build", "n": 7}
    assert outer_r["dur_s"] >= inner_r["dur_s"] >= 0.0


def test_jax_span_first_flag_and_block(tmp_path):
    sink = tmp_path / "spans.jsonl"
    trace.enable(str(sink))
    f = jax.jit(lambda x: x * 2 + 1)
    for _ in range(3):
        with trace.jax_span("stage.x", compile_key="stage.x/shape1") as sp:
            sp.declare(f(jnp.arange(8.0)))
    trace.disable()
    recs = trace_cli.load_spans(str(sink))
    assert [r["first"] for r in recs] == [True, False, False]
    assert all("block_s" in r and r["block_s"] >= 0.0 for r in recs)
    # distinct compile key -> its own first flag
    trace.enable(str(sink))
    with trace.jax_span("stage.x", compile_key="stage.x/shape2") as sp:
        sp.declare(f(jnp.arange(16.0)))
    trace.disable()
    assert trace_cli.load_spans(str(sink))[-1]["first"] is True


def test_span_records_error(tmp_path):
    sink = tmp_path / "spans.jsonl"
    trace.enable(str(sink))
    with pytest.raises(ValueError, match="boom"):
        with trace.span("failing"):
            raise ValueError("boom")
    trace.disable()
    (rec,) = trace_cli.load_spans(str(sink))
    assert rec["error"] == "ValueError: boom"


def test_disabled_tracer_installs_no_hook_and_enters_no_annotation(
        monkeypatch):
    def refused(name):
        raise AssertionError(f"annotation {name!r} entered while disabled")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refused)
    with trace.span("outer"):
        with trace.jax_span("inner") as sp:
            sp.declare(jnp.ones(2))
    gc.collect()
    assert trace._on_gc not in gc.callbacks
    assert trace._STATE.buffer == []


# --------------------------------------------------------------------------
# tracer: buffered sink, garbage-collection spans, profiler mirror
# --------------------------------------------------------------------------

def _lines(path):
    return path.read_text().splitlines() if path.exists() else []


def test_records_reach_the_sink_only_at_disable_and_at_the_bound(tmp_path):
    sink = tmp_path / "spans.jsonl"
    trace.enable(str(sink))
    written = trace._STATE.records_written
    for i in range(trace.FLUSH_RECORDS - 1):
        with trace.span("s", i=i):
            pass
    assert _lines(sink) == []
    assert trace._STATE.records_written == written
    with trace.span("s", i=trace.FLUSH_RECORDS - 1):
        pass
    assert len(_lines(sink)) == trace.FLUSH_RECORDS
    with trace.span("last"):
        pass
    assert len(_lines(sink)) == trace.FLUSH_RECORDS
    trace.disable()
    recs = trace_cli.load_spans(str(sink))
    assert recs[-1]["name"] == "last"
    assert [r["attrs"]["i"] for r in recs if r["name"] == "s"] == \
        list(range(trace.FLUSH_RECORDS))
    assert trace._STATE.records_written - written == len(recs)


def test_gc_collection_is_a_span_and_its_hook_goes_at_disable(tmp_path):
    sink = tmp_path / "spans.jsonl"
    trace.enable(str(sink))
    assert trace._on_gc in gc.callbacks
    with trace.span("outer"):
        gc.collect()
    trace.disable()
    assert trace._on_gc not in gc.callbacks
    recs = trace_cli.load_spans(str(sink))
    outer = next(r for r in recs if r["name"] == "outer")
    full = [r for r in recs if r["name"] == "python.gc"
            and r["attrs"]["generation"] == 2]
    assert full, [r["name"] for r in recs]
    assert full[-1]["parent"] == outer["id"]
    assert full[-1]["attrs"]["collected"] >= 0
    assert full[-1]["dur_s"] <= outer["dur_s"]
    gc.collect()                         # nothing recorded once disabled
    assert trace_cli.load_spans(str(sink)) == recs


def test_spans_land_on_a_host_plane_of_the_profiler_trace(tmp_path):
    from jax.profiler import ProfileData
    trace.enable(str(tmp_path / "spans.jsonl"))
    with jax.profiler.trace(str(tmp_path / "prof")):
        with trace.jax_span("mirror.outer") as sp:
            with trace.span("mirror.inner"):
                sp.declare(jnp.arange(8.0) * 2)
    trace.disable()
    (path,) = glob.glob(str(tmp_path / "prof" / "**" / "*.xplane.pb"),
                        recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("mirror."):
                    found[ev.name] = (ev.start_ns, ev.duration_ns)
    assert set(found) == {"mirror.outer", "mirror.inner"}
    (o0, od), (i0, idur) = found["mirror.outer"], found["mirror.inner"]
    assert o0 <= i0 and i0 + idur <= o0 + od     # the nesting is kept


# --------------------------------------------------------------------------
# metrics: histogram percentiles on hand-computed fixtures
# --------------------------------------------------------------------------

def test_histogram_percentile_fixture():
    h = Histogram("t", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 3.0, 3.0, 8.0):
        h.observe(v)
    # rank(p50) = 2.5 -> third bucket (2, 4]: lo=2, hi=4, frac=0.25 -> 2.5
    assert h.percentile(50) == pytest.approx(2.5)
    # rank(p99) = 4.95 -> overflow bucket -> observed max
    assert h.percentile(99) == pytest.approx(8.0)
    assert h.percentile(0) == pytest.approx(0.5)    # clamped to observed min
    assert h.percentile(100) == pytest.approx(8.0)
    assert h.count == 5 and h.mean == pytest.approx(3.2)
    assert h.min == 0.5 and h.max == 8.0
    d = h.to_dict()
    assert d["p50"] == pytest.approx(2.5) and d["p99"] == pytest.approx(8.0)


def test_histogram_empty_and_bounds():
    h = Histogram("t", buckets=(1.0,))
    assert h.percentile(50) == 0.0
    assert h.to_dict()["count"] == 0
    with pytest.raises(ValueError):
        h.percentile(101)
    with pytest.raises(ValueError):
        Histogram("t", buckets=())


def test_registry_get_or_create_and_snapshot():
    reg = Registry()
    reg.counter("a").inc()
    reg.counter("a").inc(2)
    reg.gauge("g").set(0.5)
    reg.histogram("h", buckets=(1.0,)).observe(0.3)
    snap = reg.snapshot()
    assert snap["counters"] == {"a": 3}
    assert snap["gauges"] == {"g": 0.5}
    assert snap["histograms"]["h"]["count"] == 1
    json.dumps(snap)                 # snapshot must be JSON-able
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


# --------------------------------------------------------------------------
# serve: latency histogram e2e + drain guard
# --------------------------------------------------------------------------

def _tiny_engine(max_batch=2, max_new=4):
    from repro.models.transformer import TransformerConfig, init_transformer
    from repro.serve.engine import ServeConfig, ServeEngine
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                            n_kv_heads=2, d_ff=48, dtype=jnp.float32)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    return ServeEngine(params, cfg, ServeConfig(
        max_batch=max_batch, max_seq=32, max_new_tokens=max_new))


def test_serve_latency_percentiles_e2e():
    eng = _tiny_engine()
    hist = REGISTRY.histogram("serve.request_latency_s")
    done0 = REGISTRY.counter("serve.completed").value
    count0 = hist.count
    eng.submit(np.array([1, 2, 3], np.int32))
    eng.submit(np.array([4, 5], np.int32))
    eng.drain()
    assert REGISTRY.counter("serve.completed").value == done0 + 2
    assert hist.count == count0 + 2
    p = hist.percentiles()
    assert 0.0 < p["p50"] <= p["p99"]
    assert REGISTRY.counter("serve.tokens").value > 0
    assert 0.0 <= REGISTRY.gauge("serve.slot_occupancy").value <= 1.0


def test_drain_completes_within_derived_bound():
    eng = _tiny_engine()
    r1 = eng.submit(np.array([1, 2, 3], np.int32))
    r2 = eng.submit(np.array([4, 5], np.int32))
    # bound: (remaining_prompt - 1 overlaps first token) + max_new per req
    bound = sum(r.remaining_prompt + eng.cfg.max_new_tokens
                for r in (r1, r2))
    steps = eng.drain()
    assert r1.done and r2.done
    assert 0 < steps <= bound


def test_drain_guard_raises_with_engine_state():
    eng = _tiny_engine(max_batch=1)
    eng.submit(np.array([1, 2, 3, 4], np.int32))
    with pytest.raises(RuntimeError, match="step bound") as ei:
        eng.drain(max_steps=2)
    state = ei.value.engine_state
    assert state["max_batch"] == 1
    slot = state["slots"][0]
    assert slot is not None and not slot["done"]
    # the engine is still steppable after the guard fires
    assert eng.drain() > 0
    assert eng.slots == [None]


# --------------------------------------------------------------------------
# plan trie: registry counters == legacy per-node sums
# --------------------------------------------------------------------------

def test_plan_trie_counter_parity():
    from repro.eval.plans import (GridSpec, execute_plan, expand_grid)
    runs = expand_grid(GridSpec(samplers=("a", "b"), engines=("x",),
                                ks=(1, 2), metrics=("m", "n")))
    noop = lambda parent, run: (parent, run.key)
    _, trie = execute_plan(runs, {s: noop for s in
                                  ("corpus", "embed", "sample", "index",
                                   "search", "metric")})
    counters = trie.metrics.snapshot()["counters"]
    by_stage = {}
    for node in trie.nodes.values():
        ex, rq = by_stage.get(node.stage, (0, 0))
        by_stage[node.stage] = (ex + node.executions, rq + node.requests)
    for stage, (ex, rq) in by_stage.items():
        assert counters[f"plan.executions.{stage}"] == ex
        assert counters[f"plan.requests.{stage}"] == rq
    assert trie.stage_counts() == by_stage
    # sharing actually happened: 8 cells, corpus executed once
    assert trie.stage_counts()["corpus"] == (1, 8)
    assert trie.stage_counts()["metric"] == (8, 8)


def test_plan_trie_isolated_registries():
    from repro.eval.plans import PlanTrie
    t1, t2 = PlanTrie(), PlanTrie()
    t1.run((("corpus",),), lambda: 1)
    assert t2.metrics.snapshot()["counters"] == {}
    assert t1.metrics is not t2.metrics is not REGISTRY


# --------------------------------------------------------------------------
# sampling core: draw-cache hit/miss counters
# --------------------------------------------------------------------------

def test_sampler_draw_cache_counters():
    from repro.core import QRelTable
    from repro.core.sampling_core import SamplerSession, SamplerSpec
    from repro.data.synthetic import generate_qrels
    q, e, s, _, _, ne = generate_qrels(num_queries=64, qrels_per_query=4,
                                       num_topics=8, seed=0)
    qrels = QRelTable(jnp.asarray(q), jnp.asarray(e), jnp.asarray(s),
                      jnp.ones(len(q), bool))
    sess = SamplerSession(qrels, num_queries=64, num_entities=ne,
                          spec=SamplerSpec(target_size=16.0, seed=0))
    hit0 = REGISTRY.counter("sampling.draw.hit").value
    miss0 = REGISTRY.counter("sampling.draw.miss").value
    sess.draw(seed=1)
    sess.draw(seed=1)     # cached
    sess.draw(seed=2)     # new key
    assert REGISTRY.counter("sampling.draw.miss").value == miss0 + 2
    assert REGISTRY.counter("sampling.draw.hit").value == hit0 + 1


def test_tuning_resolve_counters():
    from repro.kernels import tuning
    hit0 = REGISTRY.counter("tuning.resolve.hit").value
    miss0 = REGISTRY.counter("tuning.resolve.miss").value
    tuning.resolve("topk", n=1024, dtype="float32")
    hit1 = REGISTRY.counter("tuning.resolve.hit").value
    miss1 = REGISTRY.counter("tuning.resolve.miss").value
    assert (hit1 + miss1) - (hit0 + miss0) == 1   # exactly one resolution


# --------------------------------------------------------------------------
# launch/trace.py: aggregation + CLI
# --------------------------------------------------------------------------

def test_trace_cli_aggregate_compile_share():
    spans = (
        [{"name": "s", "id": i, "parent": None, "t0": 0.0, "dur_s": 1.0,
          "first": i == 1} for i in range(1, 5)]      # 1 first + 3 steady
        + [{"name": "plain", "id": 9, "parent": None, "t0": 0.0,
            "dur_s": 0.5}])
    aggs = trace_cli.aggregate(spans)
    s = aggs["s"]
    assert s["count"] == 4 and s["total_s"] == pytest.approx(4.0)
    # steady mean 1.0, one first call of 1.0 -> no compile surplus
    assert s["compile_s"] == pytest.approx(0.0)
    assert aggs["plain"]["first_count"] == 0
    assert aggs["plain"]["compile_share"] == 0.0
    # compile-dominated first call
    aggs2 = trace_cli.aggregate(
        [{"name": "s", "dur_s": 5.0, "first": True},
         {"name": "s", "dur_s": 1.0, "first": False}])
    assert aggs2["s"]["compile_s"] == pytest.approx(4.0)
    assert aggs2["s"]["compile_share"] == pytest.approx(4.0 / 6.0)


def test_trace_cli_percentile_exact():
    vals = sorted([1.0, 2.0, 3.0, 4.0])
    assert trace_cli._percentile(vals, 50) == pytest.approx(2.5)
    assert trace_cli._percentile(vals, 100) == pytest.approx(4.0)
    assert trace_cli._percentile([7.0], 99) == 7.0
    assert trace_cli._percentile([], 50) == 0.0


def test_trace_cli_main_json(tmp_path, capsys):
    sink = tmp_path / "t.jsonl"
    trace.enable(str(sink))
    with trace.span("alpha", x=1):
        with trace.jax_span("beta") as sp:
            sp.declare(jnp.arange(4))
    trace.disable()
    out_json = tmp_path / "agg.json"
    assert trace_cli.main([str(sink), "--json", str(out_json)]) == 0
    payload = json.loads(out_json.read_text())
    assert payload["spans"] == 2
    assert set(payload["stages"]) == {"alpha", "beta"}
    table = capsys.readouterr().out
    assert "alpha" in table and "beta" in table
    # --json - prints the JSON payload only
    assert trace_cli.main([str(sink), "--json", "-"]) == 0
    assert json.loads(capsys.readouterr().out)["spans"] == 2


def test_trace_cli_rejects_bad_jsonl(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"name": "ok"}\nnot json\n')
    with pytest.raises(ValueError, match="bad.jsonl:2"):
        trace_cli.load_spans(str(bad))
    assert trace_cli.main([str(tmp_path / "missing.jsonl")]) == 2


# --------------------------------------------------------------------------
# timing helpers
# --------------------------------------------------------------------------

def test_timeit_and_provenance():
    us = timeit(lambda: jnp.arange(16.0) * 2, n=2)
    assert us > 0.0
    meta = provenance()
    assert meta["jax"] and meta["backend"] and meta["device_count"] >= 1
    assert set(meta) >= {"platform", "python", "jax", "backend",
                         "device_kind", "device_count", "git_sha"}


# --------------------------------------------------------------------------
# instrumented stages emit spans end-to-end (search + sampling + eval)
# --------------------------------------------------------------------------

def test_instrumented_stages_emit_spans(tmp_path):
    from repro.core import QRelTable
    from repro.core.sampling_core import SamplerSession, SamplerSpec
    from repro.data.synthetic import generate_qrels
    from repro.retrieval.search_core import SearchConfig, SearchSession
    sink = tmp_path / "trace.jsonl"
    trace.enable(str(sink))
    vecs = jax.random.normal(jax.random.PRNGKey(0), (128, 16))
    session = SearchSession(vecs, SearchConfig(engine="exact", query_chunk=3),
                            key=jax.random.PRNGKey(0))
    session.search(vecs[:8], k=3)                  # chunks of 3, 3 and 2
    q, e, s, _, _, ne = generate_qrels(num_queries=64, qrels_per_query=4,
                                       num_topics=8, seed=0)
    qrels = QRelTable(jnp.asarray(q), jnp.asarray(e), jnp.asarray(s),
                      jnp.ones(len(q), bool))
    samp = SamplerSession(qrels, num_queries=64, num_entities=ne,
                          spec=SamplerSpec(target_size=16.0, seed=0))
    samp.draw(seed=3)
    trace.disable()
    recs = trace_cli.load_spans(str(sink))
    names = {r["name"] for r in recs}
    assert {"search.build", "search.chunk", "sampling.graph",
            "sampling.labels", "sampling.draw"} <= names

    def named(name):
        return [r for r in recs if r["name"] == name]

    (scored,) = named("search.scored")
    assert scored["attrs"] == {"q": 8, "chunks": 3}
    steps = [r for r in recs if r["parent"] == scored["id"]]
    assert [r["name"] for r in sorted(steps, key=lambda r: r["id"])] == \
        ["search.upload", "search.chunk", "search.readback"] * 3
    assert [r["attrs"]["q"] for r in named("search.readback")] == [3, 3, 2]
