"""Streaming shard-local build (DESIGN.md §13): sharded-from-birth corpora.

Covers the tentpole invariants:

  * 1-device mesh: the born build/search path is bit-identical to the
    global build for every engine x backend (including int8 — the lift),
    and the born sampler session is bit-identical to the legacy sharded
    one (same labels, same draws).
  * Streaming: chunked host->device transfer reassembles the host array
    exactly, for any chunk size; ShardedQRels host-side routing matches
    the on-device `_route_by_query` compaction.
  * 2-device host mesh (subprocess): set-equal top-k for every engine x
    backend — including int8 (per-shard scales + float rerank) and
    ivfflat (shard-local centroid refinement) — identical LP labels, and
    uneven/tiny-shard padding regressions.
  * The legacy build-globally-then-partition path keeps its int8
    rejection (pinned messages), and `build.peak_bytes_per_device` is
    reported after every born build.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph_builder as gb
from repro.core.sampling_core import SamplerSession, SamplerSpec
from repro.distributed.sharded_corpus import (ShardedCorpus, ShardedQRels,
                                              stream_to_sharded)
from repro.launch.mesh import make_host_mesh
from repro.obs.memory import PEAK_GAUGE
from repro.obs.metrics import REGISTRY
from repro.retrieval.engines import (available_retrieval_engines,
                                     get_retrieval_engine)
from repro.retrieval.backends import available_backends
from repro.retrieval.search_core import SearchConfig, SearchSession
from repro.retrieval.sharded import sharded_search


@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh()


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    vecs = rng.standard_normal((300, 16)).astype(np.float32)
    queries = rng.standard_normal((9, 16)).astype(np.float32)
    return vecs, queries


# ---------------------------------------------------------------------------
# streaming transfer + ShardedCorpus / ShardedQRels construction
# ---------------------------------------------------------------------------

def test_stream_to_sharded_chunked_equals_host(mesh):
    """Chunked streaming (chunk smaller than the shard) reassembles the
    host array bit-exactly, including the zero pad rows."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    host = np.arange(7 * 3, dtype=np.float32).reshape(7, 3)
    sharding = NamedSharding(mesh, P(("data", "model"), None))
    out = stream_to_sharded(host, sharding, (8, 3), chunk_rows=2)
    got = np.asarray(out)
    assert np.array_equal(got[:7], host)
    assert (got[7:] == 0).all()


def test_sharded_corpus_geometry(mesh, data):
    vecs, _ = data
    corpus = ShardedCorpus.from_host(vecs[:299], mesh=mesh, chunk_rows=64)
    assert corpus.n == 299
    assert corpus.num_shards == 1
    assert corpus.rows_per_shard * corpus.num_shards >= corpus.n
    assert corpus.pad == corpus.rows_per_shard * corpus.num_shards - 299
    assert np.array_equal(np.asarray(corpus.vecs)[:299], vecs[:299])


def test_sharded_qrels_table_matches_routing(mesh):
    """Host-side routing + table() reproduces exactly the valid qrel rows
    (as a multiset), with per-shard stable original order."""
    rng = np.random.default_rng(3)
    nq, ne, nnz = 17, 50, 120
    q = rng.integers(0, nq, nnz).astype(np.int32)
    e = rng.integers(0, ne, nnz).astype(np.int32)
    s = rng.random(nnz).astype(np.float32)
    v = rng.random(nnz) < 0.8
    qrels = gb.QRelTable(q, e, s, v)
    born = ShardedQRels.from_host(qrels, num_queries=nq, num_entities=ne,
                                  mesh=mesh, chunk_rows=16)
    assert born.num_shards == 1
    tab = born.table()
    got = sorted(zip(np.asarray(tab.query_ids)[np.asarray(tab.valid)],
                     np.asarray(tab.entity_ids)[np.asarray(tab.valid)],
                     np.asarray(tab.scores)[np.asarray(tab.valid)]))
    want = sorted(zip(q[v], e[v], s[v]))
    assert got == want


# ---------------------------------------------------------------------------
# 1-device bit parity: born search == global search, all engine x backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["exact", "tfidf", "lsh", "ivfflat"])
def test_streamed_search_bit_identical_one_device(mesh, data, engine):
    vecs, queries = data
    for backend in available_backends():
        ref = SearchSession(vecs, SearchConfig(engine=engine,
                                               backend=backend))
        got = SearchSession(vecs, SearchConfig(engine=engine,
                                               backend=backend,
                                               streamed=True, mesh=mesh))
        assert got.config.sharded and got.config.streamed
        assert np.array_equal(got.search(queries, k=5),
                              ref.search(queries, k=5)), (engine, backend)


def test_streamed_accepts_prebuilt_sharded_corpus(mesh, data):
    """Passing a ShardedCorpus directly == streaming the host array."""
    vecs, queries = data
    corpus = ShardedCorpus.from_host(vecs, mesh=mesh)
    via_corpus = SearchSession(corpus, SearchConfig(engine="exact"))
    via_flag = SearchSession(vecs, SearchConfig(engine="exact",
                                                streamed=True, mesh=mesh))
    assert via_corpus.corpus_size == vecs.shape[0]
    assert np.array_equal(via_corpus.search(queries, k=4),
                          via_flag.search(queries, k=4))


# ---------------------------------------------------------------------------
# 1-device bit parity: born sampler == legacy sharded sampler
# ---------------------------------------------------------------------------

def test_streamed_sampler_bit_identical_one_device(mesh):
    rng = np.random.default_rng(1)
    nq, ne, nnz = 40, 120, 500
    qrels = gb.QRelTable(rng.integers(0, nq, nnz).astype(np.int32),
                         rng.integers(0, ne, nnz).astype(np.int32),
                         rng.random(nnz).astype(np.float32),
                         np.ones(nnz, bool))
    legacy = SamplerSession(qrels, num_queries=nq, num_entities=ne,
                            spec=SamplerSpec(engine="ell", sharded=True,
                                             mesh=mesh))
    born = SamplerSession(qrels, num_queries=nq, num_entities=ne,
                          spec=SamplerSpec(engine="ell", streamed=True,
                                           mesh=mesh))
    l0, c0 = legacy.labels()
    l1, c1 = born.labels()
    assert np.array_equal(np.asarray(l0), np.asarray(l1))
    assert np.array_equal(np.asarray(c0), np.asarray(c1))
    assert np.array_equal(np.asarray(legacy.draw(seed=3).entity_mask),
                          np.asarray(born.draw(seed=3).entity_mask))


def test_streamed_sampler_accepts_prebuilt_qrels(mesh):
    rng = np.random.default_rng(2)
    nq, ne, nnz = 20, 60, 200
    qrels = gb.QRelTable(rng.integers(0, nq, nnz).astype(np.int32),
                         rng.integers(0, ne, nnz).astype(np.int32),
                         rng.random(nnz).astype(np.float32),
                         np.ones(nnz, bool))
    born = ShardedQRels.from_host(qrels, num_queries=nq, num_entities=ne,
                                  mesh=mesh)
    s0 = SamplerSession(born, num_queries=nq, num_entities=ne,
                        spec=SamplerSpec(engine="ell"))
    s1 = SamplerSession(qrels, num_queries=nq, num_entities=ne,
                        spec=SamplerSpec(engine="ell", streamed=True,
                                         mesh=mesh))
    assert np.array_equal(np.asarray(s0.labels()[0]),
                          np.asarray(s1.labels()[0]))
    with pytest.raises(ValueError, match="routed for"):
        SamplerSession(born, num_queries=nq + 7, num_entities=ne,
                       spec=SamplerSpec(engine="ell")).labels()


# ---------------------------------------------------------------------------
# satellites: legacy int8 rejection pins, peak gauge
# ---------------------------------------------------------------------------

def test_legacy_sharded_int8_rejection_pinned(mesh, data):
    """The build-globally-then-partition path keeps rejecting int8 (it
    has no plan for one quantization scale over rows split across shards)
    — the born path is the supported route.  Both messages are pinned."""
    vecs, queries = data
    with pytest.raises(ValueError, match="one quantization scale over rows"):
        SearchSession(vecs, SearchConfig(sharded=True, backend="int8",
                                         mesh=mesh))
    eng = dataclasses.replace(get_retrieval_engine("exact"), backend="int8")
    index = eng.build(jax.random.PRNGKey(0), jnp.asarray(vecs))
    with pytest.raises(ValueError,
                       match="use backend='jnp' or 'pallas' for sharded"):
        sharded_search(eng, index, jnp.asarray(queries), k=3, mesh=mesh)
    # ...but the same config over a born corpus works (the int8 lift)
    session = SearchSession(vecs, SearchConfig(backend="int8",
                                               streamed=True, mesh=mesh))
    assert session.search(queries, k=3).shape == (queries.shape[0], 3)


def test_peak_gauge_recorded_on_born_build(mesh, data):
    vecs, queries = data
    REGISTRY.gauge(PEAK_GAUGE).set(0)
    SearchSession(vecs, SearchConfig(engine="exact", streamed=True,
                                     mesh=mesh))
    assert REGISTRY.gauge(PEAK_GAUGE).value > 0


def test_streamed_requires_mesh(data):
    vecs, _ = data
    with pytest.raises(ValueError, match="streamed build needs a mesh"):
        SearchSession(vecs, SearchConfig(streamed=True))
    with pytest.raises(ValueError, match="streamed sampling needs a mesh"):
        SamplerSession(gb.QRelTable(np.zeros(4, np.int32),
                                    np.zeros(4, np.int32),
                                    np.ones(4, np.float32),
                                    np.ones(4, bool)),
                       num_queries=2, num_entities=2,
                       spec=SamplerSpec(engine="ell", streamed=True))


# ---------------------------------------------------------------------------
# 2-device host mesh (subprocess: the test session itself sees 1 device)
# ---------------------------------------------------------------------------

_TWO_DEVICE_SCRIPT = r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh

from repro.core import graph_builder as gb
from repro.core.sampling_core import SamplerSession, SamplerSpec
from repro.obs.memory import PEAK_GAUGE
from repro.obs.metrics import REGISTRY
from repro.retrieval.backends import available_backends
from repro.retrieval.search_core import SearchConfig, SearchSession

assert len(jax.devices()) == 2, jax.devices()
mesh = Mesh(np.array(jax.devices()), ("data",))
rng = np.random.default_rng(0)

# --- search: every engine x backend, uneven shards (N=97) -----------------
vecs = rng.standard_normal((97, 16)).astype(np.float32)
queries = rng.standard_normal((7, 16)).astype(np.float32)
for engine in ("exact", "tfidf", "lsh", "ivfflat"):
    opts = {"n_lists": 4, "nprobe": 4} if engine == "ivfflat" else None
    for backend in available_backends():
        ref = SearchSession(vecs, SearchConfig(engine=engine,
                                               backend=backend,
                                               engine_opts=opts))
        got = SearchSession(vecs, SearchConfig(engine=engine,
                                               backend=backend,
                                               engine_opts=opts,
                                               streamed=True, mesh=mesh))
        a = np.sort(ref.search(queries, k=5), 1)
        b = np.sort(got.search(queries, k=5), 1)
        assert np.array_equal(a, b), (engine, backend, a, b)

# --- tiny corpus: shard pad dominates (N=5 over 2 shards) -----------------
tiny = rng.standard_normal((5, 8)).astype(np.float32)
tq = rng.standard_normal((3, 8)).astype(np.float32)
for backend in available_backends():
    ref = SearchSession(tiny, SearchConfig(backend=backend))
    got = SearchSession(tiny, SearchConfig(backend=backend,
                                           streamed=True, mesh=mesh))
    assert np.array_equal(np.sort(ref.search(tq, k=5), 1),
                          np.sort(got.search(tq, k=5), 1)), backend

# --- all-negative scores: pad sentinels must not displace real rows ------
neg = -np.abs(rng.standard_normal((9, 8))).astype(np.float32) - 1.0
nq_ = np.abs(rng.standard_normal((3, 8))).astype(np.float32)
for backend in available_backends():
    ref = SearchSession(neg, SearchConfig(backend=backend))
    got = SearchSession(neg, SearchConfig(backend=backend,
                                          streamed=True, mesh=mesh))
    assert np.array_equal(np.sort(ref.search(nq_, k=4), 1),
                          np.sort(got.search(nq_, k=4), 1)), backend

# --- sampler: identical LP labels + draws, born vs legacy sharded ---------
nq, ne, nnz = 40, 120, 500
qrels = gb.QRelTable(rng.integers(0, nq, nnz).astype(np.int32),
                     rng.integers(0, ne, nnz).astype(np.int32),
                     rng.random(nnz).astype(np.float32),
                     np.ones(nnz, bool))
legacy = SamplerSession(qrels, num_queries=nq, num_entities=ne,
                        spec=SamplerSpec(engine="ell", sharded=True,
                                         mesh=mesh))
born = SamplerSession(qrels, num_queries=nq, num_entities=ne,
                      spec=SamplerSpec(engine="ell", streamed=True,
                                       mesh=mesh))
assert np.array_equal(np.asarray(legacy.labels()[0]),
                      np.asarray(born.labels()[0]))
assert np.array_equal(np.asarray(legacy.draw(seed=5).entity_mask),
                      np.asarray(born.draw(seed=5).entity_mask))
assert REGISTRY.gauge(PEAK_GAUGE).value > 0
print("STREAM-2DEV-OK")
"""


def test_streamed_two_device_mesh():
    """Tentpole acceptance on a real 2-shard mesh: set-equal top-k for
    every engine x backend (int8 included — the lift), identical LP
    labels, and uneven/tiny/all-negative shard-padding regressions.
    Subprocess because the test session itself must see 1 CPU device."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=src + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", _TWO_DEVICE_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "STREAM-2DEV-OK" in out.stdout


# ---------------------------------------------------------------------------
# 4-device host mesh: born exact search reads the corpus in place, compiled
# ---------------------------------------------------------------------------

_FOUR_DEVICE_SCRIPT = r"""
import numpy as np
import jax
from jax.sharding import Mesh

from repro.distributed.sharded_corpus import ShardedCorpus
from repro.obs import recompile
from repro.retrieval.search_core import SearchConfig, SearchSession

assert len(jax.devices()) == 4, jax.devices()
mesh = Mesh(np.array(jax.devices()), ("data",))
rng = np.random.default_rng(0)
# 2,501 rows a device: the last device holds 3 zero pad rows, and each
# shard's kernel grid ends in a ragged block at block_n 1024
vecs = rng.standard_normal((10_001, 32)).astype(np.float32)
queries = rng.standard_normal((40, 32)).astype(np.float32)
recompile.enable()
for backend in ("jnp", "pallas"):
    ref_s, ref_i = SearchSession(vecs, SearchConfig(
        backend=backend)).search_scored(queries, k=10)
    corpus = ShardedCorpus.from_host(vecs, mesh=mesh)
    session = SearchSession(corpus, SearchConfig(backend=backend,
                                                 query_chunk=16))
    assert session.index.vecs is corpus.vecs, backend   # no copy at build
    for call in range(3):
        recompile.mark()
        s, i = session.search_scored(queries, k=10)
        if call:
            assert recompile.since() == 0, (backend, call,
                                            recompile.counts())
        assert np.array_equal(np.sort(i, 1), np.sort(ref_i, 1)), backend
        np.testing.assert_allclose(s, ref_s, rtol=1e-5, atol=1e-5)
print("FOUR-DEVICE-OK")
"""


def test_born_exact_four_device_mesh_in_place_and_compiled():
    """Born exact search over 10,001 rows on a 4-device mesh equals
    single-device exact search (jnp and pallas backends); the index's rows
    are the corpus's own buffer, and after the first pass (one compile per
    chunk shape) a pass compiles nothing.  Subprocess because the test
    session itself must see 1 CPU device."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=src + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", _FOUR_DEVICE_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "FOUR-DEVICE-OK" in out.stdout
