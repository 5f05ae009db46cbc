"""Sharded search — Layer 2 of the search core (DESIGN.md §9, §13).

Two generations of sharding live here:

**Sharded-from-birth (preferred).**  :func:`sharded_build` constructs the
index *per shard* from a :class:`~repro.distributed.sharded_corpus.
ShardedCorpus` whose rows were streamed straight into per-device buffers —
nothing proportional to the global corpus is ever resident on one device.
Shard-local exact/tfidf rows, shard-local LSH codes, shard-local int8
quantization (per-shard scales + float rerank), and IVF lists refined from
shard-local partial sums converged by a per-iteration all-reduce instead
of a global k-means.  The born index types (``Sharded*Index``) route
:func:`sharded_search` to shard-local query plans automatically.  On a
1-device mesh every born build/search is operation-for-operation the
single-device program (bit-consistent); on larger meshes results are
set-equal under the backend tie policy.

**Build-globally-then-partition (deprecated).**  The original layer built
the index once on a single device and only sharded the *scoring*: each
shard runs the engine's backend over its slice of the replicated index,
and partials merge with one tiled all-gather + ``lax.top_k``.  This path
is capped by single-device memory — exactly what the birth path removes —
and is kept only for pre-built ``engine.build`` indexes; new callers
should construct a ``ShardedCorpus`` (or ``SearchConfig(streamed=True)``)
instead.

Partition plans per engine (both generations share the merge):

  * ``exact`` / ``tfidf`` — corpus rows over the mesh; per-shard dense
    top-k via ``backend.topk``; global ids recovered from the shard's row
    offset.  Born tfidf reduces the document-frequency vector with an
    integer ``psum`` (bit-identical IDF weights on any mesh).
  * ``lsh``   — packed codes, transposed (W, N), sharded by corpus row;
    per-shard Hamming top-rerank via ``backend.hamming_topk``.  Born
    rerank never replicates the vectors: each shard scores the merged
    candidates it owns in f32 and the partial score rows merge with
    ``lax.pmax``.
  * ``ivfflat`` — centroids replicate, so every shard selects the SAME
    global top-``nprobe`` probe set.  Born lists are partitioned by row
    *origin* shard — each shard keeps a (n_lists, cap_local) ELL of its
    own rows per global list — so the union of per-shard candidates is the
    global probe membership.
  * ``int8`` (born only) — per-shard quantized scan over shard-local
    codes/scales (ranking is scale-invariant within a shard), candidate
    ids all-gathered, then the float rerank runs distributed as in lsh.
    The deprecated global-partition path still rejects int8: its −1e30
    padding sentinel would destroy the single global quantization scale.

Padding invariants: rows/lists pad to a multiple of the shard count; padded
rows mask to −inf/−1 before the merge and can never displace a real
candidate.  Dense rows (exact, tfidf, int8 codes) pad with zeros and each
shard gives the top-k kernel its row bound, ``n − row0`` clipped to the
shard, which masks the rest inside the kernel: the rows are scanned where
they lie and never copied.  LSH pad rows carry W+1 all-ones extra code
words, IVF pad rows assign to a dummy list that is never probed.

Every born plan runs as one compiled program per (index type, shapes, k,
mesh): :func:`sharded_search` dispatches to a jitted ``_born_search``,
whose indexes are pytrees with their row count static, so a call after a
shape's first compiles nothing.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.distributed import collectives as coll
from repro.distributed.compression import quantize_int8
from repro.distributed.sharded_corpus import ShardedCorpus
from repro.distributed.sharding import RETRIEVAL_RULES, partition_axes
from repro.kernels.topk_scoring import ops as topk_ops
from repro.kernels.topk_scoring.ref import pad_topk as _pad_topk
from repro.obs.metrics import REGISTRY
from repro.retrieval.backends import get_backend, rerank_candidates
from repro.retrieval.ivfflat import assign_lists, fill_lists, list_ranks
from repro.retrieval.lsh import encode

_F32 = lax.Precision.HIGHEST      # XLA's TPU default rounds f32 to bf16

#: counter of the partial-list entries the merge gathers: queries x k x
#: shards a call
MERGE_CANDIDATES = "search.merge.candidates"


def _resolve_axes(mesh: Mesh, axes: Optional[tuple]) -> tuple:
    if axes is None:
        axes = partition_axes(mesh, "corpus", RETRIEVAL_RULES)
    axes = tuple(axes) if axes else ()
    if not axes:
        raise ValueError(
            f"mesh {mesh} has none of the retrieval corpus axes "
            f"({RETRIEVAL_RULES['corpus']})")
    return axes


def _axis_count(mesh: Mesh, axes: tuple) -> int:
    d = 1
    for a in axes:
        d *= mesh.shape[a]
    return d


def _row_spec(axes: tuple, ndim: int) -> P:
    lead = axes if len(axes) > 1 else axes[0]
    return P(lead, *([None] * (ndim - 1)))


def _col_spec(axes: tuple) -> P:
    """Column-sharded 2-D array: the LSH index's transposed (W, N) codes."""
    return P(None, axes if len(axes) > 1 else axes[0])


def _merge(s: jnp.ndarray, i: jnp.ndarray, axes: tuple, k: int):
    """All-gather per-shard (scores, ids) partials along the k axis and
    reduce to the global top-k (replicated on every shard).  Its device
    ops sit under the ``search.merge`` scope in a profiler trace."""
    with jax.named_scope("search.merge"):
        s = lax.all_gather(s, axes, axis=1, tiled=True)
        i = lax.all_gather(i, axes, axis=1, tiled=True)
        top_s, pos = lax.top_k(s, min(k, s.shape[1]))
        return top_s, jnp.take_along_axis(i, pos, axis=1)


def _shard_bound(row0, rows: int, n: int):
    """Rows of a shard that starting at global row ``row0`` hold corpus
    rows (the rest are zero pad): the top-k kernel's row bound."""
    return jnp.clip(n - row0, 0, rows)


def _dense_shard_topk(backend, v_l, q, *, rows: int, n: int, k_l: int,
                      k: int, axes: tuple):
    """One shard's dense top-k over its rows, bounded to the corpus rows it
    holds, merged across the shards with global ids."""
    row0 = coll.flat_axis_index(axes) * rows
    s, i = backend.topk(q, v_l, k=k_l, n_valid=_shard_bound(row0, rows, n))
    return _merge(s, jnp.where(i >= 0, row0 + i, -1), axes, k)


def _rowwise_topk(backend, vecs: jnp.ndarray, queries: jnp.ndarray, *,
                  k: int, mesh: Mesh, axes: tuple):
    """Row-sharded dense top-k: the shared plan for exact and tfidf.

    .. deprecated:: part of the build-globally-then-partition path — the
       full index is resident on every device before the scan.  Prefer a
       sharded-from-birth build (:func:`sharded_build`)."""
    n = vecs.shape[0]
    d = _axis_count(mesh, axes)
    rows = -(-n // d)
    pad = rows * d - n
    # zero pad rows: each shard's row bound keeps them out of its top-k
    vp = jnp.pad(vecs, ((0, pad), (0, 0))) if pad else vecs
    shard_fn = functools.partial(_dense_shard_topk, backend, rows=rows, n=n,
                                 k_l=min(k, rows), k=k, axes=axes)
    fn = shard_map(shard_fn, mesh=mesh,
                   in_specs=(_row_spec(axes, 2), P(None, None)),
                   out_specs=(P(), P()), check_vma=False)
    return _pad_topk(*fn(vp, queries), k)


def _sharded_exact(engine, index, queries, *, k, mesh, axes):
    return _rowwise_topk(get_backend(engine.backend), index, queries,
                         k=k, mesh=mesh, axes=axes)


def _sharded_tfidf(engine, index, queries, *, k, mesh, axes):
    # IDF weights were folded into index.vecs at (global) build time, so the
    # sharded scan is the exact engine's plan over the weighted rows.
    return _rowwise_topk(get_backend(engine.backend), index.vecs, queries,
                         k=k, mesh=mesh, axes=axes)


def _sharded_lsh(engine, index, queries, *, k, mesh, axes):
    backend = get_backend(engine.backend)
    n = index.vecs.shape[0]
    d = _axis_count(mesh, axes)
    rows = -(-n // d)
    rerank = min(max(engine.rerank, k), n) if engine.rerank > 0 else 0
    target = rerank if rerank > 0 else k
    t_l = min(target, rows)
    qc = encode(index.proj, queries)
    pad = rows * d - n
    if pad:
        # a zero-padded code column would get a REAL Hamming distance and
        # could evict a true candidate from the local top-k, so padded rows
        # get W+1 extra all-ones words (queries and real rows get zeros):
        # their distance grows by 32·(W+1) > 32·W ≥ any real distance,
        # strictly below every real row — exact integer arithmetic, and
        # real-row distances are untouched
        w = index.codes.shape[0]
        cp = jnp.pad(index.codes, ((0, w + 1), (0, pad)))
        cp = cp.at[w:, n:].set(-1)
        qc = jnp.pad(qc, ((0, 0), (0, w + 1)))
    else:
        cp = index.codes

    def shard_fn(c_l, qc_):
        row0 = coll.flat_axis_index(axes) * rows
        s, i = backend.hamming_topk(qc_, c_l, k=t_l)
        gid = row0 + i
        ok = (i >= 0) & (gid < n)
        return _merge(jnp.where(ok, s, -jnp.inf),
                      jnp.where(ok, gid, -1), axes, target)

    fn = shard_map(shard_fn, mesh=mesh,
                   in_specs=(_col_spec(axes), P(None, None)),
                   out_specs=(P(), P()), check_vma=False)
    neg, cand = fn(cp, qc)
    if rerank <= 0:
        # match search_lsh's historical no-rerank API: positive Hamming
        # distance, lower = better (+inf for misses)
        neg, cand = _pad_topk(neg, cand, k)
        return (-neg).astype(queries.dtype), cand
    # exact rerank of the merged global candidates — identical math to the
    # single-device search_lsh rerank step, on the replicated vectors
    return rerank_candidates(index.vecs, queries, cand, k=k)


def _sharded_ivfflat(engine, index, queries, *, k, mesh, axes):
    backend = get_backend(engine.backend)
    n_lists, cap, dim = index.vecs.shape
    nprobe = min(engine.nprobe, n_lists)
    d = _axis_count(mesh, axes)
    ll = -(-n_lists // d)
    pad = ll * d - n_lists
    vecs = jnp.pad(index.vecs, ((0, pad), (0, 0), (0, 0)))
    ids = jnp.pad(index.ids, ((0, pad), (0, 0)), constant_values=-1)
    mask = jnp.pad(index.mask, ((0, pad), (0, 0)))
    k_l = min(k, nprobe * cap)

    def shard_fn(v_l, i_l, m_l, cent, q):
        l0 = coll.flat_axis_index(axes) * ll
        cscore = jnp.dot(q, cent.T, precision=_F32)  # (Q, n_lists) global
        _, probe = lax.top_k(cscore, nprobe)         # same probes everywhere
        own = (probe >= l0) & (probe < l0 + ll)
        lp = jnp.clip(probe - l0, 0, ll - 1)
        v = v_l[lp]                                  # (Q, nprobe, cap, dim)
        cid = jnp.where(m_l[lp] & own[..., None], i_l[lp], -1)
        qn = q.shape[0]
        s, gid = backend.gathered_topk(q, v.reshape(qn, -1, dim),
                                       cid.reshape(qn, -1), k=k_l)
        return _merge(s, gid, axes, k)

    fn = shard_map(shard_fn, mesh=mesh,
                   in_specs=(_row_spec(axes, 3), _row_spec(axes, 2),
                             _row_spec(axes, 2), P(None, None),
                             P(None, None)),
                   out_specs=(P(), P()), check_vma=False)
    return _pad_topk(*fn(vecs, ids, mask, index.centroids, queries), k)


_SHARDED_IMPLS: Dict[str, Callable] = {
    "exact": _sharded_exact,
    "tfidf": _sharded_tfidf,
    "lsh": _sharded_lsh,
    "ivfflat": _sharded_ivfflat,
}


# ---------------------------------------------------------------------------
# Sharded-from-birth: per-shard index construction + shard-local search.
# The index never exists globally — every field below is a row-sharded
# jax.Array whose shards were built on the device that owns them.
# ---------------------------------------------------------------------------


def _born_index(cls):
    """A born index: a frozen dataclass registered as a pytree whose arrays
    are leaves and whose row count ``n`` (and LSH's ``aug``) are static, so
    a jitted plan compiles once per index type, shapes and row count."""
    return jax.tree_util.register_dataclass(dataclasses.dataclass(
        frozen=True)(cls))


def _static():
    return dataclasses.field(metadata=dict(static=True))


@_born_index
class ShardedFlatIndex:
    """Born-sharded dense rows (exact engine): the ``ShardedCorpus``'s own
    buffer, zero past ``n``."""

    vecs: Any        # f32[rows·d, D] row-sharded
    n: int = _static()


@_born_index
class ShardedTfIdfIndex:
    """Born-sharded IDF-weighted rows; ``weights`` replicate (they are an
    O(D) statistic reduced with an integer psum — bit-identical on any
    mesh)."""

    vecs: Any        # f32[rows·d, D] row-sharded, IDF-weighted
    weights: Any     # f32[D] replicated
    n: int = _static()


@_born_index
class ShardedQuantIndex:
    """Born-sharded int8 corpus: per-shard codes with shard-local scales
    (PR 5's rejection lifted — ranking within a shard is scale-invariant,
    and cross-shard merging happens after the float rerank, so no global
    scale is ever needed).  ``vecs`` keeps the float rows (IDF-weighted for
    tfidf) sharded for the distributed rerank tail."""

    codes: Any       # i8[rows·d, D] row-sharded
    scales: Any      # f32[d] one max-abs scale per shard
    vecs: Any        # f32[rows·d, D] row-sharded
    n: int = _static()


@_born_index
class ShardedLSHIndex:
    """Born-sharded LSH: codes encoded shard-locally from the replicated
    projection; ``aug`` marks the W+1 all-ones pad-sentinel words."""

    proj: Any        # f32[D, n_bits] replicated
    codes: Any       # i32[W(+W+1), rows·d] transposed, column-sharded
    vecs: Any        # f32[rows·d, D] row-sharded (rerank)
    n: int = _static()
    aug: bool = _static()


@_born_index
class ShardedIVFIndex:
    """Born-sharded IVF: lists partitioned by row ORIGIN shard — each shard
    holds a (n_lists, cap_local) ELL of its own rows per global list, so
    no row ever moves between shards at build time.  Centroids replicate
    (they are refined from shard-local partial sums converged by a
    per-iteration all-reduce), so all shards compute identical probe
    sets and the union of per-shard candidates is the global probe
    membership."""

    centroids: Any   # f32[n_lists, D] replicated
    vecs: Any        # f32[d·n_lists, cap_local, D] row-sharded by origin
    ids: Any         # i32[d·n_lists, cap_local] global ids, −1 pad
    mask: Any        # bool[d·n_lists, cap_local]
    n: int = _static()


def _shard_geometry(corpus: ShardedCorpus):
    axes = corpus.axes
    d = corpus.num_shards
    rows = corpus.rows_per_shard
    return axes, d, rows, rows * d - corpus.n


def _local_valid(row0, rows: int, n: int):
    return (row0 + jnp.arange(rows, dtype=jnp.int32)) < n


def _quant_build(corpus: ShardedCorpus, row_vecs) -> ShardedQuantIndex:
    """Per-shard int8 quantization: each shard derives its own max-abs
    scale from its local rows only (zero pad rows cannot perturb it)."""
    axes = corpus.axes

    def f(v_l):
        codes, scale = quantize_int8(v_l)
        return codes, scale[None]

    fn = shard_map(f, mesh=corpus.mesh, in_specs=(_row_spec(axes, 2),),
                   out_specs=(_row_spec(axes, 2), P(_lead_axes(axes))),
                   check_vma=False)
    codes, scales = fn(row_vecs)
    return ShardedQuantIndex(codes, scales, row_vecs, corpus.n)


def _lead_axes(axes: tuple):
    return axes if len(axes) > 1 else axes[0]


def _build_born_exact(engine, corpus: ShardedCorpus, key):
    del key  # deterministic
    if engine.backend == "int8":
        return _quant_build(corpus, corpus.vecs)
    return ShardedFlatIndex(corpus.vecs, corpus.n)


def _build_born_tfidf(engine, corpus: ShardedCorpus, key):
    del key  # deterministic
    axes = corpus.axes
    n = corpus.n

    def f(v_l):
        # integer document frequencies psum exactly -> IDF weights are
        # bit-identical to the global build on any mesh (pad rows are
        # all-zero, so (v > 0) contributes nothing)
        df = lax.psum(jnp.sum(v_l > 0, axis=0), axes).astype(
            jnp.float32) + 1.0
        w = jnp.log1p(n / df)
        return v_l * w[None, :], w

    fn = shard_map(f, mesh=corpus.mesh, in_specs=(_row_spec(axes, 2),),
                   out_specs=(_row_spec(axes, 2), P(None)), check_vma=False)
    weighted, w = fn(corpus.vecs)
    if engine.backend == "int8":
        quant = _quant_build(corpus, weighted)
        return ShardedTfIdfIndex(quant, w, corpus.n)
    return ShardedTfIdfIndex(weighted, w, corpus.n)


def _build_born_lsh(engine, corpus: ShardedCorpus, key):
    axes, d, rows, pad = _shard_geometry(corpus)
    n = corpus.n
    proj = jax.random.normal(key, (corpus.dim, engine.n_bits),
                             corpus.vecs.dtype)

    def f(v_l, proj_):
        row0 = coll.flat_axis_index(axes) * rows
        codes = encode(proj_, v_l).T                 # (W, rows)
        if pad:
            # the legacy path's pad sentinel, applied at birth: pad rows
            # get W+1 extra all-ones words (real rows and queries zeros),
            # growing their Hamming distance past any real row's
            w = codes.shape[0]
            extra = jnp.where(_local_valid(row0, rows, n)[None, :],
                              jnp.int32(0), jnp.int32(-1))
            codes = jnp.concatenate(
                [codes, jnp.broadcast_to(extra, (w + 1, rows))], axis=0)
        return codes

    fn = shard_map(f, mesh=corpus.mesh,
                   in_specs=(_row_spec(axes, 2), P(None, None)),
                   out_specs=_col_spec(axes), check_vma=False)
    return ShardedLSHIndex(proj, fn(corpus.vecs, proj), corpus.vecs,
                           n, bool(pad))


def _build_born_ivfflat(engine, corpus: ShardedCorpus, key,
                        kmeans_iters: int = 10):
    """IVF build with shard-local centroid refinement: Lloyd iterations
    compute per-shard (sum, count) partials over local rows and converge
    them with one ``psum`` all-reduce per iteration — no device ever sees
    another shard's rows.  List fill is shard-local too: each shard packs
    its own rows into a (n_lists, cap_local) ELL keyed by the replicated
    centroids, ``cap_local`` being the longest local list on any shard."""
    axes, d, rows, pad = _shard_geometry(corpus)
    n = corpus.n
    n_lists = min(engine.n_lists, max(1, n // 8))
    # same init selection as ivfflat.kmeans (replicated): global row ids
    init_idx = jax.random.choice(key, n, (n_lists,), replace=False)

    def lloyd(v_l, init_g):
        row0 = coll.flat_axis_index(axes) * rows
        valid = _local_valid(row0, rows, n)

        # replicated init centroids: each shard contributes the init rows
        # it owns; the psum assembles the same gather kmeans() does
        lidx = init_g - row0
        own = (lidx >= 0) & (lidx < rows)
        cand = v_l[jnp.clip(lidx, 0, rows - 1)]
        cent0 = lax.psum(jnp.where(own[:, None], cand, 0.0), axes)

        # pad rows route to a dummy segment so they never pull a centroid;
        # the dummy is only materialised when pads exist (1-device parity)
        nseg = n_lists + 1 if pad else n_lists
        seg = ((lambda a: jnp.where(valid, a, n_lists)) if pad
               else (lambda a: a))

        def step(cent, _):
            a = seg(assign_lists(v_l, cent))
            sums = jax.ops.segment_sum(v_l, a,
                                       num_segments=nseg)[:n_lists]
            cnts = jax.ops.segment_sum(jnp.ones((rows, 1), v_l.dtype), a,
                                       num_segments=nseg)[:n_lists]
            sums, cnts = lax.psum((sums, cnts), axes)
            new = jnp.where(cnts > 0, sums / jnp.maximum(cnts, 1.0), cent)
            return new, None

        cent, _ = lax.scan(step, cent0, None, length=kmeans_iters)
        a = seg(assign_lists(v_l, cent))
        rank, counts = list_ranks(a, n_lists)
        return cent, a, rank, counts[None]

    fn = shard_map(lloyd, mesh=corpus.mesh,
                   in_specs=(_row_spec(axes, 2), P(None)),
                   out_specs=(P(None, None), _row_spec(axes, 1),
                              _row_spec(axes, 1), _row_spec(axes, 2)),
                   check_vma=False)
    cent, assign, rank, counts = fn(corpus.vecs, init_idx)
    cap_l = int(jnp.max(counts))

    def fill(v_l, a, r):
        row0 = coll.flat_axis_index(axes) * rows
        return fill_lists(v_l, row0 + jnp.arange(rows, dtype=jnp.int32), a,
                          r, n_lists, cap_l)

    fn = shard_map(fill, mesh=corpus.mesh,
                   in_specs=(_row_spec(axes, 2), _row_spec(axes, 1),
                             _row_spec(axes, 1)),
                   out_specs=(_row_spec(axes, 3), _row_spec(axes, 2),
                              _row_spec(axes, 2)),
                   check_vma=False)
    lvecs, lids, lmask = fn(corpus.vecs, assign, rank)
    return ShardedIVFIndex(cent, lvecs, lids, lmask, n)


_BORN_BUILDS: Dict[str, Callable] = {
    "exact": _build_born_exact,
    "tfidf": _build_born_tfidf,
    "lsh": _build_born_lsh,
    "ivfflat": _build_born_ivfflat,
}


def sharded_build(engine, corpus: ShardedCorpus, key=None):
    """Per-shard index construction over a sharded-from-birth corpus.

    Returns a born index (``Sharded*Index``) whose corpus-proportional
    fields are row-sharded jax.Arrays; :func:`sharded_search` routes them
    to the shard-local query plans.  On a 1-device mesh the built index
    is bit-identical to ``engine.build`` on the gathered rows."""
    try:
        impl = _BORN_BUILDS[engine.name]
    except KeyError:
        raise ValueError(
            f"no shard-local build plan for engine {engine.name!r}; "
            f"engines with plans: {', '.join(sorted(_BORN_BUILDS))}"
        ) from None
    if key is None:
        key = jax.random.PRNGKey(0)
    return impl(engine, corpus, key)


def _distributed_rerank(v_l, q, cand, row0, rows: int, k: int, axes):
    """Float rerank of replicated candidate ids against row-sharded
    vectors: each shard scores the candidates it owns (−inf elsewhere) and
    the partial score rows merge with ``pmax`` — every real candidate is
    owned by exactly one shard, so the merged row equals
    ``rerank_candidates`` on the gathered vectors, bit for bit on one
    device and value-equal on any mesh."""
    lid = cand - row0
    own = (cand >= 0) & (lid >= 0) & (lid < rows)
    cv = v_l[jnp.clip(lid, 0, rows - 1)]
    s = jnp.einsum("qd,qrd->qr", q, cv, precision=_F32)
    s = jnp.where(own, s, -jnp.inf)
    s = lax.pmax(s, axes)
    s = jnp.where(cand >= 0, s, -jnp.inf)
    top_s, pos = lax.top_k(s, min(k, cand.shape[1]))
    top_i = jnp.take_along_axis(cand, pos, axis=1)
    top_i = jnp.where(jnp.isfinite(top_s), top_i, -1)
    return _pad_topk(top_s, top_i, k)


def _search_born_rows(engine, index, queries, *, k: int, mesh, axes):
    """Shard-local dense scan over born rows (exact / tfidf): each shard
    scans the rows it holds, bounded to the corpus rows among them, so
    this is ``_rowwise_topk`` minus the global pad step."""
    d = _axis_count(mesh, axes)
    rows = index.vecs.shape[0] // d
    f = functools.partial(_dense_shard_topk, get_backend(engine.backend),
                          rows=rows, n=index.n, k_l=min(k, rows), k=k,
                          axes=axes)
    fn = shard_map(f, mesh=mesh,
                   in_specs=(_row_spec(axes, 2), P(None, None)),
                   out_specs=(P(), P()), check_vma=False)
    return _pad_topk(*fn(index.vecs, queries), k)


def _search_born_quant(engine, index: ShardedQuantIndex, queries, *,
                       k: int, mesh, axes):
    """Born int8 plan: per-shard quantized scan (shard-local codes — the
    integer ranking is invariant to the shard's own scale) bounded to the
    shard's corpus rows, so zero-code pad rows never enter the pool;
    candidate ids all-gathered, float rerank distributed over the sharded
    rows."""
    backend = get_backend(engine.backend)
    d = _axis_count(mesh, axes)
    rows = index.codes.shape[0] // d
    n = index.n
    pool_l = min(max(backend.rerank_factor * k, k), n, rows)
    q_codes, _ = quantize_int8(jnp.asarray(queries, jnp.float32))

    def f(c_l, v_l, qc, q):
        row0 = coll.flat_axis_index(axes) * rows
        _, i = topk_ops.topk_scores_int8(qc, c_l, k=pool_l,
                                         n_valid=_shard_bound(row0, rows, n),
                                         block_q=backend.block_q,
                                         block_n=backend.block_n)
        gid = jnp.where(i >= 0, row0 + i, -1)
        cand = lax.all_gather(gid, axes, axis=1, tiled=True)
        return _distributed_rerank(v_l, q, cand, row0, rows, k, axes)

    fn = shard_map(f, mesh=mesh,
                   in_specs=(_row_spec(axes, 2), _row_spec(axes, 2),
                             P(None, None), P(None, None)),
                   out_specs=(P(), P()), check_vma=False)
    return fn(index.codes, index.vecs, q_codes, queries)


def _search_born_lsh(engine, index: ShardedLSHIndex, queries, *, k: int,
                     mesh, axes):
    backend = get_backend(engine.backend)
    n = index.n
    d = _axis_count(mesh, axes)
    rows = index.codes.shape[1] // d
    rerank = min(max(engine.rerank, k), n) if engine.rerank > 0 else 0
    target = rerank if rerank > 0 else k
    t_l = min(target, rows)
    qc = encode(index.proj, queries)
    if index.aug:
        qc = jnp.pad(qc, ((0, 0), (0, index.codes.shape[0] - qc.shape[1])))

    def f(c_l, v_l, qc_, q):
        row0 = coll.flat_axis_index(axes) * rows
        s, i = backend.hamming_topk(qc_, c_l, k=t_l)
        gid = row0 + i
        ok = (i >= 0) & (gid < n)
        neg, cand = _merge(jnp.where(ok, s, -jnp.inf),
                           jnp.where(ok, gid, -1), axes, target)
        if rerank <= 0:
            return _pad_topk(neg, cand, k)
        return _distributed_rerank(v_l, q, cand, row0, rows, k, axes)

    fn = shard_map(f, mesh=mesh,
                   in_specs=(_col_spec(axes), _row_spec(axes, 2),
                             P(None, None), P(None, None)),
                   out_specs=(P(), P()), check_vma=False)
    s, ids = fn(index.codes, index.vecs, qc, queries)
    if rerank <= 0:
        # positive Hamming distance, matching search_lsh's no-rerank API
        return (-s).astype(queries.dtype), ids
    return s, ids


def _search_born_ivf(engine, index: ShardedIVFIndex, queries, *, k: int,
                     mesh, axes):
    backend = get_backend(engine.backend)
    n_lists = index.centroids.shape[0]
    cap_l, dim = index.vecs.shape[1], index.vecs.shape[2]
    nprobe = min(engine.nprobe, n_lists)
    k_l = min(k, nprobe * cap_l)

    def f(v_l, i_l, m_l, cent, q):
        # replicated centroids: the same probes on all shards
        cscore = jnp.dot(q, cent.T, precision=_F32)
        _, probe = lax.top_k(cscore, nprobe)
        # (list, slot) gather, as ivfflat.probe_candidates: no index copy
        slot = (probe[..., None], jnp.arange(cap_l)[None, None])
        v = v_l[slot]                            # (Q, nprobe, cap_l, dim)
        cid = jnp.where(m_l[slot], i_l[slot], -1)
        qn = q.shape[0]
        s, gid = backend.gathered_topk(q, v.reshape(qn, -1, dim),
                                       cid.reshape(qn, -1), k=k_l)
        return _merge(s, gid, axes, k)

    fn = shard_map(f, mesh=mesh,
                   in_specs=(_row_spec(axes, 3), _row_spec(axes, 2),
                             _row_spec(axes, 2), P(None, None),
                             P(None, None)),
                   out_specs=(P(), P()), check_vma=False)
    return _pad_topk(*fn(index.vecs, index.ids, index.mask,
                         index.centroids, queries), k)


_BORN_PLANS: Dict[type, Callable] = {
    ShardedFlatIndex: _search_born_rows,
    ShardedTfIdfIndex: _search_born_rows,
    ShardedQuantIndex: _search_born_quant,
    ShardedLSHIndex: _search_born_lsh,
    ShardedIVFIndex: _search_born_ivf,
}


def sharded_buffer_topk(buf_vecs, n_valid, queries, *, k: int, mesh: Mesh,
                        axes: Optional[tuple] = None, id_base: int = 0):
    """Dense exact top-k over a fixed-capacity row-sharded append buffer
    (the serving tier's live-ingest structure, DESIGN.md §14).

    ``buf_vecs`` is a row-sharded f32[cap·d, D] buffer (rows at global
    position ≥ ``n_valid`` are unused capacity); ``n_valid`` is a DYNAMIC
    scalar — appends grow it without changing any traced shape, so the
    steady-state serve loop never recompiles as rows land.  Scores are
    plain f32 inner products (buffers are small; quantization is a
    bandwidth optimisation for the big frozen index, not the tail), ids
    come back offset by ``id_base`` (the frozen corpus size), and the
    per-shard partials merge through the same all-gather + ``lax.top_k``
    path every sharded engine plan uses."""
    axes = _resolve_axes(mesh, axes)
    d = _axis_count(mesh, axes)
    rows = buf_vecs.shape[0] // d
    k_l = min(k, rows)

    def f(v_l, q, nv):
        row0 = coll.flat_axis_index(axes) * rows
        gid = row0 + jnp.arange(rows, dtype=jnp.int32)
        s = jnp.dot(q, v_l.T, precision=_F32).astype(jnp.float32)
        s = jnp.where((gid < nv)[None, :], s, -jnp.inf)
        top_s, pos = lax.top_k(s, k_l)
        top_i = jnp.where(jnp.isfinite(top_s), id_base + row0 + pos, -1)
        return _merge(top_s, top_i, axes, k)

    fn = shard_map(f, mesh=mesh,
                   in_specs=(_row_spec(axes, 2), P(None, None), P()),
                   out_specs=(P(), P()), check_vma=False)
    return _pad_topk(*fn(buf_vecs, queries, jnp.int32(n_valid)), k)


def _born_plan(index):
    """(plan, the index it runs on) for a born index: dispatch by type, in
    Python, before the plan is traced."""
    if isinstance(index, ShardedTfIdfIndex) and \
            isinstance(index.vecs, ShardedQuantIndex):
        return _search_born_quant, index.vecs
    return _BORN_PLANS[type(index)], index


@functools.partial(jax.jit,
                   static_argnames=("plan", "engine", "k", "mesh", "axes"))
def _born_search(plan, engine, index, queries, *, k: int, mesh, axes):
    """One born plan as one compiled program per (plan, engine, index
    shapes and row count, k, mesh)."""
    return plan(engine, index, queries, k=k, mesh=mesh, axes=axes)


def sharded_search(engine, index, queries: jnp.ndarray, *, k: int,
                   mesh: Mesh, axes: Optional[tuple] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mesh-partitioned ``engine.search`` with equivalent semantics:
    (scores f32[Q, k], ids i32[Q, k]) into the corpus the index was built
    from, −inf/−1 padding for misses.  Bit-consistent with single-device
    search on a 1-device mesh; set-equal under the backend tie policy on
    larger meshes.

    Born indexes from :func:`sharded_build` route to the shard-local
    plans (including int8); a pre-built global index falls through to the
    deprecated build-globally-then-partition plans below."""
    axes = _resolve_axes(mesh, axes)
    born = type(index) in _BORN_PLANS
    if not born and getattr(engine, "backend", None) == "int8":
        # the deprecated global-partition plans have no int8 plan (one
        # global quantization scale over rows split across shards); the
        # born path supports int8 via per-shard scales + float rerank —
        # build with ``sharded_build`` instead (DESIGN.md §13)
        raise ValueError(
            "sharded search does not support the 'int8' backend; use "
            "backend='jnp' or 'pallas' for sharded meshes")
    if not born and engine.name not in _SHARDED_IMPLS:
        raise ValueError(
            f"no sharded search plan for engine {engine.name!r}; engines "
            f"with plans: {', '.join(sorted(_SHARDED_IMPLS))}")
    REGISTRY.counter(MERGE_CANDIDATES).inc(
        int(queries.shape[0]) * k * _axis_count(mesh, axes))
    if born:
        plan, index = _born_plan(index)
        return _born_search(plan, engine, index, queries, k=k, mesh=mesh,
                            axes=axes)
    return _SHARDED_IMPLS[engine.name](engine, index, queries, k=k,
                                       mesh=mesh, axes=axes)
