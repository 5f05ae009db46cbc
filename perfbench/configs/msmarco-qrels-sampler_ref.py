"""Data and plain reference of the ``msmarco-qrels-sampler`` configuration.

Data: judgments of the shape of MS MARCO's passage training qrels, made
on the host from the seed by a copy of the repository's generator
(Yule-Simon entity multiplicities within Zipf-sized topics, uniform
scores).  The number of judgments per query follows the configuration's
``judgments_per_query`` histogram, the same multiset on every seed dealt
to the queries in a seeded order, so every seed gives the program the same
shapes.  The entity space is the fixed passage count of the configuration;
judged entities are those the generator mints, the rest are unjudged
passages.

Reference: the WindTunnel pipeline of arXiv:2410.20301 written out plainly
in ``jax.numpy``, independent of the program:

* Alg. 1 — keep judgments scoring above the ``tau_quantile`` quantile (by
  linear interpolation, in float32); per query, the ``fanout`` best
  (ties to the earlier row); every pair of distinct entities of a query is
  an edge of weight min(score, score); one edge per pair, its largest
  weight.
* Alg. 2 — each node keeps its ``max_degree`` heaviest edges (ties to the
  smaller neighbour), in that slot order; ``lp_rounds`` rounds of weighted
  label propagation from L(v) = v: a node takes the label whose
  neighbours' weights sum highest, summed in slot order, ties to the
  smaller label; a node without edges keeps its label.
* The draw — communities of eligible nodes (degree > 0) are kept with
  probability p_L = min(1, c·|L|/N), c found by 40 bisection steps so that
  the expected sample is ``target``·N, against ``jax.random.uniform`` of
  the draw's key; a kept label brings all its eligible nodes; a query is
  kept when one of its judged entities is.

It runs on the device after the program's state is freed.  The control
(:func:`control`) is this reference computed in bfloat16, the step below
the configuration's float32: scores, edge weights and propagation sums.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

I32_MAX = np.iinfo(np.int32).max


# ---------------------------------------------------------------------------
# data — a copy of the repository's generate_qrels (the calibration the
# configuration names), kept here so the benchmark's inputs never change
# with the program
# ---------------------------------------------------------------------------

def _simon_block(n_slots: int, alpha: float, rng: np.random.Generator):
    """Simon preferential attachment over ``n_slots`` judgment slots: a
    slot mints a new entity with probability ``alpha`` or copies the entity
    of a uniformly chosen earlier slot.  Local entity id per slot."""
    if n_slots == 0:
        return np.zeros((0,), np.int64)
    is_new = rng.random(n_slots) < alpha
    is_new[0] = True
    copy_src = (rng.random(n_slots) * np.arange(n_slots)).astype(np.int64)
    ptr = np.where(is_new, np.arange(n_slots), copy_src)
    for _ in range(max(1, int(np.ceil(np.log2(max(n_slots, 2)))) + 1)):
        ptr = ptr[ptr]
    return (np.cumsum(is_new) - 1)[ptr]


def per_query_counts(histogram: Dict[str, int], num_queries: int,
                     rng: np.random.Generator) -> np.ndarray:
    """Judgments of each query: the histogram's multiset (count -> number
    of queries) dealt to the queries in a random order."""
    counts = np.repeat(np.array([int(c) for c in histogram], np.int64),
                       [int(n) for n in histogram.values()])
    if counts.size != num_queries:
        raise ValueError(f"the histogram holds {counts.size} queries, the "
                         f"configuration {num_queries}")
    return rng.permutation(counts)


def generate_qrels(*, num_queries: int, judgments_per_query: Dict[str, int],
                   alpha: float, num_topics: int, topic_concentration: float,
                   seed: int):
    """(query ids, entity ids, scores f32, entities minted) on the host."""
    rng = np.random.default_rng(seed)
    counts = per_query_counts(judgments_per_query, num_queries, rng)
    topic_w = 1.0 / np.arange(1, num_topics + 1) ** topic_concentration
    topic_w /= topic_w.sum()
    query_topic = rng.choice(num_topics, size=num_queries, p=topic_w)
    q_ids, e_ids = [], []
    offset = 0
    for t in range(num_topics):
        qs = np.nonzero(query_topic == t)[0]
        per = counts[qs]
        n_slots = int(per.sum())
        local = _simon_block(n_slots, alpha, rng)
        q_ids.append(np.repeat(qs, per))
        e_ids.append(local + offset)
        offset += int(local.max()) + 1 if n_slots else 0
    q = np.concatenate(q_ids).astype(np.int32)
    e = np.concatenate(e_ids).astype(np.int32)
    scores = rng.random(q.shape[0]).astype(np.float32)
    return q, e, scores, offset


class Qrels(NamedTuple):
    query_ids: np.ndarray    # i32[rows]
    entity_ids: np.ndarray   # i32[rows]
    scores: np.ndarray       # f32[rows]
    judged: int              # entities the generator minted


def make_inputs(config: Dict, seed: int) -> Qrels:
    q, e, s, judged = generate_qrels(
        num_queries=config["num_queries"],
        judgments_per_query=config["judgments_per_query"],
        alpha=config["alpha"], num_topics=config["num_topics"],
        topic_concentration=config["topic_concentration"], seed=seed)
    if q.shape[0] != config["judgment_rows"]:
        raise ValueError(f"{q.shape[0]} judgment rows, the configuration "
                         f"states {config['judgment_rows']}")
    if judged > config["num_entities"]:
        raise ValueError(f"{judged} judged entities exceed the configured "
                         f"{config['num_entities']} passages")
    return Qrels(q, e, s, judged)


# ---------------------------------------------------------------------------
# reference
# ---------------------------------------------------------------------------

def tau(scores: np.ndarray, quantile: float) -> np.float32:
    """The ``quantile`` of the scores by linear interpolation, each step in
    float32: position q·(n−1), value lo·(1−f) + hi·f."""
    n = np.float32(scores.size)
    pos = np.float32(quantile) * (n - np.float32(1))
    lo_i, hi_i = int(np.floor(pos)), int(np.ceil(pos))
    part = np.partition(scores, [lo_i, hi_i])
    hw = pos - np.float32(lo_i)
    lw = np.float32(1) - hw
    return np.float32(part[lo_i] * lw) + np.float32(part[hi_i] * hw)


@functools.partial(jax.jit, static_argnames=("num_queries", "num_entities",
                                             "fanout"))
def affinity_graph(q, e, s, threshold, *, num_queries: int,
                   num_entities: int, fanout: int):
    """Alg. 1: (u, v, w, unique) sorted by (u, v) — ``unique`` marks the
    one row of each distinct pair, which holds its largest weight — and
    the degree of every node."""
    rows = q.shape[0]
    row = jnp.arange(rows, dtype=jnp.int32)
    qk = jnp.where(s > threshold, q, num_queries)        # dropped rows last
    order = jnp.lexsort((row, -s, qk))
    qs, es, ss = qk[order], e[order], s[order]
    per_query = jnp.zeros(num_queries + 1, jnp.int32).at[qs].add(1)
    rank = row - (jnp.cumsum(per_query) - per_query)[qs]
    top = (qs < num_queries) & (rank < fanout)
    at = (jnp.where(top, qs, num_queries), jnp.where(top, rank, 0))
    ent = jnp.full((num_queries, fanout), -1, jnp.int32).at[at].set(
        es, mode="drop")
    sc = jnp.zeros((num_queries, fanout), s.dtype).at[at].set(
        ss, mode="drop")
    i, j = np.triu_indices(fanout, 1)
    a, b = ent[:, i].ravel(), ent[:, j].ravel()
    ok = (a >= 0) & (b >= 0) & (a != b)
    u = jnp.where(ok, jnp.minimum(a, b), num_entities)
    v = jnp.where(ok, jnp.maximum(a, b), num_entities)
    w = jnp.minimum(sc[:, i].ravel(), sc[:, j].ravel())
    order = jnp.lexsort((-w, v, u))                      # heaviest first
    u, v, w = u[order], v[order], w[order]
    new_pair = jnp.concatenate([jnp.ones(1, bool),
                                (u[1:] != u[:-1]) | (v[1:] != v[:-1])])
    unique = new_pair & (u < num_entities)
    none = jnp.int32(num_entities)
    degrees = jnp.zeros(num_entities, jnp.int32)
    degrees = degrees.at[jnp.where(unique, u, none)].add(1, mode="drop")
    degrees = degrees.at[jnp.where(unique, v, none)].add(1, mode="drop")
    return u, v, w, unique, degrees


@functools.partial(jax.jit, static_argnames=("num_entities", "max_degree"))
def adjacency(u, v, w, unique, degrees, *, num_entities: int,
              max_degree: int):
    """Each node's ``max_degree`` heaviest edges, ties to the smaller
    neighbour, as slot-major (K, N) neighbour ids (−1: none) and weights."""
    src = jnp.concatenate([u, v])
    dst = jnp.where(jnp.concatenate([unique, unique]),
                    jnp.concatenate([v, u]), num_entities)
    ww = jnp.concatenate([w, w])
    order = jnp.lexsort((src, -ww, dst))
    src, dst, ww = src[order], dst[order], ww[order]
    first = jnp.cumsum(degrees) - degrees
    pos = jnp.arange(dst.shape[0], dtype=jnp.int32)
    rank = pos - first[jnp.minimum(dst, num_entities - 1)]
    slot = (dst < num_entities) & (rank < max_degree)
    at = (jnp.where(slot, rank, max_degree), dst)
    nbr = jnp.full((max_degree, num_entities), -1, jnp.int32).at[at].set(
        src, mode="drop")
    wgt = jnp.zeros((max_degree, num_entities), w.dtype).at[at].set(
        ww, mode="drop")
    return nbr, wgt


@functools.partial(jax.jit, static_argnames=("rounds", "dtype"))
def propagate(nbr, wgt, *, rounds: int, dtype=jnp.float32):
    """Alg. 2's label propagation; weights and sums in ``dtype``."""
    k_slots, n = nbr.shape
    valid = nbr >= 0
    weight = jnp.where(valid, wgt, 0).astype(dtype)

    def one_round(labels, _):
        lab = jnp.where(valid, labels[jnp.maximum(nbr, 0)], -1)
        score = jnp.zeros((k_slots, n), dtype)
        for k in range(k_slots):                   # in slot order
            score = score + jnp.where(lab == lab[k:k + 1],
                                      weight[k:k + 1], 0).astype(dtype)
        score = jnp.where(valid, score, -jnp.inf)
        top = jnp.max(score, axis=0, keepdims=True)
        best = jnp.min(jnp.where(valid & (score == top), lab, I32_MAX),
                       axis=0)
        return jnp.where(best == I32_MAX, labels, best), None

    labels, _ = lax.scan(one_round, jnp.arange(n, dtype=jnp.int32), None,
                         length=rounds)
    return labels


def draw_key(seed: int) -> jax.Array:
    """``jax.random.PRNGKey(seed)`` for a draw seed below 2**31."""
    return jax.random.PRNGKey(seed)


@functools.partial(jax.jit, static_argnames=("tie_rtol",))
def cluster_draw(labels, degrees, key, target, *, tie_rtol: float):
    """(p per label, entity mask, entities whose label is a near tie
    |u − p| ≤ tie_rtol·p, where one rounding of p could flip the keep)."""
    n = labels.shape[0]
    eligible = degrees > 0
    sizes = jnp.zeros(n + 1, jnp.int32).at[
        jnp.where(eligible, labels, n)].add(1)[:n]
    n_total = jnp.maximum(jnp.sum(eligible.astype(jnp.float32)), 1.0)
    want = target * n_total
    size_f = sizes.astype(jnp.float32)

    def expected(c):
        return jnp.sum(jnp.minimum(1.0, c * size_f / n_total) * size_f)

    def bisect(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        short = expected(mid) < want
        return jnp.where(short, mid, lo), jnp.where(short, hi, mid)

    lo, hi = lax.fori_loop(0, 40, bisect, (jnp.float32(0.0), n_total))
    p = jnp.minimum(1.0, 0.5 * (lo + hi) * (size_f / n_total))
    unif = jax.random.uniform(key, (n,))
    kept = (unif < p) & (sizes > 0)
    near = jnp.abs(unif - p) <= tie_rtol * p
    return p, sizes, kept[labels] & eligible, near[labels] & eligible


@functools.partial(jax.jit, static_argnames=("num_queries",))
def kept_queries(q, e, mask, *, num_queries: int):
    """Queries with at least one kept judged entity."""
    hit = jnp.where(mask[e], q, num_queries)
    return jnp.zeros(num_queries + 1, jnp.int32).at[hit].add(1)[
        :num_queries] > 0


@jax.jit
def _compact(u, v, w, keep):
    m = u.shape[0]
    at = jnp.where(keep, jnp.cumsum(keep.astype(jnp.int32)) - 1, m)
    out = (jnp.full(m, -1, jnp.int32).at[at].set(u, mode="drop"),
           jnp.full(m, -1, jnp.int32).at[at].set(v, mode="drop"),
           jnp.full(m, -1.0, w.dtype).at[at].set(w, mode="drop"))
    return out


@jax.jit
def _edges_differ(a, b):
    return jnp.sum((a[0] != b[0]) | (a[1] != b[1]) | (a[2] != b[2]))


class Draw(NamedTuple):
    """One draw of the program, as the cell keeps it."""

    seed: int
    entity_mask: jax.Array
    keep_prob: jax.Array
    query_mask: jax.Array


class Run(NamedTuple):
    """What one sampling job of the program produced."""

    edges: Optional[tuple]      # (u, v, w, valid) rows, or None: not kept
    degrees: jax.Array
    labels: jax.Array
    draws: List[Draw]


class Reference:
    """The reference pipeline over ``inputs``: graph, adjacency and labels
    computed once; draws on request."""

    def __init__(self, config: Dict, inputs: Qrels, *,
                 lp_dtype=jnp.float32):
        self.config = config
        self.q = jnp.asarray(inputs.query_ids)
        self.e = jnp.asarray(inputs.entity_ids)
        s = jnp.asarray(inputs.scores)
        n = config["num_entities"]
        u, v, w, unique, self.degrees = affinity_graph(
            self.q, self.e, s, tau(inputs.scores, config["tau_quantile"]),
            num_queries=config["num_queries"], num_entities=n,
            fanout=config["fanout"])
        self.edges = _compact(u, v, w, unique)
        nbr, wgt = adjacency(u, v, w, unique, self.degrees, num_entities=n,
                             max_degree=config["max_degree"])
        del u, v, w, unique
        self.labels = propagate(nbr, wgt, rounds=config["lp_rounds"],
                                dtype=lp_dtype)

    def compare(self, runs: List[Run], target: float) -> Dict[str, float]:
        """The numbers compared, summed over ``runs`` (gaps: their max)."""
        limits = self.config["limits"]
        out = {name: 0.0 for name in limits}
        for run in runs:
            if run.edges is not None:
                out["edges_diff"] += int(_edges_differ(
                    _compact(*run.edges), self.edges))
            out["degrees_diff"] += int(jnp.sum(run.degrees != self.degrees))
            out["labels_diff"] += int(jnp.sum(run.labels != self.labels))
            for d in run.draws:
                p, sizes, mask, near = cluster_draw(
                    self.labels, self.degrees, draw_key(d.seed), target,
                    tie_rtol=limits["keep_prob_gap"])
                out["mask_diff"] += int(jnp.sum((d.entity_mask != mask)
                                                & ~near))
                out["query_diff"] += int(jnp.sum(
                    d.query_mask != kept_queries(
                        self.q, self.e, d.entity_mask,
                        num_queries=self.config["num_queries"])))
                gap = jnp.max(jnp.where(sizes > 0, jnp.abs(d.keep_prob - p)
                                        / jnp.maximum(p, 1e-30), 0.0))
                out["keep_prob_gap"] = max(out["keep_prob_gap"], float(gap))
        return out


def control(config: Dict, traffic: Dict, seed: int,
            devices) -> Dict[str, float]:
    """The numbers compared when the reference computed in bfloat16 is put
    in the program's place: judgment scores rounded to bfloat16 (so the
    tau filter and the edge weights are bfloat16 values), propagation
    weights and sums in bfloat16; its edges, degrees, labels and its draw
    from those labels, against the float32 reference.  It runs on the
    default device, as the cell's reference does."""
    del traffic, devices
    target = config["sample_fraction"]
    inputs = make_inputs(config, seed)
    exact = Reference(config, inputs)
    rounded = np.asarray(jnp.asarray(inputs.scores).astype(jnp.bfloat16)
                         .astype(jnp.float32))
    low = Reference(config, inputs._replace(scores=rounded),
                    lp_dtype=jnp.bfloat16)
    draw_seed = seed % (2**31 - 1)
    p, _, mask, _ = cluster_draw(low.labels, low.degrees,
                                 draw_key(draw_seed), target,
                                 tie_rtol=config["limits"]["keep_prob_gap"])
    qmask = kept_queries(low.q, low.e, mask,
                         num_queries=config["num_queries"])
    edges = low.edges + (low.edges[0] >= 0,)
    run = Run(edges, low.degrees, low.labels,
              [Draw(draw_seed, mask, p, qmask)])
    return exact.compare([run], target)
