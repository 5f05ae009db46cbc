"""Sampling core front door (DESIGN.md §10) — build once, draw many.

:class:`SamplerSession` is the sampling-side twin of the search core's
:class:`~repro.retrieval.search_core.SearchSession`: one session pays the
expensive staged state — affinity-graph construction (Alg. 1) and label
propagation (Alg. 2 steps 1-3) — exactly once, and every subsequent
``draw(target_size, seed)`` runs only the cheap cluster-sampling +
reconstruction tail.  A size/seed :meth:`~SamplerSession.sweep` therefore
costs one LP run instead of |sizes| × |seeds| of them.

Configuration is one declarative :class:`SamplerSpec`:

  * ``strategy``      — a registered sampling strategy (core/samplers.py:
    ``windtunnel`` / ``uniform`` / ``full`` / ``degree_stratified``);
  * ``engine``        — a registered LP engine (core/engines.py);
  * backend knobs     — ``tau_quantile`` / ``fanout`` / ``lp_rounds`` /
    ``max_degree``, exactly the legacy :class:`WindTunnelConfig` fields;
  * ``sharded``/``mesh`` — route the graph + LP stages through the
    mesh-partitioned path (core/sharded_pipeline.py); draws always run on
    the replicated outputs, so a 1-device mesh is bit-identical to the
    single-device session;
  * ``streamed``/``stream_chunk`` — shard the QRel table from birth
    (distributed/sharded_corpus.ShardedQRels): rows are routed host-side
    and streamed straight to their shards, so no device ever holds the
    global table; a :class:`ShardedQRels` may also be passed directly as
    ``qrels`` (both imply ``sharded=True``);
  * ``target_size``/``seed`` — per-draw defaults; ``target_size`` in (0, 1]
    is a fraction of the strategy's eligible universe, > 1 an absolute
    entity count, ``None`` the strategy default (paper |L|/N rule for
    ``windtunnel``).

Stages execute lazily and exactly once per session, with ``executions`` /
``requests`` counters mirroring :meth:`repro.eval.plans.PlanTrie.stage_counts`
so the reuse is observable and testable.  Unknown strategy/engine names fail
fast with the registry's error message (the ``core/engines.py`` UX).

The legacy entry points ``run_windtunnel`` / ``run_windtunnel_sharded`` /
``run_uniform_baseline`` are thin wrappers over a session and remain
bit-compatible; new code should construct the session directly.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import engines as eng
from repro.core import graph_builder as gb
from repro.core import label_prop as lp
from repro.core import reconstructor as rc
from repro.core import sampler as sm
from repro.core.pipeline import WindTunnelConfig, WindTunnelResult
from repro.core.samplers import DrawState, get_sampler
from repro.core.sharded_pipeline import sharded_graph_and_labels
from repro.distributed.sharded_corpus import ShardedQRels
from repro.obs import REGISTRY, trace
from repro.obs import memory as obs_memory


@dataclasses.dataclass(frozen=True)
class SamplerSpec:
    """Declarative sampling-core configuration (strategy × engine × mesh)."""

    strategy: str = "windtunnel"
    engine: str = "sort"          # any name in engines.available_engines()
    tau_quantile: float = 0.5
    fanout: int = 16
    lp_rounds: int = 5
    max_degree: int = 32
    target_size: Optional[float] = None   # default draw target (None = paper)
    seed: int = 0                         # default draw seed
    sharded: bool = False
    mesh: Any = None                      # jax.sharding.Mesh when sharded
    axes: Any = None                      # mesh axes override (sharded path)
    streamed: bool = False                # route the QRel table shard-local
    stream_chunk: int = 65536             # host->device streaming chunk rows
    strategy_opts: Optional[Mapping[str, Any]] = None

    def to_config(self) -> WindTunnelConfig:
        """The backend-knob subset as the legacy pipeline config."""
        return WindTunnelConfig(
            tau_quantile=self.tau_quantile, fanout=self.fanout,
            lp_rounds=self.lp_rounds, max_degree=self.max_degree,
            target_size=self.target_size, engine=self.engine, seed=self.seed)

    @classmethod
    def from_config(cls, config: WindTunnelConfig, **overrides) -> "SamplerSpec":
        fields = {f.name: getattr(config, f.name)
                  for f in dataclasses.fields(config)}
        fields.update(overrides)
        return cls(**fields)


class SamplerDraw(NamedTuple):
    """One draw: the mask, cluster-sampling diagnostics (windtunnel only),
    and the reconstructed (Queries, Corpus, QRels) sample."""

    entity_mask: jnp.ndarray
    sample: Optional[sm.ClusterSample]
    reconstructed: rc.ReconstructedSample


# ---------------------------------------------------------------------------
# Stage functions: module-level and jitted with static config args, so every
# session (and every legacy-wrapper call) shares one compile cache entry per
# distinct configuration.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "num_queries", "num_entities", "tau_quantile", "fanout"))
def _graph_stage(qrels, *, num_queries, num_entities, tau_quantile, fanout):
    edges = gb.build_affinity_graph(qrels, num_queries=num_queries,
                                    tau_quantile=tau_quantile, fanout=fanout)
    return edges, gb.node_degrees(edges, num_entities)


@functools.partial(jax.jit, static_argnames=(
    "engine", "num_entities", "max_degree", "rounds", "node_cap",
    "edge_cap"))
def _labels_stage(edges, *, engine, num_entities, max_degree, rounds,
                  node_cap, edge_cap):
    """LP over the first ``edge_cap`` edge slots, which hold every valid
    edge, and the ``node_cap`` nodes that can hold one, renumbered in id
    order (``lp.lp_caps``): the labels and changes of LP over every node
    and slot, bit for bit, and the number of nodes that hold an edge."""
    src, dst, w, valid = gb.symmetrize(
        gb.EdgeList(*(a[:edge_cap] for a in edges)))
    src, dst, ids, active = lp.compact_nodes(
        src, dst, valid, num_nodes=num_entities, size=node_cap)
    res = eng.run_engine(eng.get_engine(engine), src, dst, w, valid,
                         num_nodes=node_cap, max_degree=max_degree,
                         rounds=rounds)
    return (lp.expand_labels(res.labels, ids, num_nodes=num_entities),
            res.changes_per_round, active)


@functools.partial(jax.jit, static_argnames=(
    "strategy", "opts", "target", "num_queries", "num_entities"))
def _draw_stage(qrels, labels, degrees, seed, *, strategy, opts, target,
                num_queries, num_entities):
    strat = get_sampler(strategy)
    if opts:
        strat = dataclasses.replace(strat, **dict(opts))
    state = DrawState(qrels, num_entities, labels, degrees)
    # per-strategy salt decorrelates same-seed draws across strategies;
    # salt 0 keeps the raw key for legacy bit-parity (see samplers.py)
    key = jax.random.PRNGKey(seed)
    if strat.salt:
        key = jax.random.fold_in(key, strat.salt)
    mask, sample = strat.draw(state, key, target)
    recon = rc.reconstruct(qrels, mask, num_queries=num_queries)
    return SamplerDraw(mask, sample, recon)


@dataclasses.dataclass
class SweepResult:
    """A size × seed sweep: per-draw results plus the stage counters that
    prove graph-build and LP ran once for the whole sweep."""

    strategy: str
    sizes: Tuple[float, ...]
    seeds: Tuple[int, ...]
    draws: Dict[Tuple[float, int], SamplerDraw]
    stage_counts: Dict[str, Tuple[int, int]]

    def to_json(self) -> dict:
        return {
            "strategy": self.strategy,
            "sizes": list(self.sizes),
            "seeds": list(self.seeds),
            "draws": [{"target_size": s, "seed": r,
                       "n_entities": int(d.entity_mask.sum()),
                       "n_queries": int(d.reconstructed.num_queries)}
                      for (s, r), d in sorted(self.draws.items())],
            "stage_counts": {st: {"executions": ex, "requests": rq}
                             for st, (ex, rq) in self.stage_counts.items()},
        }


class SamplerSession:
    """Build-once, draw-many sampling over one QRel table.

    Stages — ``graph`` (Alg. 1 edges + degrees), ``labels`` (Alg. 2 LP),
    ``draw`` (cluster sampling / baseline mask + reconstruction) — execute
    lazily, each at most once per distinct draw key, and only when the
    active strategy declares it needs them (a ``uniform`` session never
    builds the graph).  ``strategy`` can be overridden per draw, so one
    session (one staged graph + LP) serves every registered strategy — the
    eval grid draws ``full`` / ``uniform`` / ``windtunnel`` from a single
    session.
    """

    STAGES = ("graph", "labels", "draw")

    def __init__(self, qrels, *, num_queries: int,
                 num_entities: int, spec: Optional[SamplerSpec] = None,
                 **overrides):
        cfg = spec or SamplerSpec()
        if overrides:
            cfg = dataclasses.replace(cfg, **overrides)
        get_sampler(cfg.strategy)        # registry error UX, fail fast
        eng.get_engine(cfg.engine)       # same UX for the LP engine
        born = qrels if isinstance(qrels, ShardedQRels) else None
        if born is None and cfg.streamed:
            if cfg.mesh is None:
                raise ValueError("streamed sampling needs a mesh; pass "
                                 "SamplerSpec(mesh=...) (launch.mesh "
                                 "helpers)")
            born = ShardedQRels.from_host(
                qrels, num_queries=num_queries, num_entities=num_entities,
                mesh=cfg.mesh, axes=cfg.axes, chunk_rows=cfg.stream_chunk)
        if born is not None:
            # sharded-from-birth tables force the mesh-partitioned stages
            # (the global stages would gather what birth sharding avoids)
            if (born.num_queries, born.num_entities) != (num_queries,
                                                         num_entities):
                raise ValueError(
                    f"ShardedQRels routed for {born.num_queries} queries / "
                    f"{born.num_entities} entities; session asked for "
                    f"{num_queries} / {num_entities}")
            cfg = dataclasses.replace(cfg, sharded=True, streamed=True,
                                      mesh=born.mesh, axes=born.axes)
        if cfg.sharded:
            if cfg.mesh is None:
                raise ValueError("sharded sampling needs a mesh; pass "
                                 "SamplerSpec(mesh=...) (launch.mesh helpers)")
            if cfg.engine not in ("ell", "pallas"):
                raise ValueError(
                    f"sharded pipeline requires an ELL-family engine ('ell' "
                    f"or 'pallas'); got {cfg.engine!r} — the sort engine's "
                    f"global per-round shuffle is exactly what this path "
                    f"eliminates")
        self.spec = cfg
        self._born = born
        # draws run on the (routed) flat table — reconstruction and every
        # registered strategy are row-order-free, so the born permutation
        # is invisible downstream
        self.qrels = born.table() if born is not None else qrels
        self.num_queries = num_queries
        self.num_entities = num_entities
        self._graph = None      # (edges, degrees)
        self._labels = None     # (labels, changes_per_round)
        self._draws: Dict[tuple, SamplerDraw] = {}
        self._counts = {stage: [0, 0] for stage in self.STAGES}

    # -- staged state -------------------------------------------------------

    def _stage_sharded(self) -> None:
        """One shard_map region computes graph AND labels (they share the
        partitioned dataflow); both stage slots fill from it.  The fused
        region is traced as ``sampling.graph`` (where the wall time lives)
        plus a zero-cost ``sampling.labels`` marker with ``fused=True``,
        so per-stage aggregates list both stages on either path."""
        with trace.jax_span("sampling.graph", sharded=True,
                            streamed=self._born is not None,
                            engine=self.spec.engine, n=self.num_entities,
                            q=self.num_queries, fused_labels=True) as sp:
            edges, degrees, labels, changes = sharded_graph_and_labels(
                self._born if self._born is not None else self.qrels,
                num_queries=self.num_queries,
                num_entities=self.num_entities, config=self.spec.to_config(),
                mesh=self.spec.mesh, axes=self.spec.axes)
            self._graph = (edges, degrees)
            self._labels = (labels, changes)
            sp.declare(self._graph, self._labels)
        obs_memory.record_build_peak()
        with trace.span("sampling.labels", sharded=True, fused=True,
                        engine=self.spec.engine):
            pass
        self._counts["graph"][0] += 1
        self._counts["labels"][0] += 1

    def graph(self) -> tuple:
        """(EdgeList, degrees i32[N]) — Alg. 1, executed once per session."""
        self._counts["graph"][1] += 1
        if self._graph is None:
            if self.spec.sharded:
                self._stage_sharded()
            else:
                with trace.jax_span("sampling.graph",
                                    n=self.num_entities,
                                    q=self.num_queries,
                                    tau=self.spec.tau_quantile,
                                    fanout=self.spec.fanout) as sp:
                    self._graph = _graph_stage(
                        self.qrels, num_queries=self.num_queries,
                        num_entities=self.num_entities,
                        tau_quantile=self.spec.tau_quantile,
                        fanout=self.spec.fanout)
                    sp.declare(self._graph)
                self._counts["graph"][0] += 1
        return self._graph

    def labels(self) -> tuple:
        """(labels i32[N], changes i32[rounds]) — Alg. 2 LP, executed once."""
        self._counts["labels"][1] += 1
        if self._labels is None:
            if self.spec.sharded:
                self._stage_sharded()
            else:
                edges, _ = self.graph()
                node_cap, edge_cap = self._lp_caps(edges)
                with trace.jax_span("sampling.labels",
                                    engine=self.spec.engine,
                                    n=self.num_entities,
                                    rounds=self.spec.lp_rounds,
                                    max_degree=self.spec.max_degree,
                                    node_cap=node_cap,
                                    edge_cap=edge_cap) as sp:
                    labels, changes, active = _labels_stage(
                        edges, engine=self.spec.engine,
                        num_entities=self.num_entities,
                        max_degree=self.spec.max_degree,
                        rounds=self.spec.lp_rounds,
                        node_cap=node_cap, edge_cap=edge_cap)
                    self._labels = (labels, changes)
                    sp.declare(self._labels)
                # a host read: traced runs only, and never of a tracer
                if trace.is_enabled() and not isinstance(active,
                                                         jax.core.Tracer):
                    REGISTRY.gauge("sampling.labels.active_nodes").set(
                        active)
                self._counts["labels"][0] += 1
        return self._labels

    def _lp_caps(self, edges) -> Tuple[int, int]:
        """Static ``(node_cap, edge_cap)`` of LP from the judgment table's
        rows and the fanout (``lp.lp_caps``)."""
        return lp.lp_caps(rows=self.qrels.entity_ids.shape[0],
                          fanout=self.spec.fanout,
                          num_nodes=self.num_entities,
                          edge_slots=edges.u.shape[0])

    # -- draws --------------------------------------------------------------

    def _strategy(self, name: Optional[str]):
        strat = get_sampler(name or self.spec.strategy)
        opts = ()
        if self.spec.strategy_opts and strat.name == self.spec.strategy:
            opts = tuple(sorted(dict(self.spec.strategy_opts).items()))
            strat = dataclasses.replace(strat, **dict(opts))
        return strat, opts

    def draw(self, target_size: Optional[float] = None,
             seed: Optional[int] = None,
             strategy: Optional[str] = None) -> SamplerDraw:
        """One sample at (target_size, seed); cached per distinct draw key.

        ``target_size`` / ``seed`` default to the spec's; ``strategy``
        overrides the spec's strategy for this draw only (reusing the
        session's staged graph/labels).
        """
        strat, opts = self._strategy(strategy)
        target = self.spec.target_size if target_size is None else target_size
        target = None if target is None else float(target)
        seed = self.spec.seed if seed is None else int(seed)
        key = (strat.name, opts, target, seed)
        self._counts["draw"][1] += 1
        hit = key in self._draws
        REGISTRY.counter(
            "sampling.draw.hit" if hit else "sampling.draw.miss").inc()
        if not hit:
            labels = self.labels()[0] if strat.needs_labels else None
            degrees = self.graph()[1] if strat.needs_graph else None
            with trace.jax_span("sampling.draw",
                                compile_key=f"sampling.draw/{strat.name}",
                                strategy=strat.name, target=target,
                                seed=seed, cache="miss") as sp:
                self._draws[key] = _draw_stage(
                    self.qrels, labels, degrees, seed, strategy=strat.name,
                    opts=opts, target=target, num_queries=self.num_queries,
                    num_entities=self.num_entities)
                sp.declare(self._draws[key])
            self._counts["draw"][0] += 1
        return self._draws[key]

    def result(self, target_size: Optional[float] = None,
               seed: Optional[int] = None) -> WindTunnelResult:
        """Full legacy :class:`WindTunnelResult` (edges, labels, changes,
        sample, reconstruction, degrees) for cluster-sampling strategies —
        what the ``run_windtunnel*`` wrappers return."""
        draw = self.draw(target_size, seed)
        if draw.sample is None:
            raise ValueError(
                f"strategy {self.spec.strategy!r} has no cluster-sample "
                f"diagnostics; use draw() for baseline strategies")
        edges, degrees = self.graph()
        labels, changes = self.labels()
        return WindTunnelResult(edges, labels, changes, draw.sample,
                                draw.reconstructed, degrees)

    def sweep(self, sizes, seeds, *,
              strategy: Optional[str] = None) -> SweepResult:
        """Draw every (target_size, seed) cell; graph + LP run at most once
        for the whole sweep (asserted via the result's ``stage_counts``,
        which record only THIS sweep's executions/requests — a delta over
        the session counters, so repeated sweeps don't inflate the record)."""
        sizes = tuple(float(s) for s in sizes)
        seeds = tuple(int(r) for r in seeds)
        strat, _ = self._strategy(strategy)
        before = self.stage_counts()
        draws = {(s, r): self.draw(target_size=s, seed=r, strategy=strategy)
                 for s in sizes for r in seeds}
        after = self.stage_counts()
        delta = {st: (after[st][0] - before[st][0],
                      after[st][1] - before[st][1]) for st in after}
        return SweepResult(strat.name, sizes, seeds, draws, delta)

    # -- observability ------------------------------------------------------

    def stage_counts(self) -> Dict[str, Tuple[int, int]]:
        """stage -> (executions, requests), mirroring PlanTrie.stage_counts."""
        return {stage: tuple(c) for stage, c in self._counts.items()}

    def summary(self) -> str:
        lines = ["stage      executed  requested  shared"]
        for stage in self.STAGES:
            ex, rq = self._counts[stage]
            lines.append(f"{stage:<10s} {ex:8d} {rq:10d} {rq - ex:7d}")
        return "\n".join(lines)
