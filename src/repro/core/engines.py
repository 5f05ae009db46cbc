"""Label-propagation engine registry (DESIGN.md §4).

The GraphSampler's hot loop (Alg. 2 steps 1-3) admits several execution
strategies with identical semantics but very different cost models.  Rather
than string-compare an engine name inline in ``pipeline.py``, each strategy
is a first-class registered object — the PyTerrier/Trove pluggable-component
pattern — that the pipeline, the benchmark harness and the experiment
scripts all select uniformly through :func:`get_engine`.

An engine implements the :class:`LPEngine` protocol:

  * ``prepare(src, dst, w, valid, *, num_nodes, max_degree)`` — one-time
    layout transform of the symmetrized edge list into whatever adjacency
    representation the engine's round consumes (edge list, ELL table, ...).
  * ``round(labels, state)`` — one weighted-LP round; pure and jit-able so
    the multi-round loop stays a single ``lax.scan`` inside one XLA program.
  * ``finalize(labels, changes)`` — package the scan result.

Registered engines:

  * ``sort``   — sort/segment reduce-by-key rounds over the raw edge list
                 (the direct MapReduce port; unbounded degree).
  * ``ell``    — dense degree-capped ELL rounds (O(N·K²) VPU work).
  * ``pallas`` — same ELL layout, but the per-round O(K²) score/argmax body
                 runs in the Pallas TPU kernel (kernels/label_prop).  The
                 neighbour-label gather is hoisted out of the kernel and
                 happens once per round in XLA; off-TPU the kernel runs in
                 interpret mode, so the engine is selectable everywhere.

All three produce bit-identical labels on graphs whose maximum degree fits
the ELL cap (tests/test_engines.py enforces this).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Protocol, runtime_checkable

import jax.numpy as jnp
from jax import lax

from repro.core import label_prop as lp


@runtime_checkable
class LPEngine(Protocol):
    """Execution strategy for weighted label propagation."""

    name: str

    def prepare(self, src, dst, w, valid, *, num_nodes: int,
                max_degree: int) -> Any:
        """Edge list -> engine-private adjacency state."""
        ...

    def round(self, labels: jnp.ndarray, state: Any) -> jnp.ndarray:
        """One LP round: labels i32[N] -> new labels i32[N]."""
        ...

    def finalize(self, labels: jnp.ndarray,
                 changes: jnp.ndarray) -> lp.LabelPropResult:
        ...


_REGISTRY: Dict[str, LPEngine] = {}


def register(cls):
    """Class decorator: instantiate and register an engine under its name."""
    engine = cls()
    _REGISTRY[engine.name] = engine
    return cls


def get_engine(name: str) -> LPEngine:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown label-prop engine {name!r}; registered engines: "
            f"{', '.join(available_engines())}") from None


def available_engines() -> tuple:
    return tuple(sorted(_REGISTRY))


def run_engine(engine: LPEngine, src, dst, w, valid, *, num_nodes: int,
               max_degree: int, rounds: int) -> lp.LabelPropResult:
    """Shared multi-round driver: prepare once, scan the engine's round."""
    state = engine.prepare(src, dst, w, valid, num_nodes=num_nodes,
                           max_degree=max_degree)
    init = jnp.arange(num_nodes, dtype=jnp.int32)

    def step(labels, _):
        new = engine.round(labels, state)
        return new, jnp.sum((new != labels).astype(jnp.int32))

    labels, changes = lax.scan(step, init, None, length=rounds)
    return engine.finalize(labels, changes)


class _EdgeListState(NamedTuple):
    src: jnp.ndarray
    dst: jnp.ndarray
    w: jnp.ndarray
    valid: jnp.ndarray
    num_nodes: int


class _EllState(NamedTuple):
    nbr_t: jnp.ndarray   # i32[K, N] neighbour ids, -1 padding (slot-major)
    wgt_t: jnp.ndarray   # f32[K, N]


@register
class SortEngine:
    """Reference engine: reduce-by-(dst,label) + reduce-by-dst argmax as
    sort + segment ops per round (DESIGN.md §2). Handles unbounded degree."""

    name = "sort"

    def prepare(self, src, dst, w, valid, *, num_nodes: int,
                max_degree: int) -> _EdgeListState:
        del max_degree  # the sort engine never caps degree
        return _EdgeListState(src, dst, w, valid, num_nodes)

    def round(self, labels, state: _EdgeListState):
        return lp.sort_round(labels, state.src, state.dst, state.w,
                             state.valid, state.num_nodes)

    def finalize(self, labels, changes):
        return lp.LabelPropResult(labels, changes)


@register
class EllEngine:
    """Dense degree-capped engine: the slot-major (K, N) ELL layout the
    Pallas kernel consumes, scored in plain XLA with the kernel's own
    fixed-order compare-and-add, so the two engines agree bit for bit."""

    name = "ell"

    def prepare(self, src, dst, w, valid, *, num_nodes: int,
                max_degree: int) -> _EllState:
        return _EllState(*lp.edges_to_ell_t(src, dst, w, valid,
                                            num_nodes=num_nodes,
                                            max_degree=max_degree))

    def round(self, labels, state: _EllState):
        return lp.ell_round_t(labels, state.nbr_t, state.wgt_t)

    def finalize(self, labels, changes):
        return lp.LabelPropResult(labels, changes)


@register
class PallasEngine:
    """ELL layout with the per-round O(K²) body in the Pallas TPU kernel.

    The neighbour-label gather (HBM-bound, irregular) is hoisted out of the
    kernel and re-done once per round in XLA; only the dense score/argmax
    block runs in Pallas.  Off-TPU the kernel executes in interpret mode
    (kernels/label_prop/ops.py checks the backend), so CPU tests exercise
    the exact same code path.

    ``block_n = None`` defers the node block to the autotuner table
    (kernels/tuning.py) — set a concrete int to pin it.
    """

    name = "pallas"
    block_n = None

    def prepare(self, src, dst, w, valid, *, num_nodes: int,
                max_degree: int) -> _EllState:
        return _EllState(*lp.edges_to_ell_t(src, dst, w, valid,
                                            num_nodes=num_nodes,
                                            max_degree=max_degree))

    def round(self, labels, state: _EllState):
        from repro.kernels.label_prop.ops import label_prop_round_t
        return label_prop_round_t(labels, state.nbr_t, state.wgt_t,
                                  block_n=self.block_n)

    def finalize(self, labels, changes):
        return lp.LabelPropResult(labels, changes)
