"""Kernel autotuner — ask/tell hillclimb over Pallas block/grid candidates
(DESIGN.md §11).

The Pallas kernels under every retrieval engine and the LP pallas engine
used to run with hard-coded block shapes regardless of corpus size.  This
module turns the lower→compile→roofline machinery of the §Perf hillclimb
(``benchmarks/hillclimb.py::measure``, which now imports the shared helpers
from here) into a per-kernel autotuner:

* :data:`SPACES` — one :class:`TuningSpace` per kernel primitive (``topk``,
  ``hamming_topk``, ``gathered_topk``, ``label_prop_round``) enumerating the
  block/grid candidate axes.
* :class:`HillclimbTuner` — a DeepHyper-style ask/tell optimizer: ``ask()``
  proposes the next untried candidate (the default point first, then
  one-axis neighbours of the incumbent best), ``tell(point, score)`` records
  a measurement and re-seeds the frontier when the incumbent improves.
* :func:`measure` — scores one candidate by lowering + compiling the kernel
  call and reading XLA's cost analysis into the same roofline terms as
  ``launch/dryrun.py`` (compute vs HBM time; optionally a wall-clock
  sample), so padding waste and grid shape changes are visible without a
  TPU attached.
* :class:`TunedTable` — the persisted winners, keyed by
  ``(kernel, corpus-size bucket, dtype)``.  :func:`autotune` regenerates
  ``results/tuned_kernels.json``; a checked-in default table ships at
  ``src/repro/kernels/tuned_default.json``.

Dispatch-time lookup order (what every ``kernels/*/ops.py`` wrapper applies
via :func:`resolve`):

  explicit kwarg  >  tuned table entry  >  hard-coded default

The active table resolves once per process from the
``REPRO_TUNED_KERNELS`` env var (``off``/``0``/``none`` forces the
hard-coded defaults everywhere — the escape hatch; any other value is a
table path), else the checked-in default table.  Nothing else on disk is
read implicitly: ``autotune`` writes ``results/tuned_kernels.json`` and
activates it in its own process; a later process loads it only through
``REPRO_TUNED_KERNELS``.  ``set_table``/``reset_table`` override it in
process (tests, the ``--no-tuned-kernels`` CLI flags).

A table applies only on the device kind it was tuned on
(``meta["device_kind"]`` equal to the running device's): blocks chosen
for one device say nothing about another, so elsewhere every kernel takes
the hard-coded :data:`DEFAULTS`.  Note block resolution happens when a
consumer traces, so jitted callers that cached a trace keep the blocks
they were traced with until their jit cache is cleared.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import os
import time
from typing import Any, Callable, Dict, Mapping, Optional, Sequence, Tuple

from repro.obs import metrics as obs_metrics

ENV_VAR = "REPRO_TUNED_KERNELS"

# TPU v5e hardware constants (per chip) for the roofline terms.  These
# live here (the bottom of the kernel stack) so both the autotuner and
# launch/dryrun.py can read them without a kernels -> launch import.
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW = 5.0e10               # B/s per link (~50 GB/s)

RESULTS_TABLE_PATH = os.path.join("results", "tuned_kernels.json")
DEFAULT_TABLE_PATH = os.path.join(os.path.dirname(__file__),
                                  "tuned_default.json")

#: hard-coded fallback blocks — the pre-autotuner dispatch defaults
DEFAULTS: Dict[str, Dict[str, int]] = {
    "topk": {"block_q": 128, "block_n": 1024},
    "hamming_topk": {"block_q": 128, "block_n": 1024},
    "gathered_topk": {"block_q": 8, "block_c": 256},
    "label_prop_round": {"block_n": 256},
}

#: corpus-size bucket upper bounds (rows scored per call), ascending
SIZE_BUCKETS: Tuple[Tuple[int, str], ...] = (
    (1024, "le1024"), (4096, "le4096"), (16384, "le16384"),
    (65536, "le65536"),
)
_OVERFLOW_BUCKET = "gt65536"


def size_bucket(n: int) -> str:
    """Corpus-size bucket name for an n-row scoring call."""
    for bound, name in SIZE_BUCKETS:
        if n <= bound:
            return name
    return _OVERFLOW_BUCKET


def bucket_rep_size(bucket: str) -> int:
    """Representative row count the tuner measures a bucket at (the upper
    bound; 2x the last bound for the overflow bucket)."""
    for bound, name in SIZE_BUCKETS:
        if name == bucket:
            return bound
    return SIZE_BUCKETS[-1][0] * 2


def dtype_str(dtype: Any) -> str:
    """Canonical dtype key ('float32', 'int8', ...) from a dtype or str."""
    if isinstance(dtype, str):
        return dtype
    import numpy as np
    return np.dtype(dtype).name


# ---------------------------------------------------------------------------
# Tuning space + ask/tell hillclimb
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TuningSpace:
    """Candidate axes for one kernel primitive: param -> ascending values."""

    kernel: str
    axes: Mapping[str, Tuple[int, ...]]

    def candidates(self):
        """Every point of the cross product, as param dicts."""
        names = list(self.axes)
        for combo in itertools.product(*(self.axes[a] for a in names)):
            yield dict(zip(names, combo))

    def default_point(self) -> Dict[str, int]:
        """The hard-coded default, snapped to the nearest axis value."""
        point = {}
        for name, values in self.axes.items():
            want = DEFAULTS[self.kernel].get(name, values[0])
            point[name] = min(values, key=lambda v: abs(v - want))
        return point

    def neighbours(self, point: Mapping[str, int]):
        """One-axis steps up/down from ``point`` (the hillclimb moves)."""
        for name, values in self.axes.items():
            i = values.index(point[name])
            for j in (i - 1, i + 1):
                if 0 <= j < len(values):
                    yield {**point, name: values[j]}

    def shrink_to(self, limits: Mapping[str, int]) -> "TuningSpace":
        """Drop candidate values above per-axis limits (e.g. blocks larger
        than the padded problem size — they alias the largest useful
        block), keeping at least the smallest value per axis."""
        axes = {}
        for name, values in self.axes.items():
            lim = limits.get(name)
            kept = (tuple(v for v in values if v <= lim)
                    if lim is not None else values)
            axes[name] = kept or values[:1]
        return dataclasses.replace(self, axes=axes)


SPACES: Dict[str, TuningSpace] = {
    "topk": TuningSpace("topk", {
        "block_q": (8, 32, 128, 256),
        "block_n": (128, 256, 512, 1024, 2048),
    }),
    "hamming_topk": TuningSpace("hamming_topk", {
        "block_q": (8, 32, 128, 256),
        "block_n": (128, 256, 512, 1024, 2048),
    }),
    "gathered_topk": TuningSpace("gathered_topk", {
        "block_q": (8, 16, 32),
        "block_c": (128, 256, 512, 1024),
    }),
    "label_prop_round": TuningSpace("label_prop_round", {
        "block_n": (128, 256, 512, 1024, 2048),
    }),
}

#: which dtypes each primitive is tuned for (the dispatch key's third axis)
KERNEL_DTYPES: Dict[str, Tuple[str, ...]] = {
    "topk": ("float32", "int8"),
    "hamming_topk": ("int32",),
    "gathered_topk": ("float32",),
    "label_prop_round": ("float32",),
}


def _key(point: Mapping[str, int]) -> Tuple[Tuple[str, int], ...]:
    return tuple(sorted(point.items()))


class HillclimbTuner:
    """Ask/tell hillclimb over one :class:`TuningSpace`.

    The optimizer-side half of the DeepHyper ask/tell loop: the driver owns
    measurement, the tuner owns the frontier.  ``ask()`` returns the next
    untried candidate or ``None`` once every neighbour of the incumbent has
    been measured (converged); ``tell()`` records a score (lower = better)
    and, on improvement, pushes the new incumbent's neighbours.
    """

    def __init__(self, space: TuningSpace, *,
                 start: Optional[Mapping[str, int]] = None):
        self.space = space
        first = dict(start) if start is not None else space.default_point()
        self._frontier = [first]
        self._asked = set()
        self.results: Dict[Tuple, float] = {}
        self.best: Optional[Dict[str, int]] = None
        self.best_score = float("inf")

    def ask(self) -> Optional[Dict[str, int]]:
        while self._frontier:
            point = self._frontier.pop(0)
            k = _key(point)
            if k not in self._asked:
                self._asked.add(k)
                return point
        return None

    def tell(self, point: Mapping[str, int], score: float) -> None:
        self.results[_key(point)] = score
        if score < self.best_score:
            self.best, self.best_score = dict(point), score
            self._frontier.extend(self.space.neighbours(point))

    @property
    def num_evals(self) -> int:
        return len(self.results)


# ---------------------------------------------------------------------------
# Candidate measurement: lower -> compile -> roofline (+ optional wall)
# ---------------------------------------------------------------------------


def compiled_roofline(compiled) -> Dict[str, float]:
    """Roofline terms (ms) from a compiled XLA executable's cost analysis —
    the same reading as ``launch/dryrun.py``/``benchmarks/hillclimb.py``."""
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else (cost or {})
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    return {"compute_ms": flops / PEAK_FLOPS_BF16 * 1e3,
            "memory_ms": nbytes / HBM_BW * 1e3}


def measure(fn: Callable, *args, wall_iters: int = 0) -> Dict[str, float]:
    """Score one candidate: jit-lower + compile ``fn(*args)``, read the
    roofline terms, optionally sample wall clock.  ``score_ms`` is the wall
    time when sampled (interpret-mode wall clock still ranks grid/padding
    overheads), else the roofline bound max(compute, memory)."""
    import jax
    t0 = time.time()
    jitted = jax.jit(fn)
    compiled = jitted.lower(*args).compile()
    terms = compiled_roofline(compiled)
    out = {"compile_s": round(time.time() - t0, 2), **terms}
    roof = max(terms["compute_ms"], terms["memory_ms"])
    if wall_iters > 0:
        jax.block_until_ready(jitted(*args))   # warmup retired before t0
        t0 = time.time()
        for _ in range(wall_iters):
            jax.block_until_ready(jitted(*args))
        out["wall_ms"] = (time.time() - t0) / wall_iters * 1e3
        out["score_ms"] = out["wall_ms"]
    else:
        out["score_ms"] = roof
    return out


def _ceil8(n: int) -> int:
    return ((n + 7) // 8) * 8


def _bench_call(kernel: str, n: int, dtype: str):
    """(args, fn(point)->callable) measuring one kernel primitive at a
    representative shape of the bucket; shapes mirror the product path
    (Q=64 queries, D=64 dims, k=8; ELL degree 16 for LP)."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(0)
    if kernel == "topk" and dtype == "int8":
        from repro.kernels.topk_scoring import ops as topk_ops
        q = jax.random.randint(key, (64, 64), -127, 128, dtype=jnp.int8)
        c = jax.random.randint(jax.random.PRNGKey(1), (n, 64), -127, 128,
                               dtype=jnp.int8)
        return (q, c), lambda pt: (
            lambda a, b: topk_ops.topk_scores_int8(a, b, k=8, **pt))
    if kernel == "topk":
        from repro.kernels.topk_scoring import ops as topk_ops
        q = jax.random.normal(key, (64, 64), jnp.dtype(dtype))
        c = jax.random.normal(jax.random.PRNGKey(1), (n, 64), jnp.dtype(dtype))
        return (q, c), lambda pt: (
            lambda a, b: topk_ops.topk_scores(a, b, k=8, **pt))
    if kernel == "hamming_topk":
        from repro.kernels.lsh_hamming import ops as lsh_ops
        q = jax.random.randint(key, (64, 4), -2**31, 2**31 - 1,
                               dtype=jnp.int32)
        c = jax.random.randint(jax.random.PRNGKey(1), (4, n), -2**31,
                               2**31 - 1, dtype=jnp.int32)
        return (q, c), lambda pt: (
            lambda a, b: lsh_ops.hamming_topk_t(a, b, k=8, **pt))
    if kernel == "gathered_topk":
        from repro.kernels.topk_scoring import ops as topk_ops
        c = min(n, 4096)      # candidates per query (nprobe * cap scale)
        q = jax.random.normal(key, (8, 64))
        cv = jax.random.normal(jax.random.PRNGKey(1), (8, c, 64))
        ci = jax.random.randint(jax.random.PRNGKey(2), (8, c), -1, n,
                                dtype=jnp.int32)
        return (q, cv, ci), lambda pt: (
            lambda a, b, i: topk_ops.gathered_topk(a, b, i, k=8, **pt))
    if kernel == "label_prop_round":
        from repro.kernels.label_prop import ops as lp_ops
        nbr = jax.random.randint(key, (16, n), -1, n, dtype=jnp.int32)
        wgt = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (16, n)))
        labels = jnp.arange(n, dtype=jnp.int32)
        return (labels, nbr, wgt), lambda pt: (
            lambda lb, nb, w: lp_ops.label_prop_round_t(lb, nb, w, **pt))
    raise ValueError(f"unknown kernel primitive {kernel!r}; "
                     f"tunable: {', '.join(sorted(SPACES))}")


def _space_for(kernel: str, n: int) -> TuningSpace:
    """Kernel's tuning space with block candidates above the padded problem
    size dropped (they alias the largest useful block)."""
    lim = _ceil8(n)
    limits = {"block_n": lim, "block_c": min(lim, 4096)}
    return SPACES[kernel].shrink_to(limits)


def tune_kernel(kernel: str, *, n: int, dtype: str,
                space: Optional[TuningSpace] = None, max_evals: int = 12,
                wall_iters: int = 0, verbose: bool = False
                ) -> Tuple[Dict[str, int], float, int]:
    """Hillclimb one (kernel, representative size, dtype) cell; returns
    (best params, best score_ms, evals)."""
    space = space or _space_for(kernel, n)
    args, make_fn = _bench_call(kernel, n, dtype)
    tuner = HillclimbTuner(space)
    while tuner.num_evals < max_evals:
        point = tuner.ask()
        if point is None:
            break
        try:
            res = measure(make_fn(point), *args, wall_iters=wall_iters)
            score = res["score_ms"]
        except Exception as e:       # candidate failed to lower/compile
            if verbose:
                print(f"    {kernel} {point}: failed ({e!r})")
            score = float("inf")
        tuner.tell(point, score)
        if verbose:
            print(f"    {kernel}[n={n},{dtype}] {point} -> {score:.4f}ms")
    assert tuner.best is not None
    return tuner.best, tuner.best_score, tuner.num_evals


# ---------------------------------------------------------------------------
# Persisted TunedConfig table
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TunedConfig:
    kernel: str
    bucket: str
    dtype: str
    params: Tuple[Tuple[str, int], ...]   # sorted items, hashable
    score_ms: float = 0.0
    evals: int = 0

    def params_dict(self) -> Dict[str, int]:
        return dict(self.params)


@dataclasses.dataclass
class TunedTable:
    """(kernel, bucket, dtype) -> TunedConfig, with provenance metadata."""

    entries: Dict[Tuple[str, str, str], TunedConfig] = dataclasses.field(
        default_factory=dict)
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def add(self, cfg: TunedConfig) -> None:
        self.entries[(cfg.kernel, cfg.bucket, cfg.dtype)] = cfg

    def lookup(self, kernel: str, bucket: str, dtype: str
               ) -> Dict[str, int]:
        cfg = self.entries.get((kernel, bucket, dtype))
        return cfg.params_dict() if cfg is not None else {}

    def to_json(self) -> dict:
        return {"meta": self.meta,
                "entries": [{"kernel": c.kernel, "bucket": c.bucket,
                             "dtype": c.dtype, "params": c.params_dict(),
                             "score_ms": c.score_ms, "evals": c.evals}
                            for c in sorted(
                                self.entries.values(),
                                key=lambda c: (c.kernel, c.bucket,
                                               c.dtype))]}

    @classmethod
    def from_json(cls, data: dict) -> "TunedTable":
        table = cls(meta=dict(data.get("meta", {})))
        for e in data.get("entries", []):
            table.add(TunedConfig(
                kernel=e["kernel"], bucket=e["bucket"], dtype=e["dtype"],
                params=tuple(sorted((k, int(v))
                                    for k, v in e["params"].items())),
                score_ms=float(e.get("score_ms", 0.0)),
                evals=int(e.get("evals", 0))))
        return table

    def save(self, path: str) -> None:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=2)

    @classmethod
    def load(cls, path: str) -> "TunedTable":
        with open(path) as f:
            return cls.from_json(json.load(f))


# active table: resolved once per process, overridable (tests, CLI flags)
_ACTIVE: list = []


def _load_active() -> TunedTable:
    env = os.environ.get(ENV_VAR)
    if env is not None:
        if env.strip().lower() in ("", "0", "off", "none"):
            return TunedTable()          # escape hatch: hard-coded defaults
        return TunedTable.load(env)
    if os.path.exists(DEFAULT_TABLE_PATH):
        return TunedTable.load(DEFAULT_TABLE_PATH)
    return TunedTable()


def get_table() -> TunedTable:
    if not _ACTIVE:
        _ACTIVE.append(_load_active())
    return _ACTIVE[0]


def set_table(table: Optional[TunedTable]) -> None:
    """Override the active table in-process (``None`` = empty table, i.e.
    force the hard-coded defaults — the CLI ``--no-tuned-kernels`` hatch)."""
    _ACTIVE[:] = [table if table is not None else TunedTable()]


def reset_table() -> None:
    """Drop the in-process table so the next lookup re-reads env/disk."""
    _ACTIVE.clear()


def interpret_mode() -> bool:
    """Whether the Pallas kernels run in the interpreter: everywhere but on
    a TPU, where they compile natively.  Read at trace time by every
    ``kernels/*/ops.py`` wrapper."""
    import jax
    return jax.default_backend() != "tpu"


def device_kind() -> str:
    """``device_kind`` of the device the kernels dispatch to."""
    import jax
    return jax.devices()[0].device_kind


def lookup(kernel: str, *, n: int, dtype: Any) -> Dict[str, int]:
    """Tuned params for an n-row call, or {} when none recorded or when
    the active table was tuned on another device kind."""
    table = get_table()
    if table.meta.get("device_kind") != device_kind():
        return {}
    return table.lookup(kernel, size_bucket(n), dtype_str(dtype))


def resolve(kernel: str, *, n: int, dtype: Any,
            **explicit: Optional[int]) -> Dict[str, int]:
    """Final block params for one dispatch: explicit kwarg > tuned table >
    hard-coded default.  ``None`` explicit values mean 'not specified'.
    Each call counts a ``tuning.resolve.hit`` or ``.miss`` (DESIGN.md
    §12)."""
    params = dict(DEFAULTS[kernel])
    tuned = lookup(kernel, n=n, dtype=dtype)
    obs_metrics.REGISTRY.counter(
        "tuning.resolve.hit" if tuned else "tuning.resolve.miss").inc()
    params.update(tuned)
    for name, value in explicit.items():
        if name not in params:
            raise ValueError(f"kernel {kernel!r} has no block param "
                             f"{name!r}; known: {', '.join(params)}")
        if value is not None:
            params[name] = int(value)
    return params


# ---------------------------------------------------------------------------
# End-to-end autotune driver
# ---------------------------------------------------------------------------


def autotune(kernels: Optional[Sequence[str]] = None, *,
             buckets: Optional[Sequence[str]] = None,
             dtypes: Optional[Mapping[str, Sequence[str]]] = None,
             max_evals: int = 12, wall_iters: int = 1,
             out_path: Optional[str] = RESULTS_TABLE_PATH,
             activate: bool = True, verbose: bool = True) -> TunedTable:
    """Tune every (kernel, bucket, dtype) cell, persist the winners, and
    (by default) make the new table the active dispatch table.

    The CI smoke job runs this with a reduced cell set
    (``benchmarks/run.py --autotune --smoke``); the full sweep is the
    README "make it fast" quickstart.
    """
    import platform

    import jax

    kernels = list(kernels) if kernels is not None else sorted(SPACES)
    buckets = (list(buckets) if buckets is not None
               else [name for _, name in SIZE_BUCKETS])
    table = TunedTable(meta={
        "platform": platform.platform(),
        "python": platform.python_version(),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "interpret": jax.default_backend() != "tpu",
        "max_evals": max_evals,
        "wall_iters": wall_iters,
        "generated_by": "repro.kernels.tuning.autotune",
    })
    for kernel in kernels:
        for dt in (dtypes or KERNEL_DTYPES)[kernel]:
            for bucket in buckets:
                n = bucket_rep_size(bucket)
                if verbose:
                    print(f"  tuning {kernel} [{bucket}, {dt}] at n={n}...")
                params, score, evals = tune_kernel(
                    kernel, n=n, dtype=dt, max_evals=max_evals,
                    wall_iters=wall_iters, verbose=verbose)
                table.add(TunedConfig(
                    kernel=kernel, bucket=bucket, dtype=dt,
                    params=tuple(sorted(params.items())),
                    score_ms=round(score, 4), evals=evals))
                if verbose:
                    print(f"  -> {kernel}[{bucket},{dt}] best={params} "
                          f"({score:.4f}ms, {evals} evals)")
    if out_path:
        table.save(out_path)
        if verbose:
            print(f"wrote {out_path} ({len(table.entries)} entries)")
    if activate:
        set_table(table)
    return table
