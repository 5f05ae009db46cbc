"""Structured span tracer (DESIGN.md §12).

One process-global tracer produces nested, attributed spans:

    from repro.obs import trace

    with trace.span("eval.sample", sampler="windtunnel") as sp:
        ...
        sp.set(n_entities=int(mask.sum()))

Spans record wall time (``perf_counter``), a span/parent id pair (so a
reader can reconstruct the nesting), and free-form JSON attributes.  Each
record is kept in memory as its span closes and written to a JSONL sink,
one JSON object per line, when the tracer is disabled (and so at exit)
or when :data:`FLUSH_RECORDS` records are held, so a long-lived process
stays bounded and no span pays for a write of its own.

While enabled, the tracer also:

  * mirrors every span into the JAX profiler: the span enters and exits a
    ``jax.profiler.TraceAnnotation`` of its name, so under a profiler
    trace it lands on the host plane of the ``.xplane.pb``, on the device
    trace's clock (outside a profiler trace the annotation records
    nothing);
  * records each Python garbage collection as a ``python.gc`` span
    (``generation``, ``collected``) through ``gc.callbacks``, nested in
    whatever span the collection interrupted.

The JAX-aware variant understands asynchronous dispatch: a plain timer
around a jitted call measures dispatch, not execution.  ``jax_span``
lets the caller *declare* the outputs whose completion the span should
cover; on exit the tracer calls ``jax.block_until_ready`` on them and
records the blocked tail separately (``block_s``), so the span's duration
is the true wall time of the computation:

    with trace.jax_span("sampling.labels.rounds", rounds=5) as sp:
        labels, changes = _lp_rounds(...)
        sp.declare(labels, changes)

Compile vs execute: the first call of a jitted function pays tracing +
XLA compilation; steady-state calls do not.  ``jax_span`` tags each span
with ``first`` — True the first time its compile key (span name by
default, override with ``compile_key=``) is seen in the process — so a
reader can split compile-dominated first calls from steady-state
execution (``launch/trace.py`` reports the per-stage compile share).

Disabled is the default and is a strict no-op fast path: ``span()`` /
``jax_span()`` return one shared :data:`NOOP` singleton — no span object
is allocated, nothing is retained, nothing is written, no ``gc`` hook is
installed and no annotation is entered (enforced by tests/test_obs.py).
Enable with the ``REPRO_TRACE=<path>`` environment variable (honoured at
import) or programmatically / via the CLIs' ``--trace <path>`` flag
through :func:`enable`.
"""
from __future__ import annotations

import atexit
import gc
import itertools
import json
import os
import threading
import time
from typing import Any, Dict, Optional

ENV_VAR = "REPRO_TRACE"

#: records held in memory before they are written to the sink
FLUSH_RECORDS = 4096

__all__ = ["ENV_VAR", "FLUSH_RECORDS", "NOOP", "Span", "configure_from_env",
           "disable", "enable", "enabled_path", "is_enabled", "jax_span",
           "span"]


class _NoopSpan:
    """Shared do-nothing span: the disabled tracer's entire surface."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> "_NoopSpan":
        return self

    def declare(self, *outputs) -> "_NoopSpan":
        return self


NOOP = _NoopSpan()


class _State:
    """Process-global tracer state (one sink, one span-id sequence)."""

    def __init__(self) -> None:
        self.enabled = False
        self.path: Optional[str] = None
        self.sink = None                  # open file handle when enabled
        self.buffer: list = []            # records not yet written
        # re-entrant: a collection inside a flush records its own span
        self.lock = threading.RLock()
        self.ids = itertools.count(1)
        # .stack: per-thread open span ids; .gc_span: collection under way
        self.local = threading.local()
        self.seen_first: set = set()      # compile keys already traced
        self.records_written = 0          # records that reached the sink
        self.annotation = None            # jax.profiler.TraceAnnotation


_STATE = _State()


def _stack() -> list:
    stack = getattr(_STATE.local, "stack", None)
    if stack is None:
        stack = _STATE.local.stack = []
    return stack


def enable(path: str) -> None:
    """Open ``path`` as the process-global JSONL sink and start tracing.
    Parent directories are created; re-enabling to the same path appends."""
    disable()
    from jax.profiler import TraceAnnotation
    dirname = os.path.dirname(path)
    if dirname:
        os.makedirs(dirname, exist_ok=True)
    _STATE.sink = open(path, "a", encoding="utf-8")
    _STATE.path = path
    _STATE.annotation = TraceAnnotation
    gc.callbacks.append(_on_gc)
    _STATE.enabled = True


def disable() -> None:
    """Stop tracing, write the held records and close the sink
    (idempotent)."""
    _STATE.enabled = False
    _STATE.annotation = None
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)
    with _STATE.lock:
        _flush()
        sink, _STATE.sink, _STATE.path = _STATE.sink, None, None
    if sink is not None:
        try:
            sink.close()
        except OSError:
            pass


def is_enabled() -> bool:
    return _STATE.enabled


def enabled_path() -> Optional[str]:
    return _STATE.path


def _flush() -> None:
    """Write the held records to the sink; the caller holds the lock."""
    records, _STATE.buffer = _STATE.buffer, []
    sink = _STATE.sink
    if sink is None or not records:
        return
    sink.write("".join(json.dumps(r, default=str) + "\n" for r in records))
    sink.flush()
    _STATE.records_written += len(records)


def _write(record: Dict[str, Any]) -> None:
    with _STATE.lock:
        if _STATE.sink is None:
            return
        _STATE.buffer.append(record)
        if len(_STATE.buffer) >= FLUSH_RECORDS:
            _flush()


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    """``gc.callbacks`` hook: one ``python.gc`` span per collection."""
    if phase == "start":
        sp = Span("python.gc", {"generation": info["generation"]})
        _STATE.local.gc_span = sp.__enter__()
        return
    sp = getattr(_STATE.local, "gc_span", None)
    if sp is not None:
        _STATE.local.gc_span = None
        sp.attrs["collected"] = info["collected"]
        sp.__exit__(None, None, None)


class Span:
    """One live span; created only while tracing is enabled."""

    __slots__ = ("name", "attrs", "span_id", "parent_id", "_jax",
                 "_compile_key", "_outputs", "_annotation", "_t0", "_wall0")

    def __init__(self, name: str, attrs: Dict[str, Any], *,
                 jax_aware: bool = False,
                 compile_key: Optional[str] = None):
        self.name = name
        self.attrs = attrs
        self._jax = jax_aware
        self._compile_key = compile_key if compile_key is not None else name
        self._outputs: list = []
        self.span_id = 0
        self.parent_id: Optional[int] = None
        self._annotation = None

    def __enter__(self) -> "Span":
        stack = _stack()
        self.parent_id = stack[-1] if stack else None
        self.span_id = next(_STATE.ids)
        stack.append(self.span_id)
        annotation = _STATE.annotation
        if annotation is not None:
            self._annotation = annotation(self.name)
            self._annotation.__enter__()
        self._wall0 = time.time()
        self._t0 = time.perf_counter()
        return self

    def set(self, **attrs) -> "Span":
        """Attach/overwrite attributes on the open span."""
        self.attrs.update(attrs)
        return self

    def declare(self, *outputs) -> "Span":
        """Declare JAX outputs the span must wait for on exit
        (``jax_span`` only; a plain span ignores the block step)."""
        self._outputs.extend(outputs)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        block_s = None
        if self._jax and self._outputs and exc_type is None:
            import jax
            t_block = time.perf_counter()
            jax.block_until_ready(self._outputs)
            block_s = time.perf_counter() - t_block
        dur_s = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        stack = _stack()
        if stack and stack[-1] == self.span_id:
            stack.pop()
        record: Dict[str, Any] = {
            "name": self.name, "id": self.span_id,
            "parent": self.parent_id, "t0": self._wall0,
            "dur_s": dur_s,
        }
        if self._jax:
            first = self._compile_key not in _STATE.seen_first
            _STATE.seen_first.add(self._compile_key)
            record["first"] = first
            if block_s is not None:
                record["block_s"] = block_s
        if exc_type is not None:
            record["error"] = f"{exc_type.__name__}: {exc}"
        if self.attrs:
            record["attrs"] = self.attrs
        _write(record)
        return False


def span(name: str, **attrs):
    """Start a structured span; a shared no-op when tracing is disabled."""
    if not _STATE.enabled:
        return NOOP
    return Span(name, attrs)


def jax_span(name: str, *, compile_key: Optional[str] = None, **attrs):
    """JAX-aware span: ``declare(*outputs)`` inside the block and the span
    blocks on them at exit (``block_s``), tagging the record with ``first``
    (compile) vs steady-state per ``compile_key`` (default: the name)."""
    if not _STATE.enabled:
        return NOOP
    return Span(name, attrs, jax_aware=True, compile_key=compile_key)


def configure_from_env() -> None:
    """Enable tracing when ``REPRO_TRACE`` names a sink path (import-time
    hook; a blank / ``off`` / ``0`` value keeps the tracer disabled)."""
    path = os.environ.get(ENV_VAR, "").strip()
    if path and path.lower() not in ("0", "off", "none"):
        enable(path)


configure_from_env()
atexit.register(disable)
