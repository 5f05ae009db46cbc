"""The five main-path Pallas kernels compile for a TPU v5e at real widths.

Interpret mode never checks the TPU compiler's tiling rules or its fast
memory limits, so every other kernel test would pass a kernel the chip
refuses.  Here each kernel's dispatch wrapper is lowered and compiled for a
described (not attached) v5e chip at the widths its users run — D=768 over
N=1,048,576 corpus rows, LP over 1,048,576 nodes at ELL width K — with the
blocks ``tuning.resolve`` gives on a TPU, and the compiled program must hold
the kernel as a TPU custom call.

The topology is described inside a fixture: only the worker that runs this
file loads the TPU compiler library, and it skips where that cannot be done.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import tuning
from repro.kernels.label_prop.ops import label_prop_round_t
from repro.kernels.lsh_hamming.ops import hamming_topk_t
from repro.kernels.topk_scoring.ops import (gathered_topk, topk_scores,
                                            topk_scores_int8)

D = 768
N = 1_048_576
Q = 256
V5E = "TPU v5 lite"


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    assert topo.devices[0].device_kind == V5E
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture
def on_v5e(monkeypatch, no_persistent_cache):
    """Steer the wrappers as on the chip: native kernels (no interpreter),
    and block resolution for the v5e device kind."""
    monkeypatch.setattr(tuning, "interpret_mode", lambda: False)
    monkeypatch.setattr(tuning, "device_kind", lambda: V5E)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_resolve_gives_defaults_on_v5e(on_v5e):
    """The checked-in table was tuned on the CPU; on a v5e every kernel
    takes the hard-coded defaults these compiles use."""
    for kernel, dtype in (("topk", "float32"), ("topk", "int8"),
                          ("hamming_topk", "int32"),
                          ("gathered_topk", "float32"),
                          ("label_prop_round", "float32")):
        assert tuning.resolve(kernel, n=N, dtype=dtype) == \
            tuning.DEFAULTS[kernel]


@pytest.mark.parametrize("k", [10, 40])
def test_topk_scores_compiles(on_v5e, one_chip, k):
    text = _compile(lambda q, c: topk_scores(q, c, k=k), one_chip,
                    ((Q, D), jnp.float32), ((N, D), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k", [10, 40])
def test_topk_scores_int8_compiles(on_v5e, one_chip, k):
    text = _compile(lambda q, c: topk_scores_int8(q, c, k=k), one_chip,
                    ((Q, D), jnp.int8), ((N, D), jnp.int8))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k,n", [(10, N), (64, N), (10, N - 100)])
def test_hamming_topk_compiles(on_v5e, one_chip, k, n):
    """Corpus codes in the LSH index's transposed (W, N) layout; N - 100
    leaves a ragged last block."""
    text = _compile(lambda q, c: hamming_topk_t(q, c, k=k), one_chip,
                    ((Q, 4), jnp.int32), ((4, n), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("qn", [1, 64])
def test_gathered_topk_compiles(on_v5e, one_chip, qn):
    """The ivfflat probe: 8 probed lists of 2,049 slots each per query."""
    c = 8 * 2049
    text = _compile(lambda q, v, i: gathered_topk(q, v, i, k=10), one_chip,
                    ((qn, D), jnp.float32), ((qn, c, D), jnp.float32),
                    ((qn, c), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("width", [16, 32])
def test_label_prop_round_compiles(on_v5e, one_chip, width):
    """Slot-major (K, N) ELL, the layout the LP engines keep."""
    text = _compile(label_prop_round_t, one_chip, ((N,), jnp.int32),
                    ((width, N), jnp.int32), ((width, N), jnp.float32))
    assert "tpu_custom_call" in text
