"""Each configuration's control — the reference computed one precision
step below what the configuration states — fails the cell's comparison
(at a size a test holds; ``perfbench/control.py`` reads it on the chip at
the cell's own size)."""
import jax
import pytest

from perfbench import control


@pytest.mark.parametrize("seed", [1, 2**33 + 3])
@pytest.mark.parametrize("cell,expect", [
    ("sample.msmarco.job", "edges_diff"),
    ("search.dense768.batch", "score_gap")])
def test_control_comes_out_not_correct(small_root, cell, expect, seed):
    out = control.control(small_root, cell, seed, jax.devices()[:1])
    assert expect in out["control_fails"], out
