"""Kernel-autotuner tests (kernels/tuning.py, DESIGN.md §11): size
buckets, the explicit > tuned > default resolution order, the env/CLI
escape hatch, table persistence, the ask/tell hillclimb, and kernel
parity under arbitrary tuned block choices."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import tuning
from repro.kernels.topk_scoring.ops import topk_scores
from repro.kernels.topk_scoring.ref import topk_scores_ref


@pytest.fixture(autouse=True)
def _restore_table():
    """Every test leaves the process-wide active table as it found it."""
    yield
    tuning.reset_table()


def test_size_bucket_boundaries():
    assert tuning.size_bucket(1) == "le1024"
    assert tuning.size_bucket(1024) == "le1024"
    assert tuning.size_bucket(1025) == "le4096"
    assert tuning.size_bucket(65536) == "le65536"
    assert tuning.size_bucket(65537) == "gt65536"
    assert tuning.bucket_rep_size("le4096") == 4096
    assert tuning.bucket_rep_size("gt65536") == 2 * 65536


def test_dtype_str():
    assert tuning.dtype_str("int8") == "int8"
    assert tuning.dtype_str(jnp.float32) == "float32"
    assert tuning.dtype_str(jnp.int8) == "int8"
    assert tuning.dtype_str(np.dtype("int32")) == "int32"


def _table(**meta):
    """An empty table tuned, as far as its meta says, on this device."""
    return tuning.TunedTable(meta={"device_kind": tuning.device_kind(),
                                   **meta})


def test_resolve_order_explicit_over_table_over_default():
    table = _table()
    table.add(tuning.TunedConfig("topk", "le1024", "float32",
                                 (("block_n", 256), ("block_q", 32))))
    tuning.set_table(table)
    # tuned entry beats the hard-coded default
    assert tuning.resolve("topk", n=500, dtype="float32") == {
        "block_q": 32, "block_n": 256}
    # explicit kwarg beats the tuned entry; None means unspecified
    assert tuning.resolve("topk", n=500, dtype="float32",
                          block_n=128, block_q=None) == {
        "block_q": 32, "block_n": 128}
    # other buckets / dtypes fall through to the defaults
    assert tuning.resolve("topk", n=5000, dtype="float32") == \
        tuning.DEFAULTS["topk"]
    assert tuning.resolve("topk", n=500, dtype="int8") == \
        tuning.DEFAULTS["topk"]


def test_resolve_unknown_param_raises():
    with pytest.raises(ValueError, match="no block param"):
        tuning.resolve("topk", n=100, dtype="float32", block_z=64)


def test_set_table_none_forces_defaults():
    table = _table()
    table.add(tuning.TunedConfig("topk", "le1024", "float32",
                                 (("block_n", 128), ("block_q", 8))))
    tuning.set_table(table)
    assert tuning.resolve("topk", n=100, dtype="float32")["block_n"] == 128
    tuning.set_table(None)        # the --no-tuned-kernels hatch
    assert tuning.resolve("topk", n=100, dtype="float32") == \
        tuning.DEFAULTS["topk"]


def test_env_escape_hatch_and_path(tmp_path):
    """REPRO_TUNED_KERNELS=off forces defaults; =<path> loads that table.
    Subprocess because the active table resolves once per process."""
    table = _table(origin="test")
    table.add(tuning.TunedConfig("topk", "le1024", "float32",
                                 (("block_n", 512), ("block_q", 8))))
    path = tmp_path / "t.json"
    table.save(str(path))
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = ("from repro.kernels import tuning; "
              "print(tuning.resolve('topk', n=100, dtype='float32'))")
    def run(env_value):
        env = dict(os.environ, PYTHONPATH=src + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        env[tuning.ENV_VAR] = env_value
        out = subprocess.run([sys.executable, "-c", script], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stdout + out.stderr
        return out.stdout.strip()
    assert run("off") == str(tuning.DEFAULTS["topk"])
    assert "512" in run(str(path))


def test_table_save_load_roundtrip(tmp_path):
    table = tuning.TunedTable(meta={"backend": "cpu"})
    table.add(tuning.TunedConfig("hamming_topk", "le4096", "int32",
                                 (("block_n", 256), ("block_q", 32)),
                                 score_ms=1.25, evals=7))
    path = str(tmp_path / "round.json")
    table.save(path)
    loaded = tuning.TunedTable.load(path)
    assert loaded.meta == {"backend": "cpu"}
    assert loaded.entries == table.entries
    # file is plain JSON with params as a dict (human-diffable)
    raw = json.load(open(path))
    assert raw["entries"][0]["params"] == {"block_n": 256, "block_q": 32}


def test_hillclimb_converges_on_synthetic_score():
    """Ask/tell finds the global optimum of a separable convex score from
    the default start, without exhausting the cross product."""
    space = tuning.SPACES["topk"]
    target = {"block_q": 8, "block_n": 2048}
    tuner = tuning.HillclimbTuner(space)
    while True:
        point = tuner.ask()
        if point is None:
            break
        score = sum(abs(np.log2(point[a]) - np.log2(target[a]))
                    for a in target)
        tuner.tell(point, score)
    assert tuner.best == target
    assert tuner.num_evals < sum(1 for _ in space.candidates())


def test_space_shrink_and_neighbours():
    space = tuning.SPACES["topk"].shrink_to({"block_n": 300})
    assert space.axes["block_n"] == (128, 256)
    assert space.axes["block_q"] == (8, 32, 128, 256)
    nbrs = list(space.neighbours({"block_q": 8, "block_n": 256}))
    assert {"block_q": 32, "block_n": 256} in nbrs
    assert {"block_q": 8, "block_n": 128} in nbrs
    assert len(nbrs) == 2
    # shrink below the smallest candidate keeps one value per axis
    tiny = tuning.SPACES["topk"].shrink_to({"block_n": 8})
    assert tiny.axes["block_n"] == (128,)


def test_autotune_smoke_writes_table_backends_consult(tmp_path):
    """Tiny autotune end to end: tunes one cell, persists it, activates it,
    and the dispatch wrappers resolve through it."""
    out = str(tmp_path / "tuned.json")
    table = tuning.autotune(["label_prop_round"], buckets=("le1024",),
                            max_evals=3, wall_iters=0, out_path=out,
                            activate=True, verbose=False)
    assert os.path.exists(out)
    entry = table.entries[("label_prop_round", "le1024", "float32")]
    assert tuning.resolve("label_prop_round", n=1000,
                          dtype="float32") == entry.params_dict()
    assert entry.evals >= 1 and np.isfinite(entry.score_ms)


def test_parity_under_absurd_tuned_blocks():
    """Correctness is block-independent: a tuned table pinning oversized
    blocks (clamped by the padded-n floor inside the kernels) must not
    change results."""
    table = _table()
    table.add(tuning.TunedConfig("topk", "le1024", "float32",
                                 (("block_n", 2048), ("block_q", 256))))
    tuning.set_table(table)
    qs = jax.random.normal(jax.random.PRNGKey(0), (5, 16))
    cs = jax.random.normal(jax.random.PRNGKey(1), (37, 16))
    s, i = topk_scores(qs, cs, k=4)
    s_ref, i_ref = topk_scores_ref(qs, cs, k=4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=1e-5, atol=1e-5)
    assert (np.asarray(i) == np.asarray(i_ref)).all()


def test_table_from_another_device_kind_falls_back_to_defaults():
    """Blocks tuned on one device kind never reach another: the checked-in
    CPU table is ignored on a TPU, and a TPU table on the CPU."""
    table = tuning.TunedTable(meta={"device_kind": "TPU v5 lite"})
    table.add(tuning.TunedConfig("topk", "le1024", "float32",
                                 (("block_n", 128), ("block_q", 8))))
    tuning.set_table(table)
    assert tuning.device_kind() != "TPU v5 lite"
    assert tuning.lookup("topk", n=100, dtype="float32") == {}
    assert tuning.resolve("topk", n=100, dtype="float32") == \
        tuning.DEFAULTS["topk"]
    # the checked-in table records the CPU it was tuned on
    shipped = tuning.TunedTable.load(tuning.DEFAULT_TABLE_PATH)
    assert shipped.meta["device_kind"] == "cpu"


def test_results_table_in_cwd_is_not_read_implicitly(tmp_path):
    """results/tuned_kernels.json under the working directory is what
    ``--autotune`` writes; a fresh process loads the checked-in table
    unless REPRO_TUNED_KERNELS names that file."""
    table = _table()
    table.add(tuning.TunedConfig("topk", "le1024", "float32",
                                 (("block_n", 384), ("block_q", 8))))
    table.save(str(tmp_path / tuning.RESULTS_TABLE_PATH))
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    script = ("from repro.kernels import tuning; "
              "print(tuning.resolve('topk', n=100, dtype='float32'))")
    env = dict(os.environ, PYTHONPATH=src + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop(tuning.ENV_VAR, None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         cwd=str(tmp_path), capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "384" not in out.stdout
    env[tuning.ENV_VAR] = tuning.RESULTS_TABLE_PATH
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         cwd=str(tmp_path), capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "384" in out.stdout
