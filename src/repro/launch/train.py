"""Training launcher: ``--arch <id>`` selects any registry architecture at
REDUCED scale on the host mesh (this container is CPU-only; the full-scale
path is exercised by dryrun.py), with checkpointing + elastic resume.

  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --steps 20
  PYTHONPATH=src python -m repro.launch.train --arch dlrm-mlperf --steps 50
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, list_archs
from repro.launch.cells import build_cell
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.train.loop import LoopConfig, train_loop
from repro.train.optimizer import adamw_init


def _batch_like(sds, step, rng):
    def one(s):
        if not hasattr(s, "shape"):
            return s
        if s.dtype == jnp.int32:
            return jnp.asarray(rng.integers(0, 2, size=s.shape), jnp.int32)
        if s.dtype == jnp.bool_:
            return jnp.ones(s.shape, bool)
        return jnp.asarray(rng.normal(size=s.shape).astype(np.float32))
    return jax.tree.map(one, sds)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True, choices=list_archs())
    p.add_argument("--shape", default=None)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    enable_compile_cache()

    spec = get_arch(args.arch)
    shape = args.shape or next(s for s, v in spec.shapes.items()
                               if v["kind"].startswith("train"))
    mesh = make_host_mesh()
    cell = build_cell(args.arch, shape, mesh, reduced=True)
    rng = np.random.default_rng(args.seed)

    params = _batch_like(cell.args[0], 0, rng)
    params = jax.tree.map(lambda x: x * 0.02, params)
    opt_state = adamw_init(params)
    batch_sds = cell.args[2]

    cfg = LoopConfig(total_steps=args.steps, log_every=5,
                     checkpoint_every=10, checkpoint_dir=args.checkpoint_dir)
    with mesh:
        train_loop(cell.fn, params, opt_state,
                   lambda step: _batch_like(batch_sds, step,
                                            np.random.default_rng(step)),
                   cfg)


if __name__ == "__main__":
    main()
