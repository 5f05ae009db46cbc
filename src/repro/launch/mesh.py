"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (smoke tests see 1 CPU device; only dryrun.py sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax use).

Mesh geometry (TPU v5e pods):
  single-pod: (data=16, model=16)       = 256 chips
  multi-pod:  (pod=2, data=16, model=16) = 512 chips
The 'model' axis carries TP/EP/vocab sharding (highest-bandwidth inner
axis); 'data' carries DP + ZeRO-sharded parameter/optimizer state; 'pod'
carries pure DP whose gradient all-reduce crosses the DCI links — that is
the all-reduce gradient compression (distributed/compression.py) targets.

Every mesh here has Auto axes: the model code places activations with
``with_sharding_constraint`` and leaves the rest to the SPMD partitioner,
which Explicit axes (``jax.make_mesh``'s default) refuse.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with Auto axis types (see the module docstring)."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Degenerate 1-device mesh with the production axis NAMES, so the same
    sharded step functions run in smoke tests on CPU."""
    return make_mesh((1, model_axis), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """Mesh axes the global batch is sharded over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def parse_mesh(name: str):
    """CLI ``--mesh`` flag -> Mesh: ``host`` is the 1-device mesh with
    production axis names, ``auto`` puts all local devices on the data
    axis.  Shared by launch/sample.py and launch/evaluate.py so the two
    entry points agree on mesh vocabulary."""
    if name == "host":
        return make_host_mesh()
    if name == "auto":
        return make_mesh((len(jax.devices()), 1), ("data", "model"))
    raise ValueError(f"unknown mesh {name!r}; known meshes: auto, host")
