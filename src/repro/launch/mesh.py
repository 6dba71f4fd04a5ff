"""Production meshes.  Functions, not constants: importing this module must
never touch jax device state.

Every mesh in the repo comes from ``auto_mesh``: JAX 0.9 makes
``Explicit`` axes the default for ``jax.make_mesh``, under which sharding
becomes part of each array's type and ops such as ``jnp.take`` on a
replicated table, or a ``vmap`` over a sharded batch, raise.  This code is
written for GSPMD propagation, so its axes are ``Auto``."""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType

from repro.common.config import ParallelConfig


def auto_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi-pod = 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_mesh(dp: int, tp: int, pods: int = 1):
    if pods > 1:
        return auto_mesh((pods, dp, tp), ("pod", "data", "model"))
    return auto_mesh((dp, tp), ("data", "model"))


def make_host_mesh():
    """Whatever this host has, as a (data,) mesh — for tests/examples."""
    n = len(jax.devices())
    return auto_mesh((n, 1), ("data", "model"))


def parallel_config_for(mesh) -> ParallelConfig:
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    return ParallelConfig(dp=shape.get("data", 1), tp=shape.get("model", 1),
                          pods=shape.get("pod", 1))
