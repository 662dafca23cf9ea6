"""Device mesh management.

The canonical axes: dp (data), mp (tensor/model), pp (pipeline), sp
(sequence/context). Mirrors paddle.distributed.fleet's hybrid-parallel degrees
(DistributedStrategy.hybrid_configs) onto a jax.sharding.Mesh — sharding-book
style: pick a mesh, annotate, let XLA insert collectives.
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh

_current_mesh = None


def pvary(x, axis_name):
    """Mark `x` as device-varying over `axis_name` inside a shard_map
    (a scan carry that starts replicated and becomes per-device); a
    no-op where it already varies."""
    if axis_name in jax.typeof(x).vma:
        return x
    return jax.lax.pcast(x, axis_name, to="varying")


@dataclass
class MeshConfig:
    dp: int = 1
    mp: int = 1
    pp: int = 1
    sp: int = 1

    @property
    def total(self):
        return self.dp * self.mp * self.pp * self.sp


def make_mesh(dp=None, mp=1, pp=1, sp=1, devices=None):
    """Build a Mesh with axes (dp, mp, pp, sp); dp=None absorbs the rest."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if dp is None:
        dp = n // (mp * pp * sp)
    assert dp * mp * pp * sp == n, \
        f"mesh {dp}x{mp}x{pp}x{sp} != {n} devices"
    arr = np.array(devices).reshape(dp, pp, mp, sp)
    return Mesh(arr, ("dp", "pp", "mp", "sp"))


def get_mesh(dp=None, mp=1, pp=1, sp=1):
    global _current_mesh
    if _current_mesh is None:
        _current_mesh = make_mesh(dp, mp, pp, sp)
    return _current_mesh


def current_mesh():
    return _current_mesh


def set_mesh(mesh):
    global _current_mesh
    _current_mesh = mesh
    return mesh


@contextlib.contextmanager
def mesh_guard(mesh):
    global _current_mesh
    old = _current_mesh
    _current_mesh = mesh
    try:
        yield mesh
    finally:
        _current_mesh = old
