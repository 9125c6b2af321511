"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state; `dryrun.py` sets the 512-device XLA flag before
calling it.
"""
from __future__ import annotations


from repro.configs.base import MeshConfig
from repro.distributed.sharding import auto_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def mesh_config(*, multi_pod: bool = False) -> MeshConfig:
    return MeshConfig(shape=(2, 16, 16) if multi_pod else (16, 16),
                      axes=("pod", "data", "model") if multi_pod
                      else ("data", "model"))
