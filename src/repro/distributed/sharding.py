"""Logical sharding rules: parameter-path → PartitionSpec, activation
constraints, and the production mesh axis conventions.

Axis conventions (DESIGN.md §3):
  * ``("pod","data")`` — combined DP/FSDP axis (gradients, batch, ZeRO-3)
  * ``"model"``        — TP: heads / ffn / vocab / experts

Model code calls :func:`constrain` on activations; it is a no-op outside a
mesh context, so the same code runs in single-device tests and the 512-chip
dry-run.
"""
from __future__ import annotations

import re
from contextlib import contextmanager
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import MeshConfig, ShardingConfig

_ACTIVE: dict = {"mesh": None, "cfg": None}


@contextmanager
def use_mesh(mesh: Mesh, cfg: ShardingConfig):
    prev = dict(_ACTIVE)
    _ACTIVE.update(mesh=mesh, cfg=cfg)
    try:
        with mesh:
            yield
    finally:
        _ACTIVE.update(prev)


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def tp_enabled() -> bool:
    mesh, cfg = _ACTIVE["mesh"], _ACTIVE["cfg"]
    return (mesh is not None and "model" in mesh.axis_names
            and (cfg is None or cfg.tensor_parallel))


def constrain(x: jax.Array, spec: Sequence[Optional[str]]) -> jax.Array:
    """Annotate activation sharding; logical names 'dp' and 'tp' resolve to
    the mesh's data axes and model axis. No-op without an active mesh."""
    mesh = _ACTIVE["mesh"]
    if mesh is None:
        return x
    cfg = _ACTIVE["cfg"]
    has_model = "model" in mesh.axis_names
    use_tp = has_model and (cfg is None or cfg.tensor_parallel)
    use_sp = has_model and (cfg is None or cfg.sequence_parallel
                            or cfg.tensor_parallel)
    resolved = []
    for s in spec:
        if s == "dp":
            resolved.append(data_axes(mesh))
        elif s == "tp":
            resolved.append("model" if use_tp else None)
        elif s == "sp":
            resolved.append("model" if use_sp else None)
        else:
            resolved.append(s)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*resolved)))


# --------------------------------------------------------------- param rules
# path-regex → logical spec. 'fsdp' resolves to the data axes when ZeRO-3 is
# on (sharding the largest dim), 'tp' to the model axis.
_PARAM_RULES = [
    # embeddings (V, d): vocab over tp, d over fsdp
    (r"embed", ("tp", "fsdp")),
    # lm head (d, V): vocab over tp, d over fsdp
    (r"lm_head", ("fsdp", "tp")),
    # attention projections
    (r"wq$|wk$|wv$|w_qkv", ("fsdp", "tp")),     # (d_model, heads*dh)
    (r"wo$", ("tp", "fsdp")),                   # (heads*dh, d_model)
    # mlp
    (r"w_gate$|w_up$", ("fsdp", "tp")),
    (r"w_down$", ("tp", "fsdp")),
    # moe expert weights (E, d, f): experts over tp (EP), d over fsdp
    (r"experts.*w_(gate|up)$", (None, "fsdp", "tp")),
    (r"experts.*w_down$", (None, "tp", "fsdp")),
    (r"router$", ("fsdp", None)),
    # mamba2
    (r"in_proj$", ("fsdp", "tp")),
    (r"out_proj$", ("tp", "fsdp")),
    # norms / small vectors replicated
    (r"norm|scale|bias|a_log$|dt_bias$|d_skip$|conv|key_conv", None),
]


def _axes_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def spec_for_param(path: str, shape, mesh: Mesh,
                   cfg: ShardingConfig) -> P:
    """Resolve a parameter path to a PartitionSpec on ``mesh``.

    Dims not divisible by the mapped axis size fall back to replication
    (e.g. vocab 50280 on a 16-way model axis) — jit input shardings,
    unlike activation constraints, require exact divisibility."""
    ndim = len(shape)
    logical = None
    for pat, spec in _PARAM_RULES:
        if re.search(pat, path):
            logical = spec
            break
    if logical is None:
        return P()
    dax = data_axes(mesh) if cfg.fsdp else None
    tp = "model" if (cfg.tensor_parallel and "model" in mesh.axis_names) \
        else None
    out = []
    for s in logical:
        if s == "fsdp":
            out.append(dax)
        elif s == "tp":
            out.append(tp)
        else:
            out.append(s)
    out = [None] * (ndim - len(out)) + out if ndim >= len(out) \
        else out[-ndim:]
    out = [a if (shape[i] % _axes_size(mesh, a) == 0) else None
           for i, a in enumerate(out)]
    return P(*out)


def param_specs(params, mesh: Mesh, cfg: ShardingConfig):
    """Map a param pytree to a matching tree of NamedShardings."""
    flat = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat[0]:
        pstr = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        specs.append(NamedSharding(
            mesh, spec_for_param(pstr, leaf.shape, mesh, cfg)))
    return jax.tree_util.tree_unflatten(flat[1], specs)


def auto_mesh(shape, axes) -> Mesh:
    """A mesh over the local devices whose axes are all ``Auto``: the
    compiler propagates shardings from the annotations above."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh(cfg: MeshConfig) -> Mesh:
    return auto_mesh(cfg.shape, cfg.axes)
