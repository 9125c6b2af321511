"""AdamW with cosine schedule, warmup, global-norm clipping.

fp32 master weights + moments; model casts to bf16 at use sites.  Matches
the paper's recipe: β=(0.9, 0.95), wd 0.1, clip 1.0, cosine to 10% peak.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import TrainConfig


class AdamWState(NamedTuple):
    step: jax.Array
    mu: dict
    nu: dict


def cosine_schedule(cfg: TrainConfig) -> Callable[[jax.Array], jax.Array]:
    def lr(step):
        warm = cfg.learning_rate * (step + 1) / max(cfg.warmup_steps, 1)
        t = jnp.clip((step - cfg.warmup_steps)
                     / max(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
        cos = cfg.learning_rate * (0.1 + 0.45 * (1 + jnp.cos(jnp.pi * t)))
        return jnp.where(step < cfg.warmup_steps, warm, cos)
    return lr


def adamw_init(params) -> AdamWState:
    zeros = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
    return AdamWState(jnp.zeros((), jnp.int32), zeros,
                      jax.tree.map(jnp.copy, zeros))


def clip_by_global_norm(grads, max_norm: float):
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-9))
    return jax.tree.map(lambda g: g * scale, grads), gn


_NO_DECAY = ("norm", "scale", "bias", "a_log", "dt_bias", "d_skip")


@functools.partial(jax.named_call, name="optimizer")
def adamw_update(params, grads, state: AdamWState, cfg: TrainConfig,
                 lr_fn=None) -> Tuple[dict, AdamWState, dict]:
    lr_fn = lr_fn or cosine_schedule(cfg)
    grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
    step = state.step + 1
    lr = lr_fn(state.step)
    b1, b2 = cfg.beta1, cfg.beta2
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)

    flat_params = jax.tree_util.tree_flatten_with_path(params)
    paths = ["/".join(str(getattr(k, "key", k)) for k in path)
             for path, _ in flat_params[0]]

    def upd(path_leaf, g, m, n):
        path, p = path_leaf
        g = g.astype(jnp.float32)
        m = b1 * m + (1 - b1) * g
        n = b2 * n + (1 - b2) * g * g
        update = (m / c1) / (jnp.sqrt(n / c2) + 1e-8)
        if cfg.weight_decay and not any(t in path for t in _NO_DECAY):
            update = update + cfg.weight_decay * p.astype(jnp.float32)
        return (p - lr * update).astype(p.dtype), m, n

    leaves_p = [leaf for _, leaf in flat_params[0]]
    leaves_g = jax.tree_util.tree_leaves(grads)
    leaves_m = jax.tree_util.tree_leaves(state.mu)
    leaves_n = jax.tree_util.tree_leaves(state.nu)
    out = [upd((path, p), g, m, n) for path, p, g, m, n
           in zip(paths, leaves_p, leaves_g, leaves_m, leaves_n)]
    treedef = flat_params[1]
    new_p = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
    new_m = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
    new_n = jax.tree_util.tree_unflatten(treedef, [o[2] for o in out])
    return new_p, AdamWState(step, new_m, new_n), {
        "lr": lr, "grad_norm": gnorm}
