"""Model-level public API: step functions + dry-run input specs.

``input_specs`` returns ShapeDtypeStruct stand-ins for every model input of
the given (arch config × assigned shape) cell — weak-type-correct,
shardable, no device allocation.  Modality frontends are stubs: audio
archs get precomputed frame embeddings, VLMs get patch embeddings
(per the assignment: backbone only)."""
from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ASSIGNED_SHAPES, ModelConfig
from repro.models import transformer as T


def param_count(cfg: ModelConfig) -> int:
    shapes = jax.eval_shape(lambda k: T.init_lm(k, cfg),
                            jax.random.PRNGKey(0))
    return sum(math.prod(x.shape)
               for x in jax.tree_util.tree_leaves(shapes))


def active_param_count(cfg: ModelConfig) -> int:
    """MoE-aware: of the experts a layer holds, a token runs through
    top_k · held / num_experts on average; shared experts are active."""
    total = param_count(cfg)
    if cfg.family != "moe" or not cfg.moe.num_experts:
        return total
    m = cfg.moe
    per_expert = 3 * cfg.d_model * m.expert_d_ff
    pattern, n_groups = T._block_kinds(cfg)
    n_moe_layers = sum(1 for k in pattern if k not in ("cross", "ssm")
                      ) * n_groups
    active = m.top_k * m.held / m.num_experts
    return total - round(n_moe_layers * (m.held - active) * per_expert)


def input_specs(cfg: ModelConfig, shape: str,
                dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Dry-run input ShapeDtypeStructs for one assigned shape cell.

    kind 'train'   → {'tokens': (B, S+1)} (+ stub modality inputs)
    kind 'prefill' → {'tokens': (B, S)} (+ stubs)
    kind 'decode'  → {'token': (B, 1), 'caches': <pytree>} (+ stubs)
    """
    info = ASSIGNED_SHAPES[shape]
    s, b, kind = info["seq_len"], info["global_batch"], info["kind"]
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    f = functools.partial(jax.ShapeDtypeStruct, dtype=dtype)

    specs: Dict[str, Any] = {}
    if kind == "train":
        specs["tokens"] = i32((b, s + 1))
    elif kind == "prefill":
        specs["tokens"] = i32((b, s))
    else:
        specs["token"] = i32((b, 1))
        caches = jax.eval_shape(
            lambda: T.init_caches(cfg, b, s, dtype=dtype))
        specs["caches"] = caches
    if cfg.family == "vlm":
        specs["cross_kv"] = f((b, cfg.num_image_tokens, cfg.d_model))
    if cfg.family == "encdec":
        specs["src_embeds"] = f((b, cfg.num_audio_frames, cfg.d_model))
    return specs


def make_forward(cfg: ModelConfig, backend: str = "sparse"):
    def forward(params, tokens, cross_kv=None, src_embeds=None):
        ck = cross_kv
        if cfg.num_encoder_layers and src_embeds is not None:
            ck = T.apply_encoder(params, src_embeds, cfg,
                                 backend=backend)
        logits, _, _ = T.lm_apply(params, tokens, cfg,
                                  backend=backend, cross_kv=ck)
        return logits
    return forward
