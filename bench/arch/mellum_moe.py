"""Mellum2's sparse transformer, as one chip of an expert-parallel
deployment holds it: RMSNorm pre-norm blocks, GQA attention whose layers
follow the published ``layer_types`` (``sliding_attention`` a window of
``sliding_window`` keys with plain RoPE, ``full_attention`` MoBA with
YaRN RoPE), and in every layer a softmax-routed expert MLP of which this
chip holds the first ``num_experts``; untied LM head.

Configuration keys read: the published ``hidden_size``,
``num_hidden_layers``, ``layer_types`` (its first ``num_hidden_layers``),
``mlp_layer_types``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``intermediate_size``, ``moe_intermediate_size``,
``num_experts`` (held here), ``num_experts_per_tok``, ``norm_topk_prob``,
``vocab_size``, ``max_position_embeddings``, ``rms_norm_eps``,
``rope_parameters``, ``sliding_window``, ``tie_word_embeddings``; and
``router_num_experts`` (the router's published width),
``router_aux_loss_coef``, ``moba_block_size``, ``moba_top_k``,
``compute_dtype``, ``param_dtype``.

Reference layers (``jnp`` at ``HIGHEST``, every matmul through
``reference.ein``):

* rotary embedding on the halves of each head at
  ``rope_parameters[<layer type>].rope_theta``; for ``yarn``, the inverse
  frequencies of HF transformers' ``_compute_yarn_parameters`` (a linear
  ramp between the correction dims of ``beta_fast`` and ``beta_slow``
  rotations over ``original_max_position_embeddings`` blends the
  original frequencies into those divided by ``factor``) and cos and sin
  times ``attention_factor``;
* attention as ``bench/arch/moba_transformer.py`` computes it (``moba``
  and ``swa``);
* experts: a softmax over the router's ``router_num_experts`` outputs,
  the ``num_experts_per_tok`` best of each token, their gates
  renormalised to sum to one over the whole top-k; this chip's part is
  the sum, over the held experts (``0 .. num_experts-1``) among them, of
  gate × SwiGLU of ``moe_intermediate_size`` — every held expert is
  computed on every token and the chosen ones kept, so no pair is
  dropped.  What the other chips' experts would add is left out, as in
  the program.  The training objective (``batch_loss``) adds, for every
  layer, the router's auxiliary loss over the whole batch and every
  router output, ``coef · E · Σ_e f_e · p_e``.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from bench.arch import moba_transformer as T
from bench.reference import ein, nll_sum, rms
from bench.work.model import AttentionLayer

KIND = {"sliding_attention": "swa", "full_attention": "moba"}
NO_DECAY = ("norm", "scale", "bias", "a_log", "dt_bias", "d_skip")


class ExpertLayer(NamedTuple):
    """One expert layer as the work counts see it."""
    d: int              # hidden size
    f: int              # an expert's width
    experts: int        # the router's width
    held: int           # experts held here
    top_k: int          # experts per token


def layer_kinds(c: dict) -> list[str]:
    n = c["num_hidden_layers"]
    if any(t != "sparse" for t in c["mlp_layer_types"][:n]):
        raise ValueError(f"{c['name']}: every MLP layer must be sparse")
    return [KIND[t] for t in c["layer_types"][:n]]


def pattern(c: dict) -> tuple[str, ...]:
    """The shortest repeating period of the layers' kinds."""
    kinds = layer_kinds(c)
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
            return tuple(kinds[:p])


def _rope_of(c: dict, kind: str) -> dict:
    return c["rope_parameters"]["full_attention" if kind == "moba"
                                else "sliding_attention"]


# ------------------------------------------------------------- program
def program_config(c: dict):
    """The program's ``ModelConfig`` for configuration ``c``."""
    from repro.configs.base import (AttentionConfig, MoBAConfig, ModelConfig,
                                    MoEConfig, YarnRope)
    swa, full = _rope_of(c, "swa"), _rope_of(c, "moba")
    if swa["rope_type"] != "default" or full["rope_type"] != "yarn" \
            or swa["rope_theta"] != full["rope_theta"]:
        raise ValueError(f"{c['name']}: the program takes plain RoPE on "
                         f"window layers and YaRN at the same θ on MoBA")
    if not c["norm_topk_prob"]:
        raise ValueError(f"{c['name']}: the program renormalises the "
                         f"top-k gates")
    yarn = YarnRope(factor=float(full["factor"]),
                    original_max_position_embeddings=full[
                        "original_max_position_embeddings"],
                    beta_fast=float(full["beta_fast"]),
                    beta_slow=float(full["beta_slow"]),
                    attention_factor=full["attention_factor"])
    attn = AttentionConfig(
        kind="moba", moba=MoBAConfig(block_size=c["moba_block_size"],
                                     top_k=c["moba_top_k"]),
        window=c["sliding_window"], rope_on_moba=True, qk_norm=False,
        rope_theta=float(swa["rope_theta"]), moba_yarn=yarn)
    moe = MoEConfig(num_experts=c["router_num_experts"],
                    top_k=c["num_experts_per_tok"],
                    expert_d_ff=c["moe_intermediate_size"],
                    router_aux_coef=c["router_aux_loss_coef"],
                    experts_held=c["num_experts"])
    return ModelConfig(
        name=c["name"], family="moe", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        max_seq_len=c["max_position_embeddings"],
        tie_embeddings=c["tie_word_embeddings"],
        rms_norm_eps=c["rms_norm_eps"], attention=attn, moe=moe,
        layer_pattern=pattern(c), dtype=c["compute_dtype"],
        param_dtype=c["param_dtype"])


def param_specs(c: dict) -> dict:
    """``{path: (shape, init)}`` of every weight; ``init`` is ``"one"``
    for norm scales or the standard deviation of a normal draw."""
    d, h, hkv, dh = (c["hidden_size"], c["num_attention_heads"],
                     c["num_key_value_heads"], c["head_dim"])
    e, held, f = (c["router_num_experts"], c["num_experts"],
                  c["moe_intermediate_size"])
    v, per = c["vocab_size"], pattern(c)
    g = c["num_hidden_layers"] // len(per)
    specs = {"embed": ((v, d), 0.02), "final_norm": ((d,), "one")}
    if not c["tie_word_embeddings"]:
        specs["lm_head"] = ((d, v), d ** -0.5)
    for i, _ in enumerate(per):
        s = f"blocks/slot_{i}/"
        specs[s + "norm1"] = ((g, d), "one")
        specs[s + "norm2"] = ((g, d), "one")
        specs[s + "attn/wq"] = ((g, d, h * dh), d ** -0.5)
        specs[s + "attn/wk"] = ((g, d, hkv * dh), d ** -0.5)
        specs[s + "attn/wv"] = ((g, d, hkv * dh), d ** -0.5)
        specs[s + "attn/wo"] = ((g, h * dh, d), (h * dh) ** -0.5)
        specs[s + "moe/router"] = ((g, d, e), d ** -0.5)
        specs[s + "moe/experts/w_gate"] = ((g, held, d, f), d ** -0.5)
        specs[s + "moe/experts/w_up"] = ((g, held, d, f), d ** -0.5)
        specs[s + "moe/experts/w_down"] = ((g, held, f, d), f ** -0.5)
    return specs


# ----------------------------------------------------------- reference
def inv_freq(d: int, rope: dict):
    """Inverse frequencies of the d/2 rotated pairs, and cos/sin's
    scale."""
    theta = float(rope["rope_theta"])
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if rope["rope_type"] == "default":
        return freqs, 1.0
    factor = float(rope["factor"])
    orig = rope["original_max_position_embeddings"]

    def dim_of(rotations):        # the dim turning ``rotations`` times
        return d * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rope["beta_slow"])), d - 1)
    if high == low:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    scale = rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return freqs * (1 - ramp) + freqs / factor * ramp, scale


def rope(x, pos, params: dict):
    """x (..., N, d) rotated by absolute positions ``pos`` (N,)."""
    d = x.shape[-1]
    freqs, scale = inv_freq(d, params)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(c, kind, a, y, low):
    n = y.shape[0]
    h, hkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    q, k, v = (ein("nm,mk->nk", y, a[w], low).reshape(n, heads, dh)
               .transpose(1, 0, 2)
               for w, heads in (("wq", h), ("wk", hkv), ("wv", hkv)))
    pos = jnp.arange(n)
    q, k = rope(q, pos, _rope_of(c, kind)), rope(k, pos, _rope_of(c, kind))
    o = T._attention(c, kind, q, k, v, low).transpose(1, 0, 2)
    return ein("nk,km->nm", o.reshape(n, h * dh), a["wo"], low)


def _experts(c, p, y, low):
    """y (T, d) of every row of the batch -> (this chip's part (T, d),
    the router's auxiliary loss)."""
    e, k, held = (c["router_num_experts"], c["num_experts_per_tok"],
                  c["num_experts"])
    probs = jax.nn.softmax(ein("tm,me->te", y, p["router"], low), -1)
    top_p, top_e = jax.lax.top_k(probs, k)
    if c["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    w = p["experts"]
    hid = (jax.nn.silu(ein("tm,emf->etf", y, w["w_gate"], low))
           * ein("tm,emf->etf", y, w["w_up"], low))
    every = ein("etf,efm->etm", hid, w["w_down"], low)        # (held, T, d)
    here = top_e < held
    chosen = every[jnp.where(here, top_e, 0),
                   jnp.arange(y.shape[0])[:, None]]             # (T, k, d)
    out = jnp.einsum("tk,tkm->tm", jnp.where(here, top_p, 0.0), chosen,
                     precision=jax.lax.Precision.HIGHEST)
    share = jnp.mean(jax.nn.one_hot(top_e, e).sum(1), 0) / k
    aux = c["router_aux_loss_coef"] * e * jnp.sum(share * probs.mean(0))
    return out, aux


def _forward(c, params, tokens, low):
    """tokens (B, N) -> final-normed hidden states (B, N, d), aux."""
    eps = c["rms_norm_eps"]
    b, n = tokens.shape
    x = params["embed"][tokens]

    def layer(kind, p, x):
        y = rms(x, p["norm1"], eps)
        x = x + jax.vmap(lambda r: _attention(c, kind, p["attn"], r, low))(y)
        out, aux = _experts(c, p["moe"], rms(x, p["norm2"], eps)
                            .reshape(b * n, -1), low)
        return x + out.reshape(x.shape), aux

    def group(carry, gp):
        x, aux = carry
        for i, kind in enumerate(pattern(c)):
            x, a = jax.checkpoint(
                lambda x, p, kind=kind: layer(kind, p, x))(
                    x, gp[f"slot_{i}"])
            aux = aux + a
        return (x, aux), None

    (x, aux), _ = jax.lax.scan(group, (x, jnp.zeros(())), params["blocks"])
    return rms(x, params["final_norm"], eps), aux


def hidden(c: dict, params: dict, tokens: jax.Array, low: bool):
    """Final-normed hidden states (N, d) of one sequence ``tokens``."""
    return _forward(c, params, tokens[None], low)[0][0]


def head(c: dict, params: dict):
    """The LM head's (d, vocab) matrix."""
    return (params["embed"].T if c["tie_word_embeddings"]
            else params["lm_head"])


def batch_loss(c: dict, params: dict, tokens: jax.Array, low: bool):
    """Mean next-token cross entropy over the batch plus every layer's
    auxiliary loss."""
    x, aux = _forward(c, params, tokens[:, :-1], low)
    w = head(c, params)
    nll = sum(nll_sum(x[i], w, tokens[i, 1:], low)
              for i in range(tokens.shape[0]))
    return nll / (x.shape[0] * x.shape[1]) + aux


def decays(path: str) -> bool:
    """AdamW decays a leaf unless its path holds one of the program's
    no-decay names (``optim/adamw.py``)."""
    return not any(t in path for t in NO_DECAY)


# ---------------------------------------------------------------- work
def expert_layers(c: dict) -> list[ExpertLayer]:
    """One entry per expert layer, in order."""
    return [ExpertLayer(c["hidden_size"], c["moe_intermediate_size"],
                        c["router_num_experts"], c["num_experts"],
                        c["num_experts_per_tok"])] * c["num_hidden_layers"]


def active_matmul_params(c: dict) -> float:
    """Matmul parameters a token runs through in every layer (the head
    excluded): q/k/v/o, the router, and of the held experts the
    top_k · held / E a token reaches here on average."""
    d, h, hkv, dh = (c["hidden_size"], c["num_attention_heads"],
                     c["num_key_value_heads"], c["head_dim"])
    total = 0.0
    for x in expert_layers(c):
        total += (d * (h + 2 * hkv) * dh + h * dh * d + d * x.experts
                  + x.top_k * x.held * 3 * x.d * x.f / x.experts)
    return total


def attention_layers(c: dict) -> list[AttentionLayer]:
    """One entry per layer, in order; q, k and v share the head size."""
    h, hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    out = []
    for kind in layer_kinds(c):
        extra = ({"window": c["sliding_window"]} if kind == "swa" else
                 {"block_size": c["moba_block_size"],
                  "top_k": c["moba_top_k"]})
        out.append(AttentionLayer(kind, h, hkv, d, d, **extra))
    return out
