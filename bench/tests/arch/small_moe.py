"""A small mixture-of-experts transformer, an architecture module for the
CPU tests: the attention of ``bench/arch/moba_transformer.py`` with the
program's expert layer (``family="moe"``) in place of its MLP.

Configuration keys read beside the transformer's: ``num_experts``,
``num_experts_per_tok``, ``moe_intermediate_size`` and
``router_aux_loss_coef``.

Reference: a softmax router over the experts, the ``num_experts_per_tok``
best of each token, their gates renormalised to sum to one, each a SwiGLU
of ``moe_intermediate_size``; no expert drops a token, as the program's
layer drops none in a batch of 64 tokens or fewer.  The training
objective (``batch_loss``) adds, for every layer, the router's auxiliary
loss taken over the whole batch, ``coef · E · Σ_e f_e · p_e``, with
``f_e`` the share of the batch's (token, choice) pairs routed to expert
``e`` and ``p_e`` its mean router probability.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.arch import moba_transformer as T
from bench.arch.moba_transformer import (  # noqa: F401
    attention_layers, decays, head)
from bench.reference import ein, nll_sum, rms, rope


def program_config(c: dict):
    import dataclasses
    from repro.configs.base import MoEConfig
    moe = MoEConfig(num_experts=c["num_experts"],
                    top_k=c["num_experts_per_tok"],
                    expert_d_ff=c["moe_intermediate_size"],
                    router_aux_coef=c["router_aux_loss_coef"])
    return dataclasses.replace(T.program_config(c), family="moe", moe=moe)


def param_specs(c: dict) -> dict:
    d, e, f = c["hidden_size"], c["num_experts"], c["moe_intermediate_size"]
    g = T.n_groups(c)
    specs = {k: v for k, v in T.param_specs(c).items() if "/mlp/" not in k}
    for i, _ in enumerate(c["layer_pattern"]):
        s = f"blocks/slot_{i}/moe/"
        specs[s + "router"] = ((g, d, e), d ** -0.5)
        specs[s + "experts/w_gate"] = ((g, e, d, f), d ** -0.5)
        specs[s + "experts/w_up"] = ((g, e, d, f), d ** -0.5)
        specs[s + "experts/w_down"] = ((g, e, f, d), f ** -0.5)
    return specs


def _attention(c, kind, a, y, low):
    n = y.shape[0]
    h, hkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                  T.head_dim(c))
    q, k, v = (ein("nm,mk->nk", y, a[w], low).reshape(n, heads, dh)
               .transpose(1, 0, 2)
               for w, heads in (("wq", h), ("wk", hkv), ("wv", hkv)))
    if c["qk_norm"]:
        q = rms(q, a["q_norm_scale"], c["rms_norm_eps"])
        k = rms(k, a["k_norm_scale"], c["rms_norm_eps"])
    if kind == "swa" or c["rope_on_moba"]:
        pos = jnp.arange(n)
        q = rope(q, pos, float(c["rope_theta"]))
        k = rope(k, pos, float(c["rope_theta"]))
    o = T._attention(c, kind, q, k, v, low).transpose(1, 0, 2)
    return ein("nk,km->nm", o.reshape(n, h * dh), a["wo"], low)


def _experts(c, p, y, low):
    """y (T, d) of every row of the batch -> (out (T, d), aux)."""
    e, k = c["num_experts"], c["num_experts_per_tok"]
    probs = jax.nn.softmax(ein("tm,me->te", y, p["router"], low), -1)
    top_p, top_e = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    w = p["experts"]
    hid = (jax.nn.silu(ein("tm,emf->etf", y, w["w_gate"], low))
           * ein("tm,emf->etf", y, w["w_up"], low))
    every = ein("etf,efm->etm", hid, w["w_down"], low)          # (E, T, d)
    chosen = every[top_e, jnp.arange(y.shape[0])[:, None]]       # (T, k, d)
    out = jnp.einsum("tk,tkm->tm", top_p, chosen,
                     precision=jax.lax.Precision.HIGHEST)
    share = jnp.mean(jax.nn.one_hot(top_e, e).sum(1), 0) / k
    aux = c["router_aux_loss_coef"] * e * jnp.sum(share * probs.mean(0))
    return out, aux


def _forward(c, params, tokens, low):
    """tokens (B, N) -> final-normed hidden states (B, N, d), aux."""
    eps, pattern = c["rms_norm_eps"], c["layer_pattern"]
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
        for i, kind in enumerate(pattern):
            x, a = layer(kind, gp[f"slot_{i}"], x)
            aux = aux + a
        return (x, aux), None

    (x, aux), _ = jax.lax.scan(group, (x, jnp.zeros(())), params["blocks"])
    return rms(x, params["final_norm"], eps), aux


def hidden(c: dict, params: dict, tokens: jax.Array, low: bool):
    return _forward(c, params, tokens[None], low)[0][0]


def batch_loss(c: dict, params: dict, tokens: jax.Array, low: bool):
    """Mean next-token cross entropy over the batch plus every layer's
    auxiliary loss."""
    x, aux = _forward(c, params, tokens[:, :-1], low)
    w = head(c, params)
    nll = sum(nll_sum(x[i], w, tokens[i, 1:], low)
              for i in range(tokens.shape[0]))
    return nll / (x.shape[0] * x.shape[1]) + aux


def active_matmul_params(c: dict) -> int:
    d, h, hkv = (c["hidden_size"], c["num_attention_heads"],
                 c["num_key_value_heads"])
    dh = T.head_dim(c)
    layer = (d * (h + 2 * hkv) * dh + h * dh * d + d * c["num_experts"]
             + c["num_experts_per_tok"] * 3 * d * c["moe_intermediate_size"])
    return c["num_hidden_layers"] * layer
