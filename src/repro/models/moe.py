"""Mixture-of-Experts MLP: a drop-free layer over the experts it holds.

The router keeps its full width (``num_experts``): every token is routed
over all experts (softmax in float32, the ``top_k`` best, gates
renormalised to sum to one over the whole top-k).  The layer
holds experts ``0 .. held-1`` (``MoEConfig.experts_held``, all by
default), as one chip of an expert-parallel deployment holds its share,
and computes only their part of the output: the sum, over the held
experts among a token's top-k, of gate × SwiGLU_e(x).  What the absent
experts would add is another chip's; on one chip the layer runs without
that exchange.

No token is dropped at any count or imbalance: the (token, held expert)
pairs are laid out expert by expert (a counting sort) in a buffer sized
for every pair to land on a held expert, each expert's rows on whole
row tiles, and the expert FFN runs as grouped matmuls over the tiles in
use (``kernels/expert_gmm.py``).  Load-balance auxiliary loss follows
Switch Transformer, over all ``num_experts`` router outputs.

Stages, each under a ``jax.named_scope`` (inside the block's ``moe``):
``route``, ``dispatch``, ``expert_gmm`` (the grouped matmuls, Pallas
calls named ``expert_gmm``) and ``combine``.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.expert_gmm import gmm
from repro.kernels.tiling import round_up

TILE = 128          # rows of a grouped-matmul tile at training sizes


def init_moe(key, cfg: ModelConfig) -> dict:
    m = cfg.moe
    d, f, e = cfg.d_model, m.expert_d_ff, m.num_experts
    ks = jax.random.split(key, 7)
    scale = d ** -0.5
    held = m.held
    p = {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * scale,
        "experts": {
            "w_gate": jax.random.normal(ks[1], (held, d, f), jnp.float32)
            * scale,
            "w_up": jax.random.normal(ks[2], (held, d, f), jnp.float32)
            * scale,
            "w_down": jax.random.normal(ks[3], (held, f, d), jnp.float32)
            * f ** -0.5,
        },
    }
    if m.num_shared_experts:
        fs = f * m.num_shared_experts
        p["shared"] = {
            "w_gate": jax.random.normal(ks[4], (d, fs), jnp.float32) * scale,
            "w_up": jax.random.normal(ks[5], (d, fs), jnp.float32) * scale,
            "w_down": jax.random.normal(ks[6], (fs, d), jnp.float32)
            * fs ** -0.5,
        }
    return p


def route(logits: jax.Array, top_k: int):
    """Router logits (T, E) -> (probs (T, E), gates (T, k), experts
    (T, k)), in float32."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, experts = jax.lax.top_k(probs, top_k)
    return probs, gates / jnp.sum(gates, -1, keepdims=True), experts


class Layout(NamedTuple):
    """Where each (token, choice) pair sits in the grouped buffer."""
    row: jax.Array          # (T, k) buffer row; ``rows`` for an absent expert
    token: jax.Array        # (rows,) token of each row; T for padding
    tile_group: jax.Array   # (rows/tm,) held expert of each tile
    num_tiles: jax.Array    # (1,) tiles in use
    load: jax.Array         # (held,) pairs routed to each held expert
    tm: int


def layout(experts: jax.Array, held: int) -> Layout:
    """Lay the pairs of ``experts`` (T, k) that chose a held expert out
    expert by expert: expert e's rows start on a tile boundary, and each
    held expert has at least one tile.  Sized for the worst case, every
    pair on a held expert, so that none is ever dropped."""
    t, k = experts.shape
    pairs = t * min(k, held)
    tm = min(TILE, round_up(pairs, 16))
    tiles_max = -(-pairs // tm) + held
    rows = tiles_max * tm
    flat = experts.reshape(-1)
    group = jnp.where(flat < held, flat, held)       # held: not held here
    onehot = (group[:, None] == jnp.arange(held + 1)).astype(jnp.int32)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, 0) - onehot,
                               group[:, None], 1)[:, 0]
    load = onehot[:, :held].sum(0)
    tiles = jnp.maximum(1, -(-load // tm))
    start = (jnp.cumsum(tiles) - tiles) * tm
    row = jnp.where(flat < held, start[jnp.minimum(group, held - 1)] + rank,
                    rows)
    tile_group = jnp.repeat(jnp.arange(held, dtype=jnp.int32), tiles,
                            total_repeat_length=tiles_max)
    # the token of each row, by gathers (no scatter): row r is the j-th
    # pair of its tile's expert e in the pairs' order sorted by expert
    order = jnp.argsort(group, stable=True)
    e = jnp.repeat(tile_group, tm)
    j = jnp.arange(rows) - start[e]
    first = jnp.cumsum(load) - load
    pair = order[jnp.minimum(first[e] + j, t * k - 1)]
    token = jnp.where(j < load[e], pair // k, t).astype(jnp.int32)
    return Layout(row.reshape(t, k), token, tile_group,
                  jnp.sum(tiles, keepdims=True).astype(jnp.int32),
                  load.astype(jnp.int32), tm)


def apply_moe(p: dict, x: jax.Array, cfg: ModelConfig
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss, load), ``load`` (held,) the pairs
    routed to each held expert."""
    m = cfg.moe
    b, s, d = x.shape
    e, k, held = m.num_experts, m.top_k, m.held
    tokens = b * s
    dt = x.dtype
    xf = x.reshape(tokens, d)

    with jax.named_scope("route"):
        logits = jnp.dot(xf.astype(jnp.float32),
                         p["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        probs, gates, experts = route(logits, k)
        # Switch aux loss: E * sum_e (fraction_pairs_e * mean_prob_e)
        frac = jax.nn.one_hot(experts, e, dtype=jnp.float32).sum((0, 1)) \
            / (tokens * k)
        aux = e * jnp.sum(frac * probs.mean(0)) * m.router_aux_coef

    # padding rows and pairs on other chips' experts index past the end:
    # their gathers read zeros, and their gradients' scatters drop them
    with jax.named_scope("dispatch"):
        lay = layout(experts, held)
        xs = xf.at[lay.token].get(mode="fill", fill_value=0)

    with jax.named_scope("expert_gmm"):
        w = p["experts"]

        def grouped(lhs, wt):
            return gmm(lhs, wt.astype(dt), lay.tile_group, lay.num_tiles,
                       lay.tm)

        h = jax.nn.silu(grouped(xs, w["w_gate"])) * grouped(xs, w["w_up"])
        ys = grouped(h, w["w_down"])

    with jax.named_scope("combine"):
        picked = ys.at[lay.row].get(mode="fill", fill_value=0)  # (T, k, d)
        out = jnp.einsum("tk,tkd->td", gates.astype(dt), picked)

    if m.num_shared_experts and "shared" in p:
        sh = p["shared"]
        hs = (jax.nn.silu(xf @ sh["w_gate"].astype(dt))
              * (xf @ sh["w_up"].astype(dt)))
        out = out + hs @ sh["w_down"].astype(dt)
    return out.reshape(b, s, d), aux, lay.load
