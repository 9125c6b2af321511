"""jit'd public wrappers around the Pallas kernels.

`flash_moba` is the full FlashMoBA pipeline with a `jax.custom_vjp`:

  fwd:  key-block centroids (kernel) → Flash TopK (kernel) → varlen layout
        (XLA sort/cumsum — deterministic Alg. 4) → Q gather (XLA take) →
        gather-and-densify attention (kernel) → per-query lse merge
  bwd:  delta = rowsum(dO∘O) → gather to sorted layout → backward kernel
        (recompute) → segment-sum dQ, group-reduce dK/dV

Ragged query lengths (Nq not a multiple of the q tile) are padded to the
tile inside the pipeline: padded rows route to the sentinel block, so
their layout slots carry `q_pos = -1` — which the kernels already mask —
and the pad is sliced off again before returning.

``grid`` selects the MXU-tiled ``grouped`` kernel grids (default: grouped
GQA topk + kb-tiled fwd/bwd) or the legacy ``flat`` grids, kept
selectable for bisection; ``kb_tile`` sets the K/V streaming granularity
of the tiled grids (0 = auto).

Routing is non-differentiable (hard top-k; matches MoBA training
semantics) — gradients flow through attention only, which is what lets
key convolution learn clustering (paper App. B.2).

Each stage runs under a ``jax.named_scope`` that the compiled ops keep
in their ``op_name`` metadata, by which a profile can attribute device
time: ``moba_centroids``, ``moba_topk``, ``moba_layout``, ``moba_fwd``
and ``moba_merge`` forward; ``moba_bwd_prep``, ``moba_bwd`` and
``moba_bwd_reduce`` backward.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import MoBAConfig
from repro.core import routing
from repro.kernels.runtime import resolve_interpret
from repro.kernels.tiling import round_up
from repro.kernels import ref as kref
from repro.kernels.centroids import block_centroids_kernel
from repro.kernels.flash_topk import flash_topk
from repro.kernels.moba_bwd import moba_bwd
from repro.kernels.moba_fwd import moba_fwd

NEG_INF = routing.NEG_INF


class _Meta(NamedTuple):
    block_size: int
    top_k: int
    causal: bool
    q_tile: int
    scale: float
    interpret: bool
    kb_tile: int = 0
    grid: str = "grouped"


def _build_layouts(sel: jax.Array, nq: int, nb: int, tile: int):
    """sel (BH, Nq, k) -> batched VarlenLayout."""
    return jax.vmap(
        lambda s: routing.build_varlen_layout(s, nq, nb, tile))(sel)


def _flatten_kv_blocks(k: jax.Array, block_size: int):
    b, hkv, n, d = k.shape
    nb = -(-n // block_size)
    kp = routing.pad_to_blocks(k, block_size, axis=-2)
    return kp.reshape(b * hkv, nb, block_size, d), nb


def _fwd_pipeline(q, k, v, meta: _Meta):
    b, h, nq, d = q.shape
    _, hkv, n, _ = k.shape
    g = h // hkv
    bs, tk, tile = meta.block_size, meta.top_k, meta.q_tile
    tile = min(tile, nq)
    nq_p = round_up(nq, tile)

    with jax.named_scope("moba_centroids"):
        cents = block_centroids_kernel(
            k.reshape(b * hkv, n, d), bs, interpret=meta.interpret)

    with jax.named_scope("moba_topk"):
        qf = q.reshape(b * h, nq, d)
        if nq_p != nq:
            qf = jnp.pad(qf, ((0, 0), (0, nq_p - nq), (0, 0)))
        q_pos_offset = n - nq
        sel = flash_topk(qf, cents, tk, bs, group=g, num_q_heads=h,
                         causal=meta.causal, q_pos_offset=q_pos_offset,
                         q_tile=tile, grid=meta.grid,
                         interpret=meta.interpret)  # (BH, Nq_p, k)

    with jax.named_scope("moba_layout"):
        k_blocks, nb = _flatten_kv_blocks(k, bs)
        v_blocks, _ = _flatten_kv_blocks(v, bs)
        if nq_p != nq:
            # pad queries route to the sentinel block → q_pos = -1 slots
            # the kernels mask out
            row = jnp.arange(nq_p)[None, :, None]
            sel = jnp.where(row < nq, sel, nb)
        lay = _build_layouts(sel, nq_p, nb, tile)
        qi = jnp.maximum(lay.q_index, 0)                      # (BH, L)
        q_sorted = jnp.take_along_axis(qf, qi[..., None], axis=1)
        q_pos = jnp.where(lay.q_index >= 0, qi + q_pos_offset, -1)

    with jax.named_scope("moba_fwd"):
        o_l, m_l, l_l = moba_fwd(
            lay.tile_block, q_sorted, q_pos.astype(jnp.int32),
            k_blocks, v_blocks, scale=meta.scale, block_size=bs,
            n_tokens=n, num_q_heads=h, group=g, causal=meta.causal,
            q_tile=tile, kb_tile=meta.kb_tile, grid=meta.grid,
            interpret=meta.interpret)

    with jax.named_scope("moba_merge"):
        slots = lay.pair_slot.reshape(b * h, nq_p * tk)       # (BH, Nq_p*k)
        o_parts = jnp.take_along_axis(o_l, slots[..., None], axis=1)
        m_parts = jnp.take_along_axis(m_l, slots, axis=1)
        l_parts = jnp.take_along_axis(l_l, slots, axis=1)
        out, lse = kref.merge_partials(
            o_parts.reshape(b * h, nq_p, tk, d),
            m_parts.reshape(b * h, nq_p, tk),
            l_parts.reshape(b * h, nq_p, tk))
        return out[:, :nq], lse[:, :nq], lay, q_sorted, q_pos


def _flash_moba_impl(q, k, v, meta: _Meta):
    out, _, _, _, _ = _fwd_pipeline(q, k, v, meta)
    b, h, nq, d = q.shape
    return out.reshape(b, h, nq, d).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_moba(q, k, v, meta: _Meta):
    return _flash_moba_impl(q, k, v, meta)


def _flash_moba_fwd(q, k, v, meta: _Meta):
    out, lse, lay, q_sorted, q_pos = _fwd_pipeline(q, k, v, meta)
    b, h, nq, d = q.shape
    res = (q, k, v, out, lse, lay.tile_block, lay.pair_slot, q_sorted,
           q_pos)
    return out.reshape(b, h, nq, d).astype(q.dtype), res


def _flash_moba_bwd(meta: _Meta, res, g_out):
    q, k, v, out, lse, tile_block, pair_slot, q_sorted, q_pos = res
    b, h, nq, d = q.shape
    _, hkv, n, _ = k.shape
    g = h // hkv
    bs, tk, tile = meta.block_size, meta.top_k, min(meta.q_tile, nq)
    nq_p = pair_slot.shape[1]

    with jax.named_scope("moba_bwd_prep"):
        k_blocks, nb = _flatten_kv_blocks(k, bs)
        v_blocks, _ = _flatten_kv_blocks(v, bs)
        do = g_out.reshape(b * h, nq, d).astype(jnp.float32)
        delta = jnp.sum(do * out, axis=-1)                    # (BH, Nq)
        # scatter per-query tensors to the sorted layout (q_pos = -1 pad
        # and sentinel slots gather row 0 but are masked in the kernel)
        qi = jnp.maximum(q_pos - (n - nq), 0)                 # query index
        do_sorted = jnp.take_along_axis(do, qi[..., None], axis=1)
        lse_sorted = jnp.take_along_axis(lse, qi, axis=1)
        delta_sorted = jnp.take_along_axis(delta, qi, axis=1)

    with jax.named_scope("moba_bwd"):
        dq_l, dk_bh, dv_bh = moba_bwd(
            tile_block, q_sorted, q_pos, do_sorted, lse_sorted,
            delta_sorted, k_blocks, v_blocks, scale=meta.scale,
            block_size=bs, n_tokens=n, num_q_heads=h, group=g,
            causal=meta.causal, q_tile=tile, kb_tile=meta.kb_tile,
            grid=meta.grid, interpret=meta.interpret)

    with jax.named_scope("moba_bwd_reduce"):
        # dQ: gather per-pair contributions and sum over the k slots.
        slots = pair_slot.reshape(b * h, nq_p * tk)
        dq_pairs = jnp.take_along_axis(dq_l, slots[..., None], axis=1)
        dq = dq_pairs.reshape(b * h, nq_p, tk, d).sum(axis=2)[:, :nq]

        # dK/dV: zero unvisited blocks, reduce over the GQA group,
        # un-block.
        visited = (jax.nn.one_hot(tile_block, nb + 1, dtype=jnp.float32)
                   .sum(axis=1)[..., :nb] > 0)                # (BH, nb)
        dk_bh = dk_bh * visited[..., None, None]
        dv_bh = dv_bh * visited[..., None, None]
        dk = dk_bh.reshape(b, hkv, g, nb, bs, d).sum(axis=2)
        dv = dv_bh.reshape(b, hkv, g, nb, bs, d).sum(axis=2)
        dk = dk.reshape(b, hkv, nb * bs, d)[:, :, :n]
        dv = dv.reshape(b, hkv, nb * bs, d)[:, :, :n]
        return (dq.reshape(b, h, nq, d).astype(q.dtype),
                dk.astype(k.dtype), dv.astype(v.dtype))


_flash_moba.defvjp(_flash_moba_fwd, _flash_moba_bwd)


def flash_moba(q: jax.Array, k: jax.Array, v: jax.Array, cfg: MoBAConfig,
               q_positions: Optional[jax.Array] = None,
               scale: Optional[float] = None, q_tile: int = 128,
               kb_tile: int = 0, grid: str = "grouped",
               interpret: Optional[bool] = None) -> jax.Array:
    """FlashMoBA attention (Pallas kernel path).

    q (B,H,Nq,d); k,v (B,Hkv,N,d).  ``q_positions`` must be the contiguous
    suffix of the kv sequence (training/prefill); decode uses
    `core.moba.moba_decode_attention`.

    ``grid``: 'grouped' (default — grouped-GQA topk grid + kb-tiled
    fwd/bwd) or 'flat' (legacy seed-era grids, kept for bisection).
    ``kb_tile``: K/V streaming granularity of the tiled grids, 0 = auto
    (``min(block_size, 128)``).  Nq may be ragged (padded internally).
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    meta = _Meta(cfg.block_size, cfg.top_k, cfg.causal,
                 q_tile, float(scale), resolve_interpret(interpret),
                 kb_tile, grid)
    return _flash_moba(q, k, v, meta)
