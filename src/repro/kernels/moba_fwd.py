"""Pallas kernel: FlashMoBA forward — gather-and-densify (paper Alg. 1).

TPU adaptation (see DESIGN.md §2): queries routed to each key block are
pre-gathered into the key-block-major sorted layout (`Q_sorted`) by one XLA
take; the kernel then runs dense MXU matmuls per tile, with the key block
selected by a **scalar-prefetched** per-tile block id driving the K/V
BlockSpec index_map.  Each tile emits un-normalized partial outputs +
softmax stats (o, m, l); the per-query lse-merge over its k partials
happens in the wrapper (`ops.flash_moba`).

Two grids:

* ``grouped`` (default, kb-tiled): grid (BH, T, nkb) with a third
  dimension over ``kb_tile``-wide chunks of the key block.  The K/V
  BlockSpec streams (kb_tile, d) slices — Pallas double-buffers the
  DMAs across consecutive kb steps — and the online-softmax merge is
  carried *inside* the kernel across kb-tiles in (Tq, d)/(Tq, 1) VMEM
  scratch, so K/V DMA granularity is decoupled from ``block_size`` and
  large-block configs no longer force block-sized VMEM residency.
* ``flat`` (legacy, kept selectable for bisection): grid (BH, T) with
  whole-(B, d) K/V blocks per step.

The query's own block is part of the routed pair list (selection forces
it), so a single universal mask `key_pos <= q_pos` gives exactly MoBA
semantics: no-op for past blocks, causal inside the own block.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret
from repro.kernels.tiling import check_blocks, resolve_kb_tile

NEG_INF = -1e30
# kernel operands in pallas_call order, named in block-contract errors
_OPERANDS = ("q", "q_pos", "k", "v", "o", "m", "l")


def _fwd_kernel(tb_ref, qs_ref, qpos_ref, k_ref, v_ref,
                o_ref, m_ref, l_ref, *,
                scale: float, block_size: int, n_blocks: int,
                n_tokens: int, causal: bool):
    """Legacy flat grid: one whole key block per step."""
    bh = pl.program_id(0)
    t = pl.program_id(1)
    blk = tb_ref[bh, t]

    q = qs_ref[0].astype(jnp.float32)            # (Tq, d)
    kb = k_ref[0, 0].astype(jnp.float32)         # (B, d)
    vb = v_ref[0, 0].astype(jnp.float32)
    qpos = qpos_ref[0]                           # (Tq, 1) int32
    tq = q.shape[0]

    s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Tq, B)
    s = s * scale
    kpos = (blk * block_size
            + jax.lax.broadcasted_iota(jnp.int32, (tq, block_size), 1))
    mask = (qpos >= 0) & (blk < n_blocks) & (kpos < n_tokens)
    if causal:
        mask &= kpos <= qpos
    s = jnp.where(mask, s, NEG_INF)

    m = jnp.max(s, axis=1, keepdims=True)                     # (Tq, 1)
    m_safe = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(s - m_safe) * mask.astype(jnp.float32)
    l = jnp.sum(p, axis=1, keepdims=True)
    o = jax.lax.dot_general(p, vb, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    any_valid = jnp.max(mask.astype(jnp.float32), axis=1, keepdims=True)
    o_ref[0] = o
    m_ref[0] = jnp.where(any_valid > 0, m, NEG_INF)
    l_ref[0] = l


def _fwd_kernel_tiled(tb_ref, qs_ref, qpos_ref, k_ref, v_ref,
                      o_ref, m_ref, l_ref, o_acc, m_acc, l_acc, *,
                      scale: float, block_size: int, kb_tile: int,
                      n_kb: int, n_blocks: int, n_tokens: int,
                      causal: bool):
    """kb-tiled grid (BH, T, nkb): streams (kb_tile, d) K/V slices and
    carries the online-softmax merge across kb steps in VMEM scratch.
    The (o, m, l) output windows depend only on (bh, t), so they stay
    resident across a tile's kb run and are written once at the last
    kb step."""
    bh = pl.program_id(0)
    t = pl.program_id(1)
    kb = pl.program_id(2)
    blk = tb_ref[bh, t]

    @pl.when(kb == 0)
    def _init():
        o_acc[...] = jnp.zeros_like(o_acc)
        m_acc[...] = jnp.full_like(m_acc, NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

    q = qs_ref[0].astype(jnp.float32)            # (Tq, d)
    kbt = k_ref[0, 0].astype(jnp.float32)        # (kb_tile, d)
    vbt = v_ref[0, 0].astype(jnp.float32)
    qpos = qpos_ref[0]                           # (Tq, 1) int32
    tq = q.shape[0]

    s = jax.lax.dot_general(q, kbt, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = (blk * block_size + kb * kb_tile
            + jax.lax.broadcasted_iota(jnp.int32, (tq, kb_tile), 1))
    mask = (qpos >= 0) & (blk < n_blocks) & (kpos < n_tokens)
    if causal:
        mask &= kpos <= qpos
    s = jnp.where(mask, s, NEG_INF)

    # online-softmax merge into the running (o, m, l).  With every lane
    # masked, m stays exactly NEG_INF and alpha = exp(NEG_INF - m_safe)
    # underflows to 0, so empty chunks contribute nothing.
    m_prev = m_acc[...]                                       # (Tq, 1)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    m_safe = jnp.maximum(m_cur, NEG_INF / 2)
    alpha = jnp.exp(m_prev - m_safe)
    p = jnp.exp(s - m_safe) * mask.astype(jnp.float32)        # (Tq, kbt)
    m_acc[...] = m_cur
    l_acc[...] = l_acc[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    o_acc[...] = (o_acc[...] * alpha
                  + jax.lax.dot_general(p, vbt, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32))

    @pl.when(kb == n_kb - 1)
    def _emit():
        l = l_acc[...]
        o_ref[0] = o_acc[...]
        m_ref[0] = jnp.where(l > 0, m_acc[...], NEG_INF)
        l_ref[0] = l


def moba_fwd(tile_block: jax.Array, q_sorted: jax.Array, q_pos: jax.Array,
             k_blocks: jax.Array, v_blocks: jax.Array, *,
             scale: float, block_size: int, n_tokens: int,
             num_q_heads: int, group: int, causal: bool = True,
             q_tile: int = 128, kb_tile: int = 0, grid: str = "grouped",
             interpret: bool | None = None
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Run the forward kernel over flattened (batch·head) layouts.

    tile_block (BH, T) int32; q_sorted (BH, L, d); q_pos (BH, L) int32;
    k_blocks/v_blocks (BKV, nb, B, d) with BKV = BH / group per batch —
    i.e. BH = batch*H, BKV = batch*Hkv, H = Hkv*group.

    ``grid`` selects the kb-tiled ``grouped`` grid (default) or the
    legacy ``flat`` grid; ``kb_tile`` (grouped only, 0 = auto
    ``min(block_size, 128)``) sets the K/V streaming granularity.

    Returns (o_partial (BH, L, d) f32, m (BH, L) f32, l (BH, L) f32).
    """
    if grid not in ("grouped", "flat"):
        raise ValueError(f"unknown moba_fwd grid {grid!r}: "
                         f"expected 'grouped' or 'flat'")
    interpret = resolve_interpret(interpret)
    bh, L, d = q_sorted.shape
    bkv, nb, bs, _ = k_blocks.shape
    n_tiles = L // q_tile
    assert L % q_tile == 0 and tile_block.shape == (bh, n_tiles)
    h = num_q_heads

    # per-query vectors travel as (BH, L, 1) columns: a (1, q_tile, 1)
    # block is whole in its last two dims, and the column broadcasts
    # against the (Tq, kb) score tile without a relayout
    q_pos = q_pos[..., None]
    out_shape = [
        jax.ShapeDtypeStruct((bh, L, d), jnp.float32),
        jax.ShapeDtypeStruct((bh, L, 1), jnp.float32),
        jax.ShapeDtypeStruct((bh, L, 1), jnp.float32),
    ]

    if grid == "flat":
        def kv_index(bhi, t, tb_ref):
            kv = (bhi // h) * (h // group) + (bhi % h) // group
            blk = jnp.minimum(tb_ref[bhi, t], nb - 1)
            return (kv, blk, 0, 0)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, n_tiles),
            in_specs=[
                pl.BlockSpec((1, q_tile, d), lambda bhi, t, tb: (bhi, t, 0)),
                pl.BlockSpec((1, q_tile, 1), lambda bhi, t, tb: (bhi, t, 0)),
                pl.BlockSpec((1, 1, bs, d), kv_index),
                pl.BlockSpec((1, 1, bs, d), kv_index),
            ],
            out_specs=[
                pl.BlockSpec((1, q_tile, d), lambda bhi, t, tb: (bhi, t, 0)),
                pl.BlockSpec((1, q_tile, 1), lambda bhi, t, tb: (bhi, t, 0)),
                pl.BlockSpec((1, q_tile, 1), lambda bhi, t, tb: (bhi, t, 0)),
            ],
        )
        if not interpret:
            check_blocks("moba_fwd", _OPERANDS,
                         [*grid_spec.in_specs, *grid_spec.out_specs],
                         [q_sorted, q_pos, k_blocks, v_blocks, *out_shape])
        kernel = functools.partial(
            _fwd_kernel, scale=scale, block_size=block_size, n_blocks=nb,
            n_tokens=n_tokens, causal=causal)
        o, m, l = pl.pallas_call(
            kernel, grid_spec=grid_spec, out_shape=out_shape,
            interpret=interpret, name="moba_fwd",
        )(tile_block, q_sorted, q_pos, k_blocks, v_blocks)
        return o, m[..., 0], l[..., 0]

    kb_tile = resolve_kb_tile(kb_tile, bs)
    n_kb = bs // kb_tile

    def kv_index(bhi, t, kb, tb_ref):
        kv = (bhi // h) * (h // group) + (bhi % h) // group
        blk = jnp.minimum(tb_ref[bhi, t], nb - 1)
        return (kv, blk * n_kb + kb, 0, 0)

    # expose the kb_tile slices as their own dim so the BlockSpec block
    # is exactly one DMA'd slice — Pallas overlaps the next slice's
    # fetch with the current step's compute (double buffering)
    k_t = k_blocks.reshape(bkv, nb * n_kb, kb_tile, d)
    v_t = v_blocks.reshape(bkv, nb * n_kb, kb_tile, d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, n_tiles, n_kb),
        in_specs=[
            pl.BlockSpec((1, q_tile, d), lambda bhi, t, kb, tb: (bhi, t, 0)),
            pl.BlockSpec((1, q_tile, 1), lambda bhi, t, kb, tb: (bhi, t, 0)),
            pl.BlockSpec((1, 1, kb_tile, d), kv_index),
            pl.BlockSpec((1, 1, kb_tile, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, q_tile, d), lambda bhi, t, kb, tb: (bhi, t, 0)),
            pl.BlockSpec((1, q_tile, 1), lambda bhi, t, kb, tb: (bhi, t, 0)),
            pl.BlockSpec((1, q_tile, 1), lambda bhi, t, kb, tb: (bhi, t, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((q_tile, d), jnp.float32),
            pltpu.VMEM((q_tile, 1), jnp.float32),
            pltpu.VMEM((q_tile, 1), jnp.float32),
        ],
    )
    if not interpret:
        check_blocks("moba_fwd", _OPERANDS,
                     [*grid_spec.in_specs, *grid_spec.out_specs],
                     [q_sorted, q_pos, k_t, v_t, *out_shape])
    kernel = functools.partial(
        _fwd_kernel_tiled, scale=scale, block_size=block_size,
        kb_tile=kb_tile, n_kb=n_kb, n_blocks=nb, n_tokens=n_tokens,
        causal=causal)
    o, m, l = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        interpret=interpret, name="moba_fwd",
    )(tile_block, q_sorted, q_pos, k_t, v_t)
    return o, m[..., 0], l[..., 0]
