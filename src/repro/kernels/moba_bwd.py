"""Pallas kernel: FlashMoBA backward (paper Alg. 5, TPU adaptation).

Key-block-parallel with recomputation: each tile re-derives its attention
probabilities from (Q_sorted, K_j, lse) — the attention matrix is never
stored.  dK_j/dV_j accumulate in the *output VMEM buffer* across the
consecutive tiles of block j (the sorted layout guarantees a block's tiles
are contiguous, which is the TPU-native replacement for the paper's
per-thread-block ownership), and partial dQ is written per-slot and
segment-summed by the wrapper — the deterministic replacement for CUDA
atomicAdd into dQ_accum.

Two grids:

* ``grouped`` (default, kb-tiled): grid (BH, T, nkb) streams
  (kb_tile, d) K/V slices (double-buffered by the Pallas pipeline).
  Per-tile dK/dV accumulate slice-wise in (B, d) VMEM scratch; at the
  last kb step the tile's contribution merges into the resident
  full-block output window — the dk/dv window index depends only on
  the tile's block id, never on kb, so windows are written exactly once
  per residency and never revisited.
* ``flat`` (legacy, kept selectable for bisection): grid (BH, T) with
  whole-(B, d) K/V blocks per step.
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
_OPERANDS = ("q", "q_pos", "do", "lse", "delta", "k", "v",
             "dq", "dk", "dv")


def _bwd_kernel(tb_ref, qs_ref, qpos_ref, do_ref, lse_ref, delta_ref,
                k_ref, v_ref, dq_ref, dk_ref, dv_ref, *,
                scale: float, block_size: int, n_blocks: int,
                n_tokens: int, causal: bool):
    """Legacy flat grid: one whole key block per step."""
    bh = pl.program_id(0)
    t = pl.program_id(1)
    blk = tb_ref[bh, t]
    prev_blk = tb_ref[bh, jnp.maximum(t - 1, 0)]
    mapped = jnp.minimum(blk, n_blocks - 1)
    prev_mapped = jnp.minimum(prev_blk, n_blocks - 1)
    is_first = (t == 0) | (mapped != prev_mapped)

    q = qs_ref[0].astype(jnp.float32)            # (Tq, d)
    do = do_ref[0].astype(jnp.float32)           # (Tq, d)
    kb = k_ref[0, 0].astype(jnp.float32)         # (B, d)
    vb = v_ref[0, 0].astype(jnp.float32)
    qpos = qpos_ref[0]                           # (Tq, 1) int32
    lse = lse_ref[0]                             # (Tq, 1)
    delta = delta_ref[0]                         # (Tq, 1)
    tq = q.shape[0]

    s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = (blk * block_size
            + jax.lax.broadcasted_iota(jnp.int32, (tq, block_size), 1))
    mask = (qpos >= 0) & (blk < n_blocks) & (kpos < n_tokens)
    if causal:
        mask &= kpos <= qpos
    # true post-merge probabilities: exp(s - lse_q)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)              # (Tq, B)

    dv_c = jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)  # (B, d)
    dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)    # (Tq, B)
    ds = p * (dp - delta) * scale
    dq_c = jax.lax.dot_general(ds, kb, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)  # (Tq, d)
    dk_c = jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)  # (B, d)

    dq_ref[0] = dq_c

    @pl.when(is_first)
    def _init():
        dk_ref[0, 0] = dk_c
        dv_ref[0, 0] = dv_c

    @pl.when(jnp.logical_not(is_first))
    def _accum():
        dk_ref[0, 0] += dk_c
        dv_ref[0, 0] += dv_c


def _bwd_kernel_tiled(tb_ref, qs_ref, qpos_ref, do_ref, lse_ref, delta_ref,
                      k_ref, v_ref, dq_ref, dk_ref, dv_ref,
                      dk_acc, dv_acc, *,
                      scale: float, block_size: int, kb_tile: int,
                      n_kb: int, n_blocks: int, n_tokens: int,
                      causal: bool):
    """kb-tiled grid (BH, T, nkb): recompute + grads per (kb_tile, d)
    K/V slice.  dK/dV slices land in (B, d) VMEM scratch; the tile's
    full-block contribution merges into the resident dk/dv output
    window at the last kb step."""
    bh = pl.program_id(0)
    t = pl.program_id(1)
    kb = pl.program_id(2)
    blk = tb_ref[bh, t]
    prev_blk = tb_ref[bh, jnp.maximum(t - 1, 0)]
    mapped = jnp.minimum(blk, n_blocks - 1)
    prev_mapped = jnp.minimum(prev_blk, n_blocks - 1)
    is_first = (t == 0) | (mapped != prev_mapped)

    q = qs_ref[0].astype(jnp.float32)            # (Tq, d)
    do = do_ref[0].astype(jnp.float32)           # (Tq, d)
    kbt = k_ref[0, 0].astype(jnp.float32)        # (kb_tile, d)
    vbt = v_ref[0, 0].astype(jnp.float32)
    qpos = qpos_ref[0]                           # (Tq, 1) int32
    lse = lse_ref[0]                             # (Tq, 1)
    delta = delta_ref[0]                         # (Tq, 1)
    tq = q.shape[0]

    s = jax.lax.dot_general(q, kbt, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = (blk * block_size + kb * kb_tile
            + jax.lax.broadcasted_iota(jnp.int32, (tq, kb_tile), 1))
    mask = (qpos >= 0) & (blk < n_blocks) & (kpos < n_tokens)
    if causal:
        mask &= kpos <= qpos
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)              # (Tq, kbt)

    dv_c = jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, vbt, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    dq_c = jax.lax.dot_general(ds, kbt, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    dk_c = jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)

    @pl.when(kb == 0)
    def _dq_init():
        dq_ref[0] = dq_c

    @pl.when(kb > 0)
    def _dq_accum():
        dq_ref[0] += dq_c

    row = kb * kb_tile
    dk_acc[pl.ds(row, kb_tile), :] = dk_c
    dv_acc[pl.ds(row, kb_tile), :] = dv_c

    @pl.when(kb == n_kb - 1)
    def _flush():
        @pl.when(is_first)
        def _init():
            dk_ref[0, 0] = dk_acc[...]
            dv_ref[0, 0] = dv_acc[...]

        @pl.when(jnp.logical_not(is_first))
        def _accum():
            dk_ref[0, 0] += dk_acc[...]
            dv_ref[0, 0] += dv_acc[...]


def moba_bwd(tile_block: jax.Array, q_sorted: jax.Array, q_pos: jax.Array,
             do_sorted: jax.Array, lse_sorted: jax.Array,
             delta_sorted: jax.Array, k_blocks: jax.Array,
             v_blocks: jax.Array, *, scale: float, block_size: int,
             n_tokens: int, num_q_heads: int, group: int,
             causal: bool = True, q_tile: int = 128, kb_tile: int = 0,
             grid: str = "grouped", interpret: bool | None = None
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Backward over flattened (batch·head) layouts.

    ``grid`` selects the kb-tiled ``grouped`` grid (default) or the
    legacy ``flat`` grid; ``kb_tile`` (grouped only, 0 = auto
    ``min(block_size, 128)``) sets the K/V streaming granularity.

    Returns (dq_sorted (BH,L,d), dk (BH,nb,B,d), dv (BH,nb,B,d)) — all f32;
    dk/dv are per *query head* and must be (a) masked by per-block visit
    flags (unvisited blocks hold garbage) and (b) reduced over the GQA
    group by the wrapper.
    """
    if grid not in ("grouped", "flat"):
        raise ValueError(f"unknown moba_bwd grid {grid!r}: "
                         f"expected 'grouped' or 'flat'")
    interpret = resolve_interpret(interpret)
    bh, L, d = q_sorted.shape
    bkv, nb, bs, _ = k_blocks.shape
    n_tiles = L // q_tile
    h = num_q_heads

    # per-query vectors as (BH, L, 1) columns (see `moba_fwd`)
    q_pos = q_pos[..., None]
    lse_sorted = lse_sorted[..., None]
    delta_sorted = delta_sorted[..., None]
    out_shape = [
        jax.ShapeDtypeStruct((bh, L, d), jnp.float32),
        jax.ShapeDtypeStruct((bh, nb, bs, d), jnp.float32),
        jax.ShapeDtypeStruct((bh, nb, bs, d), jnp.float32),
    ]

    if grid == "flat":
        def kv_index(bhi, t, tb_ref):
            kv = (bhi // h) * (h // group) + (bhi % h) // group
            blk = jnp.minimum(tb_ref[bhi, t], nb - 1)
            return (kv, blk, 0, 0)

        def dkv_index(bhi, t, tb_ref):
            return (bhi, jnp.minimum(tb_ref[bhi, t], nb - 1), 0, 0)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, n_tiles),
            in_specs=[
                pl.BlockSpec((1, q_tile, d), lambda bhi, t, tb: (bhi, t, 0)),
                pl.BlockSpec((1, q_tile, 1), lambda bhi, t, tb: (bhi, t, 0)),
                pl.BlockSpec((1, q_tile, d), lambda bhi, t, tb: (bhi, t, 0)),
                pl.BlockSpec((1, q_tile, 1), lambda bhi, t, tb: (bhi, t, 0)),
                pl.BlockSpec((1, q_tile, 1), lambda bhi, t, tb: (bhi, t, 0)),
                pl.BlockSpec((1, 1, bs, d), kv_index),
                pl.BlockSpec((1, 1, bs, d), kv_index),
            ],
            out_specs=[
                pl.BlockSpec((1, q_tile, d), lambda bhi, t, tb: (bhi, t, 0)),
                pl.BlockSpec((1, 1, bs, d), dkv_index),
                pl.BlockSpec((1, 1, bs, d), dkv_index),
            ],
        )
        if not interpret:
            check_blocks("moba_bwd", _OPERANDS,
                         [*grid_spec.in_specs, *grid_spec.out_specs],
                         [q_sorted, q_pos, do_sorted, lse_sorted,
                          delta_sorted, k_blocks, v_blocks, *out_shape])
        kernel = functools.partial(
            _bwd_kernel, scale=scale, block_size=block_size, n_blocks=nb,
            n_tokens=n_tokens, causal=causal)
        return pl.pallas_call(
            kernel, grid_spec=grid_spec, out_shape=out_shape,
            interpret=interpret, name="moba_bwd",
        )(tile_block, q_sorted, q_pos, do_sorted, lse_sorted, delta_sorted,
          k_blocks, v_blocks)

    kb_tile = resolve_kb_tile(kb_tile, bs)
    n_kb = bs // kb_tile

    def kv_index(bhi, t, kb, tb_ref):
        kv = (bhi // h) * (h // group) + (bhi % h) // group
        blk = jnp.minimum(tb_ref[bhi, t], nb - 1)
        return (kv, blk * n_kb + kb, 0, 0)

    def dkv_index(bhi, t, kb, tb_ref):
        # no kb: the window stays resident across a tile's kb run and
        # across the block's contiguous tile run
        return (bhi, jnp.minimum(tb_ref[bhi, t], nb - 1), 0, 0)

    k_t = k_blocks.reshape(bkv, nb * n_kb, kb_tile, d)
    v_t = v_blocks.reshape(bkv, nb * n_kb, kb_tile, d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, n_tiles, n_kb),
        in_specs=[
            pl.BlockSpec((1, q_tile, d), lambda bhi, t, kb, tb: (bhi, t, 0)),
            pl.BlockSpec((1, q_tile, 1),
                         lambda bhi, t, kb, tb: (bhi, t, 0)),
            pl.BlockSpec((1, q_tile, d), lambda bhi, t, kb, tb: (bhi, t, 0)),
            pl.BlockSpec((1, q_tile, 1),
                         lambda bhi, t, kb, tb: (bhi, t, 0)),
            pl.BlockSpec((1, q_tile, 1),
                         lambda bhi, t, kb, tb: (bhi, t, 0)),
            pl.BlockSpec((1, 1, kb_tile, d), kv_index),
            pl.BlockSpec((1, 1, kb_tile, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, q_tile, d), lambda bhi, t, kb, tb: (bhi, t, 0)),
            pl.BlockSpec((1, 1, bs, d), dkv_index),
            pl.BlockSpec((1, 1, bs, d), dkv_index),
        ],
        scratch_shapes=[
            pltpu.VMEM((bs, d), jnp.float32),
            pltpu.VMEM((bs, d), jnp.float32),
        ],
    )
    if not interpret:
        check_blocks("moba_bwd", _OPERANDS,
                     [*grid_spec.in_specs, *grid_spec.out_specs],
                     [q_sorted, q_pos, do_sorted, lse_sorted, delta_sorted,
                      k_t, v_t, *out_shape])
    kernel = functools.partial(
        _bwd_kernel_tiled, scale=scale, block_size=block_size,
        kb_tile=kb_tile, n_kb=n_kb, n_blocks=nb, n_tokens=n_tokens,
        causal=causal)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        interpret=interpret, name="moba_bwd",
    )(tile_block, q_sorted, q_pos, do_sorted, lse_sorted, delta_sorted,
      k_t, v_t)
