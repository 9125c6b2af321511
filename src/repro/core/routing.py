"""MoBA routing: block centroids, causal top-k selection, varlen layout.

Shapes convention (single batch*head slice unless noted):
  q:      (N, d)     queries
  k:      (N, d)     (possibly key-conv'd) keys
  n_blocks = ceil(N / B)

Selection semantics (faithful to the paper / Lu et al.):
  * score of block j for query t is  s_j = q_t · k̃_j  (no 1/sqrt(d))
  * blocks strictly in the future of t are masked out
  * the query's own block is always selected and counts toward top-k
    (this is what makes k/n the exact attended fraction: 7/8 sparsity for
    (B,k) ∈ {(512,2),(256,4),(128,8)} at N=8192)
  * early queries with fewer than k valid blocks select all valid ones;
    the empty slots carry the sentinel block id ``n_blocks``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30
POS_INF = 1e30


def pad_to_blocks(x: jax.Array, block_size: int, axis: int = 0) -> jax.Array:
    n = x.shape[axis]
    rem = (-n) % block_size
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


def block_centroids(k: jax.Array, block_size: int,
                    kv_len: jax.Array | None = None) -> jax.Array:
    """Mean-pool keys into block centroids.

    k: (..., N, d) -> (..., n_blocks, d).  If ``kv_len`` is given (decode
    with a partially-filled cache) positions >= kv_len are excluded from
    the mean.
    """
    *lead, n, d = k.shape
    k = pad_to_blocks(k, block_size, axis=-2)
    nb = k.shape[-2] // block_size
    kb = k.reshape(*lead, nb, block_size, d).astype(jnp.float32)
    if kv_len is None:
        denom = jnp.minimum(
            jnp.maximum(n - jnp.arange(nb) * block_size, 1), block_size
        ).astype(jnp.float32)
        valid = (jnp.arange(nb)[:, None] * block_size
                 + jnp.arange(block_size)[None, :]) < n
        kb = kb * valid[..., None]
        out = kb.sum(-2) / denom[..., None]
    else:
        pos = (jnp.arange(nb)[:, None] * block_size
               + jnp.arange(block_size)[None, :])
        valid = pos < kv_len
        denom = jnp.maximum(valid.sum(-1), 1).astype(jnp.float32)
        kb = kb * valid[..., None]
        out = kb.sum(-2) / denom[..., None]
    return out.astype(k.dtype)


def routing_scores(q: jax.Array, centroids: jax.Array) -> jax.Array:
    """q: (..., Nq, d), centroids: (..., nb, d) -> scores (..., Nq, nb)."""
    return jnp.einsum("...qd,...bd->...qb", q.astype(jnp.float32),
                      centroids.astype(jnp.float32))


def select_blocks(scores: jax.Array, top_k: int, block_size: int,
                  q_positions: jax.Array, causal: bool = True,
                  head_top_k: jax.Array | None = None) -> jax.Array:
    """Top-k block selection with causal masking + forced current block.

    scores: (..., Nq, nb); q_positions: (Nq,) absolute token positions.
    Returns int32 (..., Nq, k) of selected block ids, sentinel ``nb`` for
    empty slots.  Current block (if causal) is forced via +inf so it always
    occupies a slot — faithful to MoBA's accounting.

    ``head_top_k`` (optional int32, broadcastable against the leading
    dims of ``scores``, values in [1, top_k]) truncates each head's
    selection to its own budget: slots ranked >= head_top_k become
    sentinels.  ``top_k`` output slots are score-sorted descending with
    the own block forced first, so keeping the first ``head_top_k`` slots
    is exactly per-head top-k at static shapes (DESIGN.md §8).
    """
    nb = scores.shape[-1]
    own = q_positions // block_size  # (Nq,)
    blk = jnp.arange(nb)
    if causal:
        future = blk[None, :] > own[:, None]          # (Nq, nb)
        is_own = blk[None, :] == own[:, None]
        masked = jnp.where(future, NEG_INF, scores)
        masked = jnp.where(is_own, POS_INF, masked)
    else:
        masked = scores
    kk = min(top_k, nb)
    top_scores, top_idx = jax.lax.top_k(masked, kk)
    # slots whose score is NEG_INF are invalid -> sentinel
    top_idx = jnp.where(top_scores <= NEG_INF / 2, nb, top_idx)
    if kk < top_k:  # fewer blocks than k: pad with sentinels
        pad = jnp.full(top_idx.shape[:-1] + (top_k - kk,), nb,
                       top_idx.dtype)
        top_idx = jnp.concatenate([top_idx, pad], axis=-1)
    if head_top_k is not None:
        keep = jnp.arange(top_k) < head_top_k[..., None, None]
        top_idx = jnp.where(keep, top_idx, nb)
    return top_idx.astype(jnp.int32)


def selection_mask(top_idx: jax.Array, nb: int) -> jax.Array:
    """(..., Nq, k) block ids -> boolean (..., Nq, nb) selection mask."""
    onehot = jax.nn.one_hot(top_idx, nb + 1, dtype=jnp.bool_)
    return onehot.any(axis=-2)[..., :nb]


class VarlenLayout(NamedTuple):
    """Key-block-major padded varlen layout (paper Alg. 4, TPU-native).

    With Nq queries each selecting k blocks there are exactly Nq*k
    (query, block) pairs.  We sort pairs by block id (stable → query order
    preserved inside a block), then pad each block's run to a multiple of
    the physical tile Tq so every tile maps to exactly one key block.

    All shapes are static: capacity L = roundup(Nq*k, Tq) + nb*Tq
    upper-bounds any padding outcome (each of nb blocks wastes < Tq
    slots; sentinel pairs are parked in the trailing region) and is a
    whole number of tiles even when Nq*k is not.
    """

    q_index: jax.Array      # (L,) int32: query position per slot, -1 = pad
    slot_block: jax.Array   # (L,) int32: block id per slot, nb = pad
    tile_block: jax.Array   # (L/Tq,) int32: block id per tile, nb = inactive
    pair_slot: jax.Array    # (Nq, k) int32: slot index of each pair (for the
                            # inverse scatter when merging partials)


def build_varlen_layout(top_idx: jax.Array, nq: int, nb: int,
                        tile: int) -> VarlenLayout:
    """top_idx: (Nq, k) selected block ids (sentinel nb). Static-shape,
    fully-jittable construction of the key-block-major layout."""
    k = top_idx.shape[-1]
    flat_block = top_idx.reshape(-1)                       # (Nq*k,)
    flat_q = jnp.repeat(jnp.arange(nq, dtype=jnp.int32), k)

    # stable sort by block id
    order = jnp.argsort(flat_block, stable=True)
    sb = flat_block[order]
    sq = flat_q[order]

    counts = jnp.bincount(flat_block, length=nb + 1)       # (nb+1,)
    padded_counts = ((counts + tile - 1) // tile) * tile
    # sentinel pairs live in the trailing region; give them whatever space
    # remains so slot indices stay in-bounds.
    starts = jnp.concatenate(
        [jnp.zeros(1, jnp.int32),
         jnp.cumsum(padded_counts[:-1]).astype(jnp.int32)])
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(counts[:-1]).astype(jnp.int32)])

    capacity = layout_capacity(nq, k, nb, tile)
    rank = jnp.arange(sb.shape[0], dtype=jnp.int32) - offsets[sb]
    slot = starts[sb] + rank                               # (Nq*k,)

    # sentinel pairs are parked in the trailing region with q_index -1 so
    # they are masked exactly like padding.
    q_index = jnp.full((capacity,), -1, jnp.int32).at[slot].set(
        jnp.where(sb == nb, -1, sq))
    slot_block = jnp.full((capacity,), nb, jnp.int32).at[slot].set(sb)
    # Every tile of an active block's run starts with a real slot (padding
    # sits at the run's tail and runs are tile-multiples), so the first
    # slot's block id identifies the tile; nb marks inactive tiles.
    first = slot_block.reshape(-1, tile)[:, 0]
    tile_block = jnp.where(first < nb, first, nb).astype(jnp.int32)
    pair_slot = jnp.zeros((nq * k,), jnp.int32).at[order].set(slot)
    return VarlenLayout(q_index, slot_block, tile_block,
                        pair_slot.reshape(nq, k))


def layout_capacity(nq: int, k: int, nb: int, tile: int) -> int:
    return -(-nq * k // tile) * tile + nb * tile
