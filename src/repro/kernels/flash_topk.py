"""Pallas kernel: Flash TopK (paper Alg. 3, TPU adaptation).

Streams tiles of Q against tiles of the centroid matrix K̃ and maintains a
running per-query top-k (scores, block ids) in VMEM scratch — the full
(Nq × nb) score matrix never exists in HBM.

Two grids (DESIGN.md §2), one kernel:

* ``grouped`` (default, MXU-shaped): grid (B·Hkv, Nq/Tq, ct) — the q
  block covers all G query heads of a GQA group (heads are contiguous
  per kv head in the (B·H) layout), so ONE centroid-tile DMA serves the
  whole group (1/G of the flat grid's centroid traffic) and the score
  matmul is a single (G·Tq, d) · (d, C) MXU product.
* ``flat`` (kept selectable for bisection): grid (B·H, Nq/Tq, ct) — the
  same kernel with G = 1 and a per-query-head centroid DMA.

The running top-k is maintained by a **two-stage bitonic merge** over
the C candidate lanes: a tile-local tournament (sort kp-lane groups in
alternating directions, then fold pairs of groups with one
compare-exchange plus a kp-wide merge — O(log(C/kp)·log kp) stages)
leaves the tile's top kp in lanes [0, kp), and one half-cleaner against
the running list (kept ascending in lanes [kp, 2kp)) merges it.  Merge
lists are padded to ``kp`` lanes (power of two, at least the sublane
grain), and the lane width is at least 2·kp.

Every compare-exchange pairs lane ``l`` with lane ``l ^ j`` through two
``pltpu.roll`` lane rotations and lane-iota masks — no lane reversal
and no lane-splitting reshape, neither of which Mosaic lowers.  The
masks are derived by rotating the lane iota itself, so they hold for
either rotation direction.

Ties break toward the lower block id — exactly ``jax.lax.top_k``'s
order — so results are bit-identical to the oracle.

Selection semantics (must match `repro.core.routing.select_blocks`):
  * future blocks masked to −inf
  * own block forced to +inf (always selected, counts toward k)
  * slots with score ≤ −inf/2 are sentinels (block id = nb)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret
from repro.kernels.tiling import SUBLANE, check_blocks, next_pow2

NEG_INF = -1e30       # mask level (matches core.routing)
INIT = -3e30          # empty running-list slot: below every mask level
POS_INF = 1e30


# ------------------------------------------------- bitonic lane network
class _Lanes:
    """Lane-exchange helper for (R, C) tiles: ``partner(x, j)`` returns
    ``x[:, l ^ j]`` for every lane ``l`` (``j`` a power of two < C)."""

    def __init__(self, width: int):
        self.width = width
        self.lane = jax.lax.broadcasted_iota(jnp.int32, (SUBLANE, width),
                                             1)[:1]            # (1, C)
        self._up = {}

    def up(self, j):
        """Lanes whose partner ``l ^ j`` sits at ``l + j`` (bit j clear),
        read off the rotated iota so the mask matches the rotation."""
        if j not in self._up:
            lane8 = jax.lax.broadcasted_iota(jnp.int32,
                                             (SUBLANE, self.width), 1)
            src = pltpu.roll(lane8, self.width - j, 1)[:1]
            self._up[j] = src == (self.lane ^ j)
        return self._up[j]

    def partner(self, x, j):
        return jnp.where(self.up(j), pltpu.roll(x, self.width - j, 1),
                         pltpu.roll(x, j, 1))


def _cx(s, i, j, desc, lanes: _Lanes):
    """One compare-exchange stage between lanes l and l ^ j.  Where the
    pair sorts descending, the lower lane keeps the better element
    (score desc, block id asc on ties — `lax.top_k`'s order) and the
    upper lane the worse; elsewhere the reverse.  s (R, C) fp32, i (R, C)
    int32.  ``desc`` is True/False for every lane, or a power of two
    ``k > j``: descending where lane bit ``k`` is clear."""
    ps, pi = lanes.partner(s, j), lanes.partner(i, j)
    better = (s > ps) | ((s == ps) & (i < pi))
    # the lane's role, from int32 lane bits only: Mosaic does not
    # legalize a compare of two bool vectors
    bit_j = lanes.lane & j                                   # (1, C)
    if desc is True:
        take_best = bit_j == 0
    elif desc is False:
        take_best = bit_j != 0
    else:
        take_best = (bit_j * (desc // j)) == (lanes.lane & desc)
    best_s, best_i = jnp.where(better, s, ps), jnp.where(better, i, pi)
    worst_s, worst_i = jnp.where(better, ps, s), jnp.where(better, pi, i)
    return (jnp.where(take_best, best_s, worst_s),
            jnp.where(take_best, best_i, worst_i))


def _merge(s, i, kp, desc, lanes):
    """Sort every bitonic kp-lane group in direction ``desc`` (see
    `_cx`)."""
    j = kp // 2
    while j >= 1:
        s, i = _cx(s, i, j, desc, lanes)
        j //= 2
    return s, i


def _local_topk(s, i, kp, lanes):
    """Stage 1: tile-local top-kp of the C candidate lanes, left in
    lanes [0, kp) sorted descending (other lanes hold leftovers).
    Sorts kp-lane groups in alternating directions, then each round
    folds group pairs 2j apart: a compare-exchange at distance j keeps
    each pair's top kp (bitonic) in the lower group, and a kp-wide merge
    sorts it in the direction the next round expects."""
    c = lanes.width
    k = 2
    while k <= kp:
        s, i = _merge(s, i, k, k, lanes)
        k *= 2
    j = kp
    while j < c:
        s, i = _cx(s, i, j, True, lanes)
        s, i = _merge(s, i, kp, 2 * j, lanes)
        j *= 2
    return s, i


# ----------------------------------------------------------------- kernel
def _flash_topk_kernel(q_ref, c_ref, idx_ref, s_run, i_run, *,
                       top_k: int, kp: int, block_size: int,
                       cent_tile: int, n_blocks: int, n_cent_tiles: int,
                       q_tile: int, group: int, causal: bool,
                       q_pos_offset: int):
    """Grid (B·Hkv, Nq/Tq, ct) (flat: G = 1 over B·H): one centroid-tile
    DMA serves all G query heads; scores are one (G·Tq, d)·(d, C) MXU
    matmul; the running top-k lives ascending in lanes [kp, 2kp) of the
    (G·Tq, C) scratch and updates through the two-stage bitonic merge."""
    ct = pl.program_id(2)
    lanes = _Lanes(cent_tile)

    @pl.when(ct == 0)
    def _init():
        s_run[...] = jnp.full_like(s_run, INIT)
        i_run[...] = jnp.zeros_like(i_run)

    rows = group * q_tile
    d = q_ref.shape[-1]
    q = q_ref[...].astype(jnp.float32).reshape(rows, d)    # (G·Tq, d)
    cents = c_ref[0].astype(jnp.float32)                   # (C, d)
    s = jax.lax.dot_general(q, cents, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (rows, C)

    qt = pl.program_id(1)
    # the query position depends only on the row's index inside Tq —
    # every head of the group shares it
    qpos = (qt * q_tile + q_pos_offset
            + jax.lax.broadcasted_iota(jnp.int32, (rows, cent_tile), 0)
            % q_tile)
    cand = (ct * cent_tile
            + jax.lax.broadcasted_iota(jnp.int32, (rows, cent_tile), 1))
    own = qpos // block_size
    valid = cand < n_blocks
    if causal:
        s = jnp.where(cand > own, NEG_INF, s)
        s = jnp.where((cand == own) & valid, POS_INF, s)
    s = jnp.where(valid, s, NEG_INF)

    # stage 2: half-cleaner of (tile top-kp desc) ++ (running asc), then
    # sort the surviving kp lanes ascending and park them in [kp, 2kp)
    loc_s, loc_i = _local_topk(s, cand, kp, lanes)
    low = lanes.lane < kp
    ms = jnp.where(low, loc_s, s_run[...])
    mi = jnp.where(low, loc_i, i_run[...])
    ms, mi = _cx(ms, mi, kp, True, lanes)
    ms, mi = _merge(ms, mi, kp, False, lanes)
    s_run[...] = lanes.partner(ms, kp)
    i_run[...] = lanes.partner(mi, kp)

    @pl.when(ct == n_cent_tiles - 1)
    def _emit():
        # lanes [0, kp) ascending -> one merge pass sorts them descending
        fs, fi = _merge(ms, mi, kp, True, lanes)
        final = jnp.where(fs <= NEG_INF / 2, n_blocks, fi)
        for g in range(group):
            idx_ref[g] = final[g * q_tile:(g + 1) * q_tile, :top_k]


# ----------------------------------------------------------------- wrapper
def flash_topk(q: jax.Array, centroids: jax.Array, top_k: int,
               block_size: int, *, group: int = 1,
               num_q_heads: int = 0, causal: bool = True,
               q_pos_offset: int = 0, q_tile: int = 128,
               cent_tile: int = 128, grid: str = "grouped",
               interpret: bool | None = None) -> jax.Array:
    """q: (BH, Nq, d); centroids: (BKV, nb, d) where the leading dims are
    flattened (batch · heads) and BH = batch*H, BKV = batch*Hkv,
    H = Hkv*group.  ``num_q_heads`` is H (defaults to BH: single batch).

    ``grid`` selects the grouped (B·Hkv, Nq/Tq, ct) MXU grid (default)
    or the legacy per-query-head ``flat`` grid.  Returns
    (BH, Nq, top_k) int32 selected block ids (sentinel nb).
    """
    if grid not in ("grouped", "flat"):
        raise ValueError(f"unknown topk grid {grid!r}: "
                         f"expected 'grouped' or 'flat'")
    interpret = resolve_interpret(interpret)
    bh, nq, d = q.shape
    bkv, nb, _ = centroids.shape
    h = num_q_heads or bh
    assert bh // h * (h // group) == bkv
    q_tile = min(q_tile, nq)
    assert nq % q_tile == 0, (nq, q_tile)
    if cent_tile & (cent_tile - 1):
        raise ValueError(
            f"flash_topk needs a power-of-two cent_tile (the bitonic "
            f"tournament folds candidate lanes in halves); got "
            f"{cent_tile}")
    kp = max(SUBLANE, next_pow2(top_k))   # merge lists padded to the
    #                                       sublane grain / power of two
    cent_tile = max(cent_tile, 2 * kp)    # room for the (k ∪ k) merge
    n_cent_tiles = -(-nb // cent_tile)
    pad = n_cent_tiles * cent_tile - nb
    if pad:
        centroids = jnp.pad(centroids, ((0, 0), (0, pad), (0, 0)))

    if grid == "flat":
        # one query head per row block, its own kv head's centroids
        g_blk = 1

        def cent_index(hh, qt, ct):
            return ((hh // h) * (h // group) + (hh % h) // group, ct, 0)
    else:
        # dim 0 enumerates (batch, kv head): the G query heads of a
        # group are contiguous rows of q, and the dim-0 block index
        # b·Hkv + kv is exactly the centroid row
        g_blk = group

        def cent_index(gg, qt, ct):
            return (gg, ct, 0)

    kernel = functools.partial(
        _flash_topk_kernel, top_k=top_k, kp=kp, block_size=block_size,
        cent_tile=cent_tile, n_blocks=nb, n_cent_tiles=n_cent_tiles,
        q_tile=q_tile, group=g_blk, causal=causal,
        q_pos_offset=q_pos_offset)
    in_specs = [pl.BlockSpec((g_blk, q_tile, d),
                             lambda gg, qt, ct: (gg, qt, 0)),
                pl.BlockSpec((1, cent_tile, d), cent_index)]
    out_spec = pl.BlockSpec((g_blk, q_tile, top_k),
                            lambda gg, qt, ct: (gg, qt, 0))
    out_shape = jax.ShapeDtypeStruct((bh, nq, top_k), jnp.int32)
    if not interpret:
        check_blocks("flash_topk", ("q", "centroids", "idx"),
                     (*in_specs, out_spec), (q, centroids, out_shape))
    return pl.pallas_call(
        kernel,
        grid=(bh // g_blk, nq // q_tile, n_cent_tiles),
        in_specs=in_specs, out_specs=out_spec, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((g_blk * q_tile, cent_tile), jnp.float32),
                        pltpu.VMEM((g_blk * q_tile, cent_tile), jnp.int32)],
        interpret=interpret, name="flash_topk",
    )(q, centroids)
