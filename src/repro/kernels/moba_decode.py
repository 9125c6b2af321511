"""Pallas kernel: fused paged MoBA decode — scalar-prefetched page gather.

The serving engine's decode step used to gather the selected pages with
XLA (`core.moba.moba_paged_decode_attention`): routing, a (B,Hkv,G,1,k,
ps,d) gather materialized in HBM, then attention over the copy.  This
kernel removes the materialized gather: the per-(sequence, kv head,
slot) **physical page id** — block-table indirection resolved on the
selected pages only — is scalar-prefetched and drives the K/V
`BlockSpec` index_map (the DESIGN.md §2 trick applied to the block
table, §5), so the compute units read each selected page exactly once,
streamed straight from the pool.  An online-softmax accumulator in
scratch merges the pages, replacing the XLA lse-merge.

Two grids:

* ``grouped`` (default, MXU-shaped, DESIGN.md §5): grid (B·Hkv, U) over
  the **deduplicated union** of the pages any query head of the GQA
  group selected (U = G·top_k slots, unique pages compacted to the
  front, tail slots revisit page 0 so their DMA is elided).  Each step
  is one (G, ps)×(ps, d) pair of matmuls — a real MXU tile once G and
  ps are padded to the (8, 128) sublane×lane grain — with per-head
  (G, 1) online-softmax accumulators in VMEM.  Per-head page
  membership is expressed through a (G, U) table of token offsets whose
  non-member rows point past ``kv_len``, so masking alone reproduces
  per-query-head routing exactly.
* ``flat`` (legacy): grid (B·H, top_k), one (1, ps) VPU product per
  query head per step.  Kept for A/B benchmarking and as the shape
  oracle for the grouped grid.

Routing (centroid scores → forced own page → top-k) runs in the wrapper
with `core.moba.moba_paged_route` — scalar-prefetch indices must exist
before kernel launch — and touches only the (B·npg·Hkv·d) centroid
gather.  Realized HBM traffic per decode step is therefore
O(N/B·d) routing + O(U·ps·d) attention per kv head (U ≤ G·k, and just k
when the group's heads agree), with no densified intermediate: the
memory-bound decode shape the paper's small-block regime needs
(FlashMoBA, Table "kernel"; PAPERS.md decode-bottleneck).

Block layouts (DESIGN.md §5): the pool is head-major
``(P, Hkv, ps, d)``, so each step streams one kv head's page as a
``(1, 1, ps, d)`` block — its last two dims are the full ``(ps, d)``
page, the shape Mosaic accepts (a block's last two dims must be whole
(8, 128) tiles or the full array dims).  The grouped grid's per-head
token offsets arrive as one ``(1, Gp, U)`` block per kv head (full U)
and the step's column is selected in VMEM; the flat grid carries q and
its output as ``(B·H, 1, d)`` so the ``(1, 1, d)`` block is full in its
last two dims.  Every block is therefore whole for any ``page_size``
and ``head_dim``; the compiled wrappers still pass their specs through
`kernels.tiling.check_blocks` so a future layout edit fails shaped.

Equivalence: same selection (shared router) and same softmax up to
fp32 reduction order → matches the XLA path within 1e-3
(tests/test_backends.py) on ragged batches, through both grids.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.configs.base import MoBAConfig
from repro.core.moba import moba_paged_route
from repro.kernels.runtime import resolve_interpret
from repro.kernels.tiling import check_blocks, round_up, sublane

NEG_INF = -1e30


def union_pages(idx: jax.Array, sel_valid: jax.Array, npg: int
                ) -> Tuple[jax.Array, jax.Array]:
    """Deduplicate the GQA group's page selection per (batch, kv head).

    idx/sel_valid: (B, Hkv, G, 1, k) from `moba_paged_route`.  Returns
    ``(union, n_uniq)`` with ``union`` (B, Hkv, U) int32 logical page
    ids — unique pages sorted ascending and compacted to the front,
    U = G·k, padding slots 0 — and ``n_uniq`` (B, Hkv) the number of
    valid entries.  Shared with `benchmarks/decode_micro.py`, whose
    per-path HBM-bytes accounting integrates ``n_uniq``.
    """
    b, hkv, g, _, tk = idx.shape
    cap = g * tk
    ids = jnp.where(sel_valid, idx, npg).reshape(b, hkv, cap)
    s = jnp.sort(ids, axis=-1)
    first = jnp.concatenate(
        [jnp.ones((b, hkv, 1), bool), s[..., 1:] != s[..., :-1]], axis=-1)
    uniq = first & (s < npg)
    rank = jnp.cumsum(uniq, axis=-1) - 1
    tgt = jnp.where(uniq, rank, cap)             # cap == drop slot
    union = jnp.zeros((b, hkv, cap + 1), jnp.int32)
    bi = jnp.arange(b)[:, None, None]
    hi = jnp.arange(hkv)[None, :, None]
    union = union.at[bi, hi, tgt].set(s.astype(jnp.int32))
    return union[..., :cap], jnp.sum(uniq, axis=-1).astype(jnp.int32)


# ------------------------------------------------------------ grouped grid
def _decode_kernel_grouped(phys_ref, kvl_ref, ksc_ref, vsc_ref, q_ref,
                           base_ref, k_ref, v_ref, o_ref, o_acc, m_acc,
                           l_acc, *,
                           page_size: int, n_union: int, scale: float):
    """Grid (B·Hkv, U): one union page per step, (G, ps) MXU matmul.

    ``phys`` is scalar-prefetched and already drove the K/V index_map;
    ``base`` is the per-(head, slot) token offset of the page — sentinel
    npg·ps for heads that did not select it, so every token of the row
    masks out; ``kvl`` the per-row valid length; ``ksc``/``vsc`` the
    per-(row, slot) fp32 dequant scale of the streamed page (all-ones
    for unquantized pools) — the int8/fp8 tile is upcast and scaled in
    VMEM right here, before the MXU matmul, so HBM only ever moved the
    low-precision payload.  Accumulators are per-head (G, 1) VMEM tiles
    (G padded to the sublane grain)."""
    bh = pl.program_id(0)
    uu = pl.program_id(1)

    @pl.when(uu == 0)
    def _init():
        o_acc[...] = jnp.zeros_like(o_acc)
        m_acc[...] = jnp.full_like(m_acc, NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

    q = q_ref[0].astype(jnp.float32)              # (Gp, d)
    kb = k_ref[0, 0].astype(jnp.float32) * ksc_ref[bh, uu]  # (ps, d)
    vb = v_ref[0, 0].astype(jnp.float32) * vsc_ref[bh, uu]

    s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Gp, ps)
    s = s * scale
    # this step's column of the resident (Gp, U) offset table
    base_all = base_ref[0]                        # (Gp, U) int32
    col = jax.lax.broadcasted_iota(jnp.int32, base_all.shape, 1)
    base = jnp.sum(jnp.where(col == uu, base_all, 0), axis=1,
                   keepdims=True)                 # (Gp, 1)
    pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
    mask = pos < kvl_ref[bh]                      # (Gp, ps)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_acc[...]                           # (Gp, 1)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.maximum(m_cur, NEG_INF / 2)      # all-masked guard
    alpha = jnp.exp(m_prev - m_safe)
    p = jnp.exp(s - m_safe) * mask.astype(jnp.float32)
    m_acc[...] = m_cur
    l_acc[...] = l_acc[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    o_acc[...] = (o_acc[...] * alpha
                  + jax.lax.dot_general(p, vb, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32))

    @pl.when(uu == n_union - 1)
    def _emit():
        l = l_acc[...]
        o_ref[0] = (o_acc[...]
                    / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def _decode_grouped(q, pages_k, pages_v, block_table, kv_len, idx,
                    sel_valid, *, scale: float, interpret: bool,
                    scales_k=None, scales_v=None):
    b, h, _, d = q.shape
    num_pages, hkv, ps, _ = pages_k.shape
    npg = block_table.shape[1]
    g = h // hkv
    tk = idx.shape[-1]
    cap = g * tk

    union, n_uniq = union_pages(idx, sel_valid, npg)         # (B,Hkv,U)
    tbl = jnp.maximum(block_table, 0)
    phys = tbl[jnp.arange(b)[:, None, None], union]
    phys = jnp.clip(phys, 0, num_pages - 1)

    # per-(row, union-slot) dequant scales, gathered alongside the page
    # ids the index_map prefetches (ones when the pool is unquantized —
    # multiplying by 1.0 is a bitwise no-op on the fp32 tile)
    if scales_k is None:
        ksc_f = jnp.ones((b * hkv, cap), jnp.float32)
        vsc_f = ksc_f
    else:
        hsel = jnp.arange(hkv)[None, :, None]
        ksc_f = scales_k[phys, hsel].reshape(b * hkv, cap)
        vsc_f = scales_v[phys, hsel].reshape(b * hkv, cap)

    # per-(head, union-slot) token offsets: page base where the head
    # selected the page, else the npg*ps sentinel (>= kv_len by the
    # engine's pool invariant) so the whole (1, ps) row masks out —
    # masking alone reproduces per-query-head routing on a group tile
    ids_g = jnp.where(sel_valid, idx, npg)[:, :, :, 0, :]    # (B,Hkv,G,k)
    member = (ids_g[:, :, :, :, None]
              == union[:, :, None, None, :]).any(axis=3)     # (B,Hkv,G,U)
    member &= (jnp.arange(cap)[None, None, None, :]
               < n_uniq[:, :, None, None])
    base = jnp.where(member, (union * ps)[:, :, None, :], npg * ps)

    # pad the group dim to the q-dtype sublane grain so the q block,
    # scratch and output are whole (sublane, 128) tiles; padded rows
    # carry the sentinel offset, so they mask out and emit zeros
    gp = round_up(g, sublane(q.dtype))
    q_f = jnp.zeros((b * hkv, gp, d), q.dtype)
    q_f = q_f.at[:, :g].set(q[:, :, 0, :].reshape(b * hkv, g, d))
    base_f = jnp.full((b * hkv, gp, cap), npg * ps, jnp.int32)
    base_f = base_f.at[:, :g].set(base.reshape(b * hkv, g, cap))
    phys_f = phys.reshape(b * hkv, cap).astype(jnp.int32)
    kvl_f = jnp.broadcast_to(kv_len[:, None], (b, hkv)).reshape(-1)
    kvl_f = kvl_f.astype(jnp.int32)

    def kv_index(bh, uu, phys_ref, kvl_ref, ksc_ref, vsc_ref):
        return (phys_ref[bh, uu], bh % hkv, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b * hkv, cap),
        in_specs=[
            pl.BlockSpec((1, gp, d), lambda bh, uu, *_: (bh, 0, 0)),
            pl.BlockSpec((1, gp, cap), lambda bh, uu, *_: (bh, 0, 0)),
            pl.BlockSpec((1, 1, ps, d), kv_index),
            pl.BlockSpec((1, 1, ps, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, gp, d), lambda bh, uu, *_: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((gp, d), jnp.float32),
            pltpu.VMEM((gp, 1), jnp.float32),
            pltpu.VMEM((gp, 1), jnp.float32),
        ],
    )
    out_shape = jax.ShapeDtypeStruct((b * hkv, gp, d), jnp.float32)
    if not interpret:
        check_blocks("paged decode (grouped)",
                     ("q", "base", "pages_k", "pages_v", "out"),
                     (*grid_spec.in_specs, grid_spec.out_specs),
                     (q_f, base_f, pages_k, pages_v, out_shape))
    kernel = functools.partial(_decode_kernel_grouped, page_size=ps,
                               n_union=cap, scale=float(scale))
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        interpret=interpret, name="moba_decode",
    )(phys_f, kvl_f, ksc_f, vsc_f, q_f, base_f, pages_k, pages_v)
    return out[:, :g].reshape(b, h, 1, d).astype(q.dtype)


# --------------------------------------------------------- flat (legacy)
def _decode_kernel_flat(phys_ref, base_ref, kvl_ref, ksc_ref, vsc_ref,
                        q_ref, k_ref, v_ref,
                        o_ref, o_acc, m_acc, l_acc, *,
                        page_size: int, top_k: int, scale: float):
    """Grid (B·H, top_k): one selected page per step, online softmax.

    phys/base/kvl/ksc/vsc are scalar-prefetched: ``phys`` already drove
    the K/V index_map; ``base`` is the page's logical token offset
    (sentinel npg·ps for unselected slots, so every token masks out);
    ``kvl`` the per-row valid length; ``ksc``/``vsc`` the page's fp32
    dequant scales (ones for unquantized pools), applied on the VMEM
    tile after the upcast.
    """
    bh = pl.program_id(0)
    kk = pl.program_id(1)

    @pl.when(kk == 0)
    def _init():
        o_acc[...] = jnp.zeros_like(o_acc)
        m_acc[0, 0] = NEG_INF
        l_acc[0, 0] = 0.0

    q = q_ref[0].astype(jnp.float32)                   # (1, d)
    kb = k_ref[0, 0].astype(jnp.float32) * ksc_ref[bh, kk]  # (ps, d)
    vb = v_ref[0, 0].astype(jnp.float32) * vsc_ref[bh, kk]

    s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (1, ps)
    s = s * scale
    pos = (base_ref[bh, kk]
           + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1))
    mask = pos < kvl_ref[bh]
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_acc[0, 0]
    m_cur = jnp.maximum(m_prev, jnp.max(s))
    m_safe = jnp.maximum(m_cur, NEG_INF / 2)           # all-masked guard
    alpha = jnp.exp(m_prev - m_safe)
    p = jnp.exp(s - m_safe) * mask.astype(jnp.float32)
    m_acc[0, 0] = m_cur
    l_acc[0, 0] = l_acc[0, 0] * alpha + jnp.sum(p)
    o_acc[...] = (o_acc[...] * alpha
                  + jax.lax.dot_general(p, vb, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32))

    @pl.when(kk == top_k - 1)
    def _emit():
        l = l_acc[0, 0]
        o_ref[0] = (o_acc[...]
                    / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def _decode_flat(q, pages_k, pages_v, block_table, kv_len, idx, sel_valid,
                 *, scale: float, interpret: bool,
                 scales_k=None, scales_v=None):
    b, h, _, d = q.shape
    num_pages, hkv, ps, _ = pages_k.shape
    npg = block_table.shape[1]
    g = h // hkv
    tk = idx.shape[-1]
    tbl = jnp.maximum(block_table, 0)
    phys = tbl[jnp.arange(b)[:, None, None, None, None], idx]
    phys = jnp.clip(phys, 0, num_pages - 1)
    # sentinel offset npg*ps puts every token of an unselected slot past
    # kv_len (engine invariant: kv_len <= npg*ps), masking the whole page
    base = jnp.where(sel_valid, idx * ps, npg * ps)

    # flatten heads: h = hkv * g with the same (b, hkv, g) order the
    # query layout uses, so bh -> kv head is (bh % h) // g
    phys_f = phys[:, :, :, 0, :].reshape(b * h, tk).astype(jnp.int32)
    base_f = base[:, :, :, 0, :].reshape(b * h, tk).astype(jnp.int32)
    kvl_f = jnp.broadcast_to(kv_len[:, None], (b, h)).reshape(-1)
    kvl_f = kvl_f.astype(jnp.int32)
    q_f = q.reshape(b * h, 1, d)
    if scales_k is None:
        ksc_f = jnp.ones((b * h, tk), jnp.float32)
        vsc_f = ksc_f
    else:
        hsel = jnp.arange(hkv)[None, :, None, None]
        ksc_f = scales_k[phys[:, :, :, 0, :], hsel].reshape(b * h, tk)
        vsc_f = scales_v[phys[:, :, :, 0, :], hsel].reshape(b * h, tk)

    def kv_index(bh, kk, phys_ref, base_ref, kvl_ref, ksc_ref, vsc_ref):
        return (phys_ref[bh, kk], (bh % h) // g, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b * h, tk),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda bh, kk, *_: (bh, 0, 0)),
            pl.BlockSpec((1, 1, ps, d), kv_index),
            pl.BlockSpec((1, 1, ps, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda bh, kk, *_: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.SMEM((1, 1), jnp.float32),
            pltpu.SMEM((1, 1), jnp.float32),
        ],
    )
    out_shape = jax.ShapeDtypeStruct((b * h, 1, d), jnp.float32)
    if not interpret:
        check_blocks("paged decode (flat)",
                     ("q", "pages_k", "pages_v", "out"),
                     (*grid_spec.in_specs, grid_spec.out_specs),
                     (q_f, pages_k, pages_v, out_shape))
    kernel = functools.partial(_decode_kernel_flat, page_size=ps,
                               top_k=tk, scale=float(scale))
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        interpret=interpret, name="moba_decode",
    )(phys_f, base_f, kvl_f, ksc_f, vsc_f, q_f, pages_k, pages_v)
    return out.reshape(b, h, 1, d).astype(q.dtype)


# ---------------------------------------------------------------- wrapper
def moba_paged_decode_pallas(q: jax.Array, pages_k: jax.Array,
                             pages_v: jax.Array, centroids: jax.Array,
                             block_table: jax.Array, kv_len: jax.Array,
                             cfg: MoBAConfig,
                             scale: Optional[float] = None,
                             interpret: Optional[bool] = None,
                             grid: str = "grouped",
                             scales_k: Optional[jax.Array] = None,
                             scales_v: Optional[jax.Array] = None,
                             head_top_k: Optional[jax.Array] = None
                             ) -> jax.Array:
    """Drop-in for `core.moba.moba_paged_decode_attention` (same contract):

    q:           (B, H, 1, d)
    pages_k/v:   (P, Hkv, page_size, d) shared pool (one layer slot)
    centroids:   (P, Hkv, d) fp32 per-page centroid cache
    block_table: (B, npg) int32 physical page ids, -1 = unassigned
    kv_len:      (B,) int32 post-append valid lengths
    scales_k/v:  (P, Hkv) fp32 per-page dequant scales for int8/fp8
                 pools (None = unquantized); gathered per selected page
                 and scalar-prefetched, the kernels upcast + scale the
                 payload tile in VMEM before the matmuls

    ``interpret=None`` resolves through `kernels.runtime` (env var /
    TPU auto-detect); ``grid`` selects the MXU-shaped ``grouped`` grid
    (default) or the legacy per-query-head ``flat`` grid.  Routing runs
    in XLA on the centroid cache (shared `moba_paged_route`) — fp32
    regardless of pool dtype — then the fused gather+attend kernel.
    Rows with ``kv_len`` 0 (inactive slots) return zeros.
    """
    if grid not in ("grouped", "flat"):
        raise ValueError(f"unknown decode grid {grid!r}: "
                         f"expected 'grouped' or 'flat'")
    _, _, _, d = q.shape
    _, _, ps, _ = pages_k.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    interpret = resolve_interpret(interpret)

    # Per-head budgets (`head_top_k`, adaptive routing) truncate the
    # score-sorted selection inside the shared route: the flat grid sees
    # truncated slots as sentinel pages (zero tiles), the grouped grid's
    # union compaction shrinks n_uniq — real HBM-bytes savings with no
    # kernel change (DESIGN.md §8).
    idx, sel_valid = moba_paged_route(q, centroids, block_table, kv_len,
                                      cfg, page_size=ps,
                                      head_top_k=head_top_k)
    impl = _decode_grouped if grid == "grouped" else _decode_flat
    return impl(q, pages_k, pages_v, block_table, kv_len, idx, sel_valid,
                scale=scale, interpret=interpret,
                scales_k=scales_k, scales_v=scales_v)
