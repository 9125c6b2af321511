"""Mixture of Block Attention — pure-JAX reference + public entry point.

The reference path materializes the N×N mask and is the correctness oracle
for the Pallas kernels (`repro.kernels`).  The public `moba_attention`
selects an implementation from the backend registry (`core.backends`,
DESIGN.md §5).

Shapes: q (B, H, Nq, d); k, v (B, Hkv, N, d) with H % Hkv == 0 (GQA —
query heads grouped onto kv heads, paper App. C: no KV duplication, only
index remapping; here expressed via reshape).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import MoBAConfig
from repro.core import routing
from repro.core.key_conv import apply_key_conv

NEG_INF = routing.NEG_INF

# Calibration hook (core.adaptive.capture_routing_scores): when set to a
# callable, moba_selection feeds it (scores, q_positions) per call.  Only
# meaningful for eager (unjitted) passes — under jit the hook would see
# tracers, so the calibration pass always runs eagerly.
_score_sink = None


def _group_queries(q: jax.Array, num_kv_heads: int) -> jax.Array:
    b, h, n, d = q.shape
    g = h // num_kv_heads
    return q.reshape(b, num_kv_heads, g, n, d)


def _truncate_head_topk(idx: jax.Array, sel_valid: jax.Array,
                        head_top_k: Optional[jax.Array]):
    """Truncate a score-sorted (B, Hkv, G, L, k) page selection to
    per-head budgets.  ``head_top_k``: (Hkv, G) int32 in [1, k]; slots
    ranked >= the head's budget become invalid.  Rank 0 is the forced
    own page (POS_INF), so budgets >= 1 always keep it."""
    if head_top_k is None:
        return idx, sel_valid
    keep = jnp.arange(idx.shape[-1]) < head_top_k[..., None, None]
    sel_valid = sel_valid & keep                  # (Hkv,G,1,k) broadcast
    return jnp.where(sel_valid, idx, 0), sel_valid


def moba_selection(q: jax.Array, k: jax.Array, cfg: MoBAConfig,
                   q_positions: Optional[jax.Array] = None,
                   head_top_k: Optional[jax.Array] = None) -> jax.Array:
    """Routing only: returns selected block ids (B, H, Nq, top_k).

    ``k`` must already be key-conv'd if key conv is enabled.
    ``head_top_k``: optional (Hkv, G) int32 per-head budgets in
    [1, top_k]; truncated slots carry the sentinel block id.
    """
    b, hkv, n, d = k.shape
    nq = q.shape[2]
    if q_positions is None:
        q_positions = jnp.arange(nq) + (n - nq)  # suffix alignment (decode)
    cents = routing.block_centroids(k, cfg.block_size)      # (B,Hkv,nb,d)
    qg = _group_queries(q, hkv)                              # (B,Hkv,G,Nq,d)
    scores = jnp.einsum("bhgqd,bhnd->bhgqn", qg.astype(jnp.float32),
                        cents.astype(jnp.float32))
    if _score_sink is not None:
        _score_sink((scores, q_positions))
    sel = routing.select_blocks(scores, cfg.top_k, cfg.block_size,
                                q_positions, causal=cfg.causal,
                                head_top_k=head_top_k)
    return sel.reshape(b, -1, nq, cfg.top_k)


def moba_attention_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                             cfg: MoBAConfig,
                             q_positions: Optional[jax.Array] = None,
                             kv_len: Optional[jax.Array] = None,
                             scale: Optional[float] = None,
                             head_top_k: Optional[jax.Array] = None
                             ) -> jax.Array:
    """Oracle implementation: O(N^2) masked softmax attention where the
    mask is derived from MoBA block selection.

    mask[t, s] = selected[t, block(s)] AND s <= t (causal)   [causal mode]
    mask[t, s] = selected[t, block(s)]                       [bidirectional]
    """
    b, h, nq, d = q.shape
    _, hkv, n, _ = k.shape
    nb = -(-n // cfg.block_size)
    if q_positions is None:
        q_positions = jnp.arange(nq) + (n - nq)
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    sel = moba_selection(q, k, cfg, q_positions,
                         head_top_k=head_top_k)              # (B,H,Nq,k)
    sel_mask = routing.selection_mask(sel, nb)               # (B,H,Nq,nb)
    key_block = jnp.arange(n) // cfg.block_size              # (N,)
    tok_sel = jnp.take_along_axis(
        sel_mask, key_block[None, None, None, :].repeat(nq, 2), axis=-1
    )                                                        # (B,H,Nq,N)
    mask = tok_sel
    if cfg.causal:
        causal = q_positions[:, None] >= jnp.arange(n)[None, :]
        mask = mask & causal[None, None]
    if kv_len is not None:
        mask = mask & (jnp.arange(n)[None, None, None, :] < kv_len)

    qg = _group_queries(q, hkv).astype(jnp.float32)
    s = jnp.einsum("bhgqd,bhsd->bhgqs", qg, k.astype(jnp.float32)) * scale
    s = s.reshape(b, h, nq, n)
    s = jnp.where(mask, s, NEG_INF)
    # guard fully-masked rows (cannot happen causally: own block present)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask.any(-1, keepdims=True), p, 0.0)
    pg = p.reshape(b, hkv, -1, nq, n)
    o = jnp.einsum("bhgqs,bhsd->bhgqd", pg, v.astype(jnp.float32))
    return o.reshape(b, h, nq, d).astype(q.dtype)


def moba_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   cfg: MoBAConfig,
                   key_conv_weights: Optional[jax.Array] = None,
                   impl: str = "reference",
                   q_positions: Optional[jax.Array] = None,
                   scale: Optional[float] = None,
                   interpret: bool = True) -> jax.Array:
    """Public MoBA attention entry point.

    ``impl`` names a registered attention backend (``core.backends``):
    'reference' (O(N^2) oracle), 'flash'/'kernel' (Pallas FlashMoBA),
    'xla'/'sparse' (pure-XLA gather-and-densify), 'sp' (context
    parallel), plus the ``_unrolled`` dry-run variants.  ``interpret``
    only affects the Pallas backend.
    """
    from repro.core import backends as B

    if key_conv_weights is not None:
        k = apply_key_conv(key_conv_weights, k)
    be = B.resolve(impl, kind="moba", phase="prefill", cache="dense",
                   key_conv=key_conv_weights is not None)
    acfg = _as_attention_config(cfg, scale)
    return be.moba_prefill(acfg, q, k, v, q_positions=q_positions,
                           interpret=interpret)


def _as_attention_config(cfg: MoBAConfig, scale: Optional[float]):
    """Wrap a bare MoBAConfig for the backend interface (which takes the
    per-layer AttentionConfig so one signature covers dense/swa/moba)."""
    from repro.configs.base import AttentionConfig
    return AttentionConfig(kind="moba", moba=cfg, scale=scale)


def _topk_pages(masked: jax.Array, top_k: int):
    """Shared tail of paged routing: top-k over the last (page) axis,
    padded with invalid slots when the axis is shorter than ``top_k``.
    Both the decode and the chunked-prefill routes go through this so
    their selection semantics cannot drift apart.

    Returns (idx, sel_valid): selected indices (invalid slots 0) and
    their validity mask (NEG_INF-scored slots are invalid).
    """
    n = masked.shape[-1]
    kk = min(top_k, n)
    top_s, top_idx = jax.lax.top_k(masked, kk)
    if kk < top_k:
        padw = top_k - kk
        top_s = jnp.concatenate(
            [top_s, jnp.full(top_s.shape[:-1] + (padw,), NEG_INF)], -1)
        top_idx = jnp.concatenate(
            [top_idx, jnp.zeros(top_idx.shape[:-1] + (padw,),
                                top_idx.dtype)], -1)
    sel_valid = top_s > NEG_INF / 2
    return jnp.where(sel_valid, top_idx, 0), sel_valid


def moba_paged_route(q: jax.Array, centroids: jax.Array,
                     block_table: jax.Array, kv_len: jax.Array,
                     cfg: MoBAConfig,
                     page_size: Optional[int] = None,
                     head_top_k: Optional[jax.Array] = None):
    """Decode-time page routing on the per-page centroid cache.

    Shared by the XLA gather path and the Pallas decode kernel wrapper so
    both attend to exactly the same pages.  Matches the dense-cache
    decode selection semantics: causal over pages, own (last) page
    forced, per-sequence lengths, top-k padded with invalid slots when
    the table is shorter than ``top_k``.

    q:           (B, H, 1, d)
    centroids:   (P, Hkv, d) fp32 per-page centroid pool
    block_table: (B, npg) int32 physical page ids, -1 = unassigned
    kv_len:      (B,) int32 post-append valid lengths

    Returns (idx, sel_valid): logical page ids (B, Hkv, G, 1, top_k)
    int32 (invalid slots 0) and their validity mask.  ``head_top_k``
    ((Hkv, G) int32 in [1, top_k]) truncates each head's score-sorted
    selection to its calibrated budget (DESIGN.md §8).
    """
    b, h, _, d = q.shape
    hkv = centroids.shape[1]
    npg = block_table.shape[1]
    ps = page_size or cfg.block_size  # one page == one routable block
    tbl = jnp.maximum(block_table, 0)
    cents = centroids[tbl].transpose(0, 2, 1, 3)             # (B,Hkv,npg,d)
    qg = _group_queries(q, hkv).astype(jnp.float32)          # (B,Hkv,G,1,d)
    scores = jnp.einsum("bhgqd,bhnd->bhgqn", qg,
                        cents.astype(jnp.float32))
    blk_start = jnp.arange(npg) * ps
    valid = (blk_start[None, :] < kv_len[:, None]) & (block_table >= 0)
    own = jnp.maximum(kv_len - 1, 0) // ps                   # (B,)
    is_own = jnp.arange(npg)[None, :] == own[:, None]        # (B,npg)
    masked = jnp.where(valid[:, None, None, None], scores, NEG_INF)
    masked = jnp.where(is_own[:, None, None, None], routing.POS_INF, masked)
    idx, sel_valid = _topk_pages(masked, cfg.top_k)
    return _truncate_head_topk(idx, sel_valid, head_top_k)


def moba_paged_decode_attention(q: jax.Array, pages_k: jax.Array,
                                pages_v: jax.Array, centroids: jax.Array,
                                block_table: jax.Array, kv_len: jax.Array,
                                cfg: MoBAConfig,
                                scale: Optional[float] = None,
                                scales_k: Optional[jax.Array] = None,
                                scales_v: Optional[jax.Array] = None,
                                head_top_k: Optional[jax.Array] = None
                                ) -> jax.Array:
    """Single-step decode against a paged cache: route on the per-page
    centroid cache, then gather only the ``top_k`` selected pages through
    the block table — O(N/B·d) routing reads + O(k·B·d) attention reads
    per kv head, never touching the rest of the pool.

    q:           (B, H, 1, d)
    pages_k/v:   (P, Hkv, page_size, d) shared pool (one layer slot)
    centroids:   (P, Hkv, d) fp32 per-page centroid cache
    block_table: (B, npg) int32 physical page ids, -1 = unassigned
    kv_len:      (B,) int32 valid lengths *including* the token appended
                 this step (call after the cache append)
    scales_k/v:  (P, Hkv) fp32 per-page dequant scales of a quantized
                 pool (None = unquantized).  Routing above never sees
                 them — centroids are fp32 regardless of pool dtype.
    """
    b, h, _, d = q.shape
    _, hkv, ps, _ = pages_k.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    idx, sel_valid = moba_paged_route(q, centroids, block_table, kv_len,
                                      cfg, page_size=ps,
                                      head_top_k=head_top_k)
    qg = _group_queries(q, hkv).astype(jnp.float32)          # (B,Hkv,G,1,d)
    tbl = jnp.maximum(block_table, 0)
    phys = tbl[jnp.arange(b)[:, None, None, None, None], idx]
    hsel = jnp.arange(hkv)[None, :, None, None, None]

    # gather only the selected pages, per kv head: (B,Hkv,G,1,k,ps,d)
    kg = pages_k[phys, hsel].astype(jnp.float32)
    vg = pages_v[phys, hsel].astype(jnp.float32)
    if scales_k is not None:
        # mirror the page gather on the (P, Hkv) scale leaves: one
        # scalar per selected (page, kv head), broadcast over (ps, d)
        kg = kg * scales_k[phys, hsel][..., None, None]
        vg = vg * scales_v[phys, hsel][..., None, None]
    s = jnp.einsum("bhgqd,bhgqkld->bhgqkl", qg, kg) * scale
    pos = idx[..., :, None] * ps + jnp.arange(ps)            # logical pos
    tok_valid = ((pos < kv_len[:, None, None, None, None, None])
                 & sel_valid[..., None])
    s = jnp.where(tok_valid, s, NEG_INF)
    sf = s.reshape(*s.shape[:-2], -1)
    p = jax.nn.softmax(sf, axis=-1).reshape(s.shape)
    o = jnp.einsum("bhgqkl,bhgqkld->bhgqd", p, vg)
    return o.reshape(b, h, 1, d).astype(q.dtype)


def moba_paged_prefill_route(q: jax.Array, centroids: jax.Array,
                             block_table: jax.Array, kv_len: jax.Array,
                             q_len: jax.Array, cfg: MoBAConfig,
                             page_size: Optional[int] = None,
                             head_top_k: Optional[jax.Array] = None):
    """Chunked-prefill page routing on the per-page centroid cache.

    Multi-token sibling of :func:`moba_paged_route`: query j of row i sits
    at absolute position ``kv_len[i] + j`` and scores every logical page
    of its sequence, with future pages masked, the own page forced, and
    unassigned table entries invalid.  Call *after* the chunk's keys (and
    centroid recomputes) are appended, so complete pages carry exactly
    the centroids one-shot prefill would compute — any non-own page a
    query can select is complete by then, which is what makes chunked and
    one-shot prefill routing-equivalent (DESIGN.md §6; pinned by test).

    q: (B, H, L, d) right-padded chunk queries; centroids: (P, Hkv, d);
    block_table: (B, npg); kv_len: (B,) pre-chunk lengths; q_len: (B,)
    valid chunk tokens per row.

    Returns (idx, sel_valid): logical page ids (B, Hkv, G, L, top_k)
    int32 (invalid slots 0) and their validity mask.
    """
    b, h, nq, d = q.shape
    hkv = centroids.shape[1]
    npg = block_table.shape[1]
    ps = page_size or cfg.block_size  # one page == one routable block
    tbl = jnp.maximum(block_table, 0)
    cents = centroids[tbl].transpose(0, 2, 1, 3)             # (B,Hkv,npg,d)
    qg = _group_queries(q, hkv).astype(jnp.float32)          # (B,Hkv,G,L,d)
    scores = jnp.einsum("bhgqd,bhnd->bhgqn", qg,
                        cents.astype(jnp.float32))
    pos = kv_len[:, None] + jnp.arange(nq)                   # (B,L) abs pos
    own = pos // ps                                          # (B,L)
    blk = jnp.arange(npg)
    future = blk[None, None, :] > own[:, :, None]            # (B,L,npg)
    is_own = blk[None, None, :] == own[:, :, None]
    assigned = (block_table >= 0)[:, None, :]                # (B,1,npg)
    # broadcast (B,L,npg) masks into (B,Hkv,G,L,npg)
    masked = jnp.where((future | ~assigned)[:, None, None], NEG_INF, scores)
    masked = jnp.where(is_own[:, None, None], routing.POS_INF, masked)
    idx, sel_valid = _topk_pages(masked, cfg.top_k)
    idx, sel_valid = _truncate_head_topk(idx, sel_valid, head_top_k)
    # padded query rows (beyond q_len) select nothing
    row_valid = (jnp.arange(nq) < q_len[:, None])            # (B,L)
    sel_valid = sel_valid & row_valid[:, None, None, :, None]
    return jnp.where(sel_valid, idx, 0), sel_valid


def moba_paged_prefill_attention(q: jax.Array, pages_k: jax.Array,
                                 pages_v: jax.Array, centroids: jax.Array,
                                 block_table: jax.Array, kv_len: jax.Array,
                                 q_len: jax.Array, cfg: MoBAConfig,
                                 scale: Optional[float] = None,
                                 scales_k: Optional[jax.Array] = None,
                                 scales_v: Optional[jax.Array] = None,
                                 head_top_k: Optional[jax.Array] = None
                                 ) -> jax.Array:
    """Chunked-prefill MoBA attention against a paged cache.

    The chunk's queries route on the per-page centroid cache
    (:func:`moba_paged_prefill_route`), then attend over the densified
    sequence view of the pool under the selection × causal mask — earlier
    chunks' keys are visible through the block table, which is what the
    fresh-prefill path cannot do.  Padded query rows (beyond ``q_len``)
    select nothing and output zeros.

    q: (B, H, L, d); pages_k/v: (P, Hkv, ps, d); centroids: (P, Hkv, d);
    block_table: (B, npg); kv_len: (B,) pre-chunk lengths (the chunk and
    its centroid updates must already be appended); q_len: (B,);
    scales_k/v: (P, Hkv) fp32 per-page dequant scales of a quantized
    pool (None = unquantized) — applied on the densified view, never to
    the routing centroids.
    """
    b, h, nq, d = q.shape
    _, hkv, ps, _ = pages_k.shape
    npg = block_table.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    idx, sel_valid = moba_paged_prefill_route(q, centroids, block_table,
                                              kv_len, q_len, cfg,
                                              page_size=ps,
                                              head_top_k=head_top_k)
    sel_mask = routing.selection_mask(
        jnp.where(sel_valid, idx, npg), npg)                 # (B,Hkv,G,L,npg)
    pos = kv_len[:, None] + jnp.arange(nq)                   # (B,L) abs pos
    key_pos = (jnp.arange(npg * ps))                         # logical order
    causal = pos[:, :, None] >= key_pos[None, None, :]       # (B,L,n)
    tok_sel = jnp.repeat(sel_mask, ps, axis=-1)              # (B,Hkv,G,L,n)
    mask = tok_sel & causal[:, None, None]

    tbl = jnp.maximum(block_table, 0)

    def densify(pool, scales):
        g = pool[tbl].astype(jnp.float32)                    # (B,npg,h,ps,d)
        if scales is not None:
            g = g * scales[tbl][..., None, None]
        return g.transpose(0, 2, 1, 3, 4).reshape(b, hkv, npg * ps, d)

    kf = densify(pages_k, scales_k)
    vf = densify(pages_v, scales_v)
    qg = _group_queries(q, hkv).astype(jnp.float32)          # (B,Hkv,G,L,d)
    s = jnp.einsum("bhgqd,bhsd->bhgqs", qg, kf) * scale
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask.any(-1, keepdims=True), p, 0.0)
    o = jnp.einsum("bhgqs,bhsd->bhgqd", p, vf)
    return o.reshape(b, h, nq, d).astype(q.dtype)


def moba_decode_attention(q: jax.Array, k_cache: jax.Array,
                          v_cache: jax.Array, kv_len: jax.Array,
                          cfg: MoBAConfig,
                          scale: Optional[float] = None,
                          centroids: Optional[jax.Array] = None
                          ) -> jax.Array:
    """Single-step decode: q (B, H, 1, d) against a (B, Hkv, Nmax, d) cache
    of which the first ``kv_len`` positions are valid.

    Reads only centroids + the k selected blocks: O(Nmax/B · d + k·B·d) per
    query head — the sub-quadratic decode path MoBA exists for.
    """
    b, h, _, d = q.shape
    _, hkv, nmax, _ = k_cache.shape
    bs = cfg.block_size
    nb = -(-nmax // bs)
    if nb * bs != nmax:  # ragged cache tail: pad (padded tokens are
        # masked out by the kv_len check below)
        k_cache = routing.pad_to_blocks(k_cache, bs, axis=-2)
        v_cache = routing.pad_to_blocks(v_cache, bs, axis=-2)
        nmax = nb * bs
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    # incremental centroid cache (N/B·d reads) when available; otherwise
    # recompute from the full cache (N·d reads — the baseline cost)
    cents = (centroids if centroids is not None
             else routing.block_centroids(k_cache, bs, kv_len=kv_len))
    qg = _group_queries(q, hkv).astype(jnp.float32)          # (B,Hkv,G,1,d)
    scores = jnp.einsum("bhgqd,bhnd->bhgqn", qg,
                        cents.astype(jnp.float32))
    # causal over blocks: block j valid iff it contains any position < kv_len
    blk_start = jnp.arange(nb) * bs
    valid = blk_start < kv_len                               # (nb,) or (B,1..)
    valid = jnp.broadcast_to(valid, scores.shape[:-1] + (nb,))
    own = jnp.maximum(kv_len - 1, 0) // bs
    is_own = jnp.arange(nb) == own
    is_own = jnp.broadcast_to(is_own, scores.shape[:-1] + (nb,))
    masked = jnp.where(valid, scores, NEG_INF)
    masked = jnp.where(is_own, routing.POS_INF, masked)
    top_s, top_idx = jax.lax.top_k(masked, min(cfg.top_k, nb))  # (...,k)
    if top_idx.shape[-1] < cfg.top_k:
        padw = cfg.top_k - top_idx.shape[-1]
        top_s = jnp.concatenate(
            [top_s, jnp.full(top_s.shape[:-1] + (padw,), NEG_INF)], -1)
        top_idx = jnp.concatenate(
            [top_idx, jnp.zeros(top_idx.shape[:-1] + (padw,),
                                top_idx.dtype)], -1)
    sel_valid = top_s > NEG_INF / 2

    # gather the k selected blocks: (B,Hkv,G,1,k,bs,d)
    kb = k_cache.reshape(b, hkv, nb, bs, d)
    vb = v_cache.reshape(b, hkv, nb, bs, d)
    idx = jnp.where(sel_valid, top_idx, 0)

    def gather_blocks(blocks, sel):     # blocks (nb,bs,d), sel (G,1,k)
        return blocks[sel]              # (G,1,k,bs,d)

    kg = jax.vmap(jax.vmap(gather_blocks))(kb, idx)
    vg = jax.vmap(jax.vmap(gather_blocks))(vb, idx)
    s = jnp.einsum("bhgqd,bhgqkld->bhgqkl", qg, kg.astype(jnp.float32))
    s = s * scale
    pos = idx[..., :, None] * bs + jnp.arange(bs)            # (...,k,bs)
    tok_valid = (pos < kv_len) & sel_valid[..., None]
    s = jnp.where(tok_valid, s, NEG_INF)
    sf = s.reshape(*s.shape[:-2], -1)
    p = jax.nn.softmax(sf, axis=-1).reshape(s.shape)
    o = jnp.einsum("bhgqkl,bhgqkld->bhgqd", p, vg.astype(jnp.float32))
    return o.reshape(b, h, 1, d).astype(q.dtype)
