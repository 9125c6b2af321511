"""Paged KV cache with a per-page centroid cache (device side, pure jax).

A page holds ``page_size`` tokens of K and V for every kv head of one
layer slot.  ``page_size`` equals the MoBA ``block_size``, so **one page
is exactly one routable block**: the per-page centroid cache doubles as
the decode routing table, and reading it costs O(N/B·d) instead of the
O(N·d) full-cache centroid recompute the old decode path paid per step.

Layout: pools are head-major ``(num_pages, hkv, page_size, dh)``, so one
kv head's page is a contiguous ``(page_size, dh)`` tile — the block the
Pallas decode kernel streams (a TPU block's last two dims must be whole
(sublane, 128) tiles or the full array dims; DESIGN.md §5).  The append
paths scatter a token's ``(hkv, dh)`` row with one advanced index
``pool.at[page, :, offset]``.  Invalid writes (padded rows, unassigned
pages) are routed to the out-of-bounds page ``num_pages`` and dropped by
the scatter — no dump page needed.

Sequences are described *outside* the pool by a block table: row i maps
sequence i's logical page j to a physical page id (−1 = unassigned).
Block tables and sequence lengths live on the host (scheduler) and are
passed into the jitted steps as small int32 arrays each step.

Centroid semantics match the dense cache exactly (tests assert this):
  * prefill recomputes each touched page's centroid from the stored keys
    (same math as :func:`repro.core.routing.block_centroids`);
  * decode folds the new key in with one rank-1 update
    ``c ← (c·m + k)/(m+1)`` — amortized O(d) per token.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import quantization as Q

# leaves indexed by physical page id on their (first non-shard) axis —
# the unit that page-granular ops (COW copy, swap save/restore) move.
# ``scales_k``/``scales_v`` (quantized pools only) live here so COW
# copies and host swap carry payload + scales atomically for free.
# ``key_conv_state`` is per sequence *slot*, not per page, and moves via
# the ring-row helpers instead.
PAGE_LEAVES = ("pages_k", "pages_v", "scales_k", "scales_v",
               "centroids", "key_conv_tails")


def resolve_page_size(cfg: ModelConfig) -> int:
    """Page size = MoBA block size when any layer routes; else 16."""
    a = cfg.attention
    if a.moba is not None and any(k == "moba" for k in cfg.layer_pattern):
        return a.moba.block_size
    return 16


def init_page_pool(cfg: ModelConfig, num_pages: int, page_size: int,
                   with_centroids: bool, dtype=jnp.bfloat16,
                   max_seqs: int = 0, prefix_tails: bool = False,
                   kv_dtype: str = "fp32") -> Dict:
    """One layer slot's pool.  MoBA slots of key-conv models additionally
    carry a per-sequence-slot ring buffer ``key_conv_state`` of the last
    ``key_conv_width - 1`` raw (post-RoPE, pre-conv) keys, sized by
    ``max_seqs`` — the single-step decode conv and chunked prefill both
    read/write it by scheduler slot id (DESIGN.md §4).

    ``prefix_tails`` (prefix-cache engines of key-conv models) adds a
    per-*page* companion ``key_conv_tails`` holding the raw keys of each
    page's last ``width - 1`` positions: when admission maps a sequence
    onto cached pages, its ring row is loaded from the last matched
    page's tail, so the suffix prefill convs with exactly the state a
    contiguous prefill would have carried (docs/serving.md).

    ``kv_dtype`` of ``"int8"``/``"fp8"`` stores the K/V payload
    quantized with per-(page, kv head) fp32 ``scales_k``/``scales_v``
    leaves (init 1.0 so dequantizing a fresh page is a no-op); routing
    state — centroids, key-conv ring buffers and tails — stays at full
    precision regardless (``core/quantization.py``).  ``"fp32"`` keeps
    the pre-quantization layout byte-for-byte: pages at ``dtype``, no
    scales leaves."""
    hkv, dh = cfg.num_kv_heads, cfg.resolved_head_dim
    if kv_dtype not in Q.KV_DTYPES:
        raise ValueError(f"unknown kv_dtype {kv_dtype!r}; "
                         f"expected one of {Q.KV_DTYPES}")
    # only the page payload is quantized; key-conv ring buffers / tails
    # below keep the compute ``dtype`` (they feed the fp32 router)
    pg_dtype = dtype if kv_dtype == "fp32" else Q.payload_dtype(kv_dtype)
    pool = {"pages_k": jnp.zeros((num_pages, hkv, page_size, dh), pg_dtype),
            "pages_v": jnp.zeros((num_pages, hkv, page_size, dh), pg_dtype)}
    if kv_dtype != "fp32":
        pool["scales_k"] = jnp.ones((num_pages, hkv), jnp.float32)
        pool["scales_v"] = jnp.ones((num_pages, hkv), jnp.float32)
    if with_centroids:
        pool["centroids"] = jnp.zeros((num_pages, hkv, dh), jnp.float32)
        a = cfg.attention
        width = a.moba.key_conv_width if a.moba is not None else 0
        if width and max_seqs:
            pool["key_conv_state"] = jnp.zeros(
                (max_seqs, hkv, width - 1, dh), dtype)
            if prefix_tails:
                pool["key_conv_tails"] = jnp.zeros(
                    (num_pages, hkv, width - 1, dh), dtype)
    return pool


def is_paged(cache) -> bool:
    return cache is not None and "pages_k" in cache


def shard_pools(caches, mesh, n_shards: int):
    """Stack ``n_shards`` copies of a (zero-initialised) cache pytree
    along a new leading shard axis and lay the result out over the
    mesh's ``data`` axis — each shard owns its own pool slice (pages,
    centroid cache, key-conv ring buffers); nothing is replicated.

    The stacked layout is what the sharded engine's ``shard_map`` step
    functions split: inside the body each device sees leading dim 1,
    strips it, and runs the unmodified single-host step (DESIGN.md §7).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = NamedSharding(mesh, P("data"))
    return jax.tree.map(
        lambda x: jax.device_put(
            jnp.broadcast_to(x[None], (n_shards,) + x.shape), spec),
        caches)


def paged_append_decode(cache: Dict, block_table: jax.Array,
                        kv_len: jax.Array, active: jax.Array,
                        k_new: jax.Array, v_new: jax.Array) -> Dict:
    """Write one token per active sequence at position ``kv_len[i]``.

    k_new/v_new: (B, hkv, 1, dh) in compute dtype.  Updates the written
    page's centroid incrementally.  Inactive rows write nothing.

    Quantized pools (``scales_k`` present) requantize the whole tail
    page read-modify-write: gather → dequantize → insert the token →
    amax over the now-valid positions → scatter payload + scale back.
    The centroid update below is untouched — it folds the *fp32*
    incoming key into the old centroid, never reading the pool, so
    routing state is bitwise identical across ``kv_dtype`` modes.
    """
    pk, pv = cache["pages_k"], cache["pages_v"]
    num_pages, hkv, ps, dh = pk.shape
    page_idx = kv_len // ps
    off = kv_len % ps
    phys = jnp.take_along_axis(block_table, page_idx[:, None], axis=1)[:, 0]
    ok = active & (phys >= 0)
    pidx = jnp.where(ok, phys, num_pages)                   # OOB = dropped
    tok_k = k_new[:, :, 0]                                   # (B,hkv,dh)
    tok_v = v_new[:, :, 0]
    if "scales_k" in cache:
        kv_dt = Q.kv_dtype_of(pk.dtype)
        ph = jnp.maximum(phys, 0)
        onehot = (jnp.arange(ps)[None, :] == off[:, None])[:, None, :, None]
        vmask = (jnp.arange(ps)[None, :] <= off[:, None])[:, None, :, None]

        def requant(pool, scales, tok):
            page = Q.dequantize(pool[ph],
                                scales[ph][:, :, None, None])  # (B,h,ps,d)
            page = jnp.where(onehot, tok.astype(jnp.float32)[:, :, None],
                             page)
            scale = Q.compute_scale(page, (2, 3), kv_dt,
                                    where=vmask)             # (B,h)
            payload = Q.quantize(page, scale[:, :, None, None], kv_dt)
            return (pool.at[pidx].set(payload, mode="drop"),
                    scales.at[pidx].set(scale, mode="drop"))

        new_pk, new_sk = requant(pk, cache["scales_k"], tok_k)
        new_pv, new_sv = requant(pv, cache["scales_v"], tok_v)
        new = dict(cache, pages_k=new_pk, pages_v=new_pv,
                   scales_k=new_sk, scales_v=new_sv)
    else:
        new = dict(cache,
                   pages_k=pk.at[pidx, :, off].set(tok_k.astype(pk.dtype),
                                                   mode="drop"),
                   pages_v=pv.at[pidx, :, off].set(tok_v.astype(pv.dtype),
                                                   mode="drop"))
    if "centroids" in cache:
        cents = cache["centroids"]                           # (P,hkv,dh) f32
        m = off.astype(jnp.float32)[:, None, None]           # tokens in page
        old = cents[jnp.maximum(phys, 0)]                    # (B,hkv,dh)
        upd = (old * m + tok_k.astype(jnp.float32)) / (m + 1.0)
        new["centroids"] = cents.at[pidx].set(upd, mode="drop")
    return new


def paged_append_prefill(cache: Dict, block_table: jax.Array,
                         q_len: jax.Array, k_new: jax.Array,
                         v_new: jax.Array,
                         kv_len: Optional[jax.Array] = None) -> Dict:
    """Scatter a right-padded ragged prompt chunk into its pages.

    k_new/v_new: (B, hkv, L, dh); row i's valid tokens occupy absolute
    positions [kv_len[i], kv_len[i] + q_len[i]).  ``kv_len`` of None (or
    zeros) is a fresh one-shot prefill; non-zero offsets are chunked
    prefill continuations writing into a partially-filled tail page.
    Every page the chunk touches gets its centroid recomputed from the
    stored keys — for a tail page that earlier chunks started, the
    recompute reads those chunks' keys back from the pool, so the result
    is identical to a one-shot prefill of the whole prefix.

    Quantized pools stage the touched pages in fp32 — prior pool tokens
    dequantized, the incoming chunk scattered over them — then
    requantize each touched page whole (amax over its valid tokens) and
    scatter payload + scales back.  Centroids are computed *from the
    staging view* with the exact masked reduce the fp32 path uses, so
    any page fully written by this call (every page of a one-shot
    prefill) gets a bitwise-identical centroid; only a chunked/suffix
    tail page whose earlier tokens already live quantized in the pool
    sees quantization error in its centroid.
    """
    pk, pv = cache["pages_k"], cache["pages_v"]
    num_pages, hkv, ps, dh = pk.shape
    b, _, length, _ = k_new.shape
    npg = block_table.shape[1]
    if kv_len is None:
        kv_len = jnp.zeros((b,), jnp.int32)
    pos = kv_len[:, None] + jnp.arange(length)               # (B,L) abs pos
    logical = jnp.minimum(pos // ps, npg - 1)
    phys = jnp.take_along_axis(block_table, logical, axis=1)  # (B,L)
    valid = (jnp.arange(length)[None, :] < q_len[:, None]) & (phys >= 0)
    off = (pos % ps).reshape(-1)
    vals_k = k_new.transpose(0, 2, 1, 3).reshape(b * length, hkv, dh)
    vals_v = v_new.transpose(0, 2, 1, 3).reshape(b * length, hkv, dh)
    post = q_len + kv_len                                    # (B,)
    page_start = jnp.arange(npg) * ps
    cnt = jnp.clip(post[:, None] - page_start, 0, ps)
    touched = ((cnt > 0) & (block_table >= 0)
               & (page_start + ps > kv_len[:, None]))        # (B,npg)
    wmask = (jnp.arange(ps)[None, None, :]
             < cnt[..., None])[:, :, None, :, None]          # (B,npg,1,ps,1)
    idx = jnp.where(touched, block_table, num_pages).reshape(-1)

    if "scales_k" in cache:
        kv_dt = Q.kv_dtype_of(pk.dtype)
        tbl = jnp.maximum(block_table, 0)
        row = jnp.repeat(jnp.arange(b), length)
        lpage = jnp.where(valid, logical, npg).reshape(-1)   # npg = dropped

        def stage_and_quant(pool, scales, vals):
            stage = Q.dequantize(pool[tbl],
                                 scales[tbl][..., None, None])
            stage = stage.at[row, lpage, :, off].set(
                vals.astype(jnp.float32), mode="drop")       # (B,npg,h,ps,d)
            scale = Q.compute_scale(stage, (3, 4), kv_dt, where=wmask)
            payload = Q.quantize(stage, scale[..., None, None], kv_dt)
            return stage, (
                pool.at[idx].set(payload.reshape(b * npg, hkv, ps, dh),
                                 mode="drop"),
                scales.at[idx].set(scale.reshape(b * npg, hkv),
                                   mode="drop"))

        stage_k, (new_pk, new_sk) = stage_and_quant(
            pk, cache["scales_k"], vals_k)
        _, (new_pv, new_sv) = stage_and_quant(
            pv, cache["scales_v"], vals_v)
        new = dict(cache, pages_k=new_pk, pages_v=new_pv,
                   scales_k=new_sk, scales_v=new_sv)
        cent_src = stage_k                                   # (B,npg,h,ps,d)
    else:
        page = jnp.where(valid, phys, num_pages).reshape(-1)  # OOB = dropped
        new_pk = pk.at[page, :, off].set(vals_k.astype(pk.dtype),
                                         mode="drop")
        new_pv = pv.at[page, :, off].set(vals_v.astype(pv.dtype),
                                         mode="drop")
        new = dict(cache, pages_k=new_pk, pages_v=new_pv)
        cent_src = new_pk[jnp.maximum(block_table, 0)]       # (B,npg,h,ps,d)
    if "centroids" in cache:
        sums = (cent_src.astype(jnp.float32) * wmask).sum(axis=3)
        cent = sums / jnp.maximum(cnt, 1)[..., None, None].astype(
            jnp.float32)                                     # (B,npg,h,d)
        new["centroids"] = cache["centroids"].at[idx].set(
            cent.reshape(b * npg, hkv, dh), mode="drop")
    return new


def paged_gather_kv(cache: Dict, block_table: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    """Densify: (B, hkv, npg*ps, dh) K and V in logical token order.

    Positions past a sequence's length (and pages it never allocated)
    hold whatever the pool contains — callers mask with ``kv_len``.
    Quantized pools come back dequantized to fp32 (payload × per-page
    scale), so every densify consumer is dtype-oblivious.
    """
    pk, pv = cache["pages_k"], cache["pages_v"]
    num_pages, hkv, ps, dh = pk.shape
    b, npg = block_table.shape
    tbl = jnp.maximum(block_table, 0)

    def densify(pool, scales=None):
        g = pool[tbl]                                        # (B,npg,h,ps,d)
        if scales is not None:
            g = Q.dequantize(g, scales[tbl][..., None, None])
        return g.transpose(0, 2, 1, 3, 4).reshape(b, hkv, npg * ps, dh)

    return (densify(pk, cache.get("scales_k")),
            densify(pv, cache.get("scales_v")))


def swa_windowed_decode_attention(q: jax.Array, cache: Dict,
                                  block_table: jax.Array,
                                  kv_len: jax.Array, window: int,
                                  scale: Optional[float] = None
                                  ) -> jax.Array:
    """Decode-step sliding-window attention that gathers only the
    ``ceil(window/page_size)+1`` pages that can intersect the window
    (closing the DESIGN.md §4 open item): the per-step copy is bounded
    by O(window), not O(max_seq_len) densify-then-mask.

    q (B, H, 1, d); ``kv_len`` post-append lengths, so the query sits at
    position ``kv_len - 1`` and attends keys in ``(qpos-window, qpos]``.
    Numerics match the densified path exactly (same masked softmax over
    the same key set).  Rows with ``kv_len`` 0 return zeros.
    """
    from repro.core.attention import (NEG_INF, _apply_and_project,
                                      _grouped_scores)

    pk, pv = cache["pages_k"], cache["pages_v"]
    num_pages, hkv, ps, dh = pk.shape
    b, npg = block_table.shape
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    wpg = min(npg, -(-window // ps) + 1)
    qpos = kv_len - 1                                        # (B,)
    start = jnp.maximum(qpos - window + 1, 0) // ps          # first page
    logical = start[:, None] + jnp.arange(wpg)[None, :]      # (B,wpg)
    phys = jnp.take_along_axis(block_table,
                               jnp.minimum(logical, npg - 1), axis=1)
    ok = (logical < npg) & (phys >= 0)                       # (B,wpg)
    tbl = jnp.maximum(phys, 0)
    kg, vg = pk[tbl], pv[tbl]                                # (B,wpg,h,ps,d)
    if "scales_k" in cache:
        kg = Q.dequantize(kg, cache["scales_k"][tbl][..., None, None])
        vg = Q.dequantize(vg, cache["scales_v"][tbl][..., None, None])
    kg = kg.transpose(0, 2, 1, 3, 4).reshape(b, hkv, wpg * ps, dh)
    vg = vg.transpose(0, 2, 1, 3, 4).reshape(b, hkv, wpg * ps, dh)
    kpos = (logical[:, :, None] * ps
            + jnp.arange(ps)[None, None, :]).reshape(b, wpg * ps)
    mask = (jnp.repeat(ok, ps, axis=1)
            & (kpos <= qpos[:, None])
            & (qpos[:, None] - kpos < window))               # (B,wpg*ps)
    s = _grouped_scores(q, kg, scale)                        # (B,H,1,n)
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask.any(-1)[:, None, None, None], p, 0.0)
    return _apply_and_project(p, vg, q.dtype)


def gather_seq_centroids(cache: Dict, block_table: jax.Array) -> jax.Array:
    """Per-sequence centroid view (B, hkv, npg, dh) in logical order."""
    cents = cache["centroids"][jnp.maximum(block_table, 0)]  # (B,npg,h,d)
    return cents.transpose(0, 2, 1, 3)


# --------------------------------------------------------------------------
# page-granular cache ops (prefix cache / COW / swap preemption).
#
# ``caches`` here is the engine-level pytree ``{"slot_i": pool}`` whose
# leaves carry a leading layer-group dim (G, ...) — or (S, G, ...) for the
# sharded engine, selected by ``shard``.  These run OUTSIDE the jitted
# step functions, between the scheduler's plan and the step's first
# write; plain XLA scatter/gather ops are plenty (a handful of pages per
# event), and keeping them un-jitted avoids retrace churn on the ragged
# page counts.
# --------------------------------------------------------------------------

def _page_view(x, shard):
    return x if shard is None else x[shard]


@functools.partial(jax.jit, static_argnames=("shard",))
def _copy_pages_jit(caches, s, d, shard):
    def one(pool):
        new = dict(pool)
        for name in PAGE_LEAVES:
            if name in pool:
                x = pool[name]
                if shard is None:
                    new[name] = x.at[:, d].set(x[:, s])
                else:
                    xs = x[shard]
                    new[name] = x.at[shard].set(xs.at[:, d].set(xs[:, s]))
        return new

    return {k: one(v) for k, v in caches.items()}


def copy_pages(caches, src: List[int], dst: List[int],
               shard: Optional[int] = None):
    """Copy-on-write: duplicate physical pages ``src[i] -> dst[i]`` in
    every page-indexed leaf (K/V, centroid, key-conv tails), so a
    sequence diverging mid-page writes into its own copy.  Copying the
    centroid too keeps the page immediately routable — the suffix
    prefill then recomputes it from stored keys once it appends.  One
    jitted dispatch over all pools/leaves — the engine drains COWs one
    pair at a time, so the (1,)-shaped trace compiles once."""
    if not src:
        return caches
    return _copy_pages_jit(caches, jnp.asarray(src, jnp.int32),
                           jnp.asarray(dst, jnp.int32), shard)


def gather_pages_host(caches, pages: List[int],
                      shard: Optional[int] = None) -> Dict:
    """Snapshot physical pages to host numpy (swap-out): every
    page-indexed leaf sliced at ``pages``, keyed (slot_name, leaf)."""
    idx = jnp.asarray(pages, jnp.int32)
    out = {}
    for sname, pool in caches.items():
        for name in PAGE_LEAVES:
            if name in pool:
                x = _page_view(pool[name], shard)
                out[(sname, name)] = np.asarray(x[:, idx])
    return out


def scatter_pages_device(caches, pages: List[int], data: Dict,
                         shard: Optional[int] = None):
    """Swap-in: write a :func:`gather_pages_host` snapshot into the
    (freshly reserved) physical pages ``pages``."""
    idx = jnp.asarray(pages, jnp.int32)
    new = {}
    for sname, pool in caches.items():
        p2 = dict(pool)
        for name in PAGE_LEAVES:
            if name in pool:
                x = pool[name]
                vals = jnp.asarray(data[(sname, name)], x.dtype)
                if shard is None:
                    p2[name] = x.at[:, idx].set(vals)
                else:
                    p2[name] = x.at[shard].set(
                        x[shard].at[:, idx].set(vals))
        new[sname] = p2
    return new


def gather_ring_rows(caches, slot: int,
                     shard: Optional[int] = None) -> Dict:
    """Host snapshot of one sequence slot's key-conv ring row (empty
    dict for non-key-conv pools)."""
    out = {}
    for sname, pool in caches.items():
        if "key_conv_state" in pool:
            x = _page_view(pool["key_conv_state"], shard)
            out[(sname, "key_conv_state")] = np.asarray(x[:, slot])
    return out


def scatter_ring_rows(caches, slot: int, data: Dict,
                      shard: Optional[int] = None):
    new = {}
    for sname, pool in caches.items():
        p2 = pool
        if "key_conv_state" in pool:
            x = pool["key_conv_state"]
            vals = jnp.asarray(data[(sname, "key_conv_state")], x.dtype)
            if shard is None:
                x = x.at[:, slot].set(vals)
            else:
                x = x.at[shard].set(x[shard].at[:, slot].set(vals))
            p2 = dict(pool, key_conv_state=x)
        new[sname] = p2
    return new


def load_ring_from_tails(caches, slots: List[int], pages: List[int],
                         shard: Optional[int] = None):
    """Prefix-hit admission for key-conv models: sequence ``slots[i]``'s
    ring row becomes page ``pages[i]``'s raw-key tail — the last
    ``width - 1`` raw keys before the match boundary, exactly the state
    a contiguous prefill would have carried into the suffix."""
    if not slots:
        return caches
    sl = jnp.asarray(slots, jnp.int32)
    pg = jnp.asarray(pages, jnp.int32)
    new = {}
    for sname, pool in caches.items():
        p2 = pool
        if "key_conv_tails" in pool and "key_conv_state" in pool:
            ring, tails = pool["key_conv_state"], pool["key_conv_tails"]
            if shard is None:
                ring = ring.at[:, sl].set(
                    tails[:, pg].astype(ring.dtype))
            else:
                ring = ring.at[shard].set(ring[shard].at[:, sl].set(
                    tails[shard][:, pg].astype(ring.dtype)))
            p2 = dict(pool, key_conv_state=ring)
        new[sname] = p2
    return new


def update_key_conv_tails(cache: Dict, block_table: jax.Array,
                          kv_len: jax.Array, q_len: jax.Array,
                          k_raw: jax.Array) -> Dict:
    """Maintain the per-page raw-key tails through an append (runs
    inside the jitted step, right after the page write).

    k_raw (B, hkv, L, dh) are the *pre-conv* keys row i writes at
    absolute positions [kv_len[i], kv_len[i] + q_len[i]); any that land
    in a page's last ``width - 1`` positions are recorded in that page's
    tail slot.  Decode calls this with L == 1 and ``q_len`` the active
    mask.  Single-pool view — no layer-group dim (the step's scan
    strips it)."""
    tails = cache["key_conv_tails"]           # (P, hkv, depth, dh)
    num_pages, hkv, depth, dh = tails.shape
    ps = cache["pages_k"].shape[2]
    b, _, length, _ = k_raw.shape
    npg = block_table.shape[1]
    pos = kv_len[:, None] + jnp.arange(length)               # (B,L) abs
    logical = jnp.minimum(pos // ps, npg - 1)
    phys = jnp.take_along_axis(block_table, logical, axis=1)  # (B,L)
    ti = pos % ps - (ps - depth)                             # tail index
    valid = ((jnp.arange(length)[None, :] < q_len[:, None])
             & (phys >= 0) & (ti >= 0))
    slot = jnp.where(valid, phys * depth + ti,
                     num_pages * depth).reshape(-1)
    vals = k_raw.transpose(0, 2, 1, 3).reshape(b * length, hkv, dh)
    flat = tails.transpose(0, 2, 1, 3).reshape(
        num_pages * depth, hkv, dh)
    flat = flat.at[slot].set(vals.astype(tails.dtype), mode="drop")
    return dict(cache, key_conv_tails=flat.reshape(
        num_pages, depth, hkv, dh).transpose(0, 2, 1, 3))
