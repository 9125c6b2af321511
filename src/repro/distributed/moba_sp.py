"""Sequence-parallel (SP) MoBA for training/prefill and context-parallel
(CP) MoBA decode — the production distribution of the paper's technique.

Why shard_map and not bare SPMD: MoBA's varlen layout is built with a sort
over each head's (query, block) pairs.  Left to GSPMD, a sequence- or
head-sharded sort triggers involuntary full rematerialization (measured:
~700 GB/device temp on qwen3-0.6b train_4k).  The TPU-native mapping is:

* **SP (train/prefill)**: queries sharded over ``model`` on the *sequence*
  dim; K/V replicated across ``model`` (cheap under GQA — K/V are the
  small side).  Routing is per-query, so every shard routes and attends
  its own queries against its full local K with ZERO collectives inside
  the attention body.  One K/V all-gather per layer is the entire SP cost.
* **CP (decode)**: the KV cache is sharded over ``model`` on the sequence
  dim.  Each shard scores its local centroids, proposes its local top-k,
  all shards agree on the global top-k from the gathered (tp·k) candidate
  scores — *centroid scores are the only cross-chip traffic* (the paper's
  insight that routing compresses K by B× becomes a comms win here) — then
  each shard attends only its locally-owned selected blocks and the
  partials lse-merge with one tiny all-gather.  Per-step traffic is
  O(nb + tp·k·(d+2)) floats instead of O(N·d) for dense CP decode.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoBAConfig
from repro.core import routing
from repro.distributed import sharding as shmod
from repro.kernels import ref as kref

NEG_INF = routing.NEG_INF


def _mesh_info(axis: str = "model"):
    """Active mesh + the data axes usable for batch sharding.  ``axis``
    names the SP/CP axis (``model`` on training meshes; the sharded
    serving engine may CP over its own axis) and is excluded from the
    batch axes so the two never collide."""
    mesh = shmod._ACTIVE["mesh"]
    if mesh is None or axis not in mesh.axis_names:
        return None, None
    dp = tuple(a for a in shmod.data_axes(mesh) if a != axis)
    return mesh, dp


def moba_attention_sp(q: jax.Array, k: jax.Array, v: jax.Array,
                      cfg: MoBAConfig, scale: Optional[float] = None,
                      q_positions: Optional[jax.Array] = None,
                      tile: int = 128, use_scan: bool = True,
                      axis: str = "model") -> jax.Array:
    """SP MoBA: q (B,H,Nq,d) seq-sharded over ``axis``; K/V replicated."""
    b, h, nq, d = q.shape
    n = k.shape[2]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    mesh, dp = _mesh_info(axis)
    tp = mesh.shape[axis] if mesh else 1
    if mesh is None or nq % tp or nq // tp < 1:
        return kref.moba_sparse_xla(q, k, v, cfg, q_positions=q_positions,
                                    scale=scale, tile=tile,
                                    use_scan=use_scan)
    bspec = dp if b % _axes_size(mesh, dp) == 0 else None
    nq_local = nq // tp
    offset = n - nq

    # jax.checkpoint = the paper's backward-with-recomputation (Alg. 5) at
    # the XLA level: scores/probs are rebuilt tile-by-tile in the backward
    # instead of being stored by AD through the tile scan.
    @jax.checkpoint
    def local_fn(q_l, k_l, v_l):
        shard = jax.lax.axis_index(axis)
        qpos = shard * nq_local + jnp.arange(nq_local) + offset
        return kref.moba_sparse_xla(
            q_l, k_l, v_l, cfg, q_positions=qpos, scale=scale,
            tile=min(tile, nq_local), use_scan=use_scan)

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(bspec, None, axis, None),
                  P(bspec, None, None, None),
                  P(bspec, None, None, None)),
        out_specs=P(bspec, None, axis, None), check_vma=False)
    return fn(q, k, v)


def _axes_size(mesh, axes):
    s = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        s *= mesh.shape[a]
    return s


def moba_decode_cp(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                   kv_len: jax.Array, cfg: MoBAConfig,
                   scale: Optional[float] = None,
                   centroids: Optional[jax.Array] = None,
                   axis: str = "model") -> jax.Array:
    """Context-parallel MoBA decode.

    q (B,H,1,d) replicated over ``axis``; caches (B,Hkv,Nmax,d) sharded
    over ``axis`` on the sequence dim.  Distributed top-k: local
    candidates → global agreement → local block attention → lse merge.

    Falls back to single-host decode when there is no mesh OR the cache
    layout cannot shard cleanly (``nmax`` not a multiple of shards ×
    block size) — a serving engine must degrade, not crash, on an
    awkward cache length.
    """
    b, h, _, d = q.shape
    _, hkv, nmax, _ = k_cache.shape
    bs = cfg.block_size
    g = h // hkv
    tk = cfg.top_k
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    mesh, dp = _mesh_info(axis)
    tp = mesh.shape[axis] if mesh is not None else 1
    if mesh is None or nmax % (tp * bs) != 0:
        from repro.core.moba import moba_decode_attention
        return moba_decode_attention(q, k_cache, v_cache, kv_len, cfg,
                                     scale=scale, centroids=centroids)
    bspec = dp if b % _axes_size(mesh, dp) == 0 else None
    n_local = nmax // tp
    nb_local = n_local // bs

    def local_fn(q_l, k_l, v_l, kv_len_l, cents_l):
        kv_len_s = kv_len_l.reshape(())
        shard = jax.lax.axis_index(axis)
        base = shard * n_local                       # global pos of shard
        qg = q_l.reshape(b_local(q_l), hkv, g, d).astype(jnp.float32)

        kb = k_l.reshape(-1, hkv, nb_local, bs, d).astype(jnp.float32)
        if cents_l is not None:
            # incremental centroid cache: N/B·d reads instead of N·d
            cents = cents_l.astype(jnp.float32)
        else:
            # recompute local centroids over valid positions (baseline)
            pos = (base + jnp.arange(nb_local)[:, None] * bs
                   + jnp.arange(bs)[None, :])        # (nb_l, bs)
            valid_tok = pos < kv_len_s
            denom = jnp.maximum(valid_tok.sum(-1), 1).astype(jnp.float32)
            cents = ((kb * valid_tok[None, None, :, :, None]).sum(-2)
                     / denom[None, None, :, None])   # (B,Hkv,nb_l,d)

        scores = jnp.einsum("bhgd,bhnd->bhgn", qg, cents)
        blk_start = base + jnp.arange(nb_local) * bs
        blk_valid = blk_start < kv_len_s
        own = jnp.maximum(kv_len_s - 1, 0) // bs     # global own block id
        is_own = (base // bs + jnp.arange(nb_local)) == own
        masked = jnp.where(blk_valid, scores, NEG_INF)
        masked = jnp.where(is_own, routing.POS_INF, masked)

        # local top-k candidates (block global ids + scores)
        tk_l = min(tk, nb_local)
        loc_s, loc_i = jax.lax.top_k(masked, tk_l)   # (B,Hkv,G,tk_l)
        if tk_l < tk:
            loc_s = jnp.concatenate(
                [loc_s, jnp.full(loc_s.shape[:-1] + (tk - tk_l,),
                                 NEG_INF)], -1)
            loc_i = jnp.concatenate(
                [loc_i, jnp.zeros(loc_i.shape[:-1] + (tk - tk_l,),
                                  loc_i.dtype)], -1)
        glob_i = base // bs + loc_i

        # gather candidates from all shards: tiny (tp·k scalars per head)
        all_s = jax.lax.all_gather(loc_s, axis, axis=3)   # (...,tp,tk)
        all_i = jax.lax.all_gather(glob_i, axis, axis=3)
        all_s = all_s.reshape(*loc_s.shape[:3], tp * tk)
        all_i = all_i.reshape(*loc_s.shape[:3], tp * tk)
        gtop_s, gtop_pos = jax.lax.top_k(all_s, tk)          # global top-k
        gtop_i = jnp.take_along_axis(all_i, gtop_pos, axis=-1)
        gsel_valid = gtop_s > NEG_INF / 2

        # my locally-owned selected blocks → dense local attention, others
        # masked out.  Worst case each shard attends ≤ k local blocks.
        sel_here = (gsel_valid
                    & (gtop_i >= base // bs)
                    & (gtop_i < base // bs + nb_local))      # (B,Hkv,G,tk)
        loc_blk = jnp.clip(gtop_i - base // bs, 0, nb_local - 1)

        def gather_blocks(blocks, idx):   # (nb_l,bs,d), (G,tk)
            return blocks[idx]            # (G,tk,bs,d)

        kg = jax.vmap(jax.vmap(gather_blocks))(kb, loc_blk)
        vb = v_l.reshape(-1, hkv, nb_local, bs, d).astype(jnp.float32)
        vg = jax.vmap(jax.vmap(gather_blocks))(vb, loc_blk)
        s = jnp.einsum("bhgd,bhgkld->bhgkl", qg, kg) * scale
        tok_pos = (base + loc_blk[..., None] * bs
                   + jnp.arange(bs))                          # (...,tk,bs)
        tok_valid = ((tok_pos < kv_len_s) & sel_here[..., None])
        s = jnp.where(tok_valid, s, NEG_INF)
        sf = s.reshape(*s.shape[:3], -1)                      # (B,Hkv,G,kl)
        m = sf.max(-1)
        m_safe = jnp.maximum(m, NEG_INF / 2)
        p = jnp.exp(sf - m_safe[..., None]) * (sf > NEG_INF / 2)
        l = p.sum(-1)
        o = jnp.einsum("bhgx,bhgxd->bhgd", p.reshape(s.shape).reshape(
            *s.shape[:3], -1), vg.reshape(*vg.shape[:3], -1, d))
        m = jnp.where(l > 0, m, NEG_INF)

        # merge partials across shards (tiny: d+2 floats per head)
        o_all = jax.lax.all_gather(o, axis)                # (tp,...)
        m_all = jax.lax.all_gather(m, axis)
        l_all = jax.lax.all_gather(l, axis)
        mm = jnp.max(m_all, axis=0)
        mm_safe = jnp.maximum(mm, NEG_INF / 2)
        w = jnp.exp(m_all - mm_safe[None])
        lt = jnp.maximum((l_all * w).sum(0), 1e-30)
        out = (o_all * w[..., None]).sum(0) / lt[..., None]
        return out.reshape(-1, h, 1, d).astype(q_l.dtype)

    def b_local(q_l):
        return q_l.shape[0]

    cent_spec = (P(bspec, None, axis, None) if centroids is not None
                 else P())
    if centroids is None:
        fn = jax.shard_map(
            lambda q_l, k_l, v_l, kl: local_fn(q_l, k_l, v_l, kl, None),
            mesh=mesh,
            in_specs=(P(bspec, None, None, None),
                      P(bspec, None, axis, None),
                      P(bspec, None, axis, None),
                      P()),
            out_specs=P(bspec, None, None, None), check_vma=False)
        return fn(q, k_cache, v_cache,
                  kv_len.reshape(1).astype(jnp.int32))
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(bspec, None, None, None),
                  P(bspec, None, axis, None),
                  P(bspec, None, axis, None),
                  P(), cent_spec),
        out_specs=P(bspec, None, None, None), check_vma=False)
    return fn(q, k_cache, v_cache, kv_len.reshape(1).astype(jnp.int32),
              centroids)
