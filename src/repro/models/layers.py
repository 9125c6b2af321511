"""Model primitives: RMSNorm, RoPE, SwiGLU MLP, GQA attention layers.

Functional style: ``init_*(key, cfg) -> params`` / ``apply(params, x) -> y``
with params as plain dicts (checkpoint- and shard-friendly).  Compute in
``cfg.dtype`` (bf16 default), params in fp32; all attention math fp32.

Key-conv caching: the depthwise conv is causal, so a convolved key never
changes once written — the KV cache stores *convolved* keys plus a (W−1)-
deep ring buffer of raw keys for the single-step decode conv.  Routing and
attention therefore always see the same convolved keys (paper App. B).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, YarnRope
from repro.core.attention import attention_dispatch
from repro.core.key_conv import (apply_key_conv, apply_key_conv_decode,
                                 init_key_conv, key_conv_state_init)
from repro.distributed.sharding import constrain, tp_enabled


def wcast(w: jax.Array, dt) -> jax.Array:
    """Cast a (possibly FSDP-sharded) weight to compute dtype and, in
    SP/FSDP mode, pin the replication AFTER the cast so SPMD all-gathers
    bf16 instead of the fp32 master (halves weight-AG bytes)."""
    w = w.astype(dt)
    if not tp_enabled():
        w = constrain(w, (None,) * w.ndim)
    return w


def _dense_init(key, shape, scale=None):
    fan_in = shape[0]
    scale = scale if scale is not None else fan_in ** -0.5
    return jax.random.normal(key, shape, jnp.float32) * scale


# ------------------------------------------------------------------- norms
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(x.dtype)


# -------------------------------------------------------------------- rope
def rope_freqs(head_dim: int, theta: float = 10000.0,
               yarn: Optional[YarnRope] = None) -> jax.Array:
    """Inverse frequencies of the (head_dim/2) rotated pairs; with
    ``yarn``, HF transformers' ``_compute_yarn_parameters``: the dims that
    turn fewer than ``beta_slow`` times over the original context are
    interpolated (frequency / factor), those that turn more than
    ``beta_fast`` times kept, and a linear ramp blends the dims between."""
    pos = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    freqs = 1.0 / (theta ** pos)
    if yarn is None:
        return freqs

    def correction_dim(rotations):
        return (head_dim * math.log(yarn.original_max_position_embeddings
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), head_dim - 1)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / ((high - low) or 0.001), 0.0, 1.0)
    return freqs / yarn.factor * ramp + freqs * (1.0 - ramp)


def yarn_attention_factor(yarn: YarnRope) -> float:
    if yarn.attention_factor is not None:
        return yarn.attention_factor
    return 0.1 * math.log(yarn.factor) + 1.0 if yarn.factor > 1 else 1.0


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float = 10000.0,
               yarn: Optional[YarnRope] = None) -> jax.Array:
    """x: (B, H, N, d); positions: (N,) shared or (B, N) per-sequence
    (ragged serving batches where each row sits at a different offset).
    ``yarn`` scales the frequencies and cos/sin (:func:`rope_freqs`)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta, yarn)
    positions = jnp.asarray(positions)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., N, d/2)
    ang = ang[None, None] if positions.ndim == 1 else ang[:, None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None:
        mscale = yarn_attention_factor(yarn)
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------- mlp
def init_mlp(key, d_model: int, d_ff: int) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w_gate": _dense_init(k1, (d_model, d_ff)),
            "w_up": _dense_init(k2, (d_model, d_ff)),
            "w_down": _dense_init(k3, (d_ff, d_model))}


def apply_mlp(p: dict, x: jax.Array) -> jax.Array:
    dt = x.dtype
    h = jax.nn.silu(x @ wcast(p["w_gate"], dt)) * (x @ wcast(p["w_up"], dt))
    # TP mode: hidden sharded on features (Megatron); SP/FSDP mode: stay
    # sequence-sharded — replicating here costs an (B,S,d_ff) all-gather.
    h = constrain(h, ("dp", None, "tp") if tp_enabled()
                  else ("dp", "sp", None))
    out = h @ wcast(p["w_down"], dt)
    return constrain(out, ("dp", "sp", None) if not tp_enabled()
                     else ("dp", "sp", None))


# --------------------------------------------------------------- attention
def init_attention(key, cfg: ModelConfig, kind: str) -> dict:
    d, h, hkv = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    dh = cfg.resolved_head_dim
    ks = jax.random.split(key, 6)
    p = {"wq": _dense_init(ks[0], (d, h * dh)),
         "wk": _dense_init(ks[1], (d, hkv * dh)),
         "wv": _dense_init(ks[2], (d, hkv * dh)),
         "wo": _dense_init(ks[3], (h * dh, d))}
    if cfg.attention.qk_norm:
        p["q_norm_scale"] = jnp.ones((dh,), jnp.float32)
        p["k_norm_scale"] = jnp.ones((dh,), jnp.float32)
    a = cfg.attention
    if kind == "moba" and a.moba is not None and a.moba.key_conv_width:
        p["key_conv"] = init_key_conv(ks[4], a.moba.key_conv_width, hkv, dh)
    return p


def _split_heads(x, n_heads, dh):
    b, n, _ = x.shape
    return x.reshape(b, n, n_heads, dh).transpose(0, 2, 1, 3)


def _uses_rope(cfg: ModelConfig, kind: str) -> bool:
    a = cfg.attention
    if not a.use_rope or kind == "cross":
        return False
    if kind == "moba":
        return getattr(a, "rope_on_moba", True)
    return True


def apply_attention(p: dict, x: jax.Array, cfg: ModelConfig, kind: str,
                    *, positions: Optional[jax.Array] = None,
                    cache: Optional[dict] = None,
                    backend: str = "reference",
                    cross_kv: Optional[jax.Array] = None,
                    causal: bool = True,
                    page_state: Optional[dict] = None,
                    head_top_k: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, Optional[dict]]:
    """Self (or cross) attention layer.  Returns (out, updated_cache).

    ``backend`` names a registered attention backend (``core.backends``);
    every implementation choice below routes through the registry's
    capability query rather than string branches.

    The cache protocol admits two interchangeable cache kinds behind this
    one interface: the dense per-sequence cache from ``init_cache`` and
    the paged pool from ``serving.paged_cache`` (recognised by its
    ``pages_k`` leaf).  Paged caches additionally need ``page_state`` =
    {block_table (B,npg), kv_len (B,) pre-step lengths, q_len (B,) new
    tokens this step, active (B,) bool} from the scheduler.

    ``head_top_k``: optional (H,) int32 per-query-head routing budgets
    in [1, moba.top_k] from a calibrated routing profile (DESIGN.md §8).
    Only the paged MoBA paths consume it; dense/swa/cross ignore it.
    """
    dt = x.dtype
    a = cfg.attention
    b, n, _ = x.shape
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim

    q = _split_heads(x @ wcast(p["wq"], dt), h, dh)
    src = cross_kv if cross_kv is not None else x
    k = _split_heads(src @ wcast(p["wk"], dt), hkv, dh)
    v = _split_heads(src @ wcast(p["wv"], dt), hkv, dh)
    if kind == "moba" and n > 1:
        # SP layout: queries sharded on sequence, K/V replicated over
        # 'model' (see distributed/moba_sp.py)
        q = constrain(q, ("dp", None, "sp", None))
        k = constrain(k, ("dp", None, None, None))
        v = constrain(v, ("dp", None, None, None))
    else:
        q = constrain(q, ("dp", "tp", None, None))
        k = constrain(k, ("dp", "tp", None, None))

    if a.qk_norm and "q_norm_scale" in p:
        q = rms_norm(q, p["q_norm_scale"], cfg.rms_norm_eps)
        k = rms_norm(k, p["k_norm_scale"], cfg.rms_norm_eps)

    q_positions = positions
    if positions is None:
        positions = (jnp.arange(n) if cache is None
                     else cache["len"] + jnp.arange(n))
        # a fresh SWA sequence passes no positions on: the backend then
        # knows they are 0..n-1 and scores only the band (a traced arange
        # cannot be inspected); RoPE still reads ``positions``
        if kind != "swa" or cache is not None:
            q_positions = positions
    if _uses_rope(cfg, kind):
        yarn = a.moba_yarn if kind == "moba" else None
        q = apply_rope(q, positions, a.rope_theta, yarn)
        if cross_kv is None:
            k = apply_rope(k, positions, a.rope_theta, yarn)

    conv_w = p.get("key_conv") if kind == "moba" else None
    kv_len = None
    new_cache = None
    if cache is not None and "pages_k" in cache and cross_kv is None:
        with jax.named_scope("attend"):
            o, new_cache = _paged_attend(q, k, v, cache, page_state, cfg,
                                         kind, positions, backend, conv_w,
                                         head_top_k=head_top_k)
        o = o.transpose(0, 2, 1, 3).reshape(b, n, h * dh)
        out = o @ wcast(p["wo"], dt)
        return out, new_cache
    if cache is not None and cross_kv is None:
        if conv_w is not None:
            if n == 1:
                k, conv_state = apply_key_conv_decode(
                    conv_w, k, cache["key_conv_state"])
            else:  # prefill: conv the whole prefix, keep raw tail as state
                depth = cache["key_conv_state"].shape[2]
                raw = jnp.concatenate(
                    [cache["key_conv_state"], k.astype(
                        cache["key_conv_state"].dtype)], axis=2)
                conv_state = raw[:, :, -depth:] if depth else \
                    cache["key_conv_state"]
                k = apply_key_conv(conv_w, k)
        idx = cache["len"]
        kc = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, idx, 0))
        vc = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, idx, 0))
        new_cache = dict(cache, k=kc, v=vc, len=idx + n)
        if conv_w is not None:
            new_cache["key_conv_state"] = conv_state
        if "centroids" in cache:
            from repro.core import routing as _routing
            bs_ = cfg.attention.moba.block_size
            if n == 1:
                # one rank-1 centroid update for the written block
                j = idx // bs_
                m_in = (idx % bs_).astype(jnp.float32)
                old_c = jax.lax.dynamic_slice_in_dim(
                    cache["centroids"], j, 1, axis=2)       # (B,Hkv,1,dh)
                new_c = (old_c * m_in + k.astype(jnp.float32)) / (m_in + 1)
                new_cache["centroids"] = jax.lax.dynamic_update_slice(
                    cache["centroids"], new_c, (0, 0, j, 0))
            else:  # prefill: rebuild from the updated cache once
                new_cache["centroids"] = _routing.block_centroids(
                    kc, bs_, kv_len=idx + n).astype(jnp.float32)
        k, v = kc, vc
        kv_len = idx + n
    elif conv_w is not None:
        k = apply_key_conv(conv_w, k)

    with jax.named_scope("attend"):
        o = attention_dispatch(a, "dense" if kind == "cross" else kind,
                               q, k, v, key_conv_weights=None,
                               q_positions=q_positions,
                               kv_len=kv_len, backend=backend,
                               causal=causal and cross_kv is None,
                               centroids=(new_cache or {}).get("centroids")
                               if kind == "moba" else None)
    o = o.transpose(0, 2, 1, 3).reshape(b, n, h * dh)
    o = constrain(o, ("dp", None, "tp") if tp_enabled()
                  else ("dp", "sp", None))
    out = o @ wcast(p["wo"], dt)
    if n > 1:
        out = constrain(out, ("dp", "sp", None))
    return out, new_cache


def _paged_attend(q, k, v, cache, page_state, cfg: ModelConfig, kind: str,
                  positions, backend: str, conv_w=None, head_top_k=None):
    """Paged-cache attention: append new K/V through the block table, then
    attend via the backend resolved for (kind, phase, paged).  MoBA decode
    routes on the per-page centroid cache and reads only the selected
    pages; swa decode gathers only the window's pages; dense decode
    densifies via the table.  Prefill is ragged (right-padded rows of
    ``q_len`` valid tokens) and backend-shared (see core.backends);
    ``page_state['chunked']`` (a static bool) selects the chunk-aware
    prefill that attends through the block table to earlier chunks.

    Key-conv (``conv_w``): keys are convolved *before* the page write, so
    centroids and attention always see convolved keys, exactly like the
    dense cache.  The raw-key left context lives in the pool's per-slot
    ring buffer ``key_conv_state`` — prefill rows address it via
    ``page_state['slots']``, decode rows are the slots.  Fresh rows
    (``kv_len`` 0) read a zero state, which both matches the dense path's
    zero padding bitwise and makes recycled slots' stale state harmless.
    """
    from repro.core import backends as B
    from repro.core.key_conv import (apply_key_conv_decode,
                                     apply_key_conv_with_state,
                                     key_conv_state_update)
    from repro.serving import paged_cache as PC

    assert page_state is not None, "paged cache requires page_state"
    a = cfg.attention
    n = q.shape[2]
    bt = page_state["block_table"]
    kvl = page_state["kv_len"]
    q_len = page_state["q_len"]
    active = page_state["active"]
    post_len = kvl + q_len                     # lengths after this step
    needs_conv = conv_w is not None
    htk = None
    adaptive = head_top_k is not None and kind == "moba"
    if adaptive:
        # (H,) per-query-head budgets -> the (Hkv, G) grouped-GQA layout
        # every routing path speaks (h = hkv*G + g, `_group_queries`)
        hkv, g = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads
        htk = jnp.asarray(head_top_k, jnp.int32).reshape(hkv, g)
    if needs_conv and "key_conv_state" not in cache:
        from repro.serving.scheduler import UnsupportedFeatureError
        raise UnsupportedFeatureError(
            "key_conv",
            "paged pool lacks the per-slot raw-key ring buffer; build "
            "caches with init_paged_caches(..., max_seqs > 0) for "
            "key-conv configs (DESIGN.md §4)")
    new_ring = None
    if n == 1:                                 # decode: one token per seq
        k_raw = k
        if needs_conv:
            ring = cache["key_conv_state"]     # decode rows ARE the slots
            k, stepped = apply_key_conv_decode(conv_w, k, ring)
            new_ring = jnp.where(active[:, None, None, None], stepped, ring)
        be = B.resolve(backend, kind=kind, phase="decode", cache="paged",
                       key_conv=needs_conv, adaptive=adaptive)
        new_cache = PC.paged_append_decode(cache, bt, kvl, active, k, v)
        if new_ring is not None:
            new_cache["key_conv_state"] = new_ring
        if needs_conv and "key_conv_tails" in cache:
            new_cache = PC.update_key_conv_tails(
                new_cache, bt, kvl, active.astype(jnp.int32), k_raw)
        o = be.paged_decode(a, kind, q, new_cache, bt, post_len,
                            positions=positions, head_top_k=htk)
        return o, new_cache
    # ragged prefill (fresh one-shot, or one chunk of a chunked prompt)
    if needs_conv:
        ring = cache["key_conv_state"]
        slots = page_state["slots"]            # (B,) row -> sequence slot
        state = ring[jnp.maximum(slots, 0)]
        fresh = (kvl == 0) | (slots < 0)
        state = jnp.where(fresh[:, None, None, None],
                          jnp.zeros_like(state), state)
        k_raw = k
        k = apply_key_conv_with_state(conv_w, k, state)
        stepped = key_conv_state_update(state, k_raw, q_len)
        write = jnp.where(active & (slots >= 0), slots, ring.shape[0])
        new_ring = ring.at[write].set(stepped.astype(ring.dtype),
                                      mode="drop")
    be = B.resolve(backend, kind=kind, phase="prefill", cache="paged",
                   key_conv=needs_conv, adaptive=adaptive)
    new_cache = PC.paged_append_prefill(cache, bt, q_len, k, v, kv_len=kvl)
    if new_ring is not None:
        new_cache["key_conv_state"] = new_ring
    if needs_conv and "key_conv_tails" in cache:
        new_cache = PC.update_key_conv_tails(new_cache, bt, kvl, q_len,
                                             k_raw)
    if page_state.get("chunked"):
        o = be.paged_chunk_prefill(a, kind, q, new_cache, bt, kvl, q_len,
                                   head_top_k=htk)
    else:
        o = be.paged_prefill(a, kind, q, k, v, post_len=post_len,
                             positions=jnp.arange(n), head_top_k=htk)
    return o, new_cache


def init_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> dict:
    hkv, dh = cfg.num_kv_heads, cfg.resolved_head_dim
    c = {"k": jnp.zeros((batch, hkv, max_len, dh), dtype),
         "v": jnp.zeros((batch, hkv, max_len, dh), dtype),
         "len": jnp.zeros((), jnp.int32)}
    a = cfg.attention
    if kind == "moba" and a.moba is not None:
        # incremental centroid cache: decode routing reads N/B·d instead
        # of re-reading the whole K cache (beyond-paper; EXPERIMENTS §Perf)
        nb = -(-max_len // a.moba.block_size)
        c["centroids"] = jnp.zeros((batch, hkv, nb, dh), jnp.float32)
        if a.moba.key_conv_width:
            c["key_conv_state"] = key_conv_state_init(
                a.moba.key_conv_width, batch, hkv, dh, dtype)
    return c
