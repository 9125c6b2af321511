"""Dense and sliding-window attention + the per-layer dispatcher.

These are the baselines the paper compares against (dense) and interleaves
with (SWA, window 256, odd layers).  All math in fp32, inputs bf16.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import AttentionConfig

NEG_INF = -1e30


def _grouped_scores(q, k, scale):
    b, h, nq, d = q.shape
    hkv = k.shape[1]
    qg = q.reshape(b, hkv, h // hkv, nq, d).astype(jnp.float32)
    s = jnp.einsum("bhgqd,bhsd->bhgqs", qg, k.astype(jnp.float32)) * scale
    return s.reshape(b, h, nq, k.shape[2])


def _apply_and_project(p, v, out_dtype):
    b, h, nq, n = p.shape
    hkv = v.shape[1]
    pg = p.reshape(b, hkv, h // hkv, nq, n)
    o = jnp.einsum("bhgqs,bhsd->bhgqd", pg, v.astype(jnp.float32))
    return o.reshape(b, h, nq, v.shape[-1]).astype(out_dtype)


def dense_attention(q, k, v, causal: bool = True,
                    q_positions: Optional[jax.Array] = None,
                    kv_len: Optional[jax.Array] = None,
                    window: int = 0,
                    scale: Optional[float] = None) -> jax.Array:
    """Dense (optionally sliding-window) attention with GQA grouping.

    window > 0 keeps keys with q_pos - window < s <= q_pos.
    ``q_positions`` may be (Nq,) shared or (B, Nq) per-sequence (ragged
    serving batches); ``kv_len`` a scalar or (B,) per-sequence lengths.
    """
    b, h, nq, d = q.shape
    n = k.shape[2]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if q_positions is None:
        q_positions = jnp.arange(nq) + (n - nq)
    s = _grouped_scores(q, k, scale)
    spos = jnp.arange(n)
    qp = jnp.asarray(q_positions)
    qp = qp[None] if qp.ndim == 1 else qp                    # (1|B, Nq)
    mask = jnp.ones((qp.shape[0], nq, n), bool)
    if causal:
        mask &= qp[:, :, None] >= spos[None, None, :]
    if window:
        mask &= qp[:, :, None] - spos[None, None, :] < window
    if kv_len is not None:
        kvl = jnp.asarray(kv_len)
        kvl = kvl.reshape((-1, 1, 1)) if kvl.ndim else kvl
        mask &= spos[None, None, :] < kvl
    s = jnp.where(mask[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask.any(-1)[:, None, :, None], p, 0.0)
    return _apply_and_project(p, v, q.dtype)


def banded_window_attention(q, k, v, window: int,
                            scale: Optional[float] = None) -> jax.Array:
    """Causal sliding-window self-attention over whole sequences at
    positions ``0..n-1``: the function of ``dense_attention(q, k, v,
    causal=True, window=window)``, scored on a band only.

    The queries are cut into chunks of ``C`` tokens, the window rounded up
    to a multiple of 128 (and at most the sequence so rounded).  Chunk i
    scores the keys of chunks i-1 and i, which hold the whole window of
    each of its queries because ``C >= window``: ``n·2C`` scores where the
    dense path makes ``n²``.  Every score left out is one the dense path
    masks to ``NEG_INF``, whose softmax weight is exactly 0, and the math
    is the dense path's (float32 operands, the same scale and mask), so
    only the work shrinks.  Differentiated by ordinary autodiff.
    """
    with jax.named_scope("swa_band"):
        b, h, n, d = q.shape
        hkv = k.shape[1]
        if scale is None:
            scale = 1.0 / (d ** 0.5)
        c = -(-min(window, n) // 128) * 128
        nc = -(-n // c)

        def chunked(x):                           # (B,H,n,D) -> (B,H,nc,C,D)
            x = jnp.pad(x.astype(jnp.float32),
                        ((0, 0), (0, 0), (0, nc * c - n), (0, 0)))
            return x.reshape(b, x.shape[1], nc, c, x.shape[-1])

        def band(x):              # (B,Hkv,nc,C,D) -> (B,Hkv,nc,2C,D): i-1, i
            prev = jnp.pad(x[:, :, :-1],
                           ((0, 0), (0, 0), (1, 0), (0, 0), (0, 0)))
            return jnp.concatenate([prev, x], axis=3)

        qc = chunked(q).reshape(b, hkv, h // hkv, nc, c, d)
        kb, vb = band(chunked(k)), band(chunked(v))
        s = jnp.einsum("bhgcqd,bhckd->bhgcqk", qc, kb) * scale
        qpos = jnp.arange(nc * c).reshape(nc, c)
        kpos = qpos[:, :1] - c + jnp.arange(2 * c)            # (nc, 2C)
        qp, kp = qpos[:, :, None], kpos[:, None, :]
        mask = (kp >= 0) & (kp <= qp) & (qp - kp < window)   # (nc, C, 2C)
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgcqk,bhckd->bhgcqd", p, vb)
        return o.reshape(b, h, nc * c, -1)[:, :, :n].astype(q.dtype)


def attention_dispatch(cfg: AttentionConfig, kind: str, q, k, v,
                       key_conv_weights=None,
                       q_positions=None, kv_len=None,
                       backend: str = "reference",
                       causal: bool = True,
                       centroids=None) -> jax.Array:
    """Route to a registered attention backend (``core.backends``) by
    name + capability query — no per-implementation branches here.

    ``kind`` ∈ {dense, swa, moba} selects the layer behaviour; ``backend``
    selects the implementation.  Single-token calls against a cache
    (``q`` length 1 with ``kv_len``) resolve the decode phase, everything
    else the prefill phase.
    """
    from repro.core import backends as B

    needs_kconv = kind == "moba" and key_conv_weights is not None
    if kind == "moba":
        assert cfg.moba is not None
        if needs_kconv:
            from repro.core.key_conv import apply_key_conv
            k = apply_key_conv(key_conv_weights, k)
    if q.shape[2] == 1 and kv_len is not None:
        be = B.resolve(backend, kind=kind, phase="decode", cache="dense",
                       key_conv=needs_kconv)
        return be.decode(cfg, kind, q, k, v, kv_len, centroids=centroids,
                         q_positions=q_positions)
    be = B.resolve(backend, kind=kind, phase="prefill", cache="dense",
                   key_conv=needs_kconv)
    return be.prefill(cfg, kind, q, k, v, q_positions=q_positions,
                      kv_len=kv_len, causal=causal)
