"""Pallas kernel: flash sliding-window attention (the paper's odd layers).

The hybrid recipe (§5.1) interleaves SWA(256)+RoPE with MoBA layers; this
kernel covers the SWA half with FlashAttention-2 mechanics restricted to
the band ``q_pos - window < k_pos <= q_pos``: each query tile visits only
the ⌈(window+Tq)/Tk⌉ key tiles that can intersect its band (O(N·w)
instead of O(N²)), with online-softmax stats in VMEM scratch.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

NEG_INF = -1e30


def _swa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                scale: float, window: int, q_tile: int, k_tile: int,
                n_kv_tiles: int, n_tokens: int, steps: int):
    qt = pl.program_id(1)
    st = pl.program_id(2)

    @pl.when(st == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                 # (Tq, d)
    k = k_ref[0].astype(jnp.float32)                 # (Tk, d)
    v = v_ref[0].astype(jnp.float32)

    # which kv tile is this step actually visiting (mirrors the index_map)
    first_tile = jnp.maximum(qt * q_tile - (window - 1), 0) // k_tile
    unclamped = first_tile + st
    kv_tile = jnp.minimum(unclamped, n_kv_tiles - 1)

    qpos = (qt * q_tile
            + jax.lax.broadcasted_iota(jnp.int32, (q_tile, k_tile), 0))
    kpos = (kv_tile * k_tile
            + jax.lax.broadcasted_iota(jnp.int32, (q_tile, k_tile), 1))
    mask = ((kpos <= qpos) & (qpos - kpos < window)
            & (kpos < n_tokens) & (qpos < n_tokens)
            # clamped steps re-visit the last tile — contribute nothing
            & (unclamped < n_kv_tiles))

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...][:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    m_safe = jnp.maximum(m_new, NEG_INF / 2)
    alpha = jnp.exp(jnp.maximum(m_prev, NEG_INF / 2) - m_safe)
    p = jnp.exp(s - m_safe[:, None]) * mask.astype(jnp.float32)
    l_new = l_scr[...][:, 0] * alpha + jnp.sum(p, axis=1)
    acc = (acc_scr[...] * alpha[:, None]
           + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32))
    m_scr[...] = m_new[:, None]
    l_scr[...] = l_new[:, None]
    acc_scr[...] = acc

    @pl.when(st == steps - 1)
    def _emit():
        denom = jnp.maximum(l_scr[...][:, 0], 1e-30)
        o_ref[0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def swa_attention(q: jax.Array, k: jax.Array, v: jax.Array, window: int,
                  *, num_q_heads: int = 0, group: int = 1,
                  scale: Optional[float] = None, q_tile: int = 128,
                  k_tile: int = 128,
                  interpret: Optional[bool] = None) -> jax.Array:
    """q: (BH, N, d); k, v: (BKV, N, d); BH = batch·H, BKV = batch·Hkv."""
    interpret = resolve_interpret(interpret)
    bh, n, d = q.shape
    h = num_q_heads or bh
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    q_tile = min(q_tile, n)
    k_tile = min(k_tile, n)
    assert n % q_tile == 0 and n % k_tile == 0
    n_kv_tiles = n // k_tile
    # tiles a band of width `window` ending inside a q tile can touch
    steps = min((window - 1 + q_tile - 1) // k_tile + 2, n_kv_tiles)

    def kv_index(bhi, qt, st):
        kv = (bhi // h) * (h // group) + (bhi % h) // group
        first = jnp.maximum(qt * q_tile - (window - 1), 0) // k_tile
        return (kv, jnp.minimum(first + st, n_kv_tiles - 1), 0)

    kernel = functools.partial(
        _swa_kernel, scale=float(scale), window=window, q_tile=q_tile,
        k_tile=k_tile, n_kv_tiles=n_kv_tiles, n_tokens=n, steps=steps)
    return pl.pallas_call(
        kernel,
        grid=(bh, n // q_tile, steps),
        in_specs=[
            pl.BlockSpec((1, q_tile, d), lambda bhi, qt, st: (bhi, qt, 0)),
            pl.BlockSpec((1, k_tile, d), kv_index),
            pl.BlockSpec((1, k_tile, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, q_tile, d),
                               lambda bhi, qt, st: (bhi, qt, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, n, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((q_tile, 1), jnp.float32),
                        pltpu.VMEM((q_tile, 1), jnp.float32),
                        pltpu.VMEM((q_tile, d), jnp.float32)],
        interpret=interpret, name="swa",
    )(q, k, v)
