"""Pallas kernel: fused key-block centroid computation (paper Alg. 2).

Grid (heads, nb / cb); each step loads ``cb`` consecutive (B, d) key
blocks into VMEM and reduces each to its (1, d) mean, writing one
(cb, d) tile of centroids.  Output is B× smaller than K — the point of
the fusion is that subsequent routing reads K̃, not K.

TPU notes: ``cb`` is the output dtype's sublane grain (or all of nb when
nb is smaller), so the (cb, d) output block is a whole (sublane, 128)
tile or the full array — a (1, d) block per key block is not a legal
TPU block (DESIGN.md §2).  The reduction runs on the VPU in fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import resolve_interpret
from repro.kernels.tiling import check_blocks, round_up, sublane


def _centroid_kernel(k_ref, out_ref, *, block_size: int, n_tokens: int,
                     cb: int):
    j = pl.program_id(1)
    d = k_ref.shape[-1]
    kb = k_ref[0].astype(jnp.float32).reshape(cb, block_size, d)
    # mask the ragged tail (positions >= n_tokens) and the pad blocks
    start = (j * cb + jax.lax.broadcasted_iota(jnp.int32, (cb, 1), 0)
             ) * block_size
    pos = start[:, :, None] + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_size, 1), 1)
    valid = (pos < n_tokens).astype(jnp.float32)           # (cb, B, 1)
    count = jnp.clip(n_tokens - start, 0, block_size)      # (cb, 1)
    denom = jnp.maximum(count, 1).astype(jnp.float32)
    out_ref[0] = (jnp.sum(kb * valid, axis=1) / denom).astype(out_ref.dtype)


def block_centroids_kernel(k: jax.Array, block_size: int,
                           interpret: bool | None = None) -> jax.Array:
    """k: (H, N, d) -> (H, nb, d).  N need not be a block multiple: the
    ragged tail block averages only its valid positions."""
    interpret = resolve_interpret(interpret)
    h, n, d = k.shape
    nb = -(-n // block_size)
    cb = min(nb, sublane(k.dtype))
    nb_p = round_up(nb, cb)
    pad = nb_p * block_size - n
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
    in_spec = pl.BlockSpec((1, cb * block_size, d), lambda hh, j: (hh, j, 0))
    out_spec = pl.BlockSpec((1, cb, d), lambda hh, j: (hh, j, 0))
    out_shape = jax.ShapeDtypeStruct((h, nb_p, d), k.dtype)
    if not interpret:
        check_blocks("block_centroids", ("k", "centroids"),
                     (in_spec, out_spec), (k, out_shape))
    out = pl.pallas_call(
        functools.partial(_centroid_kernel, block_size=block_size,
                          n_tokens=n, cb=cb),
        grid=(h, nb_p // cb), in_specs=[in_spec], out_specs=out_spec,
        out_shape=out_shape, interpret=interpret, name="centroids",
    )(k)
    return out[:, :nb]
