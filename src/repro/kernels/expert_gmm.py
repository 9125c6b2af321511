"""Pallas kernel: grouped matmul over the experts an expert layer holds.

The expert layer (``models/moe.py``) lays its routed (token, expert)
pairs out expert by expert in a buffer of ``M`` rows, cut into tiles of
``tm`` rows that each belong to one expert: an expert's rows start on a
tile boundary, and every held expert has at least one tile (of zero rows
where no token chose it).  ``tile_group[t]`` names the expert of tile
``t``; the tiles from ``num_tiles`` on hold no pair.

* :func:`gmm` — ``out[tile t] = lhs[tile t] @ rhs[tile_group[t]]``, zeros
  on the unused tiles; grid (N/tn, M/tm), each step one (tm, K) × (K, tn)
  product.
* backward — ``dlhs = gmm(dout, rhsᵀ)``, and ``drhs[g] = Σ_t lhs[t]ᵀ
  dout[t]`` over the tiles of group ``g`` (:func:`_tgmm`); grid (K/tk,
  N/tn, M/tm), accumulating in VMEM over the consecutive tiles of a group.

The unused tiles read the last used tile's blocks again, so no DMA is
issued for them, and their compute is skipped.  Every call is named
``expert_gmm``, which heads its op's name in a device trace.

TPU notes: the blocks are whole (tm, K) row tiles and lane-width
multiples of N and K (or the whole dim where it is no multiple of 128),
so they meet Mosaic's block rule (``kernels/tiling.py``).  Products
accumulate in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret
from repro.kernels.tiling import LANE, check_blocks

NAME = "expert_gmm"
RHS_BLOCK = 1 << 21     # elements of one rhs block (4 MiB in bf16)
ACC_BLOCK = 1 << 19     # elements of tgmm's float32 accumulator (2 MiB)


def _split(dim: int, cap: int) -> int:
    """The widest lane-multiple block of ``dim`` that divides it and
    holds at most ``cap`` (at least one lane); ``dim`` itself where it
    is no multiple of a lane."""
    if dim % LANE:
        return dim
    best = LANE
    for b in range(LANE, dim + 1, LANE):
        if dim % b == 0 and b <= cap:
            best = b
    return best


def _gmm_kernel(tile_group, num_tiles, lhs_ref, rhs_ref, out_ref):
    used = pl.program_id(1) < num_tiles[0]

    @pl.when(used)
    def _():
        out_ref[...] = jnp.dot(
            lhs_ref[...], rhs_ref[0],
            preferred_element_type=jnp.float32).astype(out_ref.dtype)

    @pl.when(jnp.logical_not(used))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


def _gmm(lhs, rhs, tile_group, num_tiles, tm, interpret):
    m, k = lhs.shape
    groups, _, n = rhs.shape
    tn = _split(n, RHS_BLOCK // k)

    def last(t, nt):
        return jnp.minimum(t, nt[0] - 1)

    in_specs = [
        pl.BlockSpec((tm, k), lambda j, t, tg, nt: (last(t, nt), 0)),
        pl.BlockSpec((1, k, tn),
                     lambda j, t, tg, nt: (tg[last(t, nt)], 0, j)),
    ]
    out_spec = pl.BlockSpec((tm, tn), lambda j, t, tg, nt: (t, j))
    out_shape = jax.ShapeDtypeStruct((m, n), lhs.dtype)
    if not interpret:
        check_blocks(NAME, ("lhs", "rhs", "out"), in_specs + [out_spec],
                     (lhs, rhs, out_shape))
    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // tn, m // tm),
            in_specs=in_specs, out_specs=out_spec),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name=NAME,
    )(tile_group, num_tiles, lhs, rhs)


def _tgmm_kernel(tile_group, num_tiles, lhs_ref, dout_ref, out_ref,
                 acc_ref):
    t = pl.program_id(2)
    nt = num_tiles[0]
    g = tile_group[jnp.minimum(t, nt - 1)]
    first = (t == 0) | (tile_group[jnp.maximum(t - 1, 0)] != g)
    last = (t == nt - 1) | (tile_group[jnp.minimum(t + 1, nt - 1)] != g)

    @pl.when(t < nt)
    def _():
        @pl.when(first)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], dout_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(last)
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _tgmm(lhs, dout, tile_group, num_tiles, groups, tm, out_dtype,
          interpret):
    """``(groups, K, N)``: each group's ``lhs[rows]ᵀ @ dout[rows]``."""
    m, k = lhs.shape
    n = dout.shape[1]
    tn = _split(n, 1024)
    tk = _split(k, ACC_BLOCK // tn)
    kt = k // tk

    def last(t, nt):
        return jnp.minimum(t, nt[0] - 1)

    in_specs = [
        pl.BlockSpec((tm, tk), lambda i, j, t, tg, nt: (last(t, nt), i)),
        pl.BlockSpec((tm, tn), lambda i, j, t, tg, nt: (last(t, nt), j)),
    ]
    # 2-D (groups·K, N), so the call's result is a matrix like gmm's
    out_spec = pl.BlockSpec(
        (tk, tn), lambda i, j, t, tg, nt: (tg[last(t, nt)] * kt + i, j))
    out_shape = jax.ShapeDtypeStruct((groups * k, n), out_dtype)
    if not interpret:
        check_blocks(NAME, ("lhs", "dout", "out"), in_specs + [out_spec],
                     (lhs, dout, out_shape))
    out = pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(kt, n // tn, m // tm),
            in_specs=in_specs, out_specs=out_spec,
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=NAME,
    )(tile_group, num_tiles, lhs, dout)
    return out.reshape(groups, k, n)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _gmm_vjp(lhs, rhs, tile_group, num_tiles, tm, interpret):
    return _gmm(lhs, rhs, tile_group, num_tiles, tm, interpret)


def _gmm_fwd(lhs, rhs, tile_group, num_tiles, tm, interpret):
    out = _gmm(lhs, rhs, tile_group, num_tiles, tm, interpret)
    return out, (lhs, rhs, tile_group, num_tiles)


def _gmm_bwd(tm, interpret, res, dout):
    lhs, rhs, tile_group, num_tiles = res
    dlhs = _gmm(dout, rhs.swapaxes(1, 2), tile_group, num_tiles, tm,
                interpret)
    drhs = _tgmm(lhs, dout, tile_group, num_tiles, rhs.shape[0], tm,
                 rhs.dtype, interpret)
    return dlhs, drhs, None, None


_gmm_vjp.defvjp(_gmm_fwd, _gmm_bwd)


def gmm(lhs: jax.Array, rhs: jax.Array, tile_group: jax.Array,
        num_tiles: jax.Array, tm: int,
        interpret: bool | None = None) -> jax.Array:
    """lhs (M, K) rows laid out in tiles of ``tm``, rhs (G, K, N) one
    matrix per group, ``tile_group`` (M/tm,) int32 the group of each
    tile, ``num_tiles`` (1,) int32 the tiles in use -> (M, N) in lhs's
    dtype, zero on the unused tiles.  Differentiable in lhs and rhs."""
    if lhs.shape[0] % tm:
        raise ValueError(f"{lhs.shape[0]} rows are no whole number of "
                         f"{tm}-row tiles")
    return _gmm_vjp(lhs, rhs, tile_group, num_tiles, tm,
                    resolve_interpret(interpret))
