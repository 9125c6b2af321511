"""Shared geometry helpers and the compiled-mode block contract of the
Pallas kernels.

Mosaic, the TPU's kernel compiler, accepts a VMEM block only if each of
its last two dims is a multiple of (8, 128) — for every dtype, packed
ones included (``tests/test_tpu_compile.py`` lowers 8-row blocks in
bf16, int8 and fp8) — or equals that dim of the array it tiles;
anything else fails deep inside the
lowering with an unshaped error.  Every kernel wrapper therefore hands
its block specs to :func:`check_blocks` before a compiled
``pallas_call``, which raises a shaped ``ValueError`` naming the operand,
its block and the violated dim (DESIGN.md §2/§5).  The kernels lay out
their operands so that most blocks are whole by construction (per-query
vectors as ``(BH, L, 1)`` columns, head-major page pools, centroid tiles
of ``sublane(dtype)`` rows); what a caller can still get wrong is a
``q_tile`` that is neither a multiple of 8 nor the whole sequence.

Interpret mode (`kernels/runtime.py`) accepts any shape and never calls
the contract — CPU CI runs the small test geometries there.
"""
from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp

LANE = 128      # TPU lane count: the minor block dim's grain
SUBLANE = 8     # the second-minor block dim's grain, any dtype


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def resolve_kb_tile(kb_tile: int, block_size: int) -> int:
    """K/V streaming granularity of the kb-tiled training grids: ``0``
    picks one lane-width slice, or the whole block when it is smaller
    (small blocks mask-pad instead of splitting).  It must split the
    key block evenly so the kb grid dim covers it exactly."""
    kb_tile = min(kb_tile or LANE, block_size)
    if block_size % kb_tile:
        raise ValueError(
            f"kb_tile={kb_tile} must evenly divide block_size={block_size} "
            f"so the kb grid dimension covers the key block exactly")
    return kb_tile


def sublane(dtype) -> int:
    """Rows of one packed (rows × 128) vreg tile for ``dtype``: 8 for
    fp32 (and any wider dtype), 16 for bf16, 32 for int8/fp8.  Padding a
    tile's row count to this avoids partial vregs; legality only needs
    :data:`SUBLANE`."""
    return SUBLANE * max(1, 4 // jnp.dtype(dtype).itemsize)


def check_blocks(kernel: str, names: Sequence[str], specs: Sequence,
                 operands: Sequence) -> None:
    """Mosaic's block rule for a compiled ``pallas_call``: ``specs[i]``
    (a ``BlockSpec``) tiles ``operands[i]`` (anything with ``.shape``),
    called ``names[i]`` in the error.  Raises one ``ValueError`` listing
    every violating block."""
    problems = []
    for name, spec, x in zip(names, specs, operands, strict=True):
        block, shape = tuple(spec.block_shape), tuple(x.shape)
        for axis, grain in ((-2, SUBLANE), (-1, LANE)):
            if len(block) < -axis:
                continue
            b, full = block[axis], shape[axis]
            if b % grain and b != full:
                problems.append(
                    f"{name} block {block} over {shape}: dim {axis} = {b} "
                    f"must be a multiple of {grain} or the full {full}")
    if problems:
        raise ValueError(
            f"compiled {kernel} kernel block contract violated: "
            + "; ".join(problems)
            + " — choose a conforming geometry (q_tile a multiple of "
              f"{SUBLANE} or the whole sequence) or run interpret mode "
              "(REPRO_PALLAS_INTERPRET=1)")
