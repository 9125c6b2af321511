"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Interpret mode accepts any block shape; the chip's compiler (Mosaic) does
not.  These tests lower every kernel of the serving and training paths
with ``interpret=False`` at qwen3-0.6b widths (H=16, Hkv=8, d=128, block
and page 128, top_k 8) in bf16 for a *described* ``v5e:2x2`` topology —
no chip needed — and check that the compiled program holds the kernel
(``tpu_custom_call``).  A block layout Mosaic refuses fails here, not on
the chip.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker imports this file.
"""
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.sharding import SingleDeviceSharding

from repro.configs.base import MoBAConfig
from repro.kernels import ops
from repro.kernels.centroids import block_centroids_kernel
from repro.kernels.flash_topk import flash_topk
from repro.kernels.moba_decode import moba_paged_decode_pallas
from repro.kernels.tiling import LANE, check_blocks

H, HKV, D, BS, TOP_K = 16, 8, 128, 128, 8     # qwen3-0.6b (MoBA)
N = 2048                                      # sequence length
PAGES, BATCH, PAGES_PER_SEQ = 64, 4, 16       # decode pool
MOBA = MoBAConfig(block_size=BS, top_k=TOP_K)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_centroids_lower(one_chip):
    _compile(lambda k: block_centroids_kernel(k, BS, interpret=False),
             [((HKV, N, D), jnp.bfloat16)], one_chip)


@pytest.mark.parametrize("grid", ["grouped", "flat"])
def test_flash_topk_lowers(one_chip, grid):
    _compile(lambda q, c: flash_topk(q, c, TOP_K, BS, group=H // HKV,
                                     num_q_heads=H, grid=grid,
                                     interpret=False),
             [((H, N, D), jnp.bfloat16), ((HKV, N // BS, D), jnp.bfloat16)],
             one_chip)


QKV = [((1, H, N, D), jnp.bfloat16), ((1, HKV, N, D), jnp.bfloat16),
       ((1, HKV, N, D), jnp.bfloat16)]


@pytest.mark.parametrize("grid", ["grouped", "flat"])
def test_flash_moba_forward_lowers(one_chip, grid):
    _compile(lambda q, k, v: ops.flash_moba(q, k, v, MOBA, grid=grid,
                                            interpret=False),
             QKV, one_chip)


@pytest.mark.parametrize("grid", ["grouped", "flat"])
def test_flash_moba_grad_lowers(one_chip, grid):
    """``jax.grad`` of flash_moba lowers the forward and ``moba_bwd``."""
    def loss(q, k, v):
        out = ops.flash_moba(q, k, v, MOBA, grid=grid, interpret=False)
        return out.astype(jnp.float32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)), QKV, one_chip)


# the MoBA stage scope (``kernels/ops.py``) each kernel runs under
KERNEL_OF_STAGE = {"moba_centroids": "centroids", "moba_topk": "flash_topk",
                   "moba_fwd": "moba_fwd", "moba_bwd": "moba_bwd"}


@pytest.mark.parametrize("grid", ["grouped", "flat"])
def test_flash_moba_grad_kernels_carry_their_scopes(one_chip, grid):
    """Every ``tpu_custom_call`` of the lowered grad is named by its
    Pallas call, which heads its op's name in a device trace, and has
    its stage scope in its ``op_name``."""
    def loss(q, k, v):
        out = ops.flash_moba(q, k, v, MOBA, grid=grid, interpret=False)
        return out.astype(jnp.float32).sum()

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), QKV, one_chip)
    seen = set()
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = re.match(r"\s*(?:ROOT )?%([\w-]+?)(?:\.\d+)? = ", line)[1]
        path = re.search(r'op_name="([^"]*)"', line)[1]
        # a segment may be wrapped by a transformation: jvp(moba_fwd)
        segs = [re.sub(r"^(?:[\w.]+\()*(.*?)\)*$", r"\1", s)
                for s in path.split("/")]
        stages = [s for s in segs if s in KERNEL_OF_STAGE]
        assert stages, path
        assert name == KERNEL_OF_STAGE[stages[-1]], (name, stages)
        seen.add(name)
    assert seen == set(KERNEL_OF_STAGE.values())


@pytest.mark.parametrize("grid", ["grouped", "flat"])
def test_flash_moba_q_tile_8_lowers(one_chip, grid):
    """The contract's edge in bf16: q and do blocks of 8 rows over a
    2048-row sequence, neither bf16's 16-row tile nor the full dim."""
    def loss(q, k, v):
        out = ops.flash_moba(q, k, v, MOBA, grid=grid, q_tile=8,
                             interpret=False)
        return out.astype(jnp.float32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)), QKV, one_chip)


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.float8_e4m3fn])
def test_eight_row_block_lowers(one_chip, dtype):
    """The contract's edge in 1-byte dtypes: a copy through (8, 128)
    blocks of a (64, 128) array passes ``check_blocks`` and lowers,
    though a packed tile of these dtypes holds 32 rows."""
    def copy(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    spec = pl.BlockSpec((8, LANE), lambda i: (i, 0))
    shape = jax.ShapeDtypeStruct((64, LANE), dtype)
    check_blocks("copy", ("x", "out"), (spec, spec), (shape, shape))
    _compile(lambda x: pl.pallas_call(
        copy, grid=(8,), in_specs=[spec], out_specs=spec,
        out_shape=shape, interpret=False)(x), [((64, LANE), dtype)],
        one_chip)


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8])
@pytest.mark.parametrize("grid", ["grouped", "flat"])
def test_paged_decode_lowers(one_chip, grid, kv_dtype):
    """Paged decode on the head-major ``(P, Hkv, ps, d)`` pool, bf16 and
    int8 (with per-page fp32 scales)."""
    quant = kv_dtype == jnp.int8

    def fn(q, pk, pv, cents, table, kv_len, *scales):
        return moba_paged_decode_pallas(
            q, pk, pv, cents, table, kv_len, MOBA, grid=grid,
            interpret=False, scales_k=scales[0] if quant else None,
            scales_v=scales[1] if quant else None)

    pool = ((PAGES, HKV, BS, D), kv_dtype)
    shapes = [((BATCH, H, 1, D), jnp.bfloat16), pool, pool,
              ((PAGES, HKV, D), jnp.float32),
              ((BATCH, PAGES_PER_SEQ), jnp.int32), ((BATCH,), jnp.int32)]
    if quant:
        shapes += [((PAGES, HKV), jnp.float32)] * 2
    _compile(fn, shapes, one_chip)


# Mellum2-12B-A2.5B: the expert layer one chip of eight holds (8 of 64
# experts of width 896, top 8, hidden 2304) at 1x4096 tokens, and the
# MoBA kernels at 32 q / 4 kv heads of 128 (GQA group 8)
MELLUM = dict(d=2304, f=896, experts=64, held=8, top_k=8, tokens=4096)


@pytest.fixture(scope="module")
def expert_layer_text(one_chip):
    """The compiled gradient of the held-experts layer, its Pallas calls
    lowered for the chip (the layer picks interpret mode off a TPU)."""
    from repro.configs.base import ModelConfig, MoEConfig
    from repro.models import moe as MOE
    m = MELLUM
    cfg = ModelConfig(family="moe", d_model=m["d"], dtype="bfloat16",
                      moe=MoEConfig(num_experts=m["experts"],
                                    top_k=m["top_k"], expert_d_ff=m["f"],
                                    experts_held=m["held"]))
    params = jax.eval_shape(lambda: MOE.init_moe(jax.random.PRNGKey(0),
                                                 cfg))

    def loss(p, x):
        out, aux, _ = MOE.apply_moe(p, x, cfg)
        return out.astype(jnp.float32).sum() + aux

    shard = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype,  # noqa: E731
                                           sharding=one_chip)
    old = os.environ.get("REPRO_PALLAS_INTERPRET")
    os.environ["REPRO_PALLAS_INTERPRET"] = "0"
    try:
        x = jax.ShapeDtypeStruct((1, m["tokens"], m["d"]), jnp.bfloat16,
                                 sharding=one_chip)
        return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            jax.tree.map(shard, params), x).compile().as_text()
    finally:
        if old is None:
            del os.environ["REPRO_PALLAS_INTERPRET"]
        else:
            os.environ["REPRO_PALLAS_INTERPRET"] = old


def _custom_calls(text):
    return [re.sub(r'\s*(?:ROOT )?', "", ln, count=1).split(", metadata=")[0]
            for ln in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln]


def test_expert_gmm_lowers_and_carries_its_name(expert_layer_text):
    """Forward, input-gradient and weight-gradient grouped matmuls of the
    three expert matrices: nine calls, every one named ``expert_gmm``."""
    names = [re.match(r"%([\w-]+?)(?:\.\d+)? = ", c)[1]
             for c in _custom_calls(expert_layer_text)]
    assert names == ["expert_gmm"] * 9


def test_only_the_expert_gmm_pattern_finds_its_calls(expert_layer_text):
    """The benchmark finds a kernel's device ops by a pattern on the op's
    HLO text (``MATCH`` in ``bench/work/<kernel>.py``): no other kernel's
    pattern takes an ``expert_gmm`` call."""
    import importlib
    root = Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    patterns = {p.stem: importlib.import_module(f"bench.work.{p.stem}").MATCH
                for p in sorted((root / "bench" / "work").glob("*.py"))
                if "MATCH = " in p.read_text()}
    assert "expert_gmm" in patterns
    for call in _custom_calls(expert_layer_text):
        hits = {k for k, pat in patterns.items() if re.search(pat, call)}
        assert hits == {"expert_gmm"}, (hits, call[:200])


def test_flash_moba_grad_lowers_at_gqa_group_8(one_chip):
    h, hkv, d, n = 32, 4, 128, 4096

    def loss(q, k, v):
        out = ops.flash_moba(q, k, v, MOBA, interpret=False)
        return out.astype(jnp.float32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)),
             [((1, h, n, d), jnp.bfloat16), ((1, hkv, n, d), jnp.bfloat16),
              ((1, hkv, n, d), jnp.bfloat16)], one_chip)
