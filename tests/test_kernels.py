"""Per-kernel allclose vs the pure-jnp oracles, shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoBAConfig
from repro.core import moba, routing
from repro.kernels import ops, ref
from repro.kernels.centroids import block_centroids_kernel
from repro.kernels.flash_topk import flash_topk
from repro.kernels.moba_fwd import moba_fwd


def make_qkv(seed=0, b=1, h=4, hkv=2, n=256, d=32, dtype=jnp.float32,
             scale=0.5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, h, n, d), dtype) * scale
    k = jax.random.normal(ks[1], (b, hkv, n, d), dtype) * scale
    v = jax.random.normal(ks[2], (b, hkv, n, d), dtype)
    return q, k, v


TOLS = {jnp.float32: dict(rtol=2e-4, atol=2e-4),
        jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,bs", [(256, 32), (128, 16), (512, 64), (192, 32)])
def test_centroid_kernel_sweep(n, bs, dtype):
    k = jax.random.normal(jax.random.PRNGKey(n), (4, n, 32), dtype)
    out = block_centroids_kernel(k, bs)
    expected = ref.centroids_ref(k, bs)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("grid", ["grouped", "flat"])
@pytest.mark.parametrize("n,bs,k,qt", [(256, 32, 3, 64), (256, 32, 8, 128),
                                       (512, 64, 2, 128), (128, 16, 4, 32)])
def test_flash_topk_sweep(n, bs, k, qt, grid):
    q, kk, _ = make_qkv(n + k, n=n)
    cfg = MoBAConfig(block_size=bs, top_k=k)
    cents = routing.block_centroids(kk, bs).reshape(-1, n // bs, 32)
    sel_k = flash_topk(q.reshape(-1, n, 32), cents, k, bs,
                       group=2, num_q_heads=4, q_tile=qt, grid=grid)
    sel_r = moba.moba_selection(q, kk, cfg).reshape(-1, n, k)
    assert int((sel_k != sel_r).sum()) == 0


@pytest.mark.parametrize("grid", ["grouped", "flat"])
def test_flash_topk_padded_centroid_edge(grid):
    """nb % cent_tile != 0: the wrapper pads the centroid array and the
    kernels must never select a pad block (9 blocks, cent_tile 8)."""
    n, bs, k = 288, 32, 4
    q, kk, _ = make_qkv(7, n=n)
    cfg = MoBAConfig(block_size=bs, top_k=k)
    cents = routing.block_centroids(kk, bs).reshape(-1, n // bs, 32)
    sel_k = flash_topk(q.reshape(-1, n, 32), cents, k, bs,
                       group=2, num_q_heads=4, q_tile=96, cent_tile=8,
                       grid=grid)
    sel_r = moba.moba_selection(q, kk, cfg).reshape(-1, n, k)
    assert int((sel_k != sel_r).sum()) == 0


def test_flash_topk_unknown_grid_rejected():
    q, kk, _ = make_qkv(1, n=128)
    cents = routing.block_centroids(kk, 32).reshape(-1, 4, 32)
    with pytest.raises(ValueError, match="grouped"):
        flash_topk(q.reshape(-1, 128, 32), cents, 2, 32,
                   group=2, num_q_heads=4, grid="typo")


def test_flash_topk_bidirectional():
    q, kk, _ = make_qkv(3, n=128)
    cfg = MoBAConfig(block_size=16, top_k=3, causal=False)
    cents = routing.block_centroids(kk, 16).reshape(-1, 8, 32)
    sel_k = flash_topk(q.reshape(-1, 128, 32), cents, 3, 16,
                       group=2, num_q_heads=4, q_tile=64, causal=False)
    sel_r = moba.moba_selection(q, kk, cfg).reshape(-1, 128, 3)
    assert int((sel_k != sel_r).sum()) == 0


def test_moba_fwd_partials_vs_oracle():
    """Direct check of the forward kernel's (o, m, l) partials."""
    q, k, v = make_qkv(5, b=1, h=2, hkv=1, n=128, d=16)
    cfg = MoBAConfig(block_size=16, top_k=3)
    tile = 32
    nb = 8
    sel = moba.moba_selection(q, k, cfg).reshape(2, 128, 3)
    lay = jax.vmap(
        lambda s: routing.build_varlen_layout(s, 128, nb, tile))(sel)
    qf = q.reshape(2, 128, 16)
    qi = jnp.maximum(lay.q_index, 0)
    q_sorted = jnp.take_along_axis(qf, qi[..., None], axis=1)
    q_pos = jnp.where(lay.q_index >= 0, qi, -1).astype(jnp.int32)
    k_blocks = k.reshape(1, nb, 16, 16)
    v_blocks = v.reshape(1, nb, 16, 16)
    o, m, l = moba_fwd(lay.tile_block, q_sorted, q_pos, k_blocks, v_blocks,
                       scale=0.25, block_size=16, n_tokens=128,
                       num_q_heads=2, group=2, q_tile=tile)
    for hh in range(2):
        oracle = ref.moba_partials_ref(
            q_sorted[hh], q_pos[hh], lay.slot_block[hh],
            k_blocks[0], v_blocks[0], 0.25, 16)
        np.testing.assert_allclose(np.asarray(o[hh]), np.asarray(oracle.o),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(m[hh]), np.asarray(oracle.m),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(l[hh]), np.asarray(oracle.l),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,bs,k,h,hkv,d",
                         [(256, 32, 3, 4, 2, 32),
                          (128, 16, 8, 2, 1, 16),
                          (512, 128, 2, 2, 2, 64),
                          (256, 64, 4, 8, 2, 32)])
def test_flash_moba_end_to_end_sweep(n, bs, k, h, hkv, d, dtype):
    q, kk, v = make_qkv(n * k + h, h=h, hkv=hkv, n=n, d=d, dtype=dtype)
    cfg = MoBAConfig(block_size=bs, top_k=k)
    o_k = ops.flash_moba(q, kk, v, cfg, q_tile=min(128, n))
    o_r = moba.moba_attention_reference(q, kk, v, cfg)
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32),
                               **TOLS[dtype])


def test_flash_moba_ragged_kv():
    """N not a multiple of block size exercises the tail mask."""
    q, kk, v = make_qkv(17, n=192, d=32)
    cfg = MoBAConfig(block_size=128, top_k=2)
    o_k = ops.flash_moba(q, kk, v, cfg, q_tile=64)
    o_r = moba.moba_attention_reference(q, kk, v, cfg)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=2e-4, atol=2e-4)


# ------------------------------------------------- GQA grid/dtype matrix
@pytest.mark.parametrize("grid", ["grouped", "flat"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("group,bs", [(1, 32), (2, 32), (4, 32),
                                      (1, 64), (2, 64), (4, 64),
                                      (2, 128)])
def test_flash_moba_gqa_grid_matrix(group, bs, dtype, grid):
    """End-to-end equivalence across GQA group sizes × block sizes ×
    dtypes, through both the MXU grouped/tiled and legacy flat grids."""
    h, n, d = 4, 256, 32
    hkv = h // group
    k = 2 if bs >= 128 else 3
    q, kk, v = make_qkv(group * 31 + bs, h=h, hkv=hkv, n=n, d=d,
                        dtype=dtype)
    cfg = MoBAConfig(block_size=bs, top_k=k)
    o_k = ops.flash_moba(q, kk, v, cfg, q_tile=128, grid=grid)
    o_r = moba.moba_attention_reference(q, kk, v, cfg)
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32),
                               **TOLS[dtype])


@pytest.mark.parametrize("grid", ["grouped", "flat"])
def test_flash_moba_odd_length(grid):
    """Nq not a multiple of q_tile: the wrapper pads to the tile with
    sentinel-routed rows (q_pos = -1) and slices the pad back off —
    forward and gradients must match the oracle exactly as in the
    aligned case (the ragged-length satellite)."""
    q, kk, v = make_qkv(43, n=200, d=32)
    cfg = MoBAConfig(block_size=32, top_k=3)
    o_k = ops.flash_moba(q, kk, v, cfg, q_tile=128, grid=grid)
    o_r = moba.moba_attention_reference(q, kk, v, cfg)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=2e-4, atol=2e-4)

    def loss_k(q, k, v):
        return jnp.sum(ops.flash_moba(q, k, v, cfg, q_tile=128,
                                      grid=grid) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(moba.moba_attention_reference(q, k, v, cfg) ** 2)

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, kk, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, kk, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("grid", ["grouped", "flat"])
def test_flash_moba_gqa_group_8_forward_and_backward(grid):
    """Eight query heads to a kv head, as Mellum2's 32 q / 4 kv: the
    output and dq, dk, dv as the oracle's."""
    q, kk, v = make_qkv(61, h=8, hkv=1, n=256, d=32)
    cfg = MoBAConfig(block_size=32, top_k=3)
    o_k = ops.flash_moba(q, kk, v, cfg, q_tile=64, grid=grid)
    o_r = moba.moba_attention_reference(q, kk, v, cfg)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=2e-4, atol=2e-4)

    def loss(attend):
        return lambda q, k, v: jnp.sum(jnp.sin(attend(q, k, v)))

    gk = jax.grad(loss(lambda q, k, v: ops.flash_moba(
        q, k, v, cfg, q_tile=64, grid=grid)), argnums=(0, 1, 2))(q, kk, v)
    gr = jax.grad(loss(lambda q, k, v: moba.moba_attention_reference(
        q, k, v, cfg)), argnums=(0, 1, 2))(q, kk, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("kb_tile", [8, 16, 64])
def test_flash_moba_kb_tile_sweep(kb_tile):
    """Explicit kb_tile settings (sub-block K/V streaming) are
    numerically identical to whole-block processing."""
    q, kk, v = make_qkv(53, n=256, d=32)
    cfg = MoBAConfig(block_size=64, top_k=3)
    o_r = moba.moba_attention_reference(q, kk, v, cfg)
    o_k = ops.flash_moba(q, kk, v, cfg, q_tile=64, kb_tile=kb_tile,
                         grid="grouped")
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=2e-4, atol=2e-4)


def test_flash_moba_grads_match_reference():
    q, kk, v = make_qkv(23, n=256, d=32)
    cfg = MoBAConfig(block_size=32, top_k=3)

    def loss_k(q, k, v):
        return jnp.sum(ops.flash_moba(q, k, v, cfg, q_tile=64) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(moba.moba_attention_reference(q, k, v, cfg) ** 2)

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, kk, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, kk, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def test_moba_sparse_xla_matches_reference():
    q, kk, v = make_qkv(29, n=256, d=32)
    cfg = MoBAConfig(block_size=32, top_k=3)
    o_s = ref.moba_sparse_xla(q, kk, v, cfg, tile=64)
    o_r = moba.moba_attention_reference(q, kk, v, cfg)
    np.testing.assert_allclose(np.asarray(o_s), np.asarray(o_r),
                               rtol=2e-4, atol=2e-4)


def test_moba_sparse_xla_grads():
    q, kk, v = make_qkv(31, n=128, d=16)
    cfg = MoBAConfig(block_size=16, top_k=4)

    def loss_s(q, k, v):
        return jnp.sum(ref.moba_sparse_xla(q, k, v, cfg, tile=32) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(moba.moba_attention_reference(q, k, v, cfg) ** 2)

    gs = jax.grad(loss_s, argnums=(0, 1, 2))(q, kk, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, kk, v)
    for a, b in zip(gs, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def test_flash_moba_with_key_conv_grads():
    """Gradient flows to key-conv weights through the kernel path."""
    from repro.core.key_conv import apply_key_conv, init_key_conv
    q, kk, v = make_qkv(37, n=128, d=16)
    cfg = MoBAConfig(block_size=16, top_k=3, key_conv_width=3)
    w = init_key_conv(jax.random.PRNGKey(0), 3, 2, 16)

    def loss(w):
        kc = apply_key_conv(w, kk)
        return jnp.sum(ops.flash_moba(q, kc, v, cfg, q_tile=32) ** 2)

    g = jax.grad(loss)(w)
    assert bool(jnp.isfinite(g).all())
    assert float(jnp.abs(g).max()) > 0
