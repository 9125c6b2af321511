"""Quantized int8/fp8 K/V page pools: the fp32-oracle accuracy gate.

Covers the PR-8 acceptance surface: quantize-on-append / dequantize-on-
read page pools (``core/quantization.py``, ``serving/paged_cache.py``)
measured against the fp32 paths they shadow —

  * quantize/dequantize round-trip error bounds per dtype, including
    the all-zero page (scale 1.0, exact) and extreme-scale pages;
  * the XLA gather path and both Pallas decode grids on a quantized
    pool vs the *fp32* XLA oracle on the same underlying K/V, within
    per-dtype tolerance — and the Pallas grids vs the quantized XLA
    path at float-rounding distance (dequantization happens in the
    kernel, not in a pre-pass);
  * routing state is bitwise identical across ``kv_dtype`` modes:
    prefill and decode appends produce byte-equal centroids, so
    ``moba_paged_route`` selects identical pages (asserted directly,
    and as needle-block retrieval parity on NIAH batches);
  * engine-level greedy decode on NIAH prompts agrees token-for-token
    across backends at each quantized dtype (xla vs flash on the same
    int8/fp8 pool), the serving analogue of the kernel-level gate;
  * the compiled-mode block contract holds byte-wide payload blocks to
    the same (8, 128)-or-full rule as fp32.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import moba
from repro.core import quantization as Q
from repro.data.niah import make_niah_batch
from repro.kernels import moba_decode as MD
from repro.models import transformer as T
from repro.serving import paged_cache as PC
from repro.serving.engine import Engine, EngineConfig

QUANT = ("int8", "fp8")
# end-to-end attention-output tolerance vs the fp32 oracle; keep in
# sync with benchmarks.decode_micro.AGREE_TOL
TOL = {"int8": 5e-2, "fp8": 2e-1}
# Pallas grids vs the quantized XLA path: same math, float-rounding only
KERNEL_TOL = 1e-3


# ------------------------------------------------------- round-trip bounds
@pytest.mark.parametrize("kv_dtype", QUANT)
def test_roundtrip_error_bound(kv_dtype):
    """|dequant(quant(x)) - x| <= scale/2 (int8, round-to-nearest) or
    one e4m3 ulp (fp8) — per (page, head) with amax-derived scales."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 3.0, size=(4, 16, 2, 8)), jnp.float32)
    scale = Q.compute_scale(x, (1, 3), kv_dtype)           # (4,2)
    payload = Q.quantize(x, scale[:, None, :, None], kv_dtype)
    assert payload.dtype == Q.PAYLOAD_DTYPES[kv_dtype]
    back = Q.dequantize(payload, scale[:, None, :, None])
    err = np.abs(np.asarray(back) - np.asarray(x))
    s = np.asarray(scale)[:, None, :, None]
    if kv_dtype == "int8":
        assert (err <= s * 0.5 + 1e-7).all()
    else:
        # e4m3: 3 mantissa bits → relative error <= 2^-4 per element
        assert (err <= np.abs(np.asarray(x)) * 2 ** -4 + s).all()


def test_all_zero_page_is_exact():
    """amax == 0 pins the scale to 1.0 so a fresh (or genuinely zero)
    page round-trips exactly and dequantizing init state is a no-op."""
    x = jnp.zeros((2, 8, 2, 4), jnp.float32)
    for kv_dtype in QUANT:
        scale = Q.compute_scale(x, (1, 3), kv_dtype)
        assert (np.asarray(scale) == 1.0).all()
        back = Q.dequantize(Q.quantize(x, scale[:, None, :, None],
                                       kv_dtype),
                            scale[:, None, :, None])
        assert (np.asarray(back) == 0.0).all()


def test_partial_page_scale_ignores_stale_positions():
    """The masked amax (``where=``) must not let garbage beyond the
    valid prefix inflate (or deflate) the scale."""
    x = jnp.concatenate([jnp.full((1, 4, 1, 2), 2.0),
                         jnp.full((1, 4, 1, 2), 1e6)], axis=1)
    wmask = (jnp.arange(8) < 4)[None, :, None, None]
    scale = Q.compute_scale(x, (1, 3), "int8", where=wmask)
    np.testing.assert_allclose(np.asarray(scale), 2.0 / 127.0, rtol=1e-6)


# ------------------------------------------ decode paths vs the fp32 oracle
GEOMETRIES = {
    "ragged": dict(kv_lens=(37, 16, 5, 61), npg=8, num_pages=32),
    # tail page a single token deep, and a one-page sequence
    "tiny-tails": dict(kv_lens=(17, 16, 1), npg=4, num_pages=16),
}


def _build(kv_dtype, geom, cfg):
    """Pool populated by the real prefill-append path (quantization
    happens where serving does it, not in the test)."""
    ps = PC.resolve_page_size(cfg)
    hkv, d, h = cfg.num_kv_heads, cfg.resolved_head_dim, cfg.num_heads
    kv_lens = np.asarray(geom["kv_lens"])
    b, npg = len(kv_lens), geom["npg"]
    rng = np.random.default_rng(7)
    pool = PC.init_page_pool(cfg, geom["num_pages"], ps,
                             with_centroids=True, dtype=jnp.float32,
                             kv_dtype=kv_dtype)
    kc = jnp.asarray(rng.normal(size=(b, hkv, npg * ps, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(b, hkv, npg * ps, d)), jnp.float32)
    free = list(range(geom["num_pages"]))
    rng.shuffle(free)
    table = np.full((b, npg), -1, np.int32)
    for i, n in enumerate(kv_lens):
        for j in range(-(-int(n) // ps)):
            table[i, j] = free.pop()
    table = jnp.asarray(table)
    pool = PC.paged_append_prefill(pool, table, jnp.asarray(kv_lens),
                                   kc, vc)
    q = jnp.asarray(rng.normal(size=(b, h, 1, d)), jnp.float32)
    return pool, table, jnp.asarray(kv_lens), q


def _decode_outs(pool, table, kv_lens, q, cfg):
    sk, sv = pool.get("scales_k"), pool.get("scales_v")
    args = (q, pool["pages_k"], pool["pages_v"], pool["centroids"],
            table, kv_lens, cfg.attention.moba)
    return {
        "xla": np.asarray(moba.moba_paged_decode_attention(
            *args, scales_k=sk, scales_v=sv)),
        "pallas_grouped": np.asarray(MD.moba_paged_decode_pallas(
            *args, grid="grouped", scales_k=sk, scales_v=sv)),
        "pallas_flat": np.asarray(MD.moba_paged_decode_pallas(
            *args, grid="flat", scales_k=sk, scales_v=sv)),
    }


@pytest.mark.parametrize("kv_dtype", QUANT)
@pytest.mark.parametrize("geom", GEOMETRIES, ids=GEOMETRIES)
def test_quantized_decode_within_tolerance_of_fp32_oracle(kv_dtype, geom):
    cfg = get_smoke_config("moba-340m")
    g = GEOMETRIES[geom]
    pool0, table, kv_lens, q = _build("fp32", g, cfg)
    oracle = _decode_outs(pool0, table, kv_lens, q, cfg)["xla"]
    pool, *_ = _build(kv_dtype, g, cfg)
    outs = _decode_outs(pool, table, kv_lens, q, cfg)
    tol = TOL[kv_dtype]
    for name, out in outs.items():
        err = np.abs(out - oracle).max()
        assert err <= tol, (name, err)
    # the kernels dequantize in VMEM — they must sit on the quantized
    # XLA path at float-rounding distance, not merely inside ``tol``
    for grid in ("pallas_grouped", "pallas_flat"):
        np.testing.assert_allclose(outs[grid], outs["xla"],
                                   atol=KERNEL_TOL, rtol=KERNEL_TOL)
    # routing state: byte-equal centroids, so identical page selection
    np.testing.assert_array_equal(np.asarray(pool["centroids"]),
                                  np.asarray(pool0["centroids"]))
    idx0, v0 = moba.moba_paged_route(q, pool0["centroids"], table,
                                     kv_lens, cfg.attention.moba)
    idx1, v1 = moba.moba_paged_route(q, pool["centroids"], table,
                                     kv_lens, cfg.attention.moba)
    np.testing.assert_array_equal(np.asarray(idx0), np.asarray(idx1))
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))


@pytest.mark.parametrize("kv_dtype", QUANT)
def test_decode_append_requantizes_and_keeps_routing_fp32(kv_dtype):
    """Token-at-a-time decode appends: attention over the requantized
    tail page stays within tolerance of the fp32 pool, and the
    incremental centroid update is bitwise identical (it folds the fp32
    incoming key, never reading the quantized payload)."""
    cfg = get_smoke_config("moba-340m")
    g = dict(kv_lens=(37, 16, 5, 61), npg=8, num_pages=32)
    pool0, table, kv_lens, q = _build("fp32", g, cfg)
    pool, *_ = _build(kv_dtype, g, cfg)
    hkv, d = cfg.num_kv_heads, cfg.resolved_head_dim
    b = len(g["kv_lens"])
    rng = np.random.default_rng(11)
    active = jnp.ones((b,), bool)
    for step in range(3):
        kt = jnp.asarray(rng.normal(size=(b, hkv, 1, d)), jnp.float32)
        vt = jnp.asarray(rng.normal(size=(b, hkv, 1, d)), jnp.float32)
        pool0 = PC.paged_append_decode(pool0, table, kv_lens, active,
                                       kt, vt)
        pool = PC.paged_append_decode(pool, table, kv_lens, active,
                                      kt, vt)
        kv_lens = kv_lens + 1
    np.testing.assert_array_equal(np.asarray(pool["centroids"]),
                                  np.asarray(pool0["centroids"]))
    oracle = _decode_outs(pool0, table, kv_lens, q, cfg)["xla"]
    out = _decode_outs(pool, table, kv_lens, q, cfg)["xla"]
    assert np.abs(out - oracle).max() <= TOL[kv_dtype]


@pytest.mark.parametrize("kv_dtype", QUANT)
def test_swa_windowed_decode_dequantizes(kv_dtype):
    """The SWA window-bounded gather reads the same quantized pool
    (moba-340m interleaves swa + moba slots over one pool layout)."""
    cfg = get_smoke_config("moba-340m")
    g = GEOMETRIES["ragged"]
    pool0, table, kv_lens, q = _build("fp32", g, cfg)
    pool, *_ = _build(kv_dtype, g, cfg)
    ref = PC.swa_windowed_decode_attention(q, pool0, table, kv_lens, 31)
    out = PC.swa_windowed_decode_attention(q, pool, table, kv_lens, 31)
    assert np.abs(np.asarray(out) - np.asarray(ref)).max() <= TOL[kv_dtype]


# ----------------------------------------------------- NIAH serving gate
def _niah_prompts(cfg, n, seq_len):
    batch = make_niah_batch(np.random.default_rng(13), n, seq_len,
                            cfg.vocab_size)
    return [batch["tokens"][i] for i in range(n)], batch["needle_pos"]


@pytest.mark.parametrize("kv_dtype", QUANT)
def test_niah_greedy_tokens_agree_across_backends(kv_dtype):
    """Engine-level gate: on NIAH prompts, the xla and flash engines
    decode identical greedy streams from the same quantized pool — the
    kernel-side dequantization is numerically interchangeable with the
    XLA gather path all the way through the serving stack."""
    cfg = get_smoke_config("moba-340m")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    prompts, _ = _niah_prompts(cfg, 3, 112)

    def run(backend):
        eng = Engine(cfg, params, EngineConfig(
            max_seqs=3, max_seq_len=160, attn_backend=backend,
            kv_dtype=kv_dtype))
        reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
        eng.run()
        return [r.out for r in reqs]

    assert run("xla") == run("flash")


@pytest.mark.parametrize("kv_dtype", QUANT)
def test_niah_needle_routing_parity_with_fp32(kv_dtype):
    """Retrieval-side acceptance: on planted-needle contexts the router
    selects byte-identical pages from a quantized pool — the needle's
    block is found (or missed) exactly as in fp32, so quantization
    cannot change *which* history decode attends to, only its low-order
    bits."""
    cfg = get_smoke_config("moba-340m")
    ps = PC.resolve_page_size(cfg)
    hkv, d, h = cfg.num_kv_heads, cfg.resolved_head_dim, cfg.num_heads
    b, npg = 4, 8
    rng = np.random.default_rng(17)
    kv_lens = np.full((b,), npg * ps, np.int32)
    # keys near zero except a loud needle block per row: routing must
    # pick the needle page identically in both modes
    kc = rng.normal(0, 0.05, size=(b, hkv, npg * ps, d))
    needle_page = rng.integers(0, npg, size=b)
    for i in range(b):
        s = needle_page[i] * ps
        kc[i, :, s:s + ps] = rng.normal(0, 2.0, size=(hkv, ps, d))
    kc = jnp.asarray(kc, jnp.float32)
    vc = jnp.asarray(rng.normal(size=(b, hkv, npg * ps, d)), jnp.float32)
    table = jnp.asarray(np.arange(b * npg, dtype=np.int32).reshape(b, npg))
    q = jnp.asarray(rng.normal(size=(b, h, 1, d)), jnp.float32)

    def route(kv_dt):
        pool = PC.init_page_pool(cfg, b * npg, ps, with_centroids=True,
                                 dtype=jnp.float32, kv_dtype=kv_dt)
        pool = PC.paged_append_prefill(pool, table, jnp.asarray(kv_lens),
                                       kc, vc)
        idx, valid = moba.moba_paged_route(q, pool["centroids"], table,
                                           jnp.asarray(kv_lens),
                                           cfg.attention.moba)
        return np.asarray(idx), np.asarray(valid)

    i0, v0 = route("fp32")
    i1, v1 = route(kv_dtype)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(v0, v1)


# --------------------------------------------------- tiling + pool layout
def test_tiling_contract_knows_byte_dtypes():
    """Mosaic's block rule does not scale with the dtype: a byte-wide
    payload block needs the same (8, 128)-or-full dims as fp32.  A page
    of a head-major int8/fp8 pool is a whole (ps, d) block at any page
    size, so the contract passes it; a q tile of 8 rows passes too."""
    from jax.experimental import pallas as pl
    from repro.kernels.tiling import check_blocks
    page = pl.BlockSpec((1, 1, 16, 128), lambda *i: (0, 0, 0, 0))
    rows = pl.BlockSpec((1, 8, 128), lambda *i: (0, 0, 0))
    for dt in (jnp.int8, jnp.float8_e4m3fn, jnp.float32):
        check_blocks("paged decode", ["pages_k", "q"], [page, rows],
                     [jax.ShapeDtypeStruct((64, 8, 16, 128), dt),
                      jax.ShapeDtypeStruct((4, 64, 128), dt)])
        with pytest.raises(ValueError, match="multiple of 8"):
            check_blocks("paged decode", ["q"],
                         [pl.BlockSpec((1, 4, 128), lambda *i: (0, 0, 0))],
                         [jax.ShapeDtypeStruct((4, 64, 128), dt)])


def test_pool_layout_and_fp32_passthrough():
    """Quantized pools carry per-(page, head) fp32 scales init to 1.0;
    kv_dtype='fp32' keeps the pre-quantization layout byte-for-byte
    (no scales leaves, pages at the compute dtype)."""
    cfg = get_smoke_config("moba-340m")
    ps = PC.resolve_page_size(cfg)
    plain = PC.init_page_pool(cfg, 8, ps, with_centroids=True,
                              dtype=jnp.float32)
    via_arg = PC.init_page_pool(cfg, 8, ps, with_centroids=True,
                                dtype=jnp.float32, kv_dtype="fp32")
    assert set(plain) == set(via_arg)
    assert via_arg["pages_k"].dtype == jnp.float32
    qpool = PC.init_page_pool(cfg, 8, ps, with_centroids=True,
                              dtype=jnp.float32, kv_dtype="int8")
    assert qpool["pages_k"].dtype == jnp.int8
    assert qpool["scales_k"].shape == (8, cfg.num_kv_heads)
    assert qpool["scales_v"].dtype == jnp.float32
    assert (np.asarray(qpool["scales_k"]) == 1.0).all()
    assert qpool["centroids"].dtype == jnp.float32
    assert {"scales_k", "scales_v"} <= set(PC.PAGE_LEAVES)
    with pytest.raises(ValueError, match="kv_dtype"):
        PC.init_page_pool(cfg, 8, ps, with_centroids=True,
                          kv_dtype="int4")
