"""Attention-backend registry + fused paged-decode kernel.

Covers the PR-2 acceptance surface: registry aliases and capability
declarations (every backend must actually run what it declares), the
Pallas decode kernel vs the XLA paged path on ragged batches — through
both the grouped MXU grid and the legacy flat grid, including the
kv_len==0 / non-8-multiple page_size / non-128 head_dim / G==1 edge
geometries — the SWA window-bounded page gather vs densify,
admission-time UnsupportedFeatureError, preemption-replay equality
through the engine on the flash backend, and the interpret/compiled
lowering toggle (env var, registry attribute, ``flash:compiled`` spec,
and the compiled-mode block contract)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import AttentionConfig, MoBAConfig
from repro.core import backends as B
from repro.core import moba
from repro.core.attention import attention_dispatch, dense_attention
from repro.kernels import moba_decode as MD
from repro.kernels import runtime as KR
from repro.kernels.moba_decode import moba_paged_decode_pallas
from repro.models import transformer as T
from repro.serving import paged_cache as PC
from repro.serving.engine import Engine, EngineConfig, engine_supported
from repro.serving.scheduler import ServingError, UnsupportedFeatureError


def _build_paged(rng, kv_lens, *, hkv=2, d=16, ps=16, npg=8, num_pages=32):
    """Scatter dense ragged caches into a paged pool (pool slots that are
    never written keep garbage, as in a recycled production pool)."""
    b = len(kv_lens)
    kc = jnp.asarray(rng.normal(size=(b, hkv, npg * ps, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(b, hkv, npg * ps, d)), jnp.float32)
    free = list(range(num_pages))
    rng.shuffle(free)
    table = np.full((b, npg), -1, np.int32)
    for i, n in enumerate(kv_lens):
        for j in range(-(-n // ps)):
            table[i, j] = free.pop()
    table = jnp.asarray(table)
    cache = {
        "pages_k": jnp.asarray(rng.normal(size=(num_pages, hkv, ps, d)),
                               jnp.float32),
        "pages_v": jnp.asarray(rng.normal(size=(num_pages, hkv, ps, d)),
                               jnp.float32),
        "centroids": jnp.zeros((num_pages, hkv, d), jnp.float32)}
    cache = PC.paged_append_prefill(cache, table, jnp.asarray(kv_lens),
                                    kc, vc)
    return cache, table, kc, vc


# ------------------------------------------------------------------ registry
PAGED_BACKENDS = ("xla", "flash", "sharded")


@pytest.fixture(params=PAGED_BACKENDS)
def paged_backend(request):
    """Every paged-capable non-reference backend.  Engine-level paged
    tests parametrize over this one fixture instead of keeping a copy
    per backend — a new paged backend gets the whole sweep by adding
    its name here."""
    return request.param


_REF = {}


def _reference_fixture():
    """Shared (cfg, params, prompts, reference-engine outputs) for the
    cross-backend sweep — computed once, not once per fixture param."""
    if not _REF:
        cfg = get_smoke_config("moba-340m")
        params = T.init_lm(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
                   for n in (40, 33, 21)]
        eng = Engine(cfg, params, EngineConfig(
            max_seqs=3, max_seq_len=64, attn_backend="reference"))
        reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        eng.run()
        _REF.update(cfg=cfg, params=params, prompts=prompts,
                    outs=[r.out for r in reqs])
    return _REF


def test_registry_names_and_aliases():
    assert set(B.names()) >= {"reference", "xla", "xla_unrolled", "flash",
                              "sp", "sp_unrolled", "sharded"}
    assert B.get("sparse") is B.get("xla")
    assert B.get("sparse_unrolled") is B.get("xla_unrolled")
    assert B.get("kernel") is B.get("flash")
    assert B.get("pallas") is B.get("flash")
    with pytest.raises(B.BackendCapabilityError):
        B.get("no_such_backend")


def test_capability_query_rejects_and_names_alternatives():
    with pytest.raises(B.BackendCapabilityError, match="reference"):
        B.resolve("sp", kind="moba", phase="decode", cache="paged")
    # sp does resolve for what it declares
    assert B.resolve("sp", kind="moba", phase="prefill").name == "sp"


def test_capability_matrix_backends_run_what_they_declare():
    """Every declared (kind, phase, dense-cache) cell of every local
    backend must execute and agree with the reference backend.  sp/sp_*
    need a mesh (exercised in test_distributed) so only their
    declarations are checked."""
    rng = np.random.default_rng(0)
    mcfg = MoBAConfig(block_size=16, top_k=2)
    cfg = AttentionConfig(kind="moba", window=32, moba=mcfg)
    b, h, hkv, n, d = 2, 4, 2, 64, 16
    q = jnp.asarray(rng.normal(size=(b, h, n, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, hkv, n, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, hkv, n, d)), jnp.float32)
    qd = q[:, :, :1]
    kv_len = jnp.asarray(40)          # dense caches share one length
    ref = B.get("reference")
    for name in ("reference", "xla", "xla_unrolled", "flash", "sharded"):
        be = B.get(name)
        caps = be.capabilities
        for kind in caps.kinds:
            assert "prefill" in caps.phases and "decode" in caps.phases
            out = be.prefill(cfg, kind, q, k, v)
            want = ref.prefill(cfg, kind, q, k, v)
            np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                       atol=2e-3, rtol=2e-3)
            out = be.decode(cfg, kind, qd, k, v, kv_len,
                            q_positions=(kv_len - 1)[None])
            want = ref.decode(cfg, kind, qd, k, v, kv_len,
                              q_positions=(kv_len - 1)[None])
            np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                       atol=2e-3, rtol=2e-3)
    for name in ("sp", "sp_unrolled"):
        assert B.get(name).capabilities.caches == ("dense",)


def test_attention_dispatch_routes_legacy_strings():
    """The former moba_impl strings keep working through the registry."""
    rng = np.random.default_rng(1)
    mcfg = MoBAConfig(block_size=16, top_k=2)
    cfg = AttentionConfig(kind="moba", moba=mcfg)
    q = jnp.asarray(rng.normal(size=(1, 4, 64, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 64, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 64, 16)), jnp.float32)
    ref = attention_dispatch(cfg, "moba", q, k, v, backend="reference")
    for legacy in ("sparse", "sparse_unrolled", "kernel"):
        out = attention_dispatch(cfg, "moba", q, k, v, backend=legacy)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3, rtol=2e-3)


# ------------------------------------------------------- fused decode kernel
GRIDS = ("grouped", "flat")

GEOMETRIES = {
    # ragged batch incl. a tail page mid-fill and an inactive kv_len=0 row
    "ragged": dict(kv_lens=(37, 16, 5, 128, 0), top_k=3, h=4, hkv=2,
                   d=16, ps=16, npg=8, num_pages=48),
    # page_size not a multiple of the 8-row sublane grain, head_dim not
    # a multiple of the 128 lane count: interpret mode must still agree
    "odd-tiles": dict(kv_lens=(25, 60, 3), top_k=2, h=4, hkv=2,
                      d=24, ps=12, npg=6, num_pages=24),
    # G == 1 (Hkv == H): the grouped grid degenerates to one query row
    # per kv head and must still dedupe/mask correctly
    "g1": dict(kv_lens=(40, 1, 16), top_k=3, h=4, hkv=4,
               d=16, ps=16, npg=4, num_pages=16),
}


def _decode_case(geom):
    rng = np.random.default_rng(2)
    kv_lens = np.array(geom["kv_lens"])
    cfg = MoBAConfig(block_size=geom["ps"], top_k=geom["top_k"])
    cache, table, _, _ = _build_paged(
        rng, kv_lens, hkv=geom["hkv"], d=geom["d"], ps=geom["ps"],
        npg=geom["npg"], num_pages=geom["num_pages"])
    q = jnp.asarray(rng.normal(size=(len(kv_lens), geom["h"], 1,
                                     geom["d"])), jnp.float32)
    args = (q, cache["pages_k"], cache["pages_v"], cache["centroids"],
            table, jnp.asarray(kv_lens), cfg)
    return args, kv_lens, cfg


@pytest.mark.parametrize("grid", GRIDS)
@pytest.mark.parametrize("geom", GEOMETRIES, ids=GEOMETRIES)
def test_pallas_paged_decode_matches_xla(geom, grid):
    """Acceptance: both kernel grids match the XLA paged path within
    1e-3 on ragged batches and the edge geometries above, and emit
    zeros on inactive (kv_len == 0) rows."""
    args, kv_lens, cfg = _decode_case(GEOMETRIES[geom])
    ref = moba.moba_paged_decode_attention(*args)
    out = moba_paged_decode_pallas(*args, grid=grid)
    active = kv_lens > 0
    np.testing.assert_allclose(np.asarray(out)[active],
                               np.asarray(ref)[active],
                               atol=1e-3, rtol=1e-3)
    assert np.all(np.asarray(out)[~active] == 0.0)
    # and under jit (the engine always runs it jitted)
    jout = jax.jit(lambda *a: moba_paged_decode_pallas(
        *a, cfg, grid=grid))(*args[:-1])
    np.testing.assert_allclose(np.asarray(jout)[active],
                               np.asarray(ref)[active],
                               atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("grid", GRIDS)
def test_pallas_paged_decode_short_table(grid):
    """Tables shorter than top_k: selection pads with invalid slots."""
    rng = np.random.default_rng(3)
    kv_lens = np.array([17, 9])
    cfg = MoBAConfig(block_size=16, top_k=8)     # top_k > npg
    cache, table, _, _ = _build_paged(rng, kv_lens, npg=2, num_pages=8)
    q = jnp.asarray(rng.normal(size=(2, 4, 1, 16)), jnp.float32)
    args = (q, cache["pages_k"], cache["pages_v"], cache["centroids"],
            table, jnp.asarray(kv_lens), cfg)
    ref = moba.moba_paged_decode_attention(*args)
    out = moba_paged_decode_pallas(*args, grid=grid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-3, rtol=1e-3)


def test_union_pages_dedupes_and_compacts():
    """The grouped grid's page union: unique valid ids, sorted and
    compacted to the front, padding zeros past n_uniq."""
    idx = jnp.asarray([[[[[3, 1, 3]], [[1, 1, 0]]]]])   # (1,1,2,1,3)
    valid = jnp.asarray([[[[[True, True, False]],
                           [[True, False, True]]]]])
    union, n_uniq = MD.union_pages(idx, valid, npg=8)
    assert union.shape == (1, 1, 6)
    assert int(n_uniq[0, 0]) == 3
    assert union[0, 0, :3].tolist() == [0, 1, 3]        # sorted unique
    assert union[0, 0, 3:].tolist() == [0, 0, 0]        # padding


def test_pallas_decode_unknown_grid_rejected():
    args, _, cfg = _decode_case(GEOMETRIES["ragged"])
    with pytest.raises(ValueError, match="grouped"):
        moba_paged_decode_pallas(*args, grid="typo")


# ------------------------------------------- interpret/compiled toggle
def test_resolve_interpret_precedence(monkeypatch):
    """Explicit arg > env var > auto (non-TPU hosts interpret)."""
    monkeypatch.delenv(KR.ENV_VAR, raising=False)
    assert KR.resolve_interpret(True) is True
    assert KR.resolve_interpret(False) is False
    assert KR.resolve_interpret(None) is True           # CPU test host
    monkeypatch.setenv(KR.ENV_VAR, "0")
    assert KR.resolve_interpret(None) is False
    assert KR.resolve_interpret(True) is True           # arg still wins
    monkeypatch.setenv(KR.ENV_VAR, "compiled")
    assert KR.resolve_interpret(None) is False
    monkeypatch.setenv(KR.ENV_VAR, "interpret")
    assert KR.resolve_interpret(None) is True
    monkeypatch.setenv(KR.ENV_VAR, "maybe")
    with pytest.raises(ValueError, match=KR.ENV_VAR):
        KR.resolve_interpret(None)


def _spec(*block):
    from jax.experimental import pallas as pl
    return pl.BlockSpec(block, lambda *i: (0,) * len(block))


def _shape(*dims):
    return jax.ShapeDtypeStruct(dims, jnp.float32)


def test_compiled_mode_tiling_asserts(monkeypatch):
    """The compiled-mode block contract (``kernels/tiling.check_blocks``)
    is Mosaic's rule: each of a block's last two dims is a multiple of
    (8, 128) — for every dtype — or the full array dim; a violation
    raises a shaped error naming the operand before any pallas_call.
    The paged-decode blocks are whole (ps, d) pages of the head-major
    pool, so even the odd-tiles geometry (ps=12, d=24) passes it and a
    compiled request reaches ``pallas_call`` with interpret=False."""
    from repro.kernels import tiling as TL
    with pytest.raises(ValueError, match=r"q block \(1, 12, 128\) over "
                       r"\(4, 1020, 128\): dim -2 = 12 must be a multiple "
                       r"of 8 or the full 1020"):
        TL.check_blocks("k", ["q"], [_spec(1, 12, 128)],
                        [_shape(4, 1020, 128)])
    with pytest.raises(ValueError, match="dim -1 = 64 must be a multiple "
                                         "of 128 or the full 256"):
        TL.check_blocks("k", ["x"], [_spec(1, 8, 64)], [_shape(2, 16, 256)])
    with pytest.raises(ValueError, match="x block.*; y block"):
        TL.check_blocks("k", ["x", "y"], [_spec(1, 4, 128), _spec(1, 8, 8)],
                        [_shape(2, 16, 128), _shape(2, 8, 16)])
    TL.check_blocks("k", ["x"], [_spec(1, 12, 24)], [_shape(3, 12, 24)])
    TL.check_blocks("k", ["x"], [_spec(1, 1, 12, 24)],
                    [_shape(9, 2, 12, 24)])               # a whole page
    TL.check_blocks("k", ["x"], [_spec(1, 8, 128)],
                    [jax.ShapeDtypeStruct((2, 64, 256), jnp.int8)])

    seen = []
    real = MD.pl.pallas_call

    def spy(*a, **kw):
        seen.append(kw.get("interpret"))
        kw["interpret"] = True
        return real(*a, **kw)

    monkeypatch.setattr(MD.pl, "pallas_call", spy)
    args, _, cfg = _decode_case(GEOMETRIES["odd-tiles"])
    for grid in ("grouped", "flat"):
        moba_paged_decode_pallas(*args, interpret=False, grid=grid)
    assert seen == [False, False]


def test_compiled_mode_moba_tiling_contract():
    """The fwd/bwd/topk wrappers hand their block specs to the contract
    before a compiled pallas_call: a q_tile that is neither a multiple of
    8 nor the whole sequence raises the shaped error naming the kernel
    and operand; a kb_tile that does not split the key block raises in
    either mode."""
    from repro.kernels.flash_topk import flash_topk
    from repro.kernels.moba_bwd import moba_bwd
    from repro.kernels.moba_fwd import moba_fwd

    tb = jnp.zeros((2, 2), jnp.int32)
    qs = jnp.zeros((2, 24, 16), jnp.float32)
    qp = jnp.zeros((2, 24), jnp.int32)
    kb = jnp.zeros((1, 4, 16, 16), jnp.float32)
    kw = dict(scale=0.25, block_size=16, n_tokens=64, num_q_heads=2,
              group=2, q_tile=12, interpret=False)
    for grid in ("grouped", "flat"):
        with pytest.raises(ValueError, match=r"compiled moba_fwd kernel "
                           r"block contract.*q block \(1, 12, 16\)"):
            moba_fwd(tb, qs, qp, kb, kb, grid=grid, **kw)
        with pytest.raises(ValueError, match=r"compiled moba_bwd kernel "
                           r"block contract.*q_pos block \(1, 12, 1\)"):
            moba_bwd(tb, qs, qp, qs, qp.astype(jnp.float32),
                     qp.astype(jnp.float32), kb, kb, grid=grid, **kw)
    with pytest.raises(ValueError, match="evenly divide block_size=16"):
        moba_fwd(tb, qs, qp, kb, kb, kb_tile=12,
                 **dict(kw, interpret=True))

    q = jnp.zeros((2, 36, 16), jnp.float32)
    cents = jnp.zeros((1, 4, 16), jnp.float32)
    with pytest.raises(ValueError, match=r"compiled flash_topk kernel "
                       r"block contract.*q block \(2, 12, 16\)"):
        flash_topk(q, cents, 2, 16, group=2, num_q_heads=2, q_tile=12,
                   interpret=False)
    with pytest.raises(ValueError, match="power-of-two cent_tile"):
        flash_topk(q, cents, 2, 16, group=2, num_q_heads=2, q_tile=12,
                   cent_tile=96, interpret=False)


def test_registry_interpret_toggle_reaches_pallas_call(monkeypatch):
    """Acceptance: flipping the registry toggle makes the flash backend
    invoke ``pl.pallas_call`` with interpret=False — asserted by
    monkeypatching pallas_call itself (execution is forced back to
    interpret so the CPU host can still run the kernel)."""
    seen = []
    real = MD.pl.pallas_call

    def spy(*a, **kw):
        seen.append(kw.get("interpret"))
        kw["interpret"] = True
        return real(*a, **kw)

    monkeypatch.setattr(MD.pl, "pallas_call", spy)
    flash = B.get("flash")
    monkeypatch.setattr(flash, "interpret", False)
    args, kv_lens, cfg = _decode_case(GEOMETRIES["ragged"])
    q, pk, pv, cents, table, kvl, _ = args
    cache = {"pages_k": pk, "pages_v": pv, "centroids": cents}
    acfg = AttentionConfig(kind="moba", moba=cfg)
    # flat grid: the ragged test geometry (d=16) is not compiled-
    # tileable, and the toggle wiring is grid-independent
    out = flash.moba_paged_decode(acfg, q, cache, table, kvl,
                                  grid="flat")
    assert seen == [False]
    ref = moba.moba_paged_decode_attention(*args)
    active = kv_lens > 0
    np.testing.assert_allclose(np.asarray(out)[active],
                               np.asarray(ref)[active],
                               atol=1e-3, rtol=1e-3)
    # env var reaches the same seam when the attribute is unset
    monkeypatch.setattr(flash, "interpret", None)
    monkeypatch.setenv(KR.ENV_VAR, "compiled")
    flash.moba_paged_decode(acfg, q, cache, table, kvl, grid="flat")
    assert seen == [False, False]


def test_no_hardcoded_interpret_defaults_in_kernels():
    """Acceptance: kernels/ carries no ``interpret=True`` defaults —
    every wrapper defers to ``kernels.runtime.resolve_interpret``."""
    import pathlib
    import re

    import repro.kernels
    kdir = pathlib.Path(repro.kernels.__file__).parent
    scanned = {p.name for p in sorted(kdir.glob("*.py"))}
    # the scan must actually see every kernel-layer module (guards
    # against the glob silently missing a moved/renamed file)
    for required in ("flash_topk.py", "moba_fwd.py", "moba_bwd.py",
                     "moba_decode.py", "ops.py", "tiling.py",
                     "runtime.py"):
        assert required in scanned, required
    for p in sorted(kdir.glob("*.py")):
        src = p.read_text()
        assert not re.search(r"interpret\s*:\s*bool\s*=\s*True", src), p
        assert not re.search(r"interpret\s*=\s*True", src), p


def test_parse_backend_spec(monkeypatch):
    flash = B.get("flash")
    monkeypatch.setattr(flash, "interpret", None)
    monkeypatch.setattr(flash, "decode_grid", "grouped")
    monkeypatch.setattr(flash, "train_grid", "grouped")
    monkeypatch.setattr(flash, "kb_tile", 0)
    assert B.parse_backend_spec("xla") == "xla"
    assert B.parse_backend_spec("flash:compiled") == "flash"
    assert flash.interpret is False
    assert B.parse_backend_spec("flash:interpret") == "flash"
    assert flash.interpret is True
    assert B.parse_backend_spec("pallas:flat") == "pallas"  # via alias
    assert flash.decode_grid == "flat"
    assert flash.train_grid == "flat"       # grid options set both grids
    assert B.parse_backend_spec("flash:grouped") == "flash"
    assert flash.decode_grid == "grouped"
    assert flash.train_grid == "grouped"
    assert B.parse_backend_spec("flash:kb_tile=64") == "flash"
    assert flash.kb_tile == 64
    # comma-separated multi-option spec
    assert B.parse_backend_spec("flash:compiled,flat,kb_tile=0") == "flash"
    assert flash.interpret is False
    assert flash.train_grid == "flat"
    assert flash.kb_tile == 0
    with pytest.raises(B.BackendCapabilityError, match="option"):
        B.parse_backend_spec("flash:typo")
    with pytest.raises(B.BackendCapabilityError, match="kb_tile"):
        B.parse_backend_spec("flash:kb_tile=big")
    with pytest.raises(B.BackendCapabilityError, match="kb_tile"):
        B.parse_backend_spec("xla:kb_tile=64")
    with pytest.raises(B.BackendCapabilityError, match="toggle"):
        B.parse_backend_spec("xla:compiled")
    with pytest.raises(B.BackendCapabilityError, match="unknown"):
        B.parse_backend_spec("no_such:compiled")


def test_engine_accepts_backend_spec(monkeypatch):
    """EngineConfig.attn_backend takes the 'name:option' spec: the
    option lands on the registry instance and the engine stores the
    bare name; bad specs fail admission as UnsupportedFeatureError."""
    flash = B.get("flash")
    monkeypatch.setattr(flash, "decode_grid", "grouped")
    monkeypatch.setattr(flash, "train_grid", "grouped")
    ref = _reference_fixture()
    eng = Engine(ref["cfg"], ref["params"], EngineConfig(
        max_seqs=3, max_seq_len=64, attn_backend="flash:flat"))
    assert eng.attn_backend == "flash"
    assert flash.decode_grid == "flat"
    reqs = [eng.submit(p, max_new_tokens=10) for p in ref["prompts"]]
    eng.run()
    assert [r.out for r in reqs] == ref["outs"]
    with pytest.raises(UnsupportedFeatureError) as ei:
        Engine(ref["cfg"], ref["params"],
               EngineConfig(attn_backend="flash:typo"))
    assert ei.value.feature == "attn_backend"


def test_swa_windowed_decode_matches_densify():
    """Window-bounded page gather == densify-then-mask, all window/page
    alignments, on a pool whose unused pages hold garbage."""
    rng = np.random.default_rng(4)
    kv_lens = np.array([37, 16, 5, 128, 63])
    cache, table, _, _ = _build_paged(rng, kv_lens, num_pages=48)
    q = jnp.asarray(rng.normal(size=(len(kv_lens), 4, 1, 16)), jnp.float32)
    kvl = jnp.asarray(kv_lens)
    for window in (7, 16, 31, 100, 256):
        out = PC.swa_windowed_decode_attention(q, cache, table, kvl, window)
        kf, vf = PC.paged_gather_kv(cache, table)
        ref = dense_attention(q, kf, vf, causal=True,
                              q_positions=(kvl - 1)[:, None], kv_len=kvl,
                              window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


# ------------------------------------------------------------------- engine
def test_engine_backend_agrees_token_for_token(paged_backend):
    """Every paged backend's engine emits the reference engine's greedy
    stream (moba-340m interleaves swa + moba, so this also pins the
    windowed swa decode path against the old densify numerics)."""
    ref = _reference_fixture()
    eng = Engine(ref["cfg"], ref["params"], EngineConfig(
        max_seqs=3, max_seq_len=64, attn_backend=paged_backend))
    reqs = [eng.submit(p, max_new_tokens=10) for p in ref["prompts"]]
    eng.run()
    assert [r.out for r in reqs] == ref["outs"]


def test_engine_preemption_replay_exact(paged_backend):
    """Recompute-preemption through every paged backend reproduces each
    request's solo greedy stream."""
    ref = _reference_fixture()
    cfg, params = ref["cfg"], ref["params"]
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in (40, 35, 30)]
    eng = Engine(cfg, params, EngineConfig(max_seqs=3, max_seq_len=64,
                                           num_pages=8,
                                           attn_backend=paged_backend))
    reqs = [eng.submit(p, max_new_tokens=14) for p in prompts]
    eng.run()
    assert eng.stats["preemptions"] > 0, "test should exercise preemption"
    for p, r in zip(prompts, reqs):
        solo = Engine(cfg, params, EngineConfig(max_seqs=1, max_seq_len=64,
                                                attn_backend=paged_backend))
        rs = solo.submit(p, max_new_tokens=14)
        solo.run()
        assert r.out == rs.out, (r.rid, r.out, rs.out)


# ----------------------------------------------------- admission-time errors
def test_key_conv_admitted_and_served():
    """Key-conv configs are engine-servable (per-slot raw-key ring
    buffer, DESIGN.md §4): admission succeeds for every paged backend
    that declares paged key-conv, and the engine decodes greedily."""
    cfg = get_smoke_config("moba-340m", key_conv_width=3)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    assert engine_supported(cfg)
    for name in ("reference",) + PAGED_BACKENDS:
        assert B.resolve(name, kind="moba", phase="decode", cache="paged",
                         key_conv=True).name == name
    eng = Engine(cfg, params, EngineConfig(max_seqs=2, max_seq_len=64))
    rng = np.random.default_rng(0)
    req = eng.submit(rng.integers(0, cfg.vocab_size, 20, dtype=np.int32),
                     max_new_tokens=4)
    eng.run()
    assert len(req.out) == 4
    # sp stays dense-only, and the old rejection remains structured
    with pytest.raises(UnsupportedFeatureError) as ei:
        Engine(cfg, params, EngineConfig(attn_backend="sp"))
    assert ei.value.feature == "attn_backend"
    assert isinstance(ei.value, ServingError)  # CLI handling unchanged


def test_unpageable_backend_rejected_at_admission():
    cfg = get_smoke_config("moba-340m")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    with pytest.raises(UnsupportedFeatureError) as ei:
        Engine(cfg, params, EngineConfig(attn_backend="sp"))
    assert ei.value.feature == "attn_backend"
    with pytest.raises(UnsupportedFeatureError):
        Engine(cfg, params, EngineConfig(attn_backend="typo"))


def test_engine_config_moba_impl_removed():
    """The long-deprecated ``moba_impl`` alias is gone: constructing an
    EngineConfig with it raises the structured error pointing at
    ``attn_backend`` instead of silently resolving a precedence."""
    cfg = get_smoke_config("moba-340m")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    with pytest.raises(UnsupportedFeatureError) as ei:
        EngineConfig(moba_impl="xla")
    assert ei.value.feature == "moba_impl"
    assert "attn_backend='xla'" in str(ei.value)
    assert isinstance(ei.value, ServingError)  # CLI handling unchanged
    with pytest.raises(UnsupportedFeatureError):
        EngineConfig(attn_backend="flash", moba_impl="xla")
    # the InitVar leaves no field behind: replace() round-trips without
    # resurrecting the alias, and the default backend is unchanged
    import dataclasses
    ecfg = dataclasses.replace(EngineConfig(attn_backend="flash"),
                               max_seqs=2)
    assert ecfg.attn_backend == "flash" and ecfg.max_seqs == 2
    assert "moba_impl" not in {f.name for f in dataclasses.fields(ecfg)}
    assert Engine(cfg, params, EngineConfig()).attn_backend == "reference"


def test_quantized_kv_gated_at_admission():
    """kv_dtype is a declared capability, not a runtime surprise: a
    backend that never quantizes (reference, sp) rejects int8/fp8 pools
    as a structured UnsupportedFeatureError at admission — before any
    cache is allocated or trace attempted — mirroring the key-conv
    gating above."""
    cfg = get_smoke_config("moba-340m")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    for kv_dtype in ("int8", "fp8"):
        with pytest.raises(UnsupportedFeatureError) as ei:
            Engine(cfg, params, EngineConfig(attn_backend="reference",
                                             kv_dtype=kv_dtype))
        assert ei.value.feature == "attn_backend"
        assert isinstance(ei.value, ServingError)
    # the registry query underneath names the rejection the same way
    with pytest.raises(B.BackendCapabilityError, match="kv_dtype"):
        B.resolve("reference", kind="moba", phase="decode", cache="paged",
                  kv_dtype="int8")
    # quantization-capable backends admit and serve
    for name in PAGED_BACKENDS:
        assert B.resolve(name, kind="moba", phase="decode", cache="paged",
                         kv_dtype="int8").name == name
        assert "int8" in B.get(name).capabilities.kv_dtypes
        assert "fp8" in B.get(name).capabilities.kv_dtypes
    # a typo'd dtype is a config error, not a capability mismatch
    with pytest.raises(ServingError, match="kv_dtype"):
        Engine(cfg, params, EngineConfig(attn_backend="xla",
                                         kv_dtype="int4"))
    # and the generated capability matrix documents the new column
    assert "kv_dtypes" in B.capability_matrix()


def test_capability_query_key_conv():
    assert B.resolve("xla", kind="moba", phase="prefill",
                     key_conv=True).name == "xla"
    nope = B.get("reference").capabilities
    assert nope.supports("moba", "prefill", "dense", key_conv=True)
