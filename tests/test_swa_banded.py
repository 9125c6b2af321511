"""The banded sliding-window path: same function as the dense masked one,
and taken only where queries and keys are one whole sequence at 0..n-1.

``banded_window_attention`` scores each chunk of ``C`` queries (the window
rounded up to 128) against the keys of its own and the previous chunk.
Its output and its gradients must be ``dense_attention``'s.  The dispatch
tests read the ``swa_band`` scope from the lowered program's op names:
present in a training forward, absent from every call that keeps the
dense path (dense-cache prefill, decode, paged prefill, explicit
positions, the other kinds).
"""
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import AttentionConfig
from repro.core.attention import (attention_dispatch,
                                  banded_window_attention, dense_attention)
from repro.models import transformer as T


def _qkv(b, h, hkv, n, d, seed=0):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kq, (b, h, n, d), jnp.float32),
            jax.random.normal(kk, (b, hkv, n, d), jnp.float32),
            jax.random.normal(kv, (b, hkv, n, d), jnp.float32))


# (batch, q heads, kv heads, n, head_dim, window); C = window rounded up
# to 128, capped at n rounded up
CASES = [
    pytest.param(1, 2, 2, 256, 64, 100, id="n_multiple_of_C-window_lt_C"),
    pytest.param(1, 4, 2, 300, 64, 100, id="n_not_multiple-gqa2"),
    pytest.param(2, 2, 1, 256, 128, 128, id="window_eq_C-d128-batch2"),
    pytest.param(1, 2, 2, 300, 128, 128, id="window_eq_C-n_not_multiple"),
    pytest.param(2, 4, 2, 512, 64, 256, id="moba340m_window-4_chunks"),
    pytest.param(2, 4, 2, 300, 64, 512, id="window_ge_n"),
    pytest.param(1, 2, 1, 200, 128, 200, id="window_eq_n"),
    pytest.param(1, 2, 2, 40, 64, 16, id="n_below_one_lane_tile"),
]


def _out_and_grads(attn, q, k, v, ct):
    out, vjp = jax.vjp(attn, q, k, v)
    return (out, *vjp(ct))


@pytest.mark.parametrize("b,h,hkv,n,d,window", CASES)
def test_banded_matches_dense_forward_and_grads(b, h, hkv, n, d, window):
    q, k, v = _qkv(b, h, hkv, n, d)
    ct = jax.random.normal(jax.random.PRNGKey(1), q.shape, jnp.float32)
    dense = lambda q, k, v: dense_attention(q, k, v, causal=True,
                                            window=window)
    band = lambda q, k, v: banded_window_attention(q, k, v, window)
    want = jax.jit(partial(_out_and_grads, dense))(q, k, v, ct)
    got = jax.jit(partial(_out_and_grads, band))(q, k, v, ct)
    assert got[0].shape == want[0].shape and got[0].dtype == want[0].dtype
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5, err_msg=name)


def test_banded_keeps_input_dtype_and_scale():
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(1, 2, 2, 160, 64))
    out_b = banded_window_attention(q, k, v, 64, scale=0.3)
    out_d = dense_attention(q, k, v, causal=True, window=64, scale=0.3)
    assert out_b.dtype == jnp.bfloat16
    np.testing.assert_allclose(out_b.astype(jnp.float32),
                               out_d.astype(jnp.float32), rtol=0, atol=1e-2)


# ------------------------------------------------------------ dispatch


def _op_names(fn, *args) -> str:
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def _banded(fn, *args) -> bool:
    """Whether ``swa_band`` is a segment of some op's name."""
    return re.search(r"[/(]swa_band[/)]", _op_names(fn, *args)) is not None


@pytest.fixture(scope="module")
def model():
    cfg = configs.get_smoke_config("moba-340m")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 48), 0,
                                cfg.vocab_size)
    return cfg, params, tokens


def test_training_forward_takes_the_band(model):
    cfg, params, tokens = model
    loss = lambda p, t: T.lm_loss(p, {"tokens": t}, cfg, backend="xla")[0]
    assert _banded(loss, params, tokens)
    assert _banded(jax.grad(loss), params, tokens)


def test_training_forward_reads_as_the_dense_path(model):
    """The whole model's logits, band against the same step with explicit
    positions (which keeps the dense path)."""
    cfg, params, tokens = model
    band, _, _ = T.lm_apply(params, tokens, cfg, backend="xla")
    dense, _, _ = T.lm_apply(params, tokens, cfg, backend="xla",
                             positions=jnp.arange(tokens.shape[1]))
    np.testing.assert_allclose(band, dense, rtol=1e-5, atol=1e-5)


def test_explicit_positions_keep_the_dense_path(model):
    cfg, params, tokens = model
    fn = lambda p, t: T.lm_apply(p, t, cfg, backend="xla",
                                 positions=jnp.arange(t.shape[1]))[0]
    assert not _banded(fn, params, tokens)


def test_dense_cache_prefill_and_decode_keep_the_dense_path(model):
    cfg, params, tokens = model
    caches = T.init_caches(cfg, 1, 64, dtype=jnp.float32)
    pre = lambda p, t, c: T.prefill(p, t, cfg, c, backend="xla")
    assert not _banded(pre, params, tokens, caches)
    _, caches = jax.jit(pre)(params, tokens, caches)
    dec = lambda p, t, c: T.decode_step(p, t, cfg, c, backend="xla")
    assert not _banded(dec, params, tokens[:, :1], caches)


def test_paged_prefill_keeps_the_dense_path(model):
    from repro.serving import paged_cache as PC
    cfg, params, tokens = model
    ps = PC.resolve_page_size(cfg)
    caches = T.init_paged_caches(cfg, 8, ps, dtype=jnp.float32)
    n = tokens.shape[1]
    page_state = {"block_table": jnp.arange(8, dtype=jnp.int32)[None],
                  "kv_len": jnp.zeros((1,), jnp.int32),
                  "q_len": jnp.full((1,), n, jnp.int32),
                  "active": jnp.ones((1,), bool)}
    fn = lambda p, t, c, s: T.prefill(p, t, cfg, c, backend="xla",
                                      page_state=s)
    assert not _banded(fn, params, tokens, caches, page_state)


@pytest.mark.parametrize("kind,kwargs,banded", [
    ("swa", {}, True),
    ("swa", {"kv_len": 64}, False),
    ("swa", {"q_positions": jnp.arange(64)}, False),
    ("swa", {"causal": False}, False),
    ("dense", {}, False),
])
def test_dispatch_engages_only_for_a_whole_causal_swa_sequence(
        kind, kwargs, banded):
    cfg = AttentionConfig(kind="moba", window=16)
    q, k, v = _qkv(1, 2, 2, 64, 16)
    fn = lambda q, k, v: attention_dispatch(cfg, kind, q, k, v, **kwargs)
    assert _banded(fn, q, k, v) == banded


def test_queries_fewer_than_keys_keep_the_dense_path():
    cfg = AttentionConfig(kind="moba", window=16)
    q, k, v = _qkv(1, 2, 2, 64, 16)
    fn = lambda q, k, v: attention_dispatch(cfg, "swa", q[:, :, -8:], k, v)
    assert not _banded(fn, q, k, v)


def test_no_score_over_the_whole_sequence_in_the_band():
    """At n = 4C the lowered band holds no (n, n) array."""
    q, k, v = _qkv(1, 2, 2, 512, 64)
    text = _op_names(lambda q, k, v: banded_window_attention(q, k, v, 128),
                     q, k, v)
    assert not re.search(r"512x512x", text)
    assert re.search(r"4x128x256x", text)
