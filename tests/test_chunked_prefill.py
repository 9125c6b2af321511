"""Chunked prefill + key-conv paged serving (DESIGN.md §4/§6).

Pins the PR acceptance surface: chunked and one-shot prefill are
bitwise-routing-equivalent (identical pool contents and routed page ids
for every chunk size, including chunk boundaries inside a conv window
and inside a page), key-conv configs are served by the engine with
greedy tokens exactly matching the fixed-batch dense-cache oracle, and
recompute preemption replays exactly under both features.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.configs.base import MoBAConfig
from repro.core import moba
from repro.core.key_conv import (apply_key_conv, apply_key_conv_with_state,
                                 init_key_conv, key_conv_state_update)
from repro.launch import steps as S
from repro.models import transformer as T
from repro.serving import paged_cache as PC
from repro.serving.engine import Engine, EngineConfig
from repro.serving.scheduler import Scheduler


# ------------------------------------------------------------- unit level
def _chunks(n, size):
    return [(s, min(s + size, n)) for s in range(0, n, size)]


def test_chunked_append_pool_and_routing_match_oneshot():
    """Appending a prompt in chunks of any size leaves the pool (keys,
    values, centroids) bitwise identical to a one-shot append, and every
    chunk's routed page ids equal the same queries' ids under one-shot
    routing.  Chunk sizes cover page-aligned (16), page-straddling (24)
    and sub-page (7) boundaries."""
    rng = np.random.default_rng(0)
    hkv, d, ps, npg = 2, 16, 16, 8
    n, num_pages = 100, 16
    cfg = MoBAConfig(block_size=ps, top_k=3)
    kc = jnp.asarray(rng.normal(size=(1, hkv, n, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(1, hkv, n, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(1, 4, n, d)), jnp.float32)
    table = jnp.asarray(np.arange(npg, dtype=np.int32)[None])

    def fresh():
        return {"pages_k": jnp.zeros((num_pages, hkv, ps, d), jnp.float32),
                "pages_v": jnp.zeros((num_pages, hkv, ps, d), jnp.float32),
                "centroids": jnp.zeros((num_pages, hkv, d), jnp.float32)}

    one = PC.paged_append_prefill(fresh(), table, jnp.asarray([n]), kc, vc)
    idx_one, _ = moba.moba_paged_prefill_route(
        q, one["centroids"], table, jnp.asarray([0]), jnp.asarray([n]),
        cfg, page_size=ps)
    for size in (7, 16, 24):
        cache = fresh()
        for s, e in _chunks(n, size):
            cache = PC.paged_append_prefill(
                cache, table, jnp.asarray([e - s]), kc[:, :, s:e],
                vc[:, :, s:e], kv_len=jnp.asarray([s]))
            idx_c, _ = moba.moba_paged_prefill_route(
                q[:, :, s:e], cache["centroids"], table, jnp.asarray([s]),
                jnp.asarray([e - s]), cfg, page_size=ps)
            np.testing.assert_array_equal(
                np.asarray(idx_c), np.asarray(idx_one[:, :, :, s:e]),
                err_msg=f"chunk [{s},{e}) size {size}")
        for leaf in ("pages_k", "pages_v", "centroids"):
            np.testing.assert_array_equal(np.asarray(cache[leaf]),
                                          np.asarray(one[leaf]),
                                          err_msg=f"{leaf} size {size}")


def test_key_conv_state_carrying_bitwise():
    """Conv with carried ring state across chunk boundaries is bitwise
    identical to one-shot conv — including boundaries strictly inside a
    conv window (chunk 7 < width 5 spacing) — and the advanced state
    equals the last W-1 raw keys."""
    rng = np.random.default_rng(1)
    hkv, d, n, width = 2, 16, 50, 5
    w = init_key_conv(jax.random.PRNGKey(0), width, hkv, d)
    k = jnp.asarray(rng.normal(size=(1, hkv, n, d)), jnp.float32)
    one = apply_key_conv(w, k)
    zero = jnp.zeros((1, hkv, width - 1, d), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(apply_key_conv_with_state(w, k, zero)), np.asarray(one))
    for size in (7, 24):
        state = zero
        outs = []
        for s, e in _chunks(n, size):
            outs.append(apply_key_conv_with_state(w, k[:, :, s:e], state))
            state = key_conv_state_update(state, k[:, :, s:e],
                                          jnp.asarray([e - s]))
        np.testing.assert_array_equal(
            np.asarray(jnp.concatenate(outs, axis=-2)), np.asarray(one),
            err_msg=f"chunk size {size}")
        np.testing.assert_array_equal(np.asarray(state),
                                      np.asarray(k[:, :, n - width + 1:]))
    # ragged rows: a q_len 0 row keeps its state untouched
    st = key_conv_state_update(zero, k[:, :, :8], jnp.asarray([0]))
    np.testing.assert_array_equal(np.asarray(st), np.asarray(zero))


# ----------------------------------------------------------- engine level
def _engine_outs(cfg, params, prompts, gen, **ekw):
    eng = Engine(cfg, params, EngineConfig(
        max_seqs=len(prompts), max_seq_len=64, **ekw))
    reqs = [eng.submit(p, max_new_tokens=gen) for p in prompts]
    eng.run()
    return [r.out for r in reqs], eng


def test_chunked_engine_matches_oneshot_tokens():
    """Greedy streams are identical for the one-shot engine and chunked
    engines at page-aligned and page-straddling chunk sizes (ragged
    prompt lengths, swa+moba interleaved)."""
    cfg = get_smoke_config("moba-340m")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, p, dtype=np.int32)
               for p in (40, 33, 21)]
    base, _ = _engine_outs(cfg, params, prompts, 10)
    for chunk in (16, 24):
        outs, eng = _engine_outs(cfg, params, prompts, 10,
                                 prefill_chunk=chunk)
        assert outs == base, chunk
        # chunking actually spread prompts over steps
        assert eng.stats["prefill_tokens"] == sum(len(p) for p in prompts)


def test_key_conv_engine_matches_dense_oracle():
    """Acceptance: a key_conv_width > 0 config is admitted and its greedy
    decode tokens match the fixed-batch dense-cache oracle exactly —
    one-shot, chunked (boundary inside a conv window), and on the flash
    backend."""
    cfg = get_smoke_config("moba-340m", key_conv_width=3)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    batch, plen, gen = 3, 33, 8
    prompts = rng.integers(0, cfg.vocab_size, (batch, plen), np.int32)

    caches = T.init_caches(cfg, batch, plen + gen,
                           dtype=jnp.dtype(cfg.dtype))
    prefill_fn = jax.jit(S.make_prefill_step(cfg, backend="reference"),
                         donate_argnums=(2,))
    decode_fn = jax.jit(S.make_decode_step(cfg, backend="reference"),
                        donate_argnums=(2,))
    logits, caches = prefill_fn(params, jnp.asarray(prompts), caches)
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    oracle = [tok]
    for _ in range(gen - 1):
        tok, caches = decode_fn(params, tok, caches)
        oracle.append(tok)
    oracle = np.concatenate([np.asarray(t) for t in oracle], axis=1)

    for ekw in ({}, {"prefill_chunk": 7}, {"prefill_chunk": 16},
                {"attn_backend": "flash"},
                {"attn_backend": "flash", "prefill_chunk": 24}):
        outs, _ = _engine_outs(cfg, params, list(prompts), gen, **ekw)
        np.testing.assert_array_equal(np.asarray(outs, np.int32), oracle,
                                      err_msg=str(ekw))


def test_key_conv_chunked_preemption_replay_exact():
    """Recompute preemption under key-conv + chunked prefill reproduces
    every request's solo greedy stream (ring state rebuilt on replay)."""
    cfg = get_smoke_config("moba-340m", key_conv_width=3)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, p, dtype=np.int32)
               for p in (40, 35, 30)]
    eng = Engine(cfg, params, EngineConfig(max_seqs=3, max_seq_len=64,
                                           num_pages=8, prefill_chunk=24))
    reqs = [eng.submit(p, max_new_tokens=14) for p in prompts]
    eng.run()
    assert eng.stats["preemptions"] > 0, "test should exercise preemption"
    for p, r in zip(prompts, reqs):
        solo = Engine(cfg, params, EngineConfig(max_seqs=1, max_seq_len=64))
        rs = solo.submit(p, max_new_tokens=14)
        solo.run()
        assert r.out == rs.out, (r.rid, r.out, rs.out)


def test_chunked_scheduler_prefill_phase():
    """Chunked admissions enter the 'prefill' phase: they hold a slot and
    their full page reservation but are excluded from decode batches
    until the engine flips them to 'running'."""
    sched = Scheduler(num_pages=16, page_size=16, max_seqs=2,
                      max_pages_per_seq=4, chunk_tokens=16)
    from repro.serving.scheduler import Request
    r = Request(rid=0, prompt=np.zeros(40, np.int32), max_new_tokens=8)
    sched.submit(r)
    plan = sched.plan_step()
    assert plan.prefills == [r] and plan.decodes == []
    assert r.state == "prefill" and r.slot >= 0
    assert sched.alloc.available == 16 - 3      # ceil(41/16) reserved upfront
    r.cache_len = 16                            # engine ran the first chunk
    plan = sched.plan_step()
    assert plan.prefills == [r] and plan.decodes == []
    r.cache_len = 40
    r.state = "running"                         # engine: final chunk done
    plan = sched.plan_step()
    assert plan.prefills == [] and plan.decodes == [r]
