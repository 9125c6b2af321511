"""Paged-KV serving engine: decode parity, centroid-cache consistency,
page reuse hygiene, preemption resume, continuous-batching equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import MoBAConfig
from repro.core import moba, routing
from repro.launch.serve import serve, serve_fixed
from repro.models import transformer as T
from repro.serving import paged_cache as PC
from repro.serving.engine import Engine, EngineConfig
from repro.serving.scheduler import PageAllocator, Request, Scheduler


def _build_paged(rng, kv_lens, *, hkv=2, d=16, ps=16, npg=8, num_pages=32):
    """Scatter dense ragged caches into a paged pool; returns everything."""
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
    cache = {"pages_k": jnp.zeros((num_pages, hkv, ps, d), jnp.float32),
             "pages_v": jnp.zeros((num_pages, hkv, ps, d), jnp.float32),
             "centroids": jnp.zeros((num_pages, hkv, d), jnp.float32)}
    cache = PC.paged_append_prefill(cache, table, jnp.asarray(kv_lens),
                                    kc, vc)
    return cache, table, kc, vc


def test_paged_decode_matches_reference_ragged():
    """Acceptance: paged decode == moba_decode_attention (which the seed
    suite ties to moba_attention_reference) over ragged cache tails."""
    rng = np.random.default_rng(0)
    kv_lens = np.array([37, 16, 5, 128])
    cfg = MoBAConfig(block_size=16, top_k=3)
    cache, table, kc, vc = _build_paged(rng, kv_lens)
    q = jnp.asarray(rng.normal(size=(len(kv_lens), 4, 1, 16)), jnp.float32)
    out = moba.moba_paged_decode_attention(
        q, cache["pages_k"], cache["pages_v"], cache["centroids"], table,
        jnp.asarray(kv_lens), cfg)
    for i, n in enumerate(kv_lens):
        ref = moba.moba_decode_attention(q[i:i + 1], kc[i:i + 1],
                                         vc[i:i + 1], jnp.array(n), cfg)
        np.testing.assert_allclose(np.asarray(out)[i], np.asarray(ref)[0],
                                   atol=1e-3, rtol=1e-3)


def test_paged_append_decode_incremental_centroids():
    """Rank-1 decode updates must equal a from-scratch recompute."""
    rng = np.random.default_rng(1)
    kv_lens = np.array([37, 16])
    ps = 16
    cache, table, kc, vc = _build_paged(rng, kv_lens, npg=4, num_pages=16)
    table = np.asarray(table).copy()
    used = set(table.ravel())
    table[1, 1] = next(p for p in range(16) if p not in used)
    table = jnp.asarray(table)  # fresh page for seq 1 crossing its boundary
    lens = kv_lens.copy()
    for step in range(5):       # walk both tails across page boundaries
        k1 = jnp.asarray(rng.normal(size=(2, 2, 1, 16)), jnp.float32)
        v1 = jnp.asarray(rng.normal(size=(2, 2, 1, 16)), jnp.float32)
        cache = PC.paged_append_decode(cache, table, jnp.asarray(lens),
                                       jnp.asarray([True, True]), k1, v1)
        lens += 1
    kf, _ = PC.paged_gather_kv(cache, table)
    cents = np.asarray(PC.gather_seq_centroids(cache, table))
    for i, n in enumerate(lens):
        ref = routing.block_centroids(kf[i][:, :n], ps)
        np.testing.assert_allclose(cents[i][:, :-(-n // ps)],
                                   np.asarray(ref), atol=1e-5)


def _gather_engine_seq(eng, req):
    """Per-group (keys, centroids) for one running request, densified."""
    row = jnp.asarray(eng.sched.block_table[req.slot][None])
    out = []
    pattern = eng.cfg.layer_pattern
    moba_slots = [f"slot_{i}" for i, k in enumerate(pattern) if k == "moba"]
    flat = jax.tree_util.tree_map(lambda x: x, eng.caches)
    for slot in moba_slots:
        pool = flat[slot]
        n_groups = pool["pages_k"].shape[0]
        for g in range(n_groups):
            cache_g = {k: v[g] for k, v in pool.items()}
            kf, _ = PC.paged_gather_kv(cache_g, row)
            cents = PC.gather_seq_centroids(cache_g, row)
            out.append((np.asarray(kf)[0], np.asarray(cents)[0]))
    return out


def test_engine_centroid_cache_matches_recompute_interleaved():
    """After interleaved prefill/decode (continuous batching), every
    cached page centroid equals block_centroids recomputation."""
    cfg = get_smoke_config("qwen3-0.6b")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    eng = Engine(cfg, params, EngineConfig(max_seqs=3, max_seq_len=96,
                                           max_prefill_batch=1))
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size, n, dtype=np.int32),
                       max_new_tokens=24)
            for n in (33, 17, 21)]
    # max_prefill_batch=1 forces admissions on successive steps, so later
    # prefills interleave with earlier requests' decode.
    for _ in range(6):
        eng.step()
    ps = eng.page_size
    assert all(r.state == "running" for r in reqs)
    # staggered admission → sequences sit at different ragged lengths
    assert len({r.cache_len for r in reqs}) > 1
    for r in reqs:
        n = r.cache_len
        for kf, cents in _gather_engine_seq(eng, r):
            ref = routing.block_centroids(jnp.asarray(kf[:, :n]), ps)
            np.testing.assert_allclose(cents[:, :-(-n // ps)],
                                       np.asarray(ref), atol=1e-4)


def test_page_reuse_after_eviction_no_stale_keys():
    """Pages freed by a finished request are recycled; the new tenant
    must decode exactly as on a fresh pool (no stale K/V or centroids)."""
    cfg = get_smoke_config("moba-340m")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    prompt_a = rng.integers(0, cfg.vocab_size, 40, dtype=np.int32)
    prompt_b = rng.integers(0, cfg.vocab_size, 37, dtype=np.int32)
    # pool of 6 pages (96 tokens): A and B cannot coexist, B reuses A's
    ecfg = EngineConfig(max_seqs=2, max_seq_len=64, num_pages=6,
                        max_prefill_batch=1)
    eng = Engine(cfg, params, ecfg)
    ra = eng.submit(prompt_a, max_new_tokens=12)
    eng.step()
    pages_a = set(p for p in eng.sched.block_table[ra.slot] if p >= 0)
    eng.run()
    assert ra.done
    rb = eng.submit(prompt_b, max_new_tokens=12)
    eng.step()
    pages_b = set(p for p in eng.sched.block_table[rb.slot] if p >= 0)
    assert pages_a & pages_b, "B must recycle A's physical pages"
    eng.run()
    fresh = Engine(cfg, T.init_lm(jax.random.PRNGKey(0), cfg), ecfg)
    rf = fresh.submit(prompt_b, max_new_tokens=12)
    fresh.run()
    assert rb.out == rf.out, (rb.out, rf.out)


def test_paged_engine_matches_fixed_batch():
    """End-to-end: continuous-batching engine reproduces the legacy
    fixed-batch greedy loop token-for-token (ragged prompt length)."""
    for arch in ("moba-340m", "qwen3-0.6b"):
        a = np.asarray(serve(arch, batch=3, prompt_len=33, gen=8,
                             smoke=True))
        b = np.asarray(serve_fixed(arch, batch=3, prompt_len=33, gen=8,
                                   smoke=True))
        np.testing.assert_array_equal(a, b)


def test_preempted_request_resumes_exactly():
    """Recompute-preemption must not change any request's greedy output."""
    cfg = get_smoke_config("moba-340m")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in (40, 35, 30)]
    # starved pool: 3 requests × up to 64 tokens on 8 pages of 16
    eng = Engine(cfg, params, EngineConfig(max_seqs=3, max_seq_len=64,
                                           num_pages=8))
    reqs = [eng.submit(p, max_new_tokens=14) for p in prompts]
    eng.run()
    assert eng.stats["preemptions"] > 0, "test should exercise preemption"
    for p, r in zip(prompts, reqs):
        solo = Engine(cfg, params, EngineConfig(max_seqs=1,
                                                max_seq_len=64))
        rs = solo.submit(p, max_new_tokens=14)
        solo.run()
        assert r.out == rs.out, (r.rid, r.out, rs.out)


def test_scheduler_allocator_bookkeeping():
    sched = Scheduler(num_pages=7, page_size=16, max_seqs=2,
                      max_pages_per_seq=4)
    with pytest.raises(ValueError):
        sched.submit(Request(rid=0, prompt=np.zeros(100, np.int32),
                             max_new_tokens=1))  # exceeds per-seq capacity
    r1 = Request(rid=1, prompt=np.zeros(33, np.int32), max_new_tokens=8)
    r2 = Request(rid=2, prompt=np.zeros(50, np.int32), max_new_tokens=8)
    sched.submit(r1)
    sched.submit(r2)
    plan = sched.plan_step()
    assert [r.rid for r in plan.prefills] == [1, 2]
    assert sched.alloc.available == 7 - 3 - 4  # ceil(34/16)+ceil(51/16)
    r1.cache_len = 34
    r2.cache_len = 51
    plan = sched.plan_step()  # both fit inside already-allocated pages
    assert not plan.preempted
    # r1 crosses a page boundary with an empty pool → the *youngest*
    # running request (r2) is evicted; the oldest survives.
    r1.cache_len = 48
    plan = sched.plan_step()
    assert [r.rid for r in plan.preempted] == [2]
    assert r2.state == "waiting" and r2.slot == -1 and r2.n_preempt == 1
    assert [r.rid for r in plan.decodes] == [1]
    # r2's 4 pages came back, one went to r1's growth
    assert sched.alloc.available == 3


def test_allocator_free_list():
    alloc = PageAllocator(4)
    pages = [alloc.alloc() for _ in range(4)]
    assert sorted(pages) == [0, 1, 2, 3] and alloc.alloc() is None
    alloc.release(pages[:2])
    assert alloc.available == 2
