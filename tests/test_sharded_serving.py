"""Sharded multi-host serving engine (DESIGN.md §7).

Device-level tests run in subprocesses on a simulated 8-device host mesh
(``--xla_force_host_platform_device_count``, same trick as
test_distributed.py) because the device count must be set before jax
initializes.  They pin the PR acceptance surface: greedy tokens from the
sharded engine are exact vs the single-host engine and the dense-cache
oracle — across paged backends, key-conv, chunked prefill on a sharded
pool, and preemption replay — plus the hypothesis stream-invariance
property (1 vs 2 vs 4 shards, permuted router submission order), the
shard-invariant prefill-bucket regression, and the context-parallel
fallback for requests longer than one shard's pool.

Host-side pieces (router policy, bucket purity, registry capability
column) need no devices and run in-process.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

_ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(code: str):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(_ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


# --------------------------------------------------------- host-side pieces
def test_prefill_bucket_is_pure_and_shard_invariant():
    """The bucket is a pure function of (n, page_size): same inputs give
    the same width no matter which engine/shard asks — the invariant the
    sharded engine asserts so jit caches cannot fragment per shard."""
    from repro.serving.engine import prefill_bucket
    for ps in (16, 32):
        base = max(16, ps)
        for n in (1, 7, 16, 17, 40, 64, 100):
            w = prefill_bucket(n, ps)
            assert w >= n and w >= base
            assert w == prefill_bucket(n, ps)         # deterministic
            assert w % base == 0 and (w // base) & (w // base - 1) == 0
    assert prefill_bucket(40, 16) == 64
    assert prefill_bucket(17, 16) == 32


def test_router_least_loaded_deterministic():
    """Router picks the fitting shard with the least page demand, ties
    broken by lowest id; requests too large for any shard return −1."""
    from repro.serving.scheduler import Request, Scheduler
    from repro.serving.sharded import Router

    scheds = [Scheduler(num_pages=8, page_size=16, max_seqs=2,
                        max_pages_per_seq=4) for _ in range(3)]
    router = Router(scheds)
    r = lambda rid, n: Request(rid=rid, prompt=np.zeros(n, np.int32),
                               max_new_tokens=8)
    assert router.pick(r(0, 20)) == 0             # all empty → lowest id
    scheds[0].submit(r(1, 20))                    # queue demand counts
    assert scheds[0].load == 2
    assert router.pick(r(2, 20)) == 1
    scheds[1].submit(r(3, 40))
    scheds[2].submit(r(4, 20))
    assert scheds[1].load == 3 and scheds[2].load == 2
    assert router.pick(r(5, 20)) == 0             # 0 and 2 tie at 2 → 0
    assert router.pick(r(6, 100)) == -1           # fits no shard → CP


def test_sharded_backend_capability_column():
    """The `sharded` backend registers paged-capable; the capability
    column gates the sharded engine's admission query (sp backends issue
    their own collectives and must be rejected)."""
    from repro.core import backends as B
    assert "sharded" in B.names()
    be = B.resolve("sharded", kind="moba", phase="decode", cache="paged",
                   sharded=True)
    assert be.name == "sharded" and be.inner == "xla"
    for name in ("reference", "xla", "flash", "sharded"):
        assert B.get(name).capabilities.sharded, name
    for name in ("sp", "sp_unrolled"):
        assert not B.get(name).capabilities.sharded, name
        with pytest.raises(B.BackendCapabilityError, match="sharded"):
            B.resolve(name, kind="moba", phase="prefill", sharded=True)
    assert "sharded" in B.capability_matrix().splitlines()[0]


def test_sharded_backend_single_host_delegation():
    """`sharded` works on one host too: it is just its inner backend, so
    a plain Engine on attn_backend='sharded' matches the xla engine."""
    import jax
    from repro.configs import get_smoke_config
    from repro.models import transformer as T
    from repro.serving.engine import Engine, EngineConfig

    cfg = get_smoke_config("moba-340m")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in (33, 21)]
    outs = {}
    for name in ("xla", "sharded"):
        eng = Engine(cfg, params, EngineConfig(max_seqs=2, max_seq_len=64,
                                               attn_backend=name))
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.run()
        outs[name] = [r.out for r in reqs]
    assert outs["xla"] == outs["sharded"]


# ------------------------------------------------------- simulated 8-device
def test_sharded_engine_matches_single_host_and_oracle():
    """Acceptance: greedy tokens from the 4-shard engine are exact vs
    the single-host engine AND the legacy dense-cache fixed-batch
    oracle (serve vs serve_fixed wiring included)."""
    _run("""
    import numpy as np
    from repro.launch.serve import serve, serve_fixed
    a = np.asarray(serve("moba-340m", batch=4, prompt_len=33, gen=8,
                         smoke=True, attn_backend="sharded", shards=4))
    b = np.asarray(serve("moba-340m", batch=4, prompt_len=33, gen=8,
                         smoke=True, attn_backend="xla"))
    c = np.asarray(serve_fixed("moba-340m", batch=4, prompt_len=33,
                               gen=8, smoke=True))
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, c)
    print("sharded == single-host == oracle")
    """)


def test_sharded_flash_key_conv_chunked_prefill():
    """All paged backends on a sharded pool, including the Pallas flash
    kernel inside the shard_map body, key-conv ring buffers sliced per
    shard, and chunked prefill with conv state carried across chunk
    boundaries — token-exact vs the single-host engine."""
    _run("""
    import jax, numpy as np
    from repro.configs import get_smoke_config
    from repro.models import transformer as T
    from repro.serving.engine import Engine, EngineConfig
    from repro.serving.sharded import ShardedEngine
    cfg = get_smoke_config("moba-340m", key_conv_width=3)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in (40, 33, 21)]
    base = Engine(cfg, params, EngineConfig(max_seqs=3, max_seq_len=64))
    reqs = [base.submit(p, max_new_tokens=8) for p in prompts]
    base.run()
    want = [r.out for r in reqs]
    for kw in ({"attn_backend": "sharded"},
               {"attn_backend": "flash"},
               {"attn_backend": "flash", "prefill_chunk": 24},
               {"attn_backend": "reference", "prefill_chunk": 7}):
        sh = ShardedEngine(cfg, params,
                           EngineConfig(max_seqs=2, max_seq_len=64, **kw),
                           n_shards=2)
        sreqs = [sh.submit(p, max_new_tokens=8) for p in prompts]
        sh.run()
        assert [r.out for r in sreqs] == want, kw
        if kw.get("prefill_chunk"):
            assert sh.stats["prefill_tokens"] == sum(
                len(p) for p in prompts)
        print("OK", kw)
    """)


def test_sharded_preemption_replay_exact():
    """Starved per-shard pools force preemption; recompute replay on the
    owning shard reproduces every request's solo greedy stream."""
    _run("""
    import jax, numpy as np
    from repro.configs import get_smoke_config
    from repro.models import transformer as T
    from repro.serving.engine import Engine, EngineConfig
    from repro.serving.sharded import ShardedEngine
    cfg = get_smoke_config("moba-340m")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in (40, 35, 30, 38)]
    sh = ShardedEngine(cfg, params,
                       EngineConfig(max_seqs=2, max_seq_len=64,
                                    num_pages=6), n_shards=2)
    reqs = [sh.submit(p, max_new_tokens=12) for p in prompts]
    sh.run()
    assert sh.stats["preemptions"] > 0, "test should exercise preemption"
    solo = Engine(cfg, params, EngineConfig(max_seqs=1, max_seq_len=64))
    for p, r in zip(prompts, reqs):
        rs = solo.submit(p, max_new_tokens=12)
        solo.run()
        assert r.out == rs.out, (r.rid, r.out, rs.out)
    print("preemption replay OK:", sh.stats["preemptions"])
    """)


def test_sharded_bucket_invariance_regression():
    """Two shards prefilling ragged prompts in the same step must pad to
    ONE global bucket (the pure-function invariant) — per-shard local
    buckets would compile a decode-step variant per shard and fragment
    the jit cache."""
    _run("""
    import jax, numpy as np
    from repro.configs import get_smoke_config
    from repro.models import transformer as T
    from repro.serving.engine import EngineConfig, prefill_bucket
    from repro.serving.sharded import ShardedEngine
    cfg = get_smoke_config("moba-340m")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    sh = ShardedEngine(cfg, params,
                       EngineConfig(max_seqs=1, max_seq_len=64),
                       n_shards=2)
    # router spreads these across both shards; locally shard 1 would
    # bucket 18 → 32 while shard 0 needs 64
    r0 = sh.submit(rng.integers(0, cfg.vocab_size, 40, dtype=np.int32), 2)
    r1 = sh.submit(rng.integers(0, cfg.vocab_size, 18, dtype=np.int32), 2)
    assert {r0.shard, r1.shard} == {0, 1}
    sh.step()
    assert sh.prefill_widths == {prefill_bucket(40, sh.page_size)} == {64}
    sh.run()
    assert sh.prefill_widths == {64}      # no per-shard 32-wide compile
    print("bucket invariance OK")
    """)


def test_cp_fallback_long_request_matches_dense_oracle():
    """A request longer than one shard's pool routes to context-parallel
    decode over the mesh (moba_decode_cp on shard-local centroids) and
    its greedy stream matches the dense-cache reference oracle."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke_config
    from repro.launch import steps as S
    from repro.models import transformer as T
    from repro.serving.engine import EngineConfig
    from repro.serving.sharded import ShardedEngine
    cfg = get_smoke_config("moba-340m")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    sh = ShardedEngine(cfg, params,
                       EngineConfig(max_seqs=2, max_seq_len=64),
                       n_shards=4)
    prompt = rng.integers(0, cfg.vocab_size, 100, dtype=np.int32)
    short = rng.integers(0, cfg.vocab_size, 20, dtype=np.int32)
    r = sh.submit(prompt, max_new_tokens=10)      # 110 > 64-token shard
    rs = sh.submit(short, max_new_tokens=4)       # paged path untouched
    assert r.shard == -1 and rs.shard >= 0
    # drive through the public step()/has_work() loop (the Engine API
    # mirror): step() must make progress on the CP queue, not livelock
    steps = 0
    while sh.has_work():
        sh.step()
        steps += 1
        assert steps < 100, "step() livelocked on the CP queue"
    assert sh.stats["cp_requests"] == 1
    assert sh.stats["cp_s"] > 0 and sh.stats["cp_tokens"] == 10
    caches = T.init_caches(cfg, 1, 128, dtype=jnp.dtype(cfg.dtype))
    pf = jax.jit(S.make_prefill_step(cfg, backend="reference"))
    df = jax.jit(S.make_decode_step(cfg, backend="reference"))
    logits, caches = pf(params, jnp.asarray(prompt[None]), caches)
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    want = [int(tok[0, 0])]
    for _ in range(9):
        tok, caches = df(params, tok, caches)
        want.append(int(tok[0, 0]))
    assert r.out == want, (r.out, want)
    print("CP fallback == dense oracle")
    """)


def test_cp_decode_awkward_length_falls_back_gracefully():
    """moba_decode_cp must degrade to single-host math, not crash, when
    the cache length cannot shard into whole blocks."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import MoBAConfig, ShardingConfig
    from repro.core import moba
    from repro.distributed import sharding as shmod
    from repro.distributed.moba_sp import moba_decode_cp
    mesh = shmod.auto_mesh((2, 4), ("data", "model"))
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 4, 1, 16))
    kc = jax.random.normal(ks[1], (2, 2, 208, 16))   # 208 % (4*16) != 0
    vc = jax.random.normal(ks[2], (2, 2, 208, 16))
    cfg = MoBAConfig(block_size=16, top_k=3)
    with shmod.use_mesh(mesh, ShardingConfig()):
        out = jax.jit(lambda q, kc, vc: moba_decode_cp(
            q, kc, vc, jnp.array(200), cfg))(q, kc, vc)
    ref = moba.moba_decode_attention(q, kc, vc, jnp.array(200), cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)
    print("awkward length fallback OK")
    """)


def test_property_stream_invariant_to_shard_count_and_order():
    """Hypothesis: random request streams (lengths, arrival times,
    max_new_tokens) produce identical per-request greedy outputs on 1,
    2 and 4 shards, and under a permuted router submission order."""
    pytest.importorskip("hypothesis")
    _run("""
    import jax, numpy as np
    import hypothesis
    import hypothesis.strategies as st
    from hypothesis import given, settings
    from repro.configs import get_smoke_config
    from repro.models import transformer as T
    from repro.serving.engine import Engine, EngineConfig
    from repro.serving.sharded import ShardedEngine

    cfg = get_smoke_config("moba-340m")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    ecfg = lambda ms: EngineConfig(max_seqs=ms, max_seq_len=64)
    # engines are reused across examples: jit caches stay warm and the
    # scheduler fully drains every run()
    single = Engine(cfg, params, ecfg(6))
    fleets = {s: ShardedEngine(cfg, params, ecfg(3), n_shards=s)
              for s in (1, 2, 4)}
    reorder = ShardedEngine(cfg, params, ecfg(3), n_shards=2)

    req_st = st.tuples(st.integers(4, 40),     # prompt length
                       st.integers(1, 8),      # max_new_tokens
                       st.floats(0, 1))        # arrival time
    stream_st = st.lists(req_st, min_size=2, max_size=5)

    @settings(max_examples=5, deadline=None, derandomize=True,
              database=None,
              suppress_health_check=list(hypothesis.HealthCheck))
    @given(stream=stream_st, data=st.data())
    def check(stream, data):
        rng = np.random.default_rng(hash(tuple(stream)) % 2**32)
        prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
                   for n, _, _ in stream]
        outs = []
        for eng in [single] + list(fleets.values()):
            reqs = [eng.submit(p, max_new_tokens=g, arrival=t)
                    for p, (_, g, t) in zip(prompts, stream)]
            eng.run()
            outs.append([r.out for r in reqs])
        assert all(o == outs[0] for o in outs[1:]), outs
        # permuted submission order changes router assignment, not
        # any request's tokens
        perm = data.draw(st.permutations(range(len(stream))))
        rmap = {i: reorder.submit(prompts[i],
                                  max_new_tokens=stream[i][1],
                                  arrival=stream[i][2]) for i in perm}
        reorder.run()
        assert [rmap[i].out for i in range(len(stream))] == outs[0]

    check()
    print("stream invariance OK")
    """)
