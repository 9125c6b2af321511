"""Distributed-path tests on a small multi-device host mesh.

These run in a subprocess because the device count must be set before jax
initializes (the main test process keeps 1 device).
"""
import os
import subprocess
import sys
import textwrap


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


def test_sp_attention_matches_reference():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import MoBAConfig, ShardingConfig
    from repro.core import moba
    from repro.distributed import sharding as shmod
    from repro.distributed.moba_sp import moba_attention_sp
    mesh = shmod.auto_mesh((2, 4), ("data", "model"))
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 4, 128, 16))
    k = jax.random.normal(ks[1], (2, 2, 128, 16))
    v = jax.random.normal(ks[2], (2, 2, 128, 16))
    cfg = MoBAConfig(block_size=16, top_k=3)
    with shmod.use_mesh(mesh, ShardingConfig()):
        out = jax.jit(lambda q, k, v: moba_attention_sp(
            q, k, v, cfg, tile=16))(q, k, v)
    ref = moba.moba_attention_reference(q, k, v, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)
    print("SP OK")
    """)


def test_cp_decode_matches_reference():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import MoBAConfig, ShardingConfig
    from repro.core import moba
    from repro.distributed import sharding as shmod
    from repro.distributed.moba_sp import moba_decode_cp
    mesh = shmod.auto_mesh((2, 4), ("data", "model"))
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 4, 1, 16))
    kc = jax.random.normal(ks[1], (2, 2, 256, 16))
    vc = jax.random.normal(ks[2], (2, 2, 256, 16))
    cfg = MoBAConfig(block_size=16, top_k=3)
    for kv_len in (256, 200, 130):
        with shmod.use_mesh(mesh, ShardingConfig()):
            out = jax.jit(lambda q, kc, vc: moba_decode_cp(
                q, kc, vc, jnp.array(kv_len), cfg))(q, kc, vc)
        ref = moba.moba_decode_attention(q, kc, vc, jnp.array(kv_len), cfg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-4, atol=3e-4)
    print("CP decode OK")
    """)


def test_compressed_psum_all_shards_agree():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.optim import compression
    from repro.distributed.sharding import auto_mesh
    mesh = auto_mesh((8,), ("data",))
    g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))

    def body(g_local, r_local):
        tree, res = compression.compressed_psum(
            {"g": g_local}, ("data",), {"g": r_local})
        return tree["g"], res["g"]

    out, res = jax.shard_map(body, mesh=mesh,
                             in_specs=(P("data"), P("data")),
                             out_specs=(P("data"), P("data")),
                             check_vma=False)(g, jnp.zeros((8, 64)))
    true_mean = jnp.mean(g, axis=0)
    for shard in np.asarray(out).reshape(8, 1, 64):
        np.testing.assert_allclose(shard[0], np.asarray(true_mean),
                                   atol=0.05)
    print("compressed psum OK")
    """)


def test_pipeline_forward():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.distributed.pipeline import pipeline_forward
    from repro.distributed.sharding import auto_mesh
    mesh = auto_mesh((4,), ("model",))
    # 4 stages of y = tanh(x @ w_s)
    ws = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 16)) * 0.5
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16))

    def stage(w, x):
        return jnp.tanh(x @ w)

    out = pipeline_forward(stage, ws, x, mesh, axis="model",
                           num_microbatches=4)
    ref = x
    for s in range(4):
        ref = jnp.tanh(ref @ ws[s])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    print("pipeline OK")
    """)


def test_dryrun_single_cell_compiles():
    """The dry-run entry point itself (512 devices) on the smallest cell."""
    env = dict(os.environ, PYTHONPATH=os.path.join(_ROOT, "src"))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--archs", "qwen3-0.6b", "--shapes", "decode_32k",
         "--mesh", "single", "--out", "/tmp/dryrun_test"],
        capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout[-2000:]}\n" \
                              f"STDERR:\n{r.stderr[-2000:]}"
    assert "lowered + compiled OK" in r.stdout
