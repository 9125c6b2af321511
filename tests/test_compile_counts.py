"""The process's compile counters (``launch/compile_cache.py``)."""
import jax
import jax.numpy as jnp

from repro.launch.compile_cache import compile_counts


def test_a_fresh_jit_compiles_once():
    def tripled_plus_one(x):
        return x * 3 + 1

    f = jax.jit(tripled_plus_one)
    x = jnp.arange(7.0)
    name = "jit(tripled_plus_one)"
    before = compile_counts()
    f(x).block_until_ready()
    first = compile_counts()
    f(x).block_until_ready()
    second = compile_counts()
    assert first["compiles"][name] == before["compiles"].get(name, 0) + 1
    assert first["compile_s"][name] > before["compile_s"].get(name, 0.0)
    assert second["compiles"][name] == first["compiles"][name]
    assert second["compile_s"][name] == first["compile_s"][name]


def test_a_new_shape_compiles_again():
    def halved(x):
        return x / 2

    f = jax.jit(halved)
    name = "jit(halved)"
    before = compile_counts()["compiles"].get(name, 0)
    f(jnp.ones(3)).block_until_ready()
    f(jnp.ones(4)).block_until_ready()
    assert compile_counts()["compiles"][name] == before + 2


def test_snapshot_is_a_copy():
    snap = compile_counts()
    snap["compiles"]["nothing"] = 5
    assert "nothing" not in compile_counts()["compiles"]
    assert {"cache_hits", "cache_misses"} <= set(snap)
