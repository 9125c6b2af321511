"""A configuration file of ``bench/configs`` as the program runs it, and
the weights the benchmark makes for it.

The weights are made here from ``--seed`` in the program's parameter
layout (the layout is the program's checkpoint format: stacked per
layer-pattern slot), so the program and the plain reference are given the
same weights without the reference taking anything the program made.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp

BENCH = Path(__file__).resolve().parent


def load_config(name: str) -> dict:
    path = BENCH / "configs" / f"{name}.json"
    with open(path, encoding="utf-8") as f:
        c = json.load(f)
    c["name"] = name
    return c


def head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def n_groups(c: dict) -> int:
    pattern = c["layer_pattern"]
    if c["num_hidden_layers"] % len(pattern):
        raise ValueError(f"{c['name']}: {c['num_hidden_layers']} layers do "
                         f"not divide into the pattern {pattern}")
    return c["num_hidden_layers"] // len(pattern)


def layer_kinds(c: dict) -> list[str]:
    """Attention kind of every layer, in order."""
    return list(c["layer_pattern"]) * n_groups(c)


def program_config(c: dict):
    """The program's ``ModelConfig`` for configuration ``c``."""
    from repro.configs.base import AttentionConfig, MoBAConfig, ModelConfig
    moba = MoBAConfig(block_size=c["moba_block_size"], top_k=c["moba_top_k"],
                      key_conv_width=c["moba_key_conv_width"])
    attn = AttentionConfig(kind="moba", moba=moba,
                           window=c.get("sliding_window") or 256,
                           rope_on_moba=c["rope_on_moba"],
                           qk_norm=c["qk_norm"],
                           rope_theta=float(c["rope_theta"]))
    return ModelConfig(
        name=c["name"], family="dense", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=head_dim(c),
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        max_seq_len=c["max_position_embeddings"],
        tie_embeddings=c["tie_word_embeddings"],
        rms_norm_eps=c["rms_norm_eps"], attention=attn,
        layer_pattern=tuple(c["layer_pattern"]), dtype=c["compute_dtype"],
        param_dtype=c["param_dtype"])


def param_specs(c: dict) -> dict:
    """``{path: (shape, init)}`` of every weight; ``init`` is ``"one"``
    for norm scales or the standard deviation of a normal draw."""
    d, h, hkv, dh = (c["hidden_size"], c["num_attention_heads"],
                     c["num_key_value_heads"], head_dim(c))
    ff, v, g = c["intermediate_size"], c["vocab_size"], n_groups(c)
    specs = {"embed": ((v, d), 0.02), "final_norm": ((d,), "one")}
    if not c["tie_word_embeddings"]:
        specs["lm_head"] = ((d, v), d ** -0.5)
    for i, _ in enumerate(c["layer_pattern"]):
        s = f"blocks/slot_{i}/"
        specs[s + "norm1"] = ((g, d), "one")
        specs[s + "norm2"] = ((g, d), "one")
        specs[s + "attn/wq"] = ((g, d, h * dh), d ** -0.5)
        specs[s + "attn/wk"] = ((g, d, hkv * dh), d ** -0.5)
        specs[s + "attn/wv"] = ((g, d, hkv * dh), d ** -0.5)
        specs[s + "attn/wo"] = ((g, h * dh, d), (h * dh) ** -0.5)
        if c["qk_norm"]:
            specs[s + "attn/q_norm_scale"] = ((g, dh), "one")
            specs[s + "attn/k_norm_scale"] = ((g, dh), "one")
        specs[s + "mlp/w_gate"] = ((g, d, ff), d ** -0.5)
        specs[s + "mlp/w_up"] = ((g, d, ff), d ** -0.5)
        specs[s + "mlp/w_down"] = ((g, ff, d), ff ** -0.5)
    return specs


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also one past 32 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def make_weights(c: dict, seed: int) -> dict:
    """Every weight of ``c`` in float32, drawn on the device in one jitted
    call from ``seed`` (the same seed gives the same weights)."""
    specs = param_specs(c)

    def draw(key):
        flat = {}
        for i, (path, (shape, init)) in enumerate(sorted(specs.items())):
            if init == "one":
                flat[path] = jnp.ones(shape, jnp.float32)
            else:
                flat[path] = init * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
        return _nest(flat)

    return jax.jit(draw)(seed_key(seed))


def check_layout(c: dict, params) -> None:
    """Raise if the program's own parameter layout differs from the one
    the benchmark makes (names, shapes or dtypes)."""
    from repro.models import transformer as T
    want = jax.eval_shape(lambda: T.init_lm(jax.random.PRNGKey(0),
                                            program_config(c)))
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError(f"{c['name']}: the program's parameter layout "
                           f"differs from the benchmark's weights")
