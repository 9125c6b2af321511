"""A configuration file of ``bench/configs``, the architecture module it
names, and the weights the benchmark makes for it.

The configuration's ``arch`` key names its module in ``bench/arch``,
which knows the program's parameter layout (the program's checkpoint
format).  The weights are made here from ``--seed`` in that layout, so
the program and the plain reference are given the same weights without
the reference taking anything the program made.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp

BENCH = Path(__file__).resolve().parent
ARCH = BENCH / "arch"


def load_config(name: str) -> dict:
    path = BENCH / "configs" / f"{name}.json"
    with open(path, encoding="utf-8") as f:
        c = json.load(f)
    c["name"] = name
    return c


def load_module(path: Path):
    """The Python file ``path`` as a module of its own."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.cache
def _arch_module(path: Path):
    return load_module(path)


def arch(c: dict):
    """The architecture module ``bench/arch/<arch>.py`` that configuration
    ``c`` names: the program's config and weight layout, the plain
    reference's layers, and the work of a token (``bench/arch/
    moba_transformer.py`` is the first)."""
    path = ARCH / f"{c['arch']}.py"
    if not path.is_file():
        shown = (path.relative_to(BENCH.parent)
                 if path.is_relative_to(BENCH.parent) else path)
        raise FileNotFoundError(f"{c['name']}: no architecture module "
                                f"{c['arch']!r}; add {shown}")
    return _arch_module(path)


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
    specs = arch(c).param_specs(c)

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
                                            arch(c).program_config(c)))
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError(f"{c['name']}: the program's parameter layout "
                           f"differs from the benchmark's weights")
