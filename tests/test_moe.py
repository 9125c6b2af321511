"""The expert layer against a plain jnp reference of the whole layer, on
seeded random weights: drop-free at any count and imbalance, the parts
that the shares of an expert-parallel deployment compute add up to the
uncut layer, and the grouped matmul kernel against einsum."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, MoEConfig
from repro.kernels.expert_gmm import gmm
from repro.models import moe as MOE

D, F, E, K = 32, 16, 8, 2
HIGHEST = jax.lax.Precision.HIGHEST


def config(held=0, shared=0):
    return ModelConfig(family="moe", d_model=D, dtype="float32",
                       moe=MoEConfig(num_experts=E, top_k=K, expert_d_ff=F,
                                     experts_held=held,
                                     num_shared_experts=shared))


def reference(p, x, cfg):
    """Every expert on every token; each token keeps its top-k's part."""
    m = cfg.moe
    xf = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(jnp.dot(xf, p["router"], precision=HIGHEST), -1)
    gates, experts = jax.lax.top_k(probs, m.top_k)
    gates = gates / gates.sum(-1, keepdims=True)
    w = p["experts"]
    h = (jax.nn.silu(jnp.einsum("td,edf->etf", xf, w["w_gate"],
                                precision=HIGHEST))
         * jnp.einsum("td,edf->etf", xf, w["w_up"], precision=HIGHEST))
    every = jnp.einsum("etf,efd->etd", h, w["w_down"], precision=HIGHEST)
    held = w["w_gate"].shape[0]
    chosen = every[jnp.minimum(experts, held - 1),
                   jnp.arange(xf.shape[0])[:, None]]            # (T, k, d)
    gates = jnp.where(experts < held, gates, 0.0)
    out = jnp.einsum("tk,tkd->td", gates, chosen, precision=HIGHEST)
    if "shared" in p:
        sh = p["shared"]
        out = out + (jax.nn.silu(xf @ sh["w_gate"]) * (xf @ sh["w_up"])
                     ) @ sh["w_down"]
    return out.reshape(x.shape), experts


def routed_input(case, key):
    """(x (1, T, D), router (D, E)) for a routing case."""
    kx, kr = jax.random.split(key)
    if case == "balanced":
        # token t's top-k is experts tK .. tK+K-1 (mod E): each expert
        # takes T·K/E pairs
        t = 32
        x = jnp.eye(t, D)
        want = (jnp.arange(E)[None] - K * jnp.arange(t)[:, None]) % E
        router = jnp.zeros((D, E)).at[:t].set(-want.astype(jnp.float32))
        return x[None], router
    t = 200 if case == "over_64" else 48
    x = jax.random.normal(kx, (1, t, D))
    router = jax.random.normal(kr, (D, E)) * D ** -0.5
    if case == "one_expert":
        # a shared direction in every token that only expert 3 reads
        x = x.at[..., 0].set(4.0)
        router = router.at[0, 3].set(10.0)
    return x, router


@pytest.mark.parametrize("case", ["balanced", "one_expert", "over_64"])
def test_held_experts_layer_matches_reference(case):
    cfg = config()
    key = jax.random.PRNGKey(len(case))
    p = MOE.init_moe(key, cfg)
    x, p["router"] = routed_input(case, key)
    out, aux, load = jax.jit(lambda p, x: MOE.apply_moe(p, x, cfg))(p, x)
    want, experts = reference(p, x, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    t = x.shape[1]
    # drop-free: every (token, choice) pair reaches its expert
    assert int(load.sum()) == t * K
    np.testing.assert_array_equal(
        np.asarray(load), np.bincount(np.asarray(experts).ravel(),
                                      minlength=E))
    if case == "one_expert":
        assert int(load[3]) == t
    if case == "balanced":
        assert np.all(np.asarray(load) == t * K // E)


def test_held_experts_gradient_matches_reference():
    cfg = config(held=4, shared=1)
    key = jax.random.PRNGKey(3)
    p = MOE.init_moe(key, cfg)
    x = jax.random.normal(key, (2, 40, D))

    def loss(fn, p, x):
        return jnp.sum(jnp.sin(fn(p, x)))

    got = jax.grad(lambda p, x: loss(
        lambda p, x: MOE.apply_moe(p, x, cfg)[0], p, x), (0, 1))(p, x)
    want = jax.grad(lambda p, x: loss(
        lambda p, x: reference(p, x, cfg)[0], p, x), (0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("held", [1, 2, 4])
def test_shares_add_up_to_the_uncut_layer(held):
    """Each of the E/held chips of an expert-parallel deployment holds
    experts [i·held, (i+1)·held); the layer holds its first ``held``, so a
    share is the uncut layer with the router's columns rolled to put its
    experts first.  The parts of all shares, with the shared expert
    counted once, add up to the uncut layer."""
    whole = config(shared=1)
    key = jax.random.PRNGKey(held)
    p = MOE.init_moe(key, whole)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 24, D))
    full, aux_full, _ = MOE.apply_moe(p, x, whole)
    part_cfg = config(held=held)
    total = 0.0
    for i in range(E // held):
        share = {"router": jnp.roll(p["router"], -i * held, axis=1),
                 "experts": jax.tree.map(
                     lambda w: w[i * held:(i + 1) * held], p["experts"])}
        part, aux, load = MOE.apply_moe(share, x, part_cfg)
        assert load.shape == (held,)
        np.testing.assert_allclose(float(aux), float(aux_full), rtol=1e-6)
        total = total + part
    sh = p["shared"]
    xf = x.reshape(-1, D)
    total = total + ((jax.nn.silu(xf @ sh["w_gate"]) * (xf @ sh["w_up"]))
                     @ sh["w_down"]).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(total), np.asarray(full),
                               rtol=1e-5, atol=1e-5)


def test_load_counts_the_held_share_under_fixed_routing():
    """With each expert taking T·K/E pairs, the held experts' counts sum
    to T·K·held/E, and the others' pairs are not computed here."""
    held = 2
    cfg = config(held=held)
    p = MOE.init_moe(jax.random.PRNGKey(0), cfg)
    x, p["router"] = routed_input("balanced", jax.random.PRNGKey(0))
    _, _, load = MOE.apply_moe(p, x, cfg)
    t = x.shape[1]
    assert int(load.sum()) == t * K * held // E
    np.testing.assert_array_equal(np.asarray(load), [t * K // E] * held)


def test_layout_is_drop_free_in_the_worst_case():
    """Every pair on a held expert, a third of them on one: the buffer
    still holds every pair, each expert's rows on tiles of its own."""
    t, k, held = 300, 4, 8
    experts = jnp.tile(jnp.array([[0, 1, 2, 3]]), (t, 1))
    experts = experts.at[:, 0].set(5)
    lay = MOE.layout(experts, held)
    rows = np.asarray(lay.row).ravel()
    assert len(set(rows)) == t * k and rows.max() < lay.token.shape[0]
    assert int(lay.load.sum()) == t * k
    groups = np.asarray(lay.tile_group)[rows // lay.tm]
    np.testing.assert_array_equal(groups, np.asarray(experts).ravel())
    np.testing.assert_array_equal(np.asarray(lay.token)[rows],
                                  np.repeat(np.arange(t), k))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gmm_matches_einsum(dtype):
    """Tiles of one group each, unused tiles zero; gradients of both
    operands as einsum gives them."""
    tm, groups, kdim, n = 16, 3, 24, 40
    sizes = np.array([2, 1, 3])                      # tiles of each group
    tile_group = np.repeat(np.arange(groups), sizes)
    tiles = len(tile_group) + 2                      # two unused tiles
    tile_group = np.concatenate([tile_group, [groups - 1] * 2])
    key = jax.random.PRNGKey(0)
    lhs = jax.random.normal(key, (tiles * tm, kdim)).astype(dtype)
    lhs = lhs.at[len(tile_group[:-2]) * tm:].set(0)
    rhs = jax.random.normal(jax.random.fold_in(key, 1),
                            (groups, kdim, n)).astype(dtype)
    args = (jnp.asarray(tile_group, jnp.int32),
            jnp.array([sizes.sum()], jnp.int32), tm)

    def ref(lhs, rhs):
        w = rhs[np.repeat(tile_group, tm)]
        out = jnp.einsum("mk,mkn->mn", lhs.astype(jnp.float32),
                         w.astype(jnp.float32), precision=HIGHEST)
        return jnp.where(np.arange(tiles * tm)[:, None] < sizes.sum() * tm,
                         out, 0.0)

    got = gmm(lhs, rhs, *args)
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32 else \
        dict(rtol=2e-2, atol=5e-2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref(lhs, rhs)), **tol)
    dout = jax.random.normal(jax.random.fold_in(key, 2), got.shape)
    g = jax.grad(lambda a, b: jnp.sum(gmm(a, b, *args).astype(jnp.float32)
                                      * dout), (0, 1))(lhs, rhs)
    w = jax.grad(lambda a, b: jnp.sum(ref(a, b) * dout), (0, 1))(lhs, rhs)
    for a, b in zip(g, w):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   **(tol if dtype == jnp.float32 else
                                      dict(rtol=3e-2, atol=2e-1)))


def test_default_holds_every_expert():
    m = MoEConfig(num_experts=6)
    assert m.held == 6
    assert dataclasses.replace(m, experts_held=2).held == 2
