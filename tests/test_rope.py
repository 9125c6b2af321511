"""RoPE by layer kind: YaRN's inverse frequencies and scale against
values worked out by hand, and which layers take it."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import YarnRope
from repro.models import layers as L

# head 8, θ 1e4: plain inverse frequencies 1, 0.1, 0.01, 0.001.
# correction dim of r rotations over L positions: 8·ln(L/(2πr))/(2·ln 1e4)
#   L=16:   r=32 -> -1.10 (low 0), r=1 -> 0.406 (high ceil 1): ramp
#           0, 1, 1, 1 -> every pair but the first interpolated (/4)
#   L=4096: r=32 -> 1.309 (low 1), r=1 -> 2.814 (high 3): ramp
#           0, 0, 0.5, 1 -> the third pair half-way
BY_HAND = {
    16: [1.0, 0.1 / 4, 0.01 / 4, 0.001 / 4],
    4096: [1.0, 0.1, 0.01 * (0.5 / 4 + 0.5), 0.001 / 4],
}


@pytest.mark.parametrize("original", sorted(BY_HAND))
def test_yarn_inverse_frequencies_by_hand(original):
    yarn = YarnRope(factor=4.0, original_max_position_embeddings=original)
    got = np.asarray(L.rope_freqs(8, 1e4, yarn))
    np.testing.assert_allclose(got, BY_HAND[original], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(L.rope_freqs(8, 1e4)),
                               [1.0, 0.1, 0.01, 0.001], rtol=1e-6)


def test_yarn_scales_cos_and_sin():
    """The rotation keeps each pair's norm; YaRN multiplies it by the
    attention factor, 0.1·ln(factor) + 1 when none is given."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 5, 8))
    pos = jnp.arange(5)
    yarn = YarnRope(factor=4.0, original_max_position_embeddings=16)
    assert L.yarn_attention_factor(yarn) == pytest.approx(
        0.1 * math.log(4.0) + 1.0)
    given = YarnRope(factor=16.0, original_max_position_embeddings=8192,
                     attention_factor=1.2772588722239782)
    for y, scale in ((None, 1.0), (yarn, 0.1 * math.log(4.0) + 1.0),
                     (given, 1.2772588722239782)):
        out = L.apply_rope(x, pos, 1e4, y)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(out), axis=-1),
            scale * np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)


def test_mellum_takes_yarn_on_moba_layers_only(monkeypatch):
    cfg = configs.get_config("mellum2-12b-a2.5b")
    assert cfg.layer_pattern == ("swa", "swa", "swa", "moba")
    a = cfg.attention
    assert (a.rope_theta, a.window, a.rope_on_moba) == (5e5, 1024, True)
    assert a.moba_yarn == YarnRope(16.0, 8192, 32.0, 1.0,
                                   1.2772588722239782)
    assert (cfg.moe.num_experts, cfg.moe.top_k, cfg.moe.expert_d_ff,
            cfg.moe.num_shared_experts) == (64, 8, 896, 0)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.d_model) == (32, 4, 128, 2304)
    # the smoke model's layers: which RoPE each kind's q and k are given
    small = configs.get_smoke_config("mellum2-12b-a2.5b")
    seen = []
    rope = L.apply_rope
    monkeypatch.setattr(L, "apply_rope", lambda x, pos, theta, yarn=None: (
        seen.append((theta, yarn)), rope(x, pos, theta, yarn))[1])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, small.d_model))
    for kind in ("swa", "moba"):
        p = L.init_attention(jax.random.PRNGKey(0), small, kind)
        L.apply_attention(p, x, small, kind)
    y = small.attention.moba_yarn
    assert seen == [(5e5, None)] * 2 + [(5e5, y)] * 2
