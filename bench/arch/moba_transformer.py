"""The dense pre-norm transformer of ``moba-340m`` and ``qwen3-0.6b``:
RMSNorm pre-norm blocks, GQA attention whose layers are ``moba`` or
``swa`` (``layer_pattern``), SwiGLU MLP, optional per-head q/k RMSNorm,
tied or separate LM head.

Configuration keys read: the published ``hidden_size``,
``num_hidden_layers``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``intermediate_size``, ``vocab_size``,
``max_position_embeddings``, ``rms_norm_eps``, ``rope_theta``,
``sliding_window``, ``tie_word_embeddings``; the attention layout
``layer_pattern``, ``qk_norm``, ``rope_on_moba``, ``moba_block_size``,
``moba_top_k``, ``moba_key_conv_width``; and ``compute_dtype``,
``param_dtype``.

Reference layers (``hidden``):

* rotary embedding on the halves of each head (``rope_theta``), on
  ``swa`` layers and, with ``rope_on_moba``, on ``moba`` layers;
* ``moba``: keys are mean-pooled into blocks of ``moba_block_size``; a
  query scores every block by ``q . centroid`` (no softmax scale), its own
  block is always selected, later blocks never, and the best scoring
  earlier blocks fill ``moba_top_k`` places; it then attends causally to
  the keys of the selected blocks;
* ``swa``: causal attention to the last ``sliding_window`` keys.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import HIGHEST, NEG, ein, rms, rope, rows
from bench.work.model import AttentionLayer


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


# ------------------------------------------------------------- program
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


# ----------------------------------------------------------- reference
def _attention(c, kind, q, k, v, low):
    """q (H, N, d), k/v (Hkv, N, d) -> (H, N, d), causal."""
    h, n, d = q.shape
    hkv = k.shape[0]
    g = h // hkv
    bs, top_k = c["moba_block_size"], c["moba_top_k"]
    nb = -(-n // bs)
    r, nrb = rows(n)
    pad = nrb * r - n
    qg = jnp.pad(q, ((0, 0), (0, pad), (0, 0))).reshape(hkv, g, nrb, r, d)
    kpos = jnp.arange(n)
    if kind == "moba":
        kp = jnp.pad(k, ((0, 0), (0, nb * bs - n), (0, 0)))
        cnt = jnp.clip(n - jnp.arange(nb) * bs, 1, bs).astype(jnp.float32)
        cent = kp.reshape(hkv, nb, bs, d).sum(2) / cnt[:, None]
        cent = jax.lax.stop_gradient(cent)

    @jax.checkpoint
    def block(i, qb):                                   # qb (Hkv, G, R, d)
        qpos = i * r + jnp.arange(r)
        mask = qpos[:, None] >= kpos[None, :]           # (R, N)
        if kind == "swa":
            mask &= qpos[:, None] - kpos[None, :] < c["sliding_window"]
        mask = jnp.broadcast_to(mask, (hkv, g, r, n))
        if kind == "moba":
            sc = jnp.einsum("hgrd,hbd->hgrb", jax.lax.stop_gradient(qb),
                            cent, precision=HIGHEST)
            own = (qpos // bs)[:, None]
            blk = jnp.arange(nb)[None, :]
            sc = jnp.where(blk > own, NEG, sc)
            sc = jnp.where(blk == own, -NEG, sc)
            top, idx = jax.lax.top_k(sc, min(top_k, nb))
            chosen = jnp.any((idx[..., None] == jnp.arange(nb))
                             & (top > NEG / 2)[..., None], axis=-2)
            mask &= chosen[..., kpos // bs]
        s = ein("hgrd,hnd->hgrn", qb, k, low) * d ** -0.5
        s = jnp.where(mask, s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        return ein("hgrn,hnd->hgrd", p, v, low)

    out = jax.lax.map(lambda a: block(*a),
                      (jnp.arange(nrb), jnp.moveaxis(qg, 2, 0)))
    out = jnp.moveaxis(out, 0, 2).reshape(h, nrb * r, d)
    return out[:, :n]


def _layer(c, kind, p, x, low):
    n, _ = x.shape
    h, hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                 head_dim(c))
    eps = c["rms_norm_eps"]
    a = p["attn"]
    y = rms(x, p["norm1"], eps)
    q = ein("nm,mk->nk", y, a["wq"], low).reshape(n, h, d).transpose(1, 0, 2)
    k = ein("nm,mk->nk", y, a["wk"], low).reshape(n, hkv, d).transpose(1, 0, 2)
    v = ein("nm,mk->nk", y, a["wv"], low).reshape(n, hkv, d).transpose(1, 0, 2)
    if c["qk_norm"]:
        q = rms(q, a["q_norm_scale"], eps)
        k = rms(k, a["k_norm_scale"], eps)
    if kind == "swa" or c["rope_on_moba"]:
        pos = jnp.arange(n)
        q = rope(q, pos, float(c["rope_theta"]))
        k = rope(k, pos, float(c["rope_theta"]))
    o = _attention(c, kind, q, k, v, low).transpose(1, 0, 2).reshape(n, h * d)
    x = x + ein("nk,km->nm", o, a["wo"], low)
    y = rms(x, p["norm2"], eps)
    m = p["mlp"]
    hid = (jax.nn.silu(ein("nm,mf->nf", y, m["w_gate"], low))
           * ein("nm,mf->nf", y, m["w_up"], low))
    return x + ein("nf,fm->nm", hid, m["w_down"], low)


def hidden(c: dict, params: dict, tokens: jax.Array, low: bool):
    """Final-normed hidden states (N, d) of one sequence ``tokens`` (N,)."""
    x = params["embed"][tokens]
    pattern = c["layer_pattern"]

    def group(x, gp):
        for i, kind in enumerate(pattern):
            x = jax.checkpoint(
                lambda x, p, kind=kind: _layer(c, kind, p, x, low))(
                    x, gp[f"slot_{i}"])
        return x, None

    x, _ = jax.lax.scan(group, x, params["blocks"])
    return rms(x, params["final_norm"], c["rms_norm_eps"])


def head(c: dict, params: dict):
    """The LM head's (d, vocab) matrix."""
    return (params["embed"].T if c["tie_word_embeddings"]
            else params["lm_head"])


def decays(path: str) -> bool:
    """AdamW's weight decay applies to the matrices, not the norm scales."""
    return not path.rsplit("/", 1)[-1].endswith(("norm", "norm1", "norm2",
                                                   "scale"))


# ---------------------------------------------------------------- work
def active_matmul_params(c: dict) -> int:
    """Matmul parameters a token runs through in every layer (the head
    excluded): q/k/v/o and the SwiGLU MLP."""
    d, h, hkv = (c["hidden_size"], c["num_attention_heads"],
                 c["num_key_value_heads"])
    dh, ff = head_dim(c), c["intermediate_size"]
    layer = d * (h + 2 * hkv) * dh + h * dh * d + 3 * d * ff
    return c["num_hidden_layers"] * layer


def attention_layers(c: dict) -> list[AttentionLayer]:
    """One entry per layer, in order; q, k and v share the head size."""
    h, hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                 head_dim(c))
    out = []
    for kind in layer_kinds(c):
        if kind == "swa":
            extra = {"window": c["sliding_window"]}
        elif kind == "moba":
            extra = {"block_size": c["moba_block_size"],
                     "top_k": c["moba_top_k"]}
        else:
            extra = {}
        out.append(AttentionLayer(kind, h, hkv, d, d, **extra))
    return out
