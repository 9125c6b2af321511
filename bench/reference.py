"""Plain float32 reference of the benchmark's configurations.

Straight ``jax.numpy`` at ``Precision.HIGHEST``, one sequence at a time,
written from the configuration file alone: it imports nothing of the
program.  It reads the weights in the program's layout (see
``bench/model.py``), which the benchmark makes from the seed.

Layers follow the configuration:

* RMSNorm pre-norm blocks, SwiGLU MLP, optional per-head q/k RMSNorm,
  rotary embedding on the halves of each head (``rope_theta``);
* ``moba``: keys are mean-pooled into blocks of ``moba_block_size``; a
  query scores every block by ``q . centroid`` (no softmax scale), its own
  block is always selected, later blocks never, and the best scoring
  earlier blocks fill ``moba_top_k`` places; it then attends causally to
  the keys of the selected blocks;
* ``swa``: causal attention to the last ``sliding_window`` keys;
* tied or separate LM head; the loss is the mean next-token cross
  entropy.

``low=True`` computes every matmul on float8 (e4m3) operands with one
scale per tensor, accumulated in float32: the precision below the
configuration's bfloat16, the control that ``correct`` must reject.

Attention and the head run in blocks of rows, and each layer is
rematerialised, so that a sequence of the benchmark's sizes fits next to
the training state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30
ROWS = 512


def _fp8(x):
    """Round to float8 (e4m3) with one scale per tensor; the gradient
    passes straight through, so that the backward reads the rounded
    forward operands."""
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    s = 448.0 / jnp.maximum(amax, 1e-30)
    q = (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    return x + jax.lax.stop_gradient(q - x)


def _ein(spec, a, b, low):
    if low:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (..., N, d) rotated by absolute positions ``pos`` (N,)."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _rows(n):
    r = min(ROWS, n)
    return r, -(-n // r)


def _attention(c, kind, q, k, v, low):
    """q (H, N, d), k/v (Hkv, N, d) -> (H, N, d), causal."""
    h, n, d = q.shape
    hkv = k.shape[0]
    g = h // hkv
    bs, top_k = c["moba_block_size"], c["moba_top_k"]
    nb = -(-n // bs)
    r, nrb = _rows(n)
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
        s = _ein("hgrd,hnd->hgrn", qb, k, low) * d ** -0.5
        s = jnp.where(mask, s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        return _ein("hgrn,hnd->hgrd", p, v, low)

    out = jax.lax.map(lambda a: block(*a),
                      (jnp.arange(nrb), jnp.moveaxis(qg, 2, 0)))
    out = jnp.moveaxis(out, 0, 2).reshape(h, nrb * r, d)
    return out[:, :n]


def _layer(c, kind, p, x, low):
    n, _ = x.shape
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    d = c.get("head_dim") or c["hidden_size"] // h
    eps = c["rms_norm_eps"]
    a = p["attn"]
    y = _rms(x, p["norm1"], eps)
    q = _ein("nm,mk->nk", y, a["wq"], low).reshape(n, h, d).transpose(1, 0, 2)
    k = _ein("nm,mk->nk", y, a["wk"], low).reshape(n, hkv, d).transpose(1, 0, 2)
    v = _ein("nm,mk->nk", y, a["wv"], low).reshape(n, hkv, d).transpose(1, 0, 2)
    if c["qk_norm"]:
        q = _rms(q, a["q_norm_scale"], eps)
        k = _rms(k, a["k_norm_scale"], eps)
    if kind == "swa" or c["rope_on_moba"]:
        pos = jnp.arange(n)
        q = _rope(q, pos, float(c["rope_theta"]))
        k = _rope(k, pos, float(c["rope_theta"]))
    o = _attention(c, kind, q, k, v, low).transpose(1, 0, 2).reshape(n, h * d)
    x = x + _ein("nk,km->nm", o, a["wo"], low)
    y = _rms(x, p["norm2"], eps)
    m = p["mlp"]
    hid = (jax.nn.silu(_ein("nm,mf->nf", y, m["w_gate"], low))
           * _ein("nm,mf->nf", y, m["w_up"], low))
    return x + _ein("nf,fm->nm", hid, m["w_down"], low)


def hidden(c: dict, params: dict, tokens: jax.Array, low: bool = False):
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
    return _rms(x, params["final_norm"], c["rms_norm_eps"])


def head(c: dict, params: dict):
    return (params["embed"].T if c["tie_word_embeddings"]
            else params["lm_head"])


def _row_blocks(x, *cols):
    """Pad (N, ...) arrays to whole blocks of rows: (nrb, R, ...)."""
    n = x.shape[0]
    r, nrb = _rows(n)
    pad = nrb * r - n
    out = [jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
           .reshape(nrb, r, *a.shape[1:]) for a in (x,) + cols]
    return out, n


def loss(c: dict, params: dict, tokens: jax.Array, low: bool = False):
    """Mean next-token cross entropy of one row ``tokens`` (N+1,)."""
    x = hidden(c, params, tokens[:-1], low)
    w = head(c, params)
    (xb, tb, vb), n = _row_blocks(x, tokens[1:],
                                  jnp.arange(x.shape[0]) < x.shape[0])

    @jax.checkpoint
    def ce(xr, tr, vr):
        lg = _ein("rm,mv->rv", xr, w, low)
        nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, tr[:, None], -1)[:, 0]
        return jnp.sum(jnp.where(vr, nll, 0.0))

    return jnp.sum(jax.lax.map(lambda a: ce(*a), (xb, tb, vb))) / n


def served_gaps(c: dict, params: dict, tokens: jax.Array,
                rows: jax.Array, served: jax.Array):
    """For each position ``rows[i]`` of the sequence ``tokens``, how far
    the logit of ``served[i]`` lies below the best logit there."""
    x = hidden(c, params, tokens)[rows]
    w = head(c, params)
    (xb, sb), n = _row_blocks(x, served)

    def gap(xr, sr):
        lg = jnp.einsum("rm,mv->rv", xr, w, precision=HIGHEST)
        return jnp.max(lg, -1) - jnp.take_along_axis(lg, sr[:, None], -1)[:, 0]

    return jax.lax.map(lambda a: gap(*a), (xb, sb)).reshape(-1)[:n]


def low_argmax(c: dict, params: dict, tokens: jax.Array, rows: jax.Array):
    """The token that the float8 control puts first at each of ``rows``."""
    x = hidden(c, params, tokens, low=True)[rows]
    w = head(c, params)
    (xb,), n = _row_blocks(x)
    am = jax.lax.map(lambda xr: jnp.argmax(_ein("rm,mv->rv", xr, w, True),
                                           -1), xb)
    return am.reshape(-1)[:n]


# ------------------------------------------------------------- training
def decays(path: str) -> bool:
    """AdamW's weight decay applies to the matrices, not the norm scales."""
    return not path.rsplit("/", 1)[-1].endswith(("norm", "norm1", "norm2",
                                                   "scale"))


def leaf_norms(tree) -> dict:
    """``{path: norms}``: one norm per layer for stacked leaves (leading
    group axis under ``blocks``), one for the others."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        x = x.astype(jnp.float32)
        if name.startswith("blocks/"):
            out[name] = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(x * x))[None]
    return out


def adamw_step(c: dict, opt: dict, params: dict, m: dict, v: dict,
               tokens: jax.Array, step: int, low: bool = False):
    """One AdamW step (global-norm clipping, bias correction, linear
    warmup) on a batch ``tokens`` (B, N+1).  Returns (params, m, v, loss,
    per-leaf norms of the clipped gradient)."""
    def batch_loss(p):
        return jnp.mean(jnp.stack([loss(c, p, row, low) for row in tokens]))

    value, grads = jax.value_and_grad(batch_loss)(params)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    grads = jax.tree.map(
        lambda g: g * jnp.minimum(1.0, opt["grad_clip"]
                                  / jnp.maximum(gnorm, 1e-9)), grads)
    b1, b2 = opt["beta1"], opt["beta2"]
    lr = jnp.where(step < opt["warmup_steps"],
                   opt["learning_rate"] * (step + 1) / opt["warmup_steps"],
                   opt["learning_rate"])
    t = step + 1
    flat, tdef = jax.tree_util.tree_flatten_with_path(params)
    names = ["/".join(str(getattr(p, "key", p)) for p in path)
             for path, _ in flat]
    new_p, new_m, new_v = [], [], []
    for name, p, g, mi, vi in zip(names, [x for _, x in flat],
                                  jax.tree.leaves(grads), jax.tree.leaves(m),
                                  jax.tree.leaves(v)):
        mi = b1 * mi + (1 - b1) * g
        vi = b2 * vi + (1 - b2) * g * g
        upd = (mi / (1 - b1 ** t)) / (jnp.sqrt(vi / (1 - b2 ** t)) + 1e-8)
        if decays(name):
            upd = upd + opt["weight_decay"] * p
        new_p.append(p - lr * upd)
        new_m.append(mi)
        new_v.append(vi)
    unflat = lambda xs: jax.tree_util.tree_unflatten(tdef, xs)  # noqa: E731
    return (unflat(new_p), unflat(new_m), unflat(new_v), value,
            leaf_norms(grads))
