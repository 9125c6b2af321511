"""Plain float32 reference of the benchmark's configurations.

Straight ``jax.numpy`` at ``Precision.HIGHEST``, one sequence at a time,
written from the configuration file alone: it imports nothing of the
program.  It reads the weights in the program's layout (see
``bench/model.py``), which the benchmark makes from the seed.

The layers are the architecture module's (``bench/arch/<arch>.py``:
``hidden``, ``head`` and ``decays``, and optionally ``batch_loss``, the
training objective where it is more than the mean next-token cross
entropy of the rows), built from the helpers here (``ein``, ``rms``,
``rope``, ``rows``, ``nll_sum``).  What is common to every architecture
is here: the loss, the served tokens' logit gaps, and AdamW.

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

from bench.model import arch

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30
ROWS = 512


def fp8(x):
    """Round to float8 (e4m3) with one scale per tensor; the gradient
    passes straight through, so that the backward reads the rounded
    forward operands."""
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    s = 448.0 / jnp.maximum(amax, 1e-30)
    q = (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    return x + jax.lax.stop_gradient(q - x)


def ein(spec, a, b, low):
    if low:
        a, b = fp8(a), fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x (..., N, d) rotated by absolute positions ``pos`` (N,)."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def rows(n):
    r = min(ROWS, n)
    return r, -(-n // r)


def row_blocks(x, *cols):
    """Pad (N, ...) arrays to whole blocks of rows: (nrb, R, ...)."""
    n = x.shape[0]
    r, nrb = rows(n)
    pad = nrb * r - n
    out = [jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
           .reshape(nrb, r, *a.shape[1:]) for a in (x,) + cols]
    return out, n


def nll_sum(x, w, targets, low):
    """Summed cross entropy of hidden states ``x`` (N, d) under the head
    ``w`` (d, vocab) against ``targets`` (N,), in blocks of rows."""
    (xb, tb, vb), n = row_blocks(x, targets,
                                 jnp.arange(x.shape[0]) < x.shape[0])

    @jax.checkpoint
    def ce(xr, tr, vr):
        lg = ein("rm,mv->rv", xr, w, low)
        nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, tr[:, None], -1)[:, 0]
        return jnp.sum(jnp.where(vr, nll, 0.0))

    return jnp.sum(jax.lax.map(lambda a: ce(*a), (xb, tb, vb)))


def loss(c: dict, params: dict, tokens: jax.Array, low: bool = False):
    """Mean next-token cross entropy of one row ``tokens`` (N+1,)."""
    net = arch(c)
    x = net.hidden(c, params, tokens[:-1], low)
    return nll_sum(x, net.head(c, params), tokens[1:], low) / x.shape[0]


def batch_loss(c: dict, params: dict, tokens: jax.Array, low: bool = False):
    """The training objective on a batch ``tokens`` (B, N+1): the
    architecture's own ``batch_loss`` where it has one (a term taken over
    the whole batch, such as a router's auxiliary loss), else the mean of
    the rows' :func:`loss`."""
    own = getattr(arch(c), "batch_loss", None)
    if own is not None:
        return own(c, params, tokens, low)
    return jnp.mean(jnp.stack([loss(c, params, row, low) for row in tokens]))


def served_gaps(c: dict, params: dict, tokens: jax.Array,
                rows: jax.Array, served: jax.Array):
    """For each position ``rows[i]`` of the sequence ``tokens``, how far
    the logit of ``served[i]`` lies below the best logit there."""
    net = arch(c)
    x = net.hidden(c, params, tokens, False)[rows]
    w = net.head(c, params)
    (xb, sb), n = row_blocks(x, served)

    def gap(xr, sr):
        lg = jnp.einsum("rm,mv->rv", xr, w, precision=HIGHEST)
        return jnp.max(lg, -1) - jnp.take_along_axis(lg, sr[:, None], -1)[:, 0]

    return jax.lax.map(lambda a: gap(*a), (xb, sb)).reshape(-1)[:n]


def low_argmax(c: dict, params: dict, tokens: jax.Array, rows: jax.Array):
    """The token that the float8 control puts first at each of ``rows``."""
    net = arch(c)
    x = net.hidden(c, params, tokens, True)[rows]
    w = net.head(c, params)
    (xb,), n = row_blocks(x)
    am = jax.lax.map(lambda xr: jnp.argmax(ein("rm,mv->rv", xr, w, True),
                                           -1), xb)
    return am.reshape(-1)[:n]


# ------------------------------------------------------------- training
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
    value, grads = jax.value_and_grad(
        lambda p: batch_loss(c, p, tokens, low))(params)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    grads = jax.tree.map(
        lambda g: g * jnp.minimum(1.0, opt["grad_clip"]
                                  / jnp.maximum(gnorm, 1e-9)), grads)
    b1, b2 = opt["beta1"], opt["beta2"]
    lr = jnp.where(step < opt["warmup_steps"],
                   opt["learning_rate"] * (step + 1) / opt["warmup_steps"],
                   opt["learning_rate"])
    t = step + 1
    decays = arch(c).decays
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
