"""GPipe-style pipeline parallelism over a mesh axis (demonstration-grade).

Not the default strategy (scan+FSDP+TP covers the assigned shapes — see
DESIGN.md §3), but included to show how the stage schedule maps onto
``shard_map`` + ``collective_permute``: stage s holds layers
[s·L/S, (s+1)·L/S); microbatches stream through with the classic GPipe
bubble.  Works for forward/inference; training would add the reverse
schedule symmetrically.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(stage_fn: Callable, params_stacked, x, mesh: Mesh,
                     axis: str = "model", num_microbatches: int = 4):
    """x (B, ...) → stage_fn applied S times, stages sharded over ``axis``.

    params_stacked: pytree with leading dim S (= mesh.shape[axis]); stage s
    keeps slice s.  Microbatch i enters stage 0 at tick i; total ticks =
    S + M − 1 (the GPipe bubble).
    """
    s_count = mesh.shape[axis]
    b = x.shape[0]
    assert b % num_microbatches == 0
    mb = x.reshape(num_microbatches, b // num_microbatches, *x.shape[1:])

    def per_stage(params_local, mb_local):
        # params_local: this stage's params (leading dim 1); mb_local: all
        # microbatches, only stage 0 feeds real data.
        stage = jax.lax.axis_index(axis)
        params_here = jax.tree.map(lambda p: p[0], params_local)
        ticks = s_count + num_microbatches - 1
        buf = jnp.zeros_like(mb_local[0])
        outs = jnp.zeros_like(mb_local)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (if valid), others use received
            feed = jnp.where(t < num_microbatches,
                             mb_local[jnp.minimum(t, num_microbatches - 1)],
                             jnp.zeros_like(buf))
            inp = jnp.where(stage == 0, feed, buf)
            out = stage_fn(params_here, inp)
            # pass to next stage
            nxt = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % s_count) for i in range(s_count)])
            # last stage records its finished microbatch (t - (S-1))
            done_idx = t - (s_count - 1)
            is_done = (stage == s_count - 1) & (done_idx >= 0)
            outs = jnp.where(
                is_done,
                outs.at[jnp.clip(done_idx, 0, num_microbatches - 1)].set(out),
                outs)
            return (nxt, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs),
                                      jnp.arange(ticks))
        # only the last stage holds real outputs; share with all shards
        return jax.lax.psum(jnp.where(stage == s_count - 1, outs, 0.0),
                            axis)

    spec_p = P(axis)
    fn = jax.shard_map(per_stage, mesh=mesh,
                       in_specs=(jax.tree.map(lambda _: spec_p,
                                              params_stacked), P()),
                       out_specs=P(), check_vma=False)
    outs = fn(params_stacked, mb)
    return outs.reshape(b, *x.shape[1:])
