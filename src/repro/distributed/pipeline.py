"""GPipe-style pipeline parallelism over the 'pod' axis (shard_map +
ppermute).

The multi-pod mesh's leading axis defaults to outer data parallelism; this
module provides the alternative: each pod holds a contiguous stage of
layers and microbatches flow pod-to-pod over the inter-pod links. The
schedule is the classic GPipe fill/steady/drain loop — T = M + S - 1 steps
for M microbatches over S stages, bubble fraction (S-1)/T.

`pipeline_forward` is deliberately minimal (forward-only, uniform stages):
it demonstrates and tests the communication pattern the trainer would use;
tests/test_distributed.py checks it against the sequential reference on a
fabricated multi-device host mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(mesh: Mesh, stage_fn, stage_params, microbatches,
                     axis: str = "pod"):
    """Run microbatches through S pipeline stages laid over `axis`.

    stage_params: pytree with leading dim S (one slice per stage).
    microbatches: (M, ...) microbatch array entering stage 0.
    Returns (M, ...) outputs leaving stage S-1.
    """
    n_stages = mesh.shape[axis]
    m = microbatches.shape[0]
    steps = m + n_stages - 1

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False)
    def run(params_local, micro):
        params_local = jax.tree_util.tree_map(lambda a: a[0], params_local)
        sid = jax.lax.axis_index(axis)
        buf = jnp.zeros_like(micro[0])
        outs = jnp.zeros_like(micro)

        def step(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t during the fill phase
            inject = jnp.where(t < m, t, 0)
            x = jnp.where(sid == 0,
                          jnp.where(t < m, micro[inject], buf), buf)
            y = stage_fn(params_local, x)
            # pass to the next stage (ring permute; the wraparound edge
            # carries the finished output back to a replicated buffer)
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            # outputs leave stage S-1 at step t with microbatch index
            # t - (S - 1)
            out_idx = t - (n_stages - 1)
            done = out_idx >= 0
            contribution = jnp.where(
                jnp.logical_and(sid == n_stages - 1, done), y, 0.0)
            # make the finished microbatch visible on all stages
            contribution = jax.lax.psum(contribution, axis)
            outs = jnp.where(done,
                             outs.at[jnp.maximum(out_idx, 0)].set(
                                 contribution),
                             outs)
            return (nxt, outs), None

        (buf, outs), _ = jax.lax.scan(step, (buf, outs),
                                      jnp.arange(steps))
        return outs

    return run(stage_params, microbatches)


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
