"""GPipe pipeline parallelism over one mesh axis.

Each device along ``stage_axis`` owns one pipeline stage's parameters
(leading stage dim sharded over the axis).  The schedule is the classic
GPipe fill/steady/drain loop: ``n_micro + n_stages - 1`` ticks, every tick
each stage runs its microbatch and ships the activation to the next stage
with a ring ``ppermute``.  The bubble is the fill+drain overhead —
``bubble_fraction`` below is the standard (S-1)/(M+S-1) accounting.

Numerics: the composed pipeline must equal running the stages sequentially
on one device — ``tests/test_pipeline.py`` pins that in a 2-simulated-device
subprocess.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    """GPipe bubble overhead: (S-1) idle ticks out of (M+S-1) total."""
    return (n_stages - 1) / (n_micro + n_stages - 1)


def gpipe(stage_fn, params, x_mb: jax.Array, mesh: Mesh,
          stage_axis: str = "pod"):
    """Run ``n_micro`` microbatches through the stage pipeline.

    stage_fn:  (stage_params, x) -> y, the per-stage forward.
    params:    pytree whose leaves carry a leading (n_stages, ...) dim,
               sharded over ``stage_axis``.
    x_mb:      (n_micro, ...) microbatches, replicated.

    Returns (n_micro, ...) outputs after all stages, replicated.
    """
    n_stages = mesh.shape[stage_axis]
    n_micro = x_mb.shape[0]
    n_ticks = n_micro + n_stages - 1
    fwd_ring = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def local_fn(p_loc, x_loc):
        stage = jax.lax.axis_index(stage_axis)
        p_my = jax.tree.map(lambda a: a[0], p_loc)

        def tick(t, carry):
            outs, recv = carry
            # Stage 0 injects microbatch t (clipped reads during drain are
            # computed but never reach the last stage inside the loop).
            inject = x_loc[jnp.clip(t, 0, n_micro - 1)]
            x_in = jnp.where(stage == 0, inject, recv)
            y = stage_fn(p_my, x_in)
            out_idx = t - (n_stages - 1)
            write = (stage == n_stages - 1) & (out_idx >= 0)
            slot = jnp.clip(out_idx, 0, n_micro - 1)
            outs = jnp.where(write, outs.at[slot].set(y), outs)
            recv = jax.lax.ppermute(y, stage_axis, perm=fwd_ring)
            return outs, recv

        outs0 = jnp.zeros_like(x_loc)
        outs, _ = jax.lax.fori_loop(0, n_ticks, tick,
                                    (outs0, jnp.zeros_like(x_loc[0])))
        # Only the last stage holds results; psum replicates them.
        outs = jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, stage_axis)

    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(jax.tree.map(lambda _: P(stage_axis),
                                              params), P()),
                       out_specs=P(), check_vma=False)
    return fn(params, x_mb)
