"""GPipe-style pipeline parallelism over ``shard_map`` + ``lax.ppermute``.

Optional stage-parallel execution (DESIGN.md §5): stages live on
consecutive ranks of a mesh axis; microbatches flow through a
(n_micro + n_stages − 1)-tick schedule with activations handed to the
next stage by collective-permute each tick.

This is a self-contained engine (covered by tests/test_pipeline.py with a
sequential-equality oracle); the dry-run meshes default to DP×TP with the
"pod" axis as outer DP, but any stage-sliceable block stack can run
through `pipeline_apply` on a ("stage", …) mesh.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

Tree = Any


def pipeline_apply(block_fn: Callable[[Tree, jax.Array], jax.Array],
                   stage_params: Tree, x_micro: jax.Array, mesh,
                   axis: str = "stage") -> jax.Array:
    """Run `y = stageS-1(…stage0(x))` with stages sharded over `axis`.

    stage_params: leaves (n_stages, …), sharded on dim 0 over `axis`.
    x_micro: (n_micro, mb, …) microbatched input (replicated).
    Returns (n_micro, mb, …) outputs of the final stage (replicated).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    n_ticks = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def local(params_local, x_local):
        # params_local: (1, …) this rank's stage; x_local: full microbatches
        params1 = jax.tree.map(lambda a: a[0], params_local)
        idx = jax.lax.axis_index(axis)

        def tick(carry, t):
            recv, outs = carry
            # stage 0 ingests microbatch t (while valid); others take recv
            mb = jnp.clip(t, 0, n_micro - 1)
            x_in = jnp.where(idx == 0, x_local[mb], recv)
            y = block_fn(params1, x_in)
            # the last stage emits microbatch (t - n_stages + 1)
            out_t = t - (n_stages - 1)
            valid = jnp.logical_and(idx == n_stages - 1,
                                    jnp.logical_and(out_t >= 0,
                                                    out_t < n_micro))
            outs = jax.lax.cond(
                valid,
                lambda o: o.at[jnp.clip(out_t, 0, n_micro - 1)].set(y),
                lambda o: o, outs)
            recv = jax.lax.ppermute(y, axis, perm)
            return (recv, outs), None

        recv0 = jnp.zeros_like(x_local[0])
        outs0 = jnp.zeros_like(x_local)
        (_, outs), _ = jax.lax.scan(tick, (recv0, outs0),
                                    jnp.arange(n_ticks))
        # only the last stage holds real outputs; broadcast them
        outs = jax.lax.psum(
            jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    pspec = jax.tree.map(lambda _: PS(axis), stage_params)
    # replication checking off: the ring body's collectives are untyped
    fn = jax.shard_map(local, mesh=mesh, in_specs=(pspec, PS()),
                       out_specs=PS(), check_vma=False)
    return fn(stage_params, x_micro)
