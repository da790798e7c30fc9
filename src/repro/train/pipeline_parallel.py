"""Pipeline parallelism over the 'pod' axis (DESIGN.md §6).

GPipe-style fill/drain schedule written with shard_map +
lax.ppermute: each pod stage holds half the layer stack; microbatch
activations flow stage->stage over ICI while both stages stay busy in the
steady state. This module proves PP viability on the multi-pod mesh (the
default multi-pod config composes 'pod' into data parallelism instead).

The schedule below runs forward-only pipelining for serving/eval or as a
building block; training composes it with jax.grad per microbatch chunk.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_forward(stage_fn, params_by_stage, x_micro, *, mesh,
                     axis: str = "pod"):
    """stage_fn(stage_params, h) -> h; params_by_stage: pytree whose
    leaves have a leading [n_stages] dim sharded over ``axis``;
    x_micro: (n_micro, mb, ...) microbatched inputs (replicated).
    Returns (n_micro, mb, ...) outputs of the final stage.
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]

    def spmd(stage_params, xs):
        stage = jax.lax.axis_index(axis)
        sp = jax.tree.map(lambda a: a[0], stage_params)  # local stage slice
        total = n_micro + n_stages - 1
        h = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def tick(t, carry):
            h_in, outs = carry
            # stage 0 ingests microbatch t (if any remain)
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            h_cur = jnp.where(stage == 0,
                              xs[mb_idx].astype(h_in.dtype), h_in)
            h_out = stage_fn(sp, h_cur)
            # last stage emits microbatch (t - n_stages + 1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            emit = jnp.logical_and(stage == n_stages - 1,
                                   t >= n_stages - 1)
            outs = jax.lax.cond(
                emit,
                lambda o: o.at[out_idx].set(h_out.astype(o.dtype)),
                lambda o: o, outs)
            # shift activations to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            h_next = jax.lax.ppermute(h_out, axis, perm)
            return (h_next, outs)

        _, outs = jax.lax.fori_loop(0, total, tick, (h, outs))
        # only the last stage holds real outputs; share them
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    in_specs = (jax.tree.map(lambda _: P(axis), params_by_stage),
                P())
    # replica checking off: `outs` is deliberately stage-varying until
    # the final psum
    return jax.shard_map(spmd, mesh=mesh, in_specs=in_specs, out_specs=P(),
                         check_vma=False)(params_by_stage, x_micro)
