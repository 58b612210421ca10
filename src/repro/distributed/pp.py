"""GPipe-style pipeline parallelism over a mesh axis (shard_map +
collective_permute).

The ``pod`` axis can be re-purposed as a pipeline axis: each pod holds a
contiguous stage of layers; microbatches rotate through stages with
``jax.lax.ppermute``.  This is the standard 1F1B-less GPipe schedule —
bubble fraction (S-1)/(S-1+M) — implemented as a self-contained transform
so any per-stage function can be pipelined.  Demonstrated in
tests/test_distributed.py with a 4-stage MLP on 4 host devices; the
production meshes use pod=2 stages.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_apply"]


def pipeline_apply(stage_fn: Callable, n_stages: int, n_micro: int,
                   mesh, axis: str = "pod"):
    """Returns f(stage_params, x) running stage_fn pipelined over ``axis``.

    stage_params: pytree whose leaves lead with the stage dim (n_stages, ...),
    sharded one-stage-per-device along ``axis``.
    x: (n_micro, micro_batch, ...) microbatched input, replicated.
    Output: (n_micro, micro_batch, ...) after all stages.
    """

    def pipelined(stage_params, x):
        def per_stage(params, xs):
            # params: this stage's slice (leading dim 1); xs: all microbatches
            params = jax.tree.map(lambda a: a[0], params)
            stage_id = jax.lax.axis_index(axis)
            n_steps = n_stages + n_micro - 1
            buf = xs  # (n_micro, mb, ...)
            # carries are device-varying (each stage holds different data):
            # mark them as such for shard_map's vma type system
            carry = jax.lax.pvary(jnp.zeros_like(xs[0]), (axis,))
            outs = jax.lax.pvary(jnp.zeros_like(xs), (axis,))

            def step(t, state):
                carry, outs = state
                # stage 0 injects microbatch t; others take the permuted carry
                inject = jax.lax.dynamic_index_in_dim(
                    buf, jnp.clip(t, 0, n_micro - 1), keepdims=False)
                inp = jnp.where(stage_id == 0, jax.lax.pvary(inject, (axis,)),
                                carry)
                active = (t >= stage_id) & (t - stage_id < n_micro)
                out = jnp.where(active, stage_fn(params, inp), inp)
                # last stage records its finished microbatch
                done_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
                record = (stage_id == n_stages - 1) & (t >= n_stages - 1)
                updated = jax.lax.dynamic_update_index_in_dim(
                    outs, out, done_idx, 0)
                outs = jnp.where(record, updated, outs)
                # rotate stage outputs forward
                carry = jax.lax.ppermute(
                    out, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
                return carry, outs

            carry, outs = jax.lax.fori_loop(0, n_steps, step, (carry, outs))
            # all-gather nothing: outs live on the last stage; broadcast them
            outs = jax.lax.psum(
                jnp.where(stage_id == n_stages - 1, outs, jnp.zeros_like(outs)),
                axis)
            return outs

        return jax.shard_map(
            per_stage, mesh=mesh,
            in_specs=(P(axis), P()),
            out_specs=P(),
        )(stage_params, x)

    return pipelined
