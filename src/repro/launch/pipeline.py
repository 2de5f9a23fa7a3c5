"""Pipeline parallelism: GPipe-style stage scan over a mesh axis.

Completes the parallelism matrix (DP/FSDP/TP/EP/SP + PP).  The layer stack
splits into S stages sharded over a ``stage`` mesh axis; microbatches flow
through a (M + S - 1)-step software pipeline where every step runs one
stage computation and rotates activations to the next stage with
``ppermute`` (point-to-point, contiguous on a TPU ring).

Built on ``shard_map`` so the schedule is explicit rather than left to the
SPMD partitioner (EXPERIMENTS.md lesson 4: auto-propagation handles matmul
sharding well but not software pipelines).

Usage (see tests/test_pipeline.py):

    mesh = make_mesh((S,), ("stage",))
    y = pipeline_apply(mesh, stage_fn, stage_params, x, microbatches=M)

``stage_params`` leaves carry a leading stage dim (S, ...); ``stage_fn``
receives one stage's params and one microbatch of activations.  Bubble
fraction is the usual (S - 1) / (M + S - 1).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Array = jax.Array


def pipeline_apply(mesh, stage_fn: Callable, stage_params, x: Array,
                   microbatches: int, axis: str = "stage") -> Array:
    """Run ``x`` through S pipelined stages.

    x: (batch, ...) — split into ``microbatches`` equal slices along dim 0.
    Returns the full output batch (gathered from the last stage).
    """
    s = mesh.shape[axis]
    b = x.shape[0]
    assert b % microbatches == 0, "batch must divide into microbatches"
    m = microbatches
    mb = x.reshape(m, b // m, *x.shape[1:])

    def per_stage(params_local, mb_local):
        # params_local: this stage's params (leading stage dim stripped to 1)
        params_local = jax.tree.map(lambda a: a[0], params_local)
        idx = jax.lax.axis_index(axis)
        perm = [(i, (i + 1) % s) for i in range(s)]

        def step(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t (while valid); others take the
            # activation handed over by the previous stage.
            mb_t = jax.lax.dynamic_index_in_dim(
                mb_local, jnp.clip(t, 0, m - 1), keepdims=False)
            inp = jnp.where(idx == 0, mb_t, state)
            out = stage_fn(params_local, inp)
            # the last stage retires microbatch (t - S + 1)
            retire = jnp.clip(t - (s - 1), 0, m - 1)
            valid = (idx == s - 1) & (t >= s - 1)
            prev = jax.lax.dynamic_index_in_dim(outputs, retire,
                                                keepdims=False)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(valid, out, prev), retire, 0)
            state = jax.lax.ppermute(out, axis, perm)
            return (state, outputs), None

        outputs0 = jnp.zeros_like(mb_local)
        state0 = jnp.zeros_like(mb_local[0])
        (_, outputs), _ = jax.lax.scan(step, (state0, outputs0),
                                       jnp.arange(m + s - 1))
        # broadcast the last stage's outputs to every stage (so the result
        # is replicated; a real trainer would keep it stage-local)
        outputs = jax.lax.psum(
            jnp.where(idx == s - 1, outputs, jnp.zeros_like(outputs)),
            axis)
        return outputs

    p_specs = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(per_stage, mesh=mesh,
                       in_specs=(p_specs, P()), out_specs=P(),
                       check_vma=False)
    out = fn(stage_params, mb)
    return out.reshape(b, *x.shape[1:])


def bubble_fraction(stages: int, microbatches: int) -> float:
    return (stages - 1) / (microbatches + stages - 1)
