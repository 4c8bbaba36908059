"""GPipe-style pipeline parallelism over a mesh axis via shard_map +
lax.ppermute (the TPU-native inter-pod schedule: activations hop pods on
collective-permute instead of the all-reduce a pure-DP pod axis would
pay).

``spmd_pipeline(fn, stage_params, x, axis_name, n_microbatches)``:
- each device slice along ``axis_name`` holds ONE stage's params
  (stage_params leading dim == axis size, sharded on that axis),
- microbatches stream through stages with the classic skewed schedule:
  tick t runs microbatch (t - stage) on ``stage``,
- total ticks = n_microbatches + n_stages - 1; bubble fraction =
  (S-1)/(M+S-1) — reported by ``pipeline_bubble_fraction``.

Validated against the sequential execution in tests/test_pipeline.py on a
forced multi-device CPU mesh.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)


def spmd_pipeline(fn: Callable, stage_params, x, *, mesh, axis_name: str,
                  n_microbatches: int):
    """x: (n_microbatches, mb, ...) logically on stage 0.  Returns the
    same shape after every stage has processed every microbatch.

    ``fn(params_for_stage, mb_input) -> mb_output`` — one stage's compute.
    ``stage_params``: pytree with leading dim == n_stages (sharded on
    ``axis_name``).
    """
    n_stages = mesh.shape[axis_name]
    assert x.shape[0] == n_microbatches

    def stage_body(params, xs):
        # inside shard_map: params leading dim 1 (this stage's slice)
        params = jax.tree_util.tree_map(lambda t: t[0], params)
        stage = jax.lax.axis_index(axis_name)
        mb = xs[0]                          # (n_mb, mb_size, ...) local copy
        buf = jnp.zeros_like(mb[0])
        out = jnp.zeros_like(mb)
        n_ticks = n_microbatches + n_stages - 1

        def tick(t, carry):
            buf, out = carry
            # stage 0 injects microbatch t (if any), others use incoming buf
            inject = jax.lax.dynamic_index_in_dim(
                mb, jnp.clip(t, 0, n_microbatches - 1), 0, keepdims=False)
            cur = jnp.where(stage == 0, inject, buf)
            y = fn(params, cur)
            # last stage collects microbatch (t - (S-1))
            mb_id = t - (n_stages - 1)
            collect = jnp.logical_and(stage == n_stages - 1, mb_id >= 0)
            out = jax.lax.cond(
                collect,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(mb_id, 0, n_microbatches - 1), 0),
                lambda o: o, out)
            # shift activations to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(y, axis_name, perm)
            return buf, out

        _, out = jax.lax.fori_loop(0, n_ticks, tick, (buf, out))
        return out[None]                    # restore stage dim for shmap

    spec_params = jax.tree_util.tree_map(
        lambda _: P(axis_name), stage_params)
    out = jax.shard_map(
        stage_body, mesh=mesh,
        in_specs=(spec_params, P(*([None] * x.ndim))),
        out_specs=P(axis_name, *([None] * (x.ndim - 1))),
        check_vma=False,
    )(stage_params, x[None])
    # output lives on the last stage's slot; collapse the stage dim
    return out[-1]
