"""Pipeline parallelism (GPipe schedule) via shard_map + ppermute.

The layer stack is split into S stages along a 'stage' mesh axis; M
microbatches flow through; each tick every stage processes its resident
microbatch and the activations rotate stage->stage+1 with a single
collective-permute.  Bubble fraction = (S-1)/(M+S-1), the classic GPipe
trade-off.  This module is self-contained (not part of the 40-cell matrix —
those meshes name only pod/data/model axes) and is exercised by a dedicated
multi-device subprocess test.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params, x, *, mesh: Mesh,
                   n_micro: int, axis: str = "stage"):
    """Run x (B, ...) through S pipeline stages.

    stage_fn(params_for_one_stage, microbatch) -> microbatch (same shape).
    stage_params: pytree whose leaves have leading dim S (one slice/stage).
    x: global batch, split into n_micro microbatches along axis 0.
    """
    s = mesh.devices.size
    b = x.shape[0]
    assert b % n_micro == 0
    mb = b // n_micro
    micros = x.reshape(n_micro, mb, *x.shape[1:])

    def body(params_local, micros_local):
        # params_local: (1, ...) slice for this stage; micros: full (replicated)
        params_local = jax.tree.map(lambda a: a[0], params_local)
        idx = lax.axis_index(axis)
        n_ticks = n_micro + s - 1
        buf = jnp.zeros_like(micros_local[0])
        outs = jnp.zeros_like(micros_local)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any); others use rotated buf
            feed = micros_local[jnp.clip(t, 0, n_micro - 1)]
            cur = jnp.where(idx == 0, feed, buf)
            live = (t - idx >= 0) & (t - idx < n_micro)
            y = stage_fn(params_local, cur)
            y = jnp.where(live, y, cur)
            # last stage records its finished microbatch t-(S-1)
            done = jnp.where((idx == s - 1) & live,
                             y, jnp.zeros_like(y))
            outs = lax.dynamic_update_index_in_dim(
                outs, outs[jnp.clip(t - (s - 1), 0, n_micro - 1)] + done,
                jnp.clip(t - (s - 1), 0, n_micro - 1), 0)
            # rotate stage s -> s+1
            buf = lax.ppermute(y, axis,
                               [(i, (i + 1) % s) for i in range(s)])
            return (buf, outs), None

        (buf, outs), _ = lax.scan(tick, (buf, outs),
                                  jnp.arange(n_ticks, dtype=jnp.int32))
        # only the last stage holds real outputs; broadcast to all
        outs = lax.psum(jnp.where(idx == s - 1, outs, jnp.zeros_like(outs)),
                        axis)
        return outs

    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P()),      # params sharded by stage; data replicated
        out_specs=P(),
        check_vma=False,
    )(stage_params, micros)
    return out.reshape(b, *x.shape[1:])
