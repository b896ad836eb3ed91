"""Coarsened collectives — the paper's burst-coalescing insight on ICI.

One wide all-reduce moves the same bytes with one descriptor + one latency
instead of N; `bucketed_psum` flattens a gradient pytree into ~64MB buckets
(optim.compression.plan_buckets) and reduces each bucket once.  The
fig9_collectives benchmark measures per-tensor vs bucketed on the HLO level
(collective op count) and wall-time on CPU.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.optim.compression import plan_buckets, bucket_coarsen, bucket_restore


def bucketed_psum(grads: Any, *, mesh: Mesh, axis: str = "data",
                  bucket_bytes: int = 64 * 2 ** 20):
    """All-reduce a pytree over `axis` as few wide buckets (coarsened)."""
    plan = plan_buckets(grads, bucket_bytes)

    def body(*buckets):
        return tuple(lax.psum(b, axis) for b in buckets)

    buckets = bucket_coarsen(grads, plan)
    specs = tuple(P() for _ in buckets)
    reduced = jax.shard_map(body, mesh=mesh, in_specs=specs, out_specs=specs,
                        check_vma=False)(*buckets)
    return bucket_restore(list(reduced), plan)


def int8_ef_psum(grads: Any, residual: Any, *, mesh: Mesh,
                 axis: str = "data"):
    """DP all-reduce with int8 error-feedback compression: 4x fewer wire
    bytes; the quantization error is carried in `residual` (EF-SGD).

    Wire protocol: quantize locally -> psum int32 (int8 payload widened for
    overflow-safe accumulation; real ICI would use int8 RS with f32
    accumulators) -> rescale by the max of per-shard scales.
    Returns (reduced grads, new residual).
    """
    from repro.optim.compression import int8_compress_grads
    q, scales, new_resid = int8_compress_grads(grads, residual)

    leaves_q, treedef = jax.tree.flatten(q)
    leaves_s = jax.tree.leaves(scales)

    def body(*ls):
        n = len(ls) // 2
        qs, ss = ls[:n], ls[n:]
        out = []
        for qq, s in zip(qs, ss):
            smax = lax.pmax(s, axis)
            acc = lax.psum(qq.astype(jnp.int32), axis)
            out.append(acc.astype(jnp.float32) * smax)
        return tuple(out)

    specs = tuple(P() for _ in range(2 * len(leaves_q)))
    out_specs = tuple(P() for _ in leaves_q)
    reduced = jax.shard_map(body, mesh=mesh, in_specs=specs, out_specs=out_specs,
                        check_vma=False)(*leaves_q, *leaves_s)
    return jax.tree.unflatten(treedef, reduced), new_resid


def pertensor_psum(grads: Any, *, mesh: Mesh, axis: str = "data"):
    """Baseline: one all-reduce per parameter tensor (the 'narrow LSU')."""
    leaves, treedef = jax.tree.flatten(grads)

    def body(*ls):
        return tuple(lax.psum(l, axis) for l in ls)

    specs = tuple(P() for _ in leaves)
    reduced = jax.shard_map(body, mesh=mesh, in_specs=specs, out_specs=specs,
                        check_vma=False)(*leaves)
    return jax.tree.unflatten(treedef, reduced)
