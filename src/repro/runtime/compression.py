"""int8 gradient compression for data-parallel all-reduce.

A shard_map collective that quantizes each gradient leaf to int8 with a
per-leaf fp32 scale, all-reduces the int8 payload (4x less ICI traffic
than fp32, 2x less than bf16), and dequantizes.  Stochastic rounding
keeps the quantization unbiased so SGD-style convergence guarantees are
preserved in expectation.

The main train step lets GSPMD insert its own (uncompressed) gradient
reductions; this wrapper is the opt-in path (`--compress-grads`) for
ICI/DCN-bound deployments — on the multi-pod mesh the "pod" axis
all-reduce crosses data-center links, which is exactly where 4x traffic
reduction pays.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def _quantize(g, key):
    amax = jnp.max(jnp.abs(g)).astype(jnp.float32)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    scaled = g.astype(jnp.float32) / scale
    # stochastic rounding -> unbiased
    noise = jax.random.uniform(key, g.shape, jnp.float32, -0.5, 0.5)
    q = jnp.clip(jnp.round(scaled + noise), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_grad_allreduce(grads, mesh, axis: str = "data",
                              key=None):
    """Mean-all-reduce `grads` across `axis` with int8 payload."""
    if key is None:
        key = jax.random.PRNGKey(0)
    n = mesh.shape[axis]

    def reduce_leaf(g, k):
        q, scale = _quantize(g, k)
        # int8 payloads summed in int32 to avoid overflow across devices
        total = jax.lax.psum(q.astype(jnp.int32), axis)
        scale_sum = jax.lax.psum(scale, axis)
        # each device contributed its own scale; use the mean scale
        return (total.astype(jnp.float32) * (scale_sum / n) / n
                ).astype(g.dtype)

    leaves, treedef = jax.tree.flatten(grads)
    keys = list(jax.random.split(key, len(leaves)))

    @partial(shard_map, mesh=mesh,
             in_specs=(P(),) * (2 * len(leaves)),
             out_specs=(P(),) * len(leaves),
             check_vma=False)
    def run(*args):
        gs, ks = args[:len(leaves)], args[len(leaves):]
        return tuple(reduce_leaf(g, k) for g, k in zip(gs, ks))

    out = run(*leaves, *keys)
    return jax.tree.unflatten(treedef, list(out))
