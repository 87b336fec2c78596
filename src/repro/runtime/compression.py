"""Int8 error-feedback gradient compression for the slow (DCN / pod) axis.

Gradients crossing pods are quantized to int8 with a per-tensor absmax scale
before the cross-pod reduction (2x bytes vs bf16, 4x vs fp32), with error
feedback (the quantization residual is carried into the next step) so the
compression bias vanishes over time — the standard EF-SGD construction.

The reduction itself is expressed as all_gather(int8) + local sum inside
``shard_map`` (int8 psum would overflow; gathering the quantized operands
keeps the wire format int8, which is where the DCN win is).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def quantize_int8(x: jax.Array):
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def ef_compress(x: jax.Array, err: jax.Array):
    """Error-feedback quantize: returns (q, scale, new_err)."""
    target = x.astype(jnp.float32) + err
    q, scale = quantize_int8(target)
    new_err = target - dequantize_int8(q, scale)
    return q, scale, new_err


def make_compressed_mean(mesh: Mesh, axis: str):
    """Returns mean_c(stacked_tree, err_tree) -> (mean_tree, new_err_tree).

    ``stacked_tree`` leaves are (n_shards, ...) with the leading dim sharded
    over ``axis`` — each shard contributes its local gradient; the result is
    the int8-compressed mean, identical on every shard (leading dim kept).
    Error feedback is per-shard state carried across steps.
    """
    n = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]

    def local(tree, err_tree):
        def one(x, e):
            q, scale, new_e = ef_compress(x, e)
            qg = jax.lax.all_gather(q, axis)              # int8 on the wire
            sg = jax.lax.all_gather(scale, axis)
            deq = qg.astype(jnp.float32) * sg.reshape((n,) + (1,) * x.ndim)
            return jnp.sum(deq, axis=0) / n, new_e
        flat, treedef = jax.tree_util.tree_flatten(tree)
        flat_e = treedef.flatten_up_to(err_tree)
        out = [one(x, e) for x, e in zip(flat, flat_e)]
        return (treedef.unflatten([o[0] for o in out]),
                treedef.unflatten([o[1] for o in out]))

    def mean_c(stacked_tree, err_tree):
        fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(axis), P(axis)), out_specs=(P(axis), P(axis)),
            check_vma=True)
        return fn(stacked_tree, err_tree)

    return mean_c


def init_error_state(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, jnp.float32), tree)
