"""GPipe-style pipeline parallelism over a mesh axis (the multi-pod "pod"
axis can be claimed as a stage axis instead of outer-DP; DESIGN.md §7).

Schedule: classic GPipe fill-drain with M microbatches over K stages
(bubble fraction (K-1)/(M+K-1)); the inter-stage hop is a single
``lax.ppermute`` (collective-permute on the wire — point-to-point, the only
collective the schedule needs).

Implemented with ``shard_map``: stage parameters are sharded over the axis
(leading dim = stage id); activations flow through the permute ring.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_run(mesh: Mesh, axis: str, stage_fn, stage_params, x_mb):
    """Run microbatches through a K-stage pipeline.

    stage_fn: (params_for_stage, x) -> y   (same shape as x)
    stage_params: pytree with leading dim K (sharded over ``axis``)
    x_mb: (M, mb, ...) microbatched input (replicated)

    Returns (M, mb, ...) outputs of the last stage.
    """
    K = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    M = x_mb.shape[0]
    T = M + K - 1                       # fill-drain schedule length
    perm = [(i, i + 1) for i in range(K - 1)]

    def local(params, xs):
        # params: (1, ...) this stage's slice; xs: (M, mb, ...) replicated
        p = jax.tree_util.tree_map(lambda a: a[0], params)
        idx = jax.lax.axis_index(axis)

        def step(carry, t):
            buf, outs = carry           # buf: (mb, ...) incoming activation
            # stage 0 ingests microbatch t (when valid), others take buf
            mb_idx = jnp.clip(t, 0, M - 1)
            x_in = jnp.where(idx == 0, xs[mb_idx], buf)
            y = stage_fn(p, x_in)
            # last stage emits microbatch t - (K - 1)
            out_idx = jnp.clip(t - (K - 1), 0, M - 1)
            valid = (t >= K - 1)
            outs = jax.lax.cond(
                valid,
                lambda o: o.at[out_idx].set(y),
                lambda o: o,
                outs)
            buf_next = jax.lax.ppermute(y, axis, perm)
            return (buf_next, outs), None

        # the carries vary over the stage axis (each stage holds its own)
        buf0 = jax.lax.pcast(jnp.zeros_like(xs[0]), axis, to="varying")
        outs0 = jax.lax.pcast(jnp.zeros_like(xs), axis, to="varying")
        (_, outs), _ = jax.lax.scan(step, (buf0, outs0), jnp.arange(T))
        # only the last stage's collection is meaningful; replicate it
        outs = jnp.where(idx == K - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P()), out_specs=P(),
        check_vma=True)
    # a mesh with Explicit axes (``jax.make_mesh``'s default) types the
    # per-stage indexing inside the body only under a mesh context
    with jax.set_mesh(mesh):
        return fn(stage_params, x_mb)


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
