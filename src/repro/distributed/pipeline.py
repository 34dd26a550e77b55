"""GPipe-style pipeline parallelism (PP) via shard_map + collective_permute.

For depth scaling beyond what DP×TP covers: layers are split into
``n_stages`` contiguous groups laid out along a ``pipe`` mesh axis; each
microbatch flows stage->stage with lax.ppermute, with the classic GPipe
(n_stages - 1) bubble. Used by tests and exposed through the launcher
(--pp); the 256/512-chip production tables use DP×TP (better fit at <=72B).

The implementation runs every stage's weights on every rank (SPMD) but
masks non-owned stages to zero work via where-gating, which XLA DCEs per
shard after partitioning — standard shard_map pipelining."""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def pipeline_apply(
    mesh: Mesh,
    axis: str,
    layer_fn,
    stacked_params,
    x: jax.Array,
    n_microbatch: int,
):
    """Run ``layer_fn(params_i, x)`` for layers stacked on axis 0 of
    ``stacked_params``, pipelined over mesh axis ``axis``.

    x: (B, ...) with B % n_microbatch == 0. Layers must be divisible by the
    number of stages; params arrive sharded P(axis) on the stack dim."""
    n_stages = mesh.shape[axis]
    L = jax.tree.leaves(stacked_params)[0].shape[0]
    assert L % n_stages == 0, (L, n_stages)
    per_stage = L // n_stages
    B = x.shape[0]
    assert B % n_microbatch == 0

    def stage_fn(params_local, xs):
        # params_local: (per_stage, ...) — this rank's stage layers
        def body(h, lp):
            return layer_fn(lp, h), None

        out, _ = jax.lax.scan(body, xs, params_local)
        return out

    def pipelined(params_local, x_local):
        # x_local: full batch on every pipe rank (replicated in)
        mb = x_local.reshape(n_microbatch, B // n_microbatch, *x_local.shape[1:])
        sid = jax.lax.axis_index(axis)
        n_ticks = n_microbatch + n_stages - 1
        buf = jnp.zeros_like(mb[0])
        outs = jnp.zeros_like(mb)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (when valid)
            take = jnp.clip(t, 0, n_microbatch - 1)
            inject = jnp.where((sid == 0) & (t < n_microbatch), 1.0, 0.0)
            buf = jnp.where(sid == 0, inject * mb[take] + (1 - inject) * buf, buf)
            buf = stage_fn(params_local, buf)
            # last stage emits microbatch t - (n_stages - 1)
            emit_t = t - (n_stages - 1)
            et = jnp.clip(emit_t, 0, n_microbatch - 1)
            do_emit = (sid == n_stages - 1) & (emit_t >= 0)
            outs = jnp.where(do_emit, outs.at[et].set(buf), outs)
            # rotate activations to the next stage
            buf = jax.lax.ppermute(buf, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(n_ticks))
        # broadcast the last stage's outputs to every rank
        if n_stages > 1:
            outs = jax.lax.psum(jnp.where(sid == n_stages - 1, outs, 0.0), axis)
        return outs.reshape(B, *x_local.shape[1:])

    in_specs = (
        jax.tree.map(lambda _: P(axis), stacked_params),
        P(),
    )
    fn = jax.shard_map(
        pipelined, mesh=mesh, in_specs=in_specs, out_specs=P(), check_vma=False
    )
    return fn(stacked_params, x)
