"""Explicit data-parallel train step with COMPRESSED gradient all-reduce.

GSPMD hides the gradient reduction inside backward, so dtype-compressing
grads after `jax.grad` never changes wire bytes. This step takes explicit
control via shard_map over the DP axes: local grads -> int16 (or bf16)
quantized psum with a shared scale and error feedback -> replicated AdamW.
Halves DP all-reduce bytes vs f32 (visible in the dry-run HLO; §Perf).

Scope: pure-DP layouts (params replicated), the regime where DP gradient
traffic dominates (small/medium models on big meshes)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


from repro.training.optimizer import adamw_update
from repro.training.steps import TrainOptions, loss_fn

F32 = jnp.float32


def make_dp_train_step(cfg, opts: TrainOptions, mesh, dp_axes: tuple[str, ...], compress: str = "int16_ef"):
    """Returns train_step(params, opt, batch); opt must hold an "ef" tree
    when compress == "int16_ef" (init_train_state handles it)."""
    ndev = 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for a in dp_axes:
        ndev *= sizes[a]
    qmax = max(32767 // ndev, 255)  # int16-sum-safe quantization range

    def psum_compressed(g, ef):
        if compress == "bf16":
            return jax.lax.psum(g.astype(jnp.bfloat16), dp_axes).astype(F32) / ndev, ef
        # int16 + error feedback, shared scale via pmax
        xf = g.astype(F32) + ef
        scale = jax.lax.pmax(jnp.max(jnp.abs(xf)), dp_axes) / qmax + 1e-30
        q = jnp.clip(jnp.round(xf / scale), -qmax, qmax).astype(jnp.int16)
        deq_local = q.astype(F32) * scale
        summed = jax.lax.psum(q, dp_axes).astype(F32) * scale / ndev
        return summed, xf - deq_local

    use_ef = compress == "int16_ef"

    def local_step(params, opt, batch):
        from repro.distributed.sharding import use_rules

        with use_rules(None):  # no GSPMD annotations inside the manual region
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, cfg, batch, opts)
        ef = opt.get("ef") if use_ef else jax.tree.map(lambda g: jnp.zeros_like(g, dtype=F32), grads)
        pairs = jax.tree.map(psum_compressed, grads, ef)
        grads = jax.tree.map(lambda p: p[0], pairs, is_leaf=lambda x: isinstance(x, tuple))
        new_ef = jax.tree.map(lambda p: p[1], pairs, is_leaf=lambda x: isinstance(x, tuple))
        loss = jax.lax.pmean(loss, dp_axes)
        new_p, new_opt, om = adamw_update(opts.adamw, grads, {k: v for k, v in opt.items() if k != "ef"}, params)
        if use_ef:
            new_opt["ef"] = new_ef
        return new_p, new_opt, {"loss": loss, **om}

    rep = P()

    def batch_spec(b):
        return jax.tree.map(lambda _: P(dp_axes), b)

    def train_step(params, opt, batch):
        ospec = {k: (jax.tree.map(lambda _: rep, v) if k != "ef" else jax.tree.map(lambda _: rep, v)) for k, v in opt.items()}
        return jax.shard_map(
            local_step, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: rep, params), ospec, batch_spec(batch)),
            out_specs=(jax.tree.map(lambda _: rep, params), ospec, {"loss": rep, "grad_norm": rep, "lr": rep}),
            check_vma=False,
        )(params, opt, batch)

    return train_step
