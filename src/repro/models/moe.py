"""Fine-grained MoE (DeepSeek-MoE / Moonlight style): shared experts +
top-k routed experts with capacity-bounded, sort-based dispatch.

Dispatch is the TPU-friendly sort route: flatten (token, choice) pairs, sort
by expert, compute position-in-expert from segment starts, scatter into an
(E, capacity, d) buffer (expert axis sharded over `model` = EP), run batched
expert FFNs, gather back and combine. Overflowing tokens are dropped (their
weight mass is renormalized away), the standard capacity-factor contract.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import F32, _act, dense_init, mlp_apply, mlp_init


def moe_init(key, cfg, dtype=F32) -> dict:
    d = cfg.d_model
    f = cfg.expert_d_ff or cfg.d_ff
    E = cfg.n_experts
    ks = jax.random.split(key, 5)
    experts = {
        "up": jax.random.normal(ks[0], (E, d, f), dtype) / jnp.sqrt(d).astype(dtype),
        "gate": jax.random.normal(ks[1], (E, d, f), dtype) / jnp.sqrt(d).astype(dtype),
        "down": jax.random.normal(ks[2], (E, f, d), dtype) / jnp.sqrt(f).astype(dtype),
    }
    p = {"router": dense_init(ks[3], d, E, dtype), "experts": experts}
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks[4], d, f * cfg.n_shared_experts, gated=True, dtype=dtype)
    return p


def expert_capacity(n_tokens: int, cfg) -> int:
    cap = int(n_tokens * cfg.moe_top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, (cap + 7) // 8 * 8)


def _dispatch_ffn(x, top_e, top_w, wg, wu, wd, cfg, e_off, E_local, seq_chunk: int = 1024):
    """Row-wise sort dispatch + expert FFN + combine for a LOCAL expert slice
    [e_off, e_off + E_local), scanned over sequence chunks so the (B, Sc*k, d)
    dispatch transients stay bounded. x: (B, S, d); returns the partial y
    (tokens routed to other shards' experts contribute zero)."""
    B, S, d = x.shape
    if S > seq_chunk and S % seq_chunk == 0:
        nch = S // seq_chunk
        resh = lambda t: t.reshape(B, nch, seq_chunk, *t.shape[2:]).swapaxes(0, 1)

        def body(_, inp):
            xc, tec, twc = inp
            return None, _dispatch_ffn(xc, tec, twc, wg, wu, wd, cfg, e_off, E_local, seq_chunk)

        _, ys = jax.lax.scan(body, None, (resh(x), resh(top_e), resh(top_w)))
        return ys.swapaxes(0, 1).reshape(B, S, d)
    k = cfg.moe_top_k
    fe = top_e.reshape(B, S * k)
    fw = top_w.reshape(B, S * k).astype(x.dtype)
    order = jnp.argsort(fe, axis=-1)  # (B, S*k) — one sort per row
    se = jnp.take_along_axis(fe, order, axis=-1)
    sw = jnp.take_along_axis(fw, order, axis=-1)
    tok = order // k
    seg_start = jax.vmap(
        lambda row: jnp.searchsorted(row, jnp.arange(cfg.n_experts, dtype=row.dtype), side="left")
    )(se)
    pos = jnp.arange(S * k, dtype=jnp.int32)[None, :] - jnp.take_along_axis(
        seg_start, se, axis=-1
    ).astype(jnp.int32)
    cap = expert_capacity(S, cfg)
    sel = se.astype(jnp.int32) - e_off  # local expert id
    keep = (pos < cap) & (sel >= 0) & (sel < E_local)
    sel_s = jnp.where(keep, sel, E_local)  # E_local -> dropped
    pos_s = jnp.where(keep, pos, 0)
    xg = jnp.take_along_axis(x, tok[..., None], axis=1)  # (B, S*k, d)
    bidx = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[:, None], sel_s.shape)
    buf = jnp.zeros((B, E_local, cap, d), x.dtype).at[bidx, sel_s, pos_s].set(xg, mode="drop")

    g = jnp.einsum("becd,edf->becf", buf, wg.astype(x.dtype))
    u = jnp.einsum("becd,edf->becf", buf, wu.astype(x.dtype))
    h = _act(g, cfg.act) * u
    out_buf = jnp.einsum("becf,efd->becd", h, wd.astype(x.dtype))

    val = out_buf[bidx, sel_s.clip(0, E_local - 1), pos_s]
    val = jnp.where(keep[..., None], val, 0) * sw[..., None]
    return jnp.zeros((B, S, d), x.dtype).at[bidx, tok].add(val)


def moe_apply(p, x, cfg):
    """x: (B, S, d) -> (y, aux_loss).

    Distributed path (rules installed): explicit EP via shard_map — each
    `model` shard owns n_experts/tp experts, dispatches its LOCAL data-shard
    rows to them with zero communication, and one psum over `model` combines
    partial outputs (same wire cost as a Megatron MLP all-reduce, no
    replicated (B,E,cap,d) buffers — see EXPERIMENTS.md §Perf).
    Single-device path: same math with the full expert slice."""
    from repro.distributed.sharding import current_rules
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.moe_top_k

    logits = (x @ p["router"].astype(x.dtype)).astype(F32)  # (B, S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)  # (B, S, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # ---- load-balance aux loss (Switch/DeepSeek style) ----
    me = probs.mean(axis=(0, 1))  # (E,)
    onehot_counts = jnp.sum(
        jax.nn.one_hot(top_e.reshape(B, -1), E, dtype=F32), axis=(0, 1)
    ) / (B * S * k)
    aux = E * jnp.sum(me * onehot_counts)

    w = p["experts"]
    rules = current_rules()
    m = rules.model_axis if rules is not None and not rules.pure_dp else None
    tp = rules.mesh.shape[m] if m is not None else 1
    if rules is None or m is None or tp == 1 or E % tp != 0:
        y = _dispatch_ffn(x, top_e, top_w, w["gate"], w["up"], w["down"], cfg, 0, E)
    else:
        dp = rules.batch()
        xspec = P(dp, None, None)
        kspec = P(dp, None, None)
        espec = P(m, None, None)

        def local(xl, te, tw, wg, wu, wd):
            e_local = wg.shape[0]
            off = jax.lax.axis_index(m) * e_local
            yl = _dispatch_ffn(xl, te, tw, wg, wu, wd, cfg, off, e_local)
            return jax.lax.psum(yl, m)

        y = jax.shard_map(
            local, mesh=rules.mesh,
            in_specs=(xspec, kspec, kspec, espec, espec, espec),
            out_specs=xspec, check_vma=False,
        )(x, top_e, top_w, w["gate"], w["up"], w["down"])

    # ---- shared experts (always-on dense path) ----
    if cfg.n_shared_experts:
        y = y + mlp_apply(p["shared"], x, cfg.act, gated=True)
    return y, aux
