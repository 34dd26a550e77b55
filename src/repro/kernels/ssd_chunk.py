"""Pallas kernel for the Mamba2 SSD intra-chunk block (the MXU hot spot of
the ssm/hybrid families' long-context cells).

Per grid step (batch b, chunk c): computes the quadratic intra-chunk output
   y = ((C·Bᵀ) ∘ L) · (x·dt)          L[i,j] = exp(cum_i - cum_j)·[i>=j]
plus the chunk's state contribution and decay factors; the linear
inter-chunk recurrence (tiny, (B,H,P,N) per chunk) is combined outside in
jnp (see ops.ssd_pallas). Block shapes: (Q, H, P) x-tile + (Q, H, N)
B/C-tiles + (Q,Q,H) decay tile; with Q=128,H<=80,P=64,N<=128 the working set
is ~6 MiB — VMEM-safe, and the two einsums are 128x128-aligned for the MXU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.mode import interpret_mode

F32 = jnp.float32


def _ssd_intra_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref, dec_ref):
    x = x_ref[0, 0].astype(F32)  # (Q, H, P)
    dt = dt_ref[0, 0].astype(F32)  # (Q, H)
    a = a_ref[0, 0].astype(F32)  # (Q, H) log-decay
    B_ = b_ref[0, 0].astype(F32)  # (Q, H, N)
    C_ = c_ref[0, 0].astype(F32)  # (Q, H, N)
    Q = x.shape[0]
    cum = jnp.cumsum(a, axis=0)  # (Q, H)
    total = cum[-1]  # (H,)
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.exp(cum[:, None, :] - cum[None, :, :])
    L = jnp.where(tri[:, :, None], L, 0.0)
    CB = jnp.einsum("qhn,phn->qph", C_, B_, preferred_element_type=F32)
    M = CB * L
    xdt = x * dt[..., None]
    y = jnp.einsum("qph,phd->qhd", M, xdt, preferred_element_type=F32)
    # chunk state: sum_q B_q x_q dt_q decay(total - cum_q)
    w = dt * jnp.exp(total[None, :] - cum)  # (Q, H)
    st = jnp.einsum("qhn,qhd->hdn", B_ * w[..., None], x, preferred_element_type=F32)
    y_ref[0, 0] = y.astype(y_ref.dtype)
    st_ref[0, 0] = st
    dec_ref[0, 0] = total


def ssd_intra_pallas(x, dt, a, B_, C_):
    """x: (B, nc, Q, H, P); dt, a: (B, nc, Q, H); B_, C_: (B, nc, Q, H, N).

    Returns (y_intra (B,nc,Q,H,P), chunk_state (B,nc,H,P,N), total (B,nc,H),
    cum (B,nc,Q,H)); the caller combines chunks with the linear recurrence."""
    Bb, nc, Q, H, P = x.shape
    N = B_.shape[-1]
    grid = (Bb, nc)
    y, st, tot = pl.pallas_call(
        _ssd_intra_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, H, P), lambda b, c: (b, c, 0, 0, 0)),
            pl.BlockSpec((1, 1, Q, H), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, H), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, H, N), lambda b, c: (b, c, 0, 0, 0)),
            pl.BlockSpec((1, 1, Q, H, N), lambda b, c: (b, c, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, H, P), lambda b, c: (b, c, 0, 0, 0)),
            pl.BlockSpec((1, 1, H, P, N), lambda b, c: (b, c, 0, 0, 0)),
            pl.BlockSpec((1, 1, H), lambda b, c: (b, c, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, nc, Q, H, P), x.dtype),
            jax.ShapeDtypeStruct((Bb, nc, H, P, N), F32),
            jax.ShapeDtypeStruct((Bb, nc, H), F32),
        ],
        interpret=interpret_mode(),
    )(x, dt, a, B_, C_)
    return y, st, tot
