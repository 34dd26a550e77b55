"""jit'd dispatch wrappers over the Pallas kernels with jnp fallbacks.

``use_pallas`` selects the kernel path; the backend decides how a kernel
runs (:mod:`repro.kernels.mode`: interpret mode on the CPU, the only place
the kernels run today). The jnp fallbacks are the ref.py oracles, so
correctness is dispatch-invariant by construction (asserted in
tests/test_kernels.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.decode_jax import DeviceBlocks
from repro.kernels import ref as REF
from repro.kernels.reformat import kmer_pack_pallas, one_hot_pallas
from repro.kernels.sage_decode import sage_decode_pallas
from repro.kernels.ssd_chunk import ssd_intra_pallas

F32 = jnp.float32


def sage_decode(db: DeviceBlocks, *, use_pallas: bool = False):
    """Decode all blocks -> dict(tokens, read_pos, read_rev, ...)."""
    if use_pallas:
        return sage_decode_pallas(db)
    return REF.sage_decode_ref(db)


def kmer_tokens(tokens: jax.Array, k: int, *, use_pallas: bool = False):
    if use_pallas:
        return kmer_pack_pallas(tokens, k)
    return REF.kmer_pack_ref(tokens, k)


def one_hot(tokens: jax.Array, *, use_pallas: bool = False):
    if use_pallas:
        return one_hot_pallas(tokens)
    return REF.one_hot_ref(tokens)


def ssd(x, dt, A, B_, C_, chunk: int, state0=None, *, use_pallas: bool = False):
    """Full SSD: Pallas intra-chunk kernel + jnp inter-chunk recurrence.

    Mirrors repro.models.ssm.ssd_chunked exactly (same padding semantics)."""
    if not use_pallas:
        return REF.ssd_ref(x, dt, A, B_, C_, chunk, state0)

    Bb, S0, H, P = x.shape
    N = B_.shape[-1]
    Q = min(chunk, S0)
    pad = (-S0) % Q
    if pad:
        zf = lambda t: jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        x, dt, B_, C_ = zf(x), zf(dt), zf(B_), zf(C_)
    S = S0 + pad
    nc = S // Q
    a = dt.astype(F32) * A.astype(F32)[None, None, :]
    xc = x.reshape(Bb, nc, Q, H, P)
    dtc = dt.reshape(Bb, nc, Q, H).astype(F32)
    ac = a.reshape(Bb, nc, Q, H)
    Bc = B_.reshape(Bb, nc, Q, H, N).astype(F32)
    Cc = C_.reshape(Bb, nc, Q, H, N).astype(F32)

    y_intra, st_c, total = ssd_intra_pallas(xc, dtc, ac, Bc, Cc)

    state0 = jnp.zeros((Bb, H, P, N), F32) if state0 is None else state0

    def body(state, inp):
        stc, tot = inp  # (B,H,P,N), (B,H)
        new = state * jnp.exp(tot)[:, :, None, None] + stc
        return new, state  # emit the INCOMING state for this chunk

    final, states_in = jax.lax.scan(
        body, state0, (st_c.transpose(1, 0, 2, 3, 4), total.transpose(1, 0, 2))
    )
    states_in = states_in.transpose(1, 0, 2, 3, 4)  # (B,nc,H,P,N)
    cum = jnp.cumsum(ac, axis=2)  # (B,nc,Q,H)
    y_state = jnp.einsum("bcqhn,bchdn->bcqhd", Cc, states_in, preferred_element_type=F32)
    y_state = y_state * jnp.exp(cum)[..., None]
    y = (y_intra.astype(F32) + y_state).reshape(Bb, S, H, P)[:, :S0]
    return y.astype(x.dtype), final
