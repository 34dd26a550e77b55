"""Pallas kernel for SAGe_Read output formatting (§5.3: "2-bit or 1-hot").

Converts decoded base tokens into the accelerator's desired format:
  * k-mer LM token ids (packs k bases into one id = the 2-bit format folded
    onto the assigned archs' vocabularies)
  * one-hot bf16 planes (the [106]-style format)

The grid has one step per block; each step formats that block's (1, C)
token row in VMEM. Trivially parallel, MXU-free, VPU-bound. Like the decode
kernel, each ``pallas_call`` is built once per shape signature and wrapped in
``jax.jit`` so the store's bucketed reads never re-lower the formatter.

Runs only in interpret mode, on the CPU backend (:mod:`repro.kernels.mode`).
For a v5e, Mosaic refuses the (1, C) row blocks of any multi-block grid
(tests/test_tpu_compile.py); at one block the k-mer body still fails on its
row reshape, and only one-hot compiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.api import kmer_special_ids
from repro.core.decode_jax import PAD_BASE, TRACE_COUNTS
from repro.kernels.mode import interpret_mode


def kmer_ids_row(t: jax.Array, k: int, n_tok) -> jax.Array:
    """One block's k-mer ids: (C,) int32 base tokens -> (C//k,) int32 ids.

    Pure jnp row math shared by the standalone kmer kernel and the fused
    gather+decode+reformat kernel (repro.kernels.sage_decode) — one
    definition is the bit-identity guarantee between the two.
    ``n_tok=None`` is the legacy contract (PAD and in-read N
    indistinguishable); with a scalar ``n_tok`` the kmer_pack contract
    holds: N-block inside ``n_tok``, pad at/past it."""
    C = t.shape[0]
    g = t[: (C // k) * k].reshape(C // k, k)
    gz = jnp.where(g > 3, 0, g)
    ids = jnp.zeros((C // k,), jnp.int32)
    for i in range(k):  # Horner — avoids captured weight constants
        ids = ids * 4 + gz[:, i]
    sp = kmer_special_ids(k)
    has4 = jnp.any(g == PAD_BASE, axis=-1)  # PAD_BASE == 4 == N code
    if n_tok is None:
        return jnp.where(has4, sp["pad"], ids)
    gi = jnp.arange(C // k, dtype=jnp.int32)
    in_read = (gi + 1) * k <= n_tok
    return jnp.where(has4, jnp.where(in_read, sp["nblk"], sp["pad"]), ids)


def one_hot_row(t: jax.Array) -> jax.Array:
    """One block's one-hot plane: (C,) int tokens -> (C, 4) bool (callers
    cast to their output dtype). Shared with the fused kernel."""
    return t[:, None] == jnp.arange(4, dtype=jnp.int32)[None, :]


def _kmer_kernel(k: int, with_ntok: bool, *refs):
    if with_ntok:
        tok_ref, ntok_ref, out_ref = refs
        n_tok = ntok_ref[0, 0]
    else:
        tok_ref, out_ref = refs
        n_tok = None
    out_ref[0] = kmer_ids_row(tok_ref[0].astype(jnp.int32), k, n_tok)


@functools.lru_cache(maxsize=64)
def _build_kmer_pack(nb: int, C: int, k: int, with_ntok: bool, interpret: bool):
    in_specs = [pl.BlockSpec((1, C), lambda i: (i, 0))]
    if with_ntok:
        in_specs.append(pl.BlockSpec((1, 1), lambda i: (i, 0)))
    call = pl.pallas_call(
        functools.partial(_kmer_kernel, k, with_ntok),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, C // k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, C // k), jnp.int32),
        interpret=interpret,
    )

    @jax.jit
    def run(tokens, *ntok):
        TRACE_COUNTS["format_kmer_pallas"] += 1
        return call(tokens, *ntok)

    return run


def kmer_pack_pallas(
    tokens: jax.Array, k: int, n_tokens: jax.Array | None = None
) -> jax.Array:
    """tokens: (nb, C) int8 (+ per-block real-token counts (nb,)) ->
    (nb, C//k) int32. See :func:`repro.core.api.kmer_pack` for the
    PAD-vs-N-block disambiguation ``n_tokens`` enables."""
    nb, C = tokens.shape
    if nb == 0:  # a grid of zero steps cannot be built (or run)
        return jnp.zeros((0, C // k), jnp.int32)
    if n_tokens is None:
        return _build_kmer_pack(nb, C, k, False, interpret_mode())(tokens)
    ntok = jnp.asarray(n_tokens, jnp.int32)[:, None]
    return _build_kmer_pack(nb, C, k, True, interpret_mode())(tokens, ntok)


def _onehot_kernel(tok_ref, out_ref):
    t = tok_ref[0].astype(jnp.int32)  # (TILE,)
    out_ref[0] = one_hot_row(t).astype(out_ref.dtype)


@functools.lru_cache(maxsize=64)
def _build_one_hot(nb: int, C: int, interpret: bool):
    call = pl.pallas_call(
        _onehot_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, C), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, C, 4), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, C, 4), jnp.bfloat16),
        interpret=interpret,
    )

    @jax.jit
    def run(tokens):
        TRACE_COUNTS["format_onehot_pallas"] += 1
        return call(tokens)

    return run


def one_hot_pallas(tokens: jax.Array) -> jax.Array:
    """tokens: (nb, C) int8 -> (nb, C, 4) bf16 (PAD rows all-zero)."""
    nb, C = tokens.shape
    if nb == 0:  # a grid of zero steps cannot be built (or run)
        return jnp.zeros((0, C, 4), jnp.bfloat16)
    return _build_one_hot(nb, C, interpret_mode())(tokens)
