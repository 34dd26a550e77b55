"""Pallas TPU kernel for SAGe block decode (the paper's SU+RCU in TPU form).

Grid = one step per SAGe block (the analogue of the per-NAND-channel decode
units, §5.2): every stream's BlockSpec maps grid step i to that block's
word slice, so each step streams its block's compressed bits HBM->VMEM,
decodes with the data-parallel scan math of
:func:`repro.core.decode_jax.decode_block_arrays` (single source of truth,
shared with the vmap reference), and writes the token tile back.

Serving contract: the ``pallas_call`` is built once per (capacities,
classes, block-count, stream-shapes) signature — an ``lru_cache``-ed
builder wraps it in ``jax.jit`` so repeated ranged reads reuse one
compiled executable (the store's shape buckets keep the set of signatures
small). An optional ``valid`` input column carries the bucket-padding mask
into the kernel; invalid lanes emit deterministic PAD/zero planes.

VMEM sizing (the BlockSpec contract): with the default data-pipeline block
capacity (tokens<=16Ki, window<=1Mi bases), one grid step's working set is
  streams:      <= ~0.2 MiB (compressed bits)
  cons window:  window/16 u32 = 0.25 MiB
  decode temps: ~24 int32 arrays of C=16Ki = ~1.5 MiB
comfortably inside a v5e core's VMEM. Capacities are static (from SageMeta),
so the same kernel serves any read set produced by the encoder.

Runs only in interpret mode, on the CPU backend (:mod:`repro.kernels.mode`).
Mosaic refuses every kernel in this file for a v5e (tests/test_tpu_compile.py):
the ``(1, w)`` row BlockSpecs break the rule that the second-minor block dim
be a multiple of 8 or the whole array, and with one block per call the
decode body still fails on its 1-D gathers and the unpack body on cumsum.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.decode_jax import (
    TRACE_COUNTS,
    DeviceBlocks,
    _HashableCaps,
    decode_block_arrays,
    register_fused_decoder,
    register_shard_decoder,
)
from repro.core.format import D, STREAMS
from repro.kernels.mode import interpret_mode

OUT_KEYS = ("tokens", "read_pos", "read_rev", "read_start", "read_len", "read_corner")


def _kernel(caps, classes, fixed_len, names, *refs):
    ins = refs[: len(names)]
    outs = refs[len(names) :]
    blk = {n: r[0] for n, r in zip(names, ins)}  # drop the leading block dim
    dec = decode_block_arrays(blk, caps=caps, classes=classes, fixed_len=fixed_len)
    for key, oref in zip(OUT_KEYS, outs):
        oref[0] = dec[key].astype(oref.dtype)


@functools.lru_cache(maxsize=64)
def _build_pallas_decode(caps_h, classes_key, fixed_len, nb, shapes, names, interpret):
    """One jitted pallas_call per decode signature, reused across reads."""
    caps = caps_h
    classes = {k: tuple(v) for k, v in classes_key}
    R, C = caps.segs, caps.tokens
    in_specs = [pl.BlockSpec((1, w), lambda i: (i, 0)) for w in shapes]
    out_shapes = [
        jax.ShapeDtypeStruct((nb, C), jnp.int8),  # tokens
        jax.ShapeDtypeStruct((nb, R), jnp.int32),  # read_pos
        jax.ShapeDtypeStruct((nb, R), jnp.int32),  # read_rev
        jax.ShapeDtypeStruct((nb, R), jnp.int32),  # read_start
        jax.ShapeDtypeStruct((nb, R), jnp.int32),  # read_len
        jax.ShapeDtypeStruct((nb, R), jnp.int32),  # read_corner
    ]
    out_specs = [pl.BlockSpec((1, s.shape[1]), lambda i: (i, 0)) for s in out_shapes]
    call = pl.pallas_call(
        functools.partial(_kernel, caps, classes, fixed_len, names),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )

    @jax.jit
    def run(*arrays):
        TRACE_COUNTS["decode_pallas"] += 1
        return call(*arrays)

    return run


def sage_decode_arrays(
    arrays: dict[str, jax.Array],
    *,
    caps,
    classes: dict[str, tuple[int, ...]],
    fixed_len: int,
) -> dict[str, jax.Array]:
    """Decode block-major stream arrays (as gathered by the store's bucketed
    hot path) with the Pallas kernel. An optional ``arrays["valid"]`` column
    masks bucket-padding lanes per the decode_block_arrays contract."""
    names = list(STREAMS) + ["cons", "dir"]
    if "valid" in arrays:
        names.append("valid")
    ins = [jnp.asarray(arrays[n]) for n in names]
    nb = ins[0].shape[0]
    classes_key = tuple(sorted((k, tuple(v)) for k, v in classes.items()))
    run = _build_pallas_decode(
        _HashableCaps(caps), classes_key, fixed_len, nb,
        tuple(a.shape[1] for a in ins), tuple(names), interpret_mode(),
    )
    return dict(zip(OUT_KEYS, run(*ins)))


def sage_decode_pallas(db: DeviceBlocks):
    """Decode all blocks of a prepared SageFile with one pallas_call."""
    return sage_decode_arrays(
        db.arrays, caps=db.caps, classes=db.classes, fixed_len=db.fixed_len,
    )


def _build_pallas_shard_decoder(caps, classes, fixed_len, opts):
    """shard_map-local Pallas decode: each device runs one pallas_call over
    its resident lane shard (grid = per-shard bucket size), so the kernel's
    lru signature is keyed on the *per-shard* block count and stays constant
    across shard counts that keep the same per-device bucket."""

    def local(sub):
        return dict(sage_decode_arrays(
            sub, caps=caps, classes=classes, fixed_len=fixed_len,
        ))

    return local


# sessions select this path with decoder_key=("pallas", ())
register_shard_decoder("pallas", _build_pallas_shard_decoder)


# --------------------------------------------------------------------------
# fused gather + decode + reformat: ONE kernel, output in consumer layout
# --------------------------------------------------------------------------
# The two-step Pallas path launches the decode kernel, then a second format
# kernel over its token plane (two HBM round trips for the tokens). The
# fused kernel body decodes a block AND formats it while the decoded tokens
# are still in VMEM — the formatted plane is written directly, the token
# round trip disappears. Row math is shared with the standalone format
# kernels (repro.kernels.reformat.kmer_ids_row / one_hot_row), so fused
# output is bit-identical by construction. The on-device block gather runs
# in the same jit as the kernel call: one dispatch end to end.


def _fused_kernel(caps, classes, fixed_len, names, fmt_name, kmer_k, *refs):
    ins = refs[: len(names)]
    outs = refs[len(names):]
    blk = {n: r[0] for n, r in zip(names, ins)}
    dec = decode_block_arrays(blk, caps=caps, classes=classes, fixed_len=fixed_len)
    for key, oref in zip(OUT_KEYS, outs):
        oref[0] = dec[key].astype(oref.dtype)
    if fmt_name == "kmer":
        from repro.kernels.reformat import kmer_ids_row

        # n_tokens for THIS lane = dir row count masked by the valid column
        # (exactly what _fill_counts feeds the standalone format kernel)
        n_tok = blk["dir"][D["n_tokens"]].astype(jnp.int32) * blk["valid"][0]
        outs[len(OUT_KEYS)][0] = kmer_ids_row(
            dec["tokens"].astype(jnp.int32), kmer_k, n_tok
        )
    elif fmt_name == "onehot":
        from repro.kernels.reformat import one_hot_row

        outs[len(OUT_KEYS)][0] = one_hot_row(
            dec["tokens"].astype(jnp.int32)
        ).astype(outs[len(OUT_KEYS)].dtype)


@functools.lru_cache(maxsize=64)
def _build_fused_gather_decode(
    caps_h, classes_key, fixed_len, nb, shapes, names, fmt_name, kmer_k, interpret
):
    """One jitted gather + fused pallas_call per (decode signature, format)."""
    caps = caps_h
    classes = {k: tuple(v) for k, v in classes_key}
    R, C = caps.segs, caps.tokens
    in_specs = [pl.BlockSpec((1, w), lambda i: (i, 0)) for w in shapes]
    out_shapes = [
        jax.ShapeDtypeStruct((nb, C), jnp.int8),  # tokens
        jax.ShapeDtypeStruct((nb, R), jnp.int32),  # read_pos
        jax.ShapeDtypeStruct((nb, R), jnp.int32),  # read_rev
        jax.ShapeDtypeStruct((nb, R), jnp.int32),  # read_start
        jax.ShapeDtypeStruct((nb, R), jnp.int32),  # read_len
        jax.ShapeDtypeStruct((nb, R), jnp.int32),  # read_corner
    ]
    out_specs = [pl.BlockSpec((1, s.shape[1]), lambda i: (i, 0)) for s in out_shapes]
    out_keys = list(OUT_KEYS)
    if fmt_name == "kmer":
        out_shapes.append(jax.ShapeDtypeStruct((nb, C // kmer_k), jnp.int32))
        out_specs.append(pl.BlockSpec((1, C // kmer_k), lambda i: (i, 0)))
        out_keys.append("kmer")
    elif fmt_name == "onehot":
        out_shapes.append(jax.ShapeDtypeStruct((nb, C, 4), jnp.bfloat16))
        out_specs.append(pl.BlockSpec((1, C, 4), lambda i: (i, 0, 0)))
        out_keys.append("onehot")
    call = pl.pallas_call(
        functools.partial(_fused_kernel, caps, classes, fixed_len, names,
                          fmt_name, kmer_k),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )

    @jax.jit
    def run(arrays, ids, valid):
        TRACE_COUNTS["fused_pallas"] += 1
        v = valid.astype(jnp.int32)
        sub = {k: arrays[k][ids] for k in names if k != "valid"}
        sub["valid"] = v[:, None]
        out = dict(zip(out_keys, call(*[sub[n] for n in names])))
        # same expression _fill_counts uses on the two-step path
        out["n_reads"] = sub["dir"][:, D["n_reads"]] * v
        out["n_tokens"] = sub["dir"][:, D["n_tokens"]] * v
        return out

    return run


def _build_pallas_fused(caps_h, classes_key, fixed_len, fmt_name, kmer_k, opts):
    """Fused-path builder for ``fused_decode_blocks_bucketed`` (the lru'd
    kernel build keys on the padded shapes, resolved at first call)."""

    def run(arrays, ids, valid):
        names = list(STREAMS) + ["cons", "dir", "valid"]
        shapes = tuple(
            int(arrays[n].shape[1]) for n in names if n != "valid"
        ) + (1,)
        fn = _build_fused_gather_decode(
            caps_h, classes_key, fixed_len, int(ids.shape[0]), shapes,
            tuple(names), fmt_name, kmer_k, interpret_mode(),
        )
        return fn(arrays, ids, valid)

    return run


register_fused_decoder("pallas", _build_pallas_fused)


# --------------------------------------------------------------------------
# codec unpack kernel (PR 9): compressed extents -> stream rows, per block
# --------------------------------------------------------------------------
# Pallas twin of decode_jax._unpack_rows_jit (which is itself the device
# mirror of repro.core.codec.decode_blocks): grid = one step per stored
# extent, each step streams that block's packed payload HBM->VMEM and undoes
# the codec with shift/mask/gather only — descriptor parse, truncated-prefix
# copy, nibble-dictionary expansion with byte escapes. The per-step working
# set is one cap_words row (<= a few KiB after compression) plus the shared
# (N_STREAMS, 16) dictionary table, far below the decode kernel's budget.
# Signature key is (widths, cap_words, n_blocks): widths and cap_words are
# container constants, so steady-state ranged reads at a fixed bucket size
# reuse one compiled executable.


def _unpack_kernel(widths, packed_ref, dicts_ref, *outs):
    from repro.core.codec import DESC_WORDS, ESCAPE, MODE_NIBBLE, USED_MASK

    row = packed_ref[0].astype(jnp.uint32)  # (cap_words,)
    cap = row.shape[0]
    dicts = dicts_ref[...]
    ns = len(widths)
    desc = row[:ns].astype(jnp.int32)
    used = desc & jnp.int32(USED_MASK)
    modes = (desc >> 20) & 3
    nesc = row[ns:DESC_WORDS].astype(jnp.int32)
    sec = jnp.where(modes == MODE_NIBBLE, (used + 1) // 2 + (nesc + 3) // 4, used)
    sec_off = DESC_WORDS + jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(sec)[:-1]]
    )
    for si, (_s, w) in enumerate(widths):
        u = used[si]
        off = sec_off[si]
        kw = jnp.arange(w, dtype=jnp.int32)
        raw = jnp.where(kw < u, row[jnp.clip(off + kw, 0, cap - 1)], jnp.uint32(0))
        kb = jnp.arange(4 * w, dtype=jnp.int32)
        nib = (
            row[jnp.clip(off + kb // 8, 0, cap - 1)]
            >> (4 * (kb % 8)).astype(jnp.uint32)
        ) & 15
        in_use = kb < 4 * u
        is_esc = (nib == ESCAPE) & in_use
        rank = jnp.cumsum(is_esc.astype(jnp.int32)) - is_esc
        eoff = off + (u + 1) // 2
        escb = (
            row[jnp.clip(eoff + rank // 4, 0, cap - 1)]
            >> (8 * (rank % 4)).astype(jnp.uint32)
        ) & 255
        byte = jnp.where(is_esc, escb, dicts[si][nib]).astype(jnp.uint32)
        byte = jnp.where(in_use, byte, jnp.uint32(0))
        shifts = 8 * jnp.arange(4, dtype=jnp.uint32)[None, :]
        nib_row = (byte.reshape(w, 4) << shifts).sum(axis=1, dtype=jnp.uint32)
        outs[si][0] = jnp.where(modes[si] == MODE_NIBBLE, nib_row, raw).astype(
            jnp.uint32
        )


@functools.lru_cache(maxsize=64)
def _build_pallas_unpack(widths, cap, nb, interpret):
    """One jitted pallas_call per (widths, cap_words, n_blocks) signature."""
    in_specs = [
        pl.BlockSpec((1, cap), lambda i: (i, 0)),
        pl.BlockSpec((len(widths), 16), lambda i: (0, 0)),
    ]
    out_shapes = [jax.ShapeDtypeStruct((nb, w), jnp.uint32) for _s, w in widths]
    out_specs = [pl.BlockSpec((1, w), lambda i: (i, 0)) for _s, w in widths]
    call = pl.pallas_call(
        functools.partial(_unpack_kernel, widths),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )

    @jax.jit
    def run(packed, dicts):
        TRACE_COUNTS["unpack_pallas"] += 1
        return call(packed, dicts)

    return run


def sage_unpack_pallas(packed, dicts, widths) -> dict[str, jax.Array]:
    """Unpack codec extent payloads with the Pallas kernel.

    Same contract as :func:`repro.core.decode_jax.unpack_block_rows`
    (``cons`` width entries ignored; output bit-identical to
    :func:`repro.core.codec.decode_blocks`), one grid step per block."""
    wmap = dict(widths)
    wt = tuple((s, int(wmap[s])) for s in STREAMS)
    packed = jnp.asarray(packed, dtype=jnp.uint32)
    nb, cap = packed.shape
    run = _build_pallas_unpack(wt, cap, nb, interpret_mode())
    out = run(packed, jnp.asarray(dicts, dtype=jnp.uint8)[: len(wt)])
    return {s: a for (s, _w), a in zip(wt, out)}
