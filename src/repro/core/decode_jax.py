"""Data-parallel SAGe decoder (pure JAX).

This is the TPU-native adaptation of the paper's Scan Unit / Read
Construction Unit (§5.2): every sequential recurrence in the hardware FSM is
an associative scan, so one block decodes with ~a dozen vectorized
cumsum/gather/scatter passes over fixed-capacity arrays:

  unary guide codes   -> rank zero-bits (cumsum) + scatter positions
  var-width fields    -> prefix-sum widths + 64-bit-window gathers
  delta positions     -> segmented cumsum
  indel bookkeeping   -> explicit (mbb==3) detection + rank cumsums
  read reconstruction -> scatter subs/ins/del onto the token axis + gathers
                         from the 2-bit consensus window

Blocks are decoded independently (vmap / Pallas grid) — the analogue of the
paper's per-NAND-channel parallel units. All device math is int32/uint32 and
block-local (positions relative to the block's consensus window), so genomes
larger than 2^31 bases pose no problem.

``decode_block_arrays`` is the single source of truth for the math; the
Pallas kernel (repro/kernels/sage_decode.py) calls the same function on VMEM
refs, and tests check both against the sequential numpy oracle.
"""

from __future__ import annotations

import dataclasses
import functools
from collections import Counter
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core.format import D, STREAMS, SageFile
from repro.distributed.sharding import (
    block_axis_name,
    block_shard_count,
    block_specs,
)

PAD_BASE = 4  # output padding token


# --------------------------------------------------------------------------
# compile observability: trace counters
# --------------------------------------------------------------------------
# Each jitted entry point in the hot path bumps its counter *at trace time*
# (the Python body of a jitted function only runs when XLA retraces it), so
# these counters are exact recompile counts. The decode-throughput benchmark
# and the bucketing tests read them to prove the compile-once contract.

TRACE_COUNTS: Counter = Counter()


def trace_counts() -> dict[str, int]:
    """Snapshot of per-entry-point jit trace (= compile) counts."""
    return dict(TRACE_COUNTS)


def reset_trace_counts() -> None:
    TRACE_COUNTS.clear()


# --------------------------------------------------------------------------
# bit-level helpers (uint32 streams)
# --------------------------------------------------------------------------

def extract_fields(words: jax.Array, starts: jax.Array, widths: jax.Array) -> jax.Array:
    """Extract variable-width little-endian fields (width<=32) from a packed
    uint32 stream. Fully vectorized; the 64-bit window is formed from two
    adjacent words (the paper's double-register §5.2.1)."""
    words = words.astype(jnp.uint32)
    starts = starts.astype(jnp.int32)
    widths = widths.astype(jnp.int32)
    idx = jnp.clip(starts >> 5, 0, words.shape[0] - 2)
    sh = (starts & 31).astype(jnp.uint32)
    lo = words[idx] >> sh
    hi = jnp.where(sh == 0, jnp.uint32(0), words[idx + 1] << (jnp.uint32(32) - jnp.maximum(sh, 1)))
    val = lo | hi
    mask = jnp.where(
        widths <= 0,
        jnp.uint32(0),
        jnp.uint32(0xFFFFFFFF) >> jnp.clip(32 - widths, 0, 31).astype(jnp.uint32),
    )
    return (val & mask).astype(jnp.int32)


def stream_bits(words: jax.Array, nbits_cap: int) -> jax.Array:
    """Expand a packed stream's first ``nbits_cap`` bits to a 0/1 int32 array."""
    i = jnp.arange(nbits_cap, dtype=jnp.int32)
    idx = jnp.clip(i >> 5, 0, words.shape[0] - 1)
    return ((words.astype(jnp.uint32)[idx] >> (i & 31).astype(jnp.uint32)) & 1).astype(jnp.int32)


def decode_adaptive(
    gwords: jax.Array,
    awords: jax.Array,
    n: jax.Array,
    class_widths: tuple[int, ...],
    cap: int,
) -> jax.Array:
    """Decode ``n`` (<=cap) adaptive-width values: unary guide codes in
    ``gwords`` select a width class; fields packed in ``awords``."""
    ncls = len(class_widths)
    gb = cap * ncls + 1
    bits = stream_bits(gwords, gb)
    is_zero = 1 - bits
    rank = jnp.cumsum(is_zero)  # 1-based at zero positions
    # position of k-th zero via scatter (garbage ranks land at sentinel cap)
    tgt = jnp.where(is_zero == 1, jnp.minimum(rank - 1, cap), cap)
    zpos = jnp.zeros(cap + 1, dtype=jnp.int32).at[tgt].max(
        jnp.arange(gb, dtype=jnp.int32), mode="drop"
    )
    zprev = jnp.concatenate([jnp.full((1,), -1, dtype=jnp.int32), zpos[: cap - 1]])
    cls = jnp.clip(zpos[:cap] - zprev - 1, 0, ncls - 1)
    # static where-chain (no captured constant tables — Pallas-compatible)
    widths = jnp.zeros((cap,), jnp.int32)
    for i, w in enumerate(class_widths):
        widths = jnp.where(cls == i, jnp.int32(w), widths)
    k = jnp.arange(cap, dtype=jnp.int32)
    widths = jnp.where(k < n, widths, 0)
    offs = jnp.cumsum(widths) - widths
    vals = extract_fields(awords, offs, widths)
    return jnp.where(k < n, vals, 0)


def _seg_cumsum(vals: jax.Array, first_idx: jax.Array) -> jax.Array:
    """Inclusive cumsum of ``vals`` restarted at each segment; ``first_idx``
    maps element -> index of its segment's first element."""
    gc = jnp.cumsum(vals)
    gc_excl = gc - vals
    return gc - gc_excl[jnp.clip(first_idx, 0, vals.shape[0] - 1)]


# --------------------------------------------------------------------------
# the block decoder
# --------------------------------------------------------------------------

def decode_block_arrays(
    blk: dict[str, jax.Array],
    *,
    caps,
    classes: dict[str, tuple[int, ...]],
    fixed_len: int,
) -> dict[str, jax.Array]:
    """Decode one block. ``blk`` holds per-block stream word slices plus the
    directory row; everything is block-local. Returns the flat token buffer
    plus per-read metadata.

    Mask contract: an optional ``blk["valid"]`` entry (shape (1,), 0 or 1)
    gates the block. Invalid lanes — the padding that shape bucketing adds —
    decode to all-PAD tokens, zero counts, and ``read_pos == -1``, bit-for-bit
    deterministic regardless of which block's streams occupy the lane."""
    R, M = caps.segs, max(caps.mism, 1)
    I, U = max(caps.indel, 1), max(caps.multi, 1)
    C = caps.tokens
    row = blk["dir"]
    valid = blk["valid"][0] if "valid" in blk else None
    n_segs = row[D["n_segs"]]
    n_mism = row[D["n_mism"]]
    n_tok = row[D["n_tokens"]]
    if valid is not None:
        n_segs = n_segs * valid
        n_mism = n_mism * valid
        n_tok = n_tok * valid
    # host prep pre-localizes base_pos (base_pos - cons_start), keeping all
    # device math int32-safe regardless of genome size
    base_local = row[D["base_pos"]]

    ar_r = jnp.arange(R, dtype=jnp.int32)
    ar_m = jnp.arange(M, dtype=jnp.int32)
    ar_t = jnp.arange(C, dtype=jnp.int32)
    seg_mask = ar_r < n_segs
    mism_mask = ar_m < n_mism
    tok_mask = ar_t < n_tok

    # ---- per-segment streams -------------------------------------------
    map_vals = decode_adaptive(blk["mapg"], blk["mapa"], n_segs, classes["map"], R)
    if fixed_len:
        lens = jnp.where(seg_mask, jnp.int32(fixed_len), 0)
    else:
        lens = jnp.where(seg_mask, decode_adaptive(blk["leng"], blk["lena"], n_segs, classes["len"], R), 0)
    cnts = jnp.where(seg_mask, decode_adaptive(blk["cntg"], blk["cnta"], n_segs, classes["cnt"], R), 0)
    rfl = extract_fields(blk["rfl"], 3 * ar_r, jnp.full((R,), 3, jnp.int32))
    rev = (rfl & 1) & seg_mask
    cont = ((rfl >> 1) & 1) & seg_mask
    corner = ((rfl >> 2) & 1) & seg_mask

    # ---- segment positions (block-local) --------------------------------
    is_chain = seg_mask & (cont == 0) & (corner == 0)
    acc = base_local + jnp.cumsum(jnp.where(is_chain, map_vals, 0))
    unzig = (map_vals >> 1) ^ -(map_vals & 1)
    pos = jnp.where(cont == 1, acc + unzig, acc)  # corner pos unused

    # ---- token layout ----------------------------------------------------
    starts_i = jnp.cumsum(lens) - lens  # (R,) exclusive
    seg_of_t = jnp.searchsorted(jnp.cumsum(lens), ar_t, side="right").astype(jnp.int32)
    seg_of_t = jnp.clip(seg_of_t, 0, R - 1)
    seg_start_t = starts_i[seg_of_t]
    j = ar_t - seg_start_t  # read-coordinate within segment

    # ---- mismatch -> segment mapping ------------------------------------
    cnt_ends = jnp.cumsum(cnts)
    cnt_starts = cnt_ends - cnts
    seg_of_m = jnp.clip(jnp.searchsorted(cnt_ends, ar_m, side="right").astype(jnp.int32), 0, R - 1)
    mp_deltas = decode_adaptive(blk["mpg"], blk["mpa"], n_mism, classes["mp"], M)
    p_m = _seg_cumsum(mp_deltas, cnt_starts[seg_of_m])  # read coords
    mbb = extract_fields(blk["mbb"], 2 * ar_m, jnp.full((M,), 2, jnp.int32))
    mbb = jnp.where(mism_mask, mbb, 0)

    # ---- indel decode (explicit rank code: mbb==3) -----------------------
    is_ind = jnp.where(mism_mask, (mbb == 3).astype(jnp.int32), 0)
    ind_rank = jnp.cumsum(is_ind) - is_ind  # 0-based rank into idg
    idg_all = extract_fields(blk["idg"], 2 * jnp.arange(I, dtype=jnp.int32), jnp.full((I,), 2, jnp.int32))
    idg_m = idg_all[jnp.clip(ind_rank, 0, I - 1)]
    is_ins = is_ind * (idg_m & 1)
    is_multi = is_ind * ((idg_m >> 1) & 1)
    mul_rank = jnp.cumsum(is_multi) - is_multi
    idl_all = extract_fields(blk["idl"], 8 * jnp.arange(U, dtype=jnp.int32), jnp.full((U,), 8, jnp.int32))
    ilen_m = jnp.where(is_multi == 1, idl_all[jnp.clip(mul_rank, 0, U - 1)], 1) * is_ind
    ins_len_m = jnp.where(is_ins == 1, ilen_m, 0)
    del_len_m = jnp.where((is_ind == 1) & (is_ins == 0), ilen_m, 0)
    ibs_off_m = jnp.cumsum(ins_len_m) - ins_len_m  # exclusive, in bases

    # ---- consensus cursor per mismatch (for sub rank -> base) -----------
    shift_m_excl = _seg_cumsum(del_len_m - ins_len_m, cnt_starts[seg_of_m]) - (del_len_m - ins_len_m)
    cursor_m = pos[seg_of_m] + p_m + shift_m_excl
    cw = blk["cons"]

    def cons_at(idx: jax.Array) -> jax.Array:
        idx = jnp.clip(idx, 0, caps.window - 1)
        return ((cw.astype(jnp.uint32)[idx >> 4] >> (2 * (idx & 15)).astype(jnp.uint32)) & 3).astype(jnp.int32)

    cons_b_m = cons_at(cursor_m)
    sub_base = mbb + (mbb >= cons_b_m).astype(jnp.int32)  # rank -> base

    # ---- scatter mismatches onto the token axis -------------------------
    t_m = starts_i[seg_of_m] + p_m
    t_m_safe = jnp.where(mism_mask, jnp.clip(t_m, 0, C - 1), C)  # C -> dropped
    is_sub = mism_mask & (mbb < 3)
    sub_t = jnp.full((C,), -1, jnp.int32).at[jnp.where(is_sub, t_m_safe, C)].set(sub_base, mode="drop")
    # deletions: shift consensus index for t >= t_m
    del_at = jnp.zeros((C,), jnp.int32).at[t_m_safe].add(del_len_m, mode="drop")
    del_shift_t = _seg_cumsum(del_at, seg_start_t)
    # insertions: mark coverage [t_m, t_m + L)
    is_ins_m = mism_mask & (is_ins == 1)
    ins_start_mark = jnp.full((C,), -1, jnp.int32).at[jnp.where(is_ins_m, t_m_safe, C)].max(t_m, mode="drop")
    last_ins_start = jax.lax.cummax(ins_start_mark)
    ins_len_t0 = jnp.zeros((C,), jnp.int32).at[jnp.where(is_ins_m, t_m_safe, C)].max(ins_len_m, mode="drop")
    ins_off_t0 = jnp.zeros((C,), jnp.int32).at[jnp.where(is_ins_m, t_m_safe, C)].max(ibs_off_m, mode="drop")
    lis = jnp.clip(last_ins_start, 0, C - 1)
    inside_ins = (last_ins_start >= 0) & (ar_t - last_ins_start < ins_len_t0[lis]) & tok_mask
    ibs_idx_t = ins_off_t0[lis] + (ar_t - last_ins_start)
    ibs_val_t = extract_fields(blk["ibs"], 2 * jnp.clip(ibs_idx_t, 0, caps.insb), jnp.full((C,), 2, jnp.int32))

    # ---- consensus-derived tokens ----------------------------------------
    consumes = jnp.where(tok_mask & ~inside_ins, 1, 0)
    cc_t = _seg_cumsum(consumes, seg_start_t) - consumes  # exclusive
    cons_idx_t = pos[seg_of_t] + cc_t + del_shift_t
    cons_tok = cons_at(cons_idx_t)

    # ---- escape (corner) segments ----------------------------------------
    esc_lens = jnp.where(corner == 1, lens, 0)
    esc_start_seg = jnp.cumsum(esc_lens) - esc_lens
    esc_idx_t = esc_start_seg[seg_of_t] + j
    esc_val_t = extract_fields(blk["esc"], 3 * jnp.clip(esc_idx_t, 0, caps.escb), jnp.full((C,), 3, jnp.int32))
    is_corner_t = corner[seg_of_t] == 1

    tokens = jnp.where(
        is_corner_t,
        esc_val_t,
        jnp.where(inside_ins, ibs_val_t, jnp.where(sub_t >= 0, sub_t, cons_tok)),
    )

    # ---- per-read grouping + reverse-complement --------------------------
    read_first = seg_mask & (cont == 0)
    read_id_seg = jnp.cumsum(read_first.astype(jnp.int32)) - read_first.astype(jnp.int32)
    rid_scatter = jnp.where(read_first, read_id_seg, R)
    read_rev = jnp.zeros((R,), jnp.int32).at[rid_scatter].max(rev, mode="drop")
    read_pos = jnp.full((R,), -1, jnp.int32).at[rid_scatter].max(
        jnp.where(corner == 1, -1, pos), mode="drop"
    )
    read_start = jnp.zeros((R,), jnp.int32).at[rid_scatter].max(starts_i, mode="drop")
    read_len = jnp.zeros((R,), jnp.int32).at[jnp.where(seg_mask, read_id_seg, R)].add(lens, mode="drop")
    read_corner = jnp.zeros((R,), jnp.int32).at[rid_scatter].max(corner, mode="drop")

    rid_t = read_id_seg[seg_of_t]
    rev_t = read_rev[rid_t] == 1
    rstart_t = read_start[rid_t]
    rlen_t = read_len[rid_t]
    src = jnp.where(rev_t, rstart_t + (rlen_t - 1 - (ar_t - rstart_t)), ar_t)
    out = tokens[jnp.clip(src, 0, C - 1)]
    out = jnp.where(rev_t & (out < 4), 3 - out, out)
    out = jnp.where(tok_mask, out, PAD_BASE).astype(jnp.int8)

    n_reads = row[D["n_reads"]]
    if valid is not None:
        n_reads = n_reads * valid
    read_mask = jnp.arange(R, dtype=jnp.int32) < n_reads
    return {
        "tokens": out,
        "n_tokens": n_tok,
        "read_pos": jnp.where(read_mask, read_pos + row[D["cons_start"]] * (read_pos >= 0), -1),
        "read_rev": jnp.where(read_mask, read_rev, 0),
        "read_start": jnp.where(read_mask, read_start, 0),
        "read_len": jnp.where(read_mask, read_len, 0),
        "read_corner": jnp.where(read_mask, read_corner, 0),
        "n_reads": n_reads,
    }


# --------------------------------------------------------------------------
# host-side packing of a SageFile into fixed-shape device arrays
# --------------------------------------------------------------------------

@dataclasses.dataclass
class DeviceBlocks:
    """Fixed-shape, block-major layout of a SageFile.

    ``arrays`` holds host numpy right after :func:`prepare_device_blocks`;
    :meth:`to_device` moves every array to the accelerator exactly once
    (``jax.device_put``), after which ranged reads gather and decode with no
    host↔device traffic (the SageStore LRU caches the resident copy).

    Multi-device residency: ``to_device(mesh=...)`` with a 1-D block mesh
    shards every array's leading block dim across the mesh — each device
    holds only its block shard, the analogue of the paper's per-NAND-channel
    partitions. The leading dim is zero-padded up to a multiple of the shard
    count (``device_put`` requires even shards); the pad rows sit past
    ``n_blocks`` and are never gathered.
    """

    arrays: dict[str, Any]  # name -> (n_blocks, cap_words) uint32 (+dir/cons)
    caps: Any
    classes: dict[str, tuple[int, ...]]
    fixed_len: int
    n_blocks: int
    on_device: bool = False
    mesh: Optional[Mesh] = None  # block-axis mesh when shard-resident

    def block(self, bi: int) -> dict[str, Any]:
        return {k: v[bi] for k, v in self.arrays.items()}

    def to_device(self, device=None, *, mesh: Optional[Mesh] = None) -> "DeviceBlocks":
        """Device-resident copy of this DeviceBlocks (no-op when resident).

        With ``mesh`` (a 1-D block mesh), each array is placed with a
        block-axis :class:`NamedSharding` so every device holds only its
        shard of the blocks; without it, a plain single-device put."""
        if self.on_device:
            return self
        arrays = dict(self.arrays)
        if mesh is not None:
            s = block_shard_count(mesh)
            pad = (-self.n_blocks) % s
            if pad:
                arrays = {
                    k: np.concatenate(
                        [v, np.zeros((pad,) + v.shape[1:], dtype=v.dtype)]
                    )
                    for k, v in arrays.items()
                }
            arrays = {
                k: jax.device_put(v, NamedSharding(mesh, PartitionSpec(
                    block_axis_name(mesh), *([None] * (v.ndim - 1)))))
                for k, v in arrays.items()
            }
        else:
            arrays = jax.device_put(arrays, device)
        return dataclasses.replace(self, arrays=arrays, on_device=True, mesh=mesh)


def stream_row_words(meta, s: str) -> int:
    """Per-block row width (uint32 words) of stream ``s`` in the fixed-shape
    block-major layout: the worst-case per-block bit count rounded up, plus
    one slack word for the 64-bit extraction window."""
    blk_bits = meta.stream_bits.get(f"blk_{s}", 0)
    return max(2, (blk_bits + 31) // 32 + 1)


def block_row_widths(meta) -> dict[str, int]:
    """Word width of every per-block row (streams + the consensus window) —
    the column layout shared by :func:`prepare_block_arrays` and the v2
    block-extent container (repro/core/layout.py)."""
    widths = {s: stream_row_words(meta, s) for s in STREAMS}
    widths["cons"] = meta.caps.window // 16
    return widths


def localize_directory(directory: np.ndarray, ids: Optional[np.ndarray] = None) -> np.ndarray:
    """Block-local int32 directory rows for the device decoders.

    ``base_pos`` is rewritten relative to the block's consensus window
    (``base_pos - cons_start``) *before* the int32 cast, so device math stays
    int32-safe regardless of genome size."""
    rows = directory if ids is None else directory[np.asarray(ids, dtype=np.int64)]
    dir32 = np.clip(rows, -(2**31), 2**31 - 1).astype(np.int32)
    dir32[:, D["base_pos"]] = (rows[:, D["base_pos"]] - rows[:, D["cons_start"]]).astype(np.int32)
    return dir32


def _gather_rows(src: np.ndarray, starts: np.ndarray, width: int) -> np.ndarray:
    """(n,) word offsets -> (n, width) rows of ``src``, zero-filled past the
    end of the stream — one fancy-indexed gather, no per-row Python loop."""
    if src.size == 0:  # absent stream (e.g. leng/lena on fixed-length files)
        return np.zeros((starts.size, width), dtype=np.uint32)
    idx = starts[:, None] + np.arange(width, dtype=np.int64)[None, :]
    ok = idx < src.size
    out = src[np.where(ok, idx, 0)]
    out[~ok] = 0
    return out


def prepare_block_arrays(sf: SageFile, ids: Optional[np.ndarray] = None) -> dict[str, np.ndarray]:
    """Fixed-shape block-major host arrays for ``ids`` (all blocks when None).

    Fully vectorized: each stream is one strided gather over the flat
    bitstream (per-block word offsets come straight from the directory), so
    preparation costs a memcpy, not a Python loop over blocks × streams.
    This host gather defines the per-block row layout the v2 block-extent
    container persists verbatim (repro/core/layout.py)."""
    directory = sf.directory if ids is None else sf.directory[np.asarray(ids, dtype=np.int64)]
    widths = block_row_widths(sf.meta)
    arrays: dict[str, np.ndarray] = {}
    for s in STREAMS:
        offs = (directory[:, D[f"off_{s}"]] >> 5).astype(np.int64)  # word aligned
        arrays[s] = _gather_rows(
            np.ascontiguousarray(sf.streams[s], dtype=np.uint32), offs, widths[s]
        )
    # consensus windows (2-bit packed, 16 bases/word)
    w0 = (directory[:, D["cons_start"]] // 16).astype(np.int64)
    arrays["cons"] = _gather_rows(
        np.ascontiguousarray(sf.consensus2b, dtype=np.uint32), w0, widths["cons"]
    )
    arrays["dir"] = localize_directory(directory)
    return arrays


def prepare_device_blocks(sf: SageFile) -> DeviceBlocks:
    """Pack a SageFile into fixed-shape block-major arrays (host numpy)."""
    return DeviceBlocks(
        arrays=prepare_block_arrays(sf),
        caps=sf.meta.caps,
        classes=sf.meta.classes,
        fixed_len=sf.meta.fixed_read_len,
        n_blocks=sf.meta.n_blocks,
    )


@functools.partial(jax.jit, static_argnames=("caps", "classes", "fixed_len"))
def _decode_all_jit(arrays, caps, classes, fixed_len):
    TRACE_COUNTS["decode_vmap"] += 1
    classes = {k: tuple(v) for k, v in classes}
    return jax.vmap(
        lambda blk: decode_block_arrays(blk, caps=caps, classes=classes, fixed_len=fixed_len)
    )(arrays)


def _decode_arrays_vmap(arrays, db: DeviceBlocks) -> dict[str, jax.Array]:
    """Dispatch block-major arrays to the jitted vmap decoder — the single
    builder of the jit static key (hashable caps + normalized classes)."""
    classes_h = tuple(sorted((k, tuple(v)) for k, v in db.classes.items()))
    return _decode_all_jit(arrays, _HashableCaps(db.caps), classes_h, db.fixed_len)


def decode_file_jax(db: DeviceBlocks) -> dict[str, jax.Array]:
    """Decode every block of a prepared SageFile (vmapped, jitted)."""
    return _decode_arrays_vmap(db.arrays, db)


# --------------------------------------------------------------------------
# codec unpack (PR 9): stored compressed extents -> block-major stream rows
# --------------------------------------------------------------------------
# The inverse of repro.core.codec.encode_blocks, on device: pure shift/mask/
# gather work (descriptor parse, truncated-prefix gather, nibble-dictionary
# expansion) — no general-purpose inflate anywhere near the hot path. The
# static key is (widths, cap_words) via array shapes, both container-level
# constants, so a container unpacks under ONE jit signature (zero steady-
# state retraces, same contract as the decode entry points).

@functools.partial(jax.jit, static_argnames=("widths",))
def _unpack_rows_jit(packed, dicts, widths):
    from repro.core.codec import DESC_WORDS, ESCAPE, MODE_NIBBLE, USED_MASK

    TRACE_COUNTS["unpack_rows"] += 1
    n, cap = packed.shape
    packed = packed.astype(jnp.uint32)
    ns = len(widths)
    desc = packed[:, :ns].astype(jnp.int32)
    used = desc & jnp.int32(USED_MASK)
    modes = (desc >> 20) & 3
    nesc = packed[:, ns:DESC_WORDS].astype(jnp.int32)
    sec = jnp.where(modes == MODE_NIBBLE, (used + 1) // 2 + (nesc + 3) // 4, used)
    sec_off = DESC_WORDS + jnp.concatenate(
        [jnp.zeros((n, 1), jnp.int32), jnp.cumsum(sec, axis=1)[:, :-1]], axis=1
    )
    row = jnp.arange(n, dtype=jnp.int32)[:, None]
    out: dict[str, jax.Array] = {}
    for si, (s, w) in enumerate(widths):
        u = used[:, si][:, None]
        off = sec_off[:, si][:, None]
        kw = jnp.arange(w, dtype=jnp.int32)[None, :]
        raw = jnp.where(
            kw < u, packed[row, jnp.clip(off + kw, 0, cap - 1)], jnp.uint32(0)
        )
        kb = jnp.arange(4 * w, dtype=jnp.int32)[None, :]
        nib = (
            packed[row, jnp.clip(off + kb // 8, 0, cap - 1)]
            >> (4 * (kb % 8)).astype(jnp.uint32)
        ) & 15
        in_use = kb < 4 * u
        is_esc = (nib == ESCAPE) & in_use
        rank = jnp.cumsum(is_esc.astype(jnp.int32), axis=1) - is_esc
        eoff = off + (u + 1) // 2
        escb = (
            packed[row, jnp.clip(eoff + rank // 4, 0, cap - 1)]
            >> (8 * (rank % 4)).astype(jnp.uint32)
        ) & 255
        byte = jnp.where(is_esc, escb, dicts[si][nib]).astype(jnp.uint32)
        byte = jnp.where(in_use, byte, jnp.uint32(0))
        shifts = (8 * jnp.arange(4, dtype=jnp.uint32))[None, None, :]
        nib_rows = (byte.reshape(n, w, 4) << shifts).sum(axis=2, dtype=jnp.uint32)
        out[s] = jnp.where(
            (modes[:, si] == MODE_NIBBLE)[:, None], nib_rows, raw
        ).astype(jnp.uint32)
    return out


def unpack_block_rows(packed, dicts, widths) -> dict[str, jax.Array]:
    """Jitted device unpack of codec extent payloads.

    ``packed`` is (n, cap_words) uint32 (zero-padded rows straight from
    :meth:`repro.core.layout.SageContainerV2.gather_packed`), ``dicts`` the
    container's (N_STREAMS, 16) nibble dictionaries, ``widths`` the
    decoded row-width mapping (``cons`` entries are ignored — consensus
    windows travel by reference, not through the codec). Returns
    stream -> (n, W_s) uint32 rows, bit-identical to
    :func:`repro.core.codec.decode_blocks`."""
    wmap = dict(widths)
    wt = tuple((s, int(wmap[s])) for s in STREAMS)
    return _unpack_rows_jit(
        jnp.asarray(packed), jnp.asarray(dicts, dtype=jnp.uint8), wt
    )


# --------------------------------------------------------------------------
# shape-bucketed ranged decode (the compile-once serving hot path)
# --------------------------------------------------------------------------
# A jitted decoder specializes on the leading block dimension, so serving
# arbitrary block ranges naively compiles once per *range length*. Instead we
# pad every requested range up to the next power-of-two bucket and thread a
# per-lane validity mask through the decoder: the jit cache then holds at
# most one entry per bucket (log2 of the largest range), and any mix of
# range lengths reuses those entries.

def bucket_size(n: int) -> int:
    """Smallest power-of-two bucket holding ``n`` blocks (n >= 1)."""
    if n < 1:
        raise ValueError(f"cannot bucket {n} blocks")
    return 1 << (n - 1).bit_length()


def pad_block_ids(ids: np.ndarray, shards: int = 1) -> tuple[np.ndarray, np.ndarray]:
    """Pad ``ids`` to its bucket: returns (padded ids, int32 validity mask).

    Pad lanes repeat ``ids[0]`` (any in-bounds block works — the mask makes
    their decode output deterministic PAD/zeros).

    With ``shards > 1`` the bucket is computed *per shard* and the total pads
    to ``bucket(ceil(n / shards)) * shards``, so every device's shard keeps a
    power-of-two lane count (the zero-retrace guarantee holds per
    (per-shard bucket, shard count)) and ``shard_map`` sees an evenly
    divisible leading dim. ``shards=1`` reduces to the single-device rule."""
    ids = np.asarray(ids, dtype=np.int64)
    n = ids.size
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    b = bucket_size(-(-n // shards)) * shards
    padded = np.full(b, ids[0], dtype=np.int64)
    padded[:n] = ids
    valid = (np.arange(b) < n).astype(np.int32)
    return padded, valid


@jax.jit
def _gather_blocks_jit(arrays, ids, valid):
    """On-device block gather: block-major subset of every prepared array
    plus the (B, 1) validity column the masked decoders consume."""
    TRACE_COUNTS["gather"] += 1
    sub = {k: v[ids] for k, v in arrays.items()}
    sub["valid"] = valid[:, None].astype(jnp.int32)
    return sub


def gather_block_arrays(db: DeviceBlocks, ids: np.ndarray, valid: np.ndarray) -> dict[str, jax.Array]:
    """Gather a padded block-id set out of prepared arrays, on device."""
    return _gather_blocks_jit(db.arrays, jnp.asarray(ids, jnp.int32), jnp.asarray(valid, jnp.int32))


@functools.partial(jax.jit, static_argnames=("mesh",))
def _gather_group_rows_jit(groups, where, mesh):
    """Rows out of several resident block groups, in request order: every
    group gives the same rows (``where[1]``) and ``where[0]`` picks each
    lane's group, so only O(groups x lanes) rows move on device."""
    TRACE_COUNTS["group_gather"] += 1
    slot, row = where[0], where[1]
    out = {}
    for k in groups[0]:
        picked = groups[0][k][row]
        lane = (-1,) + (1,) * (picked.ndim - 1)
        for s in range(1, len(groups)):
            picked = jnp.where((slot == s).reshape(lane), groups[s][k][row], picked)
        out[k] = picked
    if mesh is not None:
        out = jax.lax.with_sharding_constraint(out, block_specs(out, mesh))
    return out


def gather_group_rows(
    groups: Sequence[dict[str, jax.Array]],
    slot: np.ndarray,
    row: np.ndarray,
    mesh: Optional[Mesh] = None,
) -> dict[str, jax.Array]:
    """One dispatch gathering lane ``i`` from row ``row[i]`` of resident group
    ``groups[slot[i]]`` for every array; block-sharded on ``mesh`` when given
    (the lane count must then divide evenly over its shards)."""
    where = np.stack([slot, row]).astype(np.int32)
    return _gather_group_rows_jit(tuple(groups), where, mesh)


def _fill_counts(out: dict[str, jax.Array], sub: dict[str, jax.Array]) -> dict[str, jax.Array]:
    """Fill per-block counts missing from a decode dict (the Pallas kernel
    emits token/read planes only) from the gathered ``dir`` rows, masked by
    the validity column — no host-side directory indexing on the hot path."""
    if "n_reads" not in out:
        v = sub["valid"][:, 0]
        out["n_reads"] = sub["dir"][:, D["n_reads"]] * v
        out["n_tokens"] = sub["dir"][:, D["n_tokens"]] * v
    return out


def decode_blocks_padded(
    db: DeviceBlocks,
    ids: np.ndarray,
    valid: np.ndarray,
    *,
    decoder: Optional[Callable[[dict[str, jax.Array]], dict[str, jax.Array]]] = None,
) -> dict[str, jax.Array]:
    """Decode an already-padded block-id set; returns padded-length outputs.

    ``decoder`` maps gathered block arrays -> decode dict (defaults to the
    jitted vmap path)."""
    sub = gather_block_arrays(db, ids, valid)
    out = dict(_decode_arrays_vmap(sub, db) if decoder is None else decoder(sub))
    return _fill_counts(out, sub)


# --------------------------------------------------------------------------
# shard_map decode: each device decodes only its resident block shard
# --------------------------------------------------------------------------
# The block axis is the paper's unit of parallelism (per-NAND-channel decode
# units, §5.2/§5.3); here it is a 1-D device mesh. One jitted entry point per
# (mesh, per-shard bucket) gathers the padded block-id set out of the
# shard-resident arrays (GSPMD inserts the collective permutes), constrains
# the gathered lanes to the block axis, and runs the per-block decoder under
# ``shard_map`` so each device decodes exactly its ``bucket`` lanes. The
# valid-lane mask contract is unchanged: every shard gets a power-of-two lane
# count with its own mask tail, so outputs are bit-identical to the
# single-device reference and the jit cache stays one entry per
# (per-shard bucket, shard count).

#: decoder_key registry for the sharded path — the per-shard local decode
#: must be rebuilt inside the cached jit (a per-read callable can't key a
#: cache), so sessions pass a hashable key instead of a closure.
_SHARD_DECODERS: dict[str, Callable] = {}


def register_shard_decoder(kind: str, build: Callable) -> None:
    """Register a sharded decode-path builder. ``build(caps, classes,
    fixed_len, opts)`` returns a callable mapping the shard-local gathered
    block arrays -> complete decode dict (counts included)."""
    _SHARD_DECODERS[kind] = build


def _build_vmap_shard_decoder(caps, classes, fixed_len, opts):
    def local(sub):
        return dict(jax.vmap(
            lambda blk: decode_block_arrays(blk, caps=caps, classes=classes, fixed_len=fixed_len)
        )(sub))
    return local


register_shard_decoder("vmap", _build_vmap_shard_decoder)


@functools.lru_cache(maxsize=32)
def _build_sharded_decode(mesh: Mesh, caps_h, classes_key, fixed_len, decoder_key):
    """One jitted gather+shard_map decode per (mesh, decode signature)."""
    axis = block_axis_name(mesh)
    classes = {k: tuple(v) for k, v in classes_key}
    kind, opts = decoder_key if decoder_key is not None else ("vmap", ())
    local_decode = _SHARD_DECODERS[kind](caps_h, classes, fixed_len, dict(opts))

    def local(sub):
        return _fill_counts(local_decode(sub), sub)

    @jax.jit
    def run(arrays, ids, valid):
        TRACE_COUNTS["decode_shard"] += 1
        sub = {k: v[ids] for k, v in arrays.items()}
        sub["valid"] = valid[:, None].astype(jnp.int32)
        sub = jax.lax.with_sharding_constraint(sub, block_specs(sub, mesh))
        # check_vma=False: pallas_call has no replication rule; every in/out
        # is fully block-sharded so replication checking is vacuous here
        return jax.shard_map(
            local, mesh=mesh, in_specs=PartitionSpec(axis),
            out_specs=PartitionSpec(axis), check_vma=False,
        )(sub)

    return run


def decode_blocks_sharded(
    db: DeviceBlocks,
    ids: np.ndarray,
    valid: np.ndarray,
    *,
    mesh: Mesh,
    decoder_key=None,
) -> dict[str, jax.Array]:
    """Decode an already-padded block-id set under ``shard_map`` on ``mesh``.

    ``ids`` must be padded to a multiple of the mesh's shard count (see
    :func:`pad_block_ids`); outputs come back block-major at the padded
    length, leading dim sharded over the block axis."""
    classes_key = tuple(sorted((k, tuple(v)) for k, v in db.classes.items()))
    run = _build_sharded_decode(mesh, _HashableCaps(db.caps), classes_key,
                                db.fixed_len, decoder_key)
    return dict(run(db.arrays, jnp.asarray(ids, jnp.int32), jnp.asarray(valid, jnp.int32)))


def decode_blocks_bucketed(
    db: DeviceBlocks,
    ids: np.ndarray,
    *,
    decoder: Optional[Callable[[dict[str, jax.Array]], dict[str, jax.Array]]] = None,
    postprocess: Optional[Callable[[dict[str, jax.Array]], dict[str, jax.Array]]] = None,
    mesh: Optional[Mesh] = None,
    decoder_key=None,
) -> dict[str, jax.Array]:
    """Bucketed ranged decode: pad ``ids`` to its power-of-two bucket, decode
    on device, and slice the outputs back to ``len(ids)``. Bit-identical to
    decoding exactly ``ids``, but compiles once per bucket instead of once
    per range length.

    ``postprocess`` (e.g. output formatting) runs on the decode dict at the
    *padded* bucket shape, so anything it jits buckets identically instead
    of specializing on the requested range length.

    With ``mesh`` the decode runs under ``shard_map`` over the block axis
    (each device decodes its lane shard; padding rounds to bucket x shards)
    and ``decoder_key`` — not ``decoder``, whose identity can't key a jit
    cache — selects the decode path (None = vmap; see
    :func:`register_shard_decoder`)."""
    if mesh is not None and decoder is not None:
        raise ValueError(
            "mesh= takes decoder_key=, not decoder= (a closure can't key the "
            "sharded jit cache); register the path via register_shard_decoder"
        )
    if mesh is None and decoder_key is not None:
        raise ValueError("decoder_key= only selects the sharded path; pass mesh= "
                         "(or use decoder= for the single-device path)")
    ids = np.asarray(ids, dtype=np.int64)
    if ids.size == 0:  # zero-block datasets/ranges: nothing to pad or decode
        R, C = db.caps.segs, db.caps.tokens
        out = {"tokens": jnp.zeros((0, C), jnp.int8),
               "n_tokens": jnp.zeros((0,), jnp.int32),
               "n_reads": jnp.zeros((0,), jnp.int32)}
        for k in ("read_pos", "read_rev", "read_start", "read_len", "read_corner"):
            out[k] = jnp.zeros((0, R), jnp.int32)
        return postprocess(out) if postprocess is not None else out
    shards = block_shard_count(mesh)
    padded, valid = pad_block_ids(ids, shards)
    if mesh is None:
        out = decode_blocks_padded(db, padded, valid, decoder=decoder)
    else:
        out = decode_blocks_sharded(db, padded, valid, mesh=mesh, decoder_key=decoder_key)
    if postprocess is not None:
        out = postprocess(out)
    if padded.size == ids.size:
        return out
    return {k: v[: ids.size] for k, v in out.items()}


# --------------------------------------------------------------------------
# fused decode: gather + unpack + reformat in ONE dispatch
# --------------------------------------------------------------------------
# The two-step hot path launches gather, decode, and format as separate jits
# (three dispatches per read). The fused path collapses them: one jit (vmap)
# or one gather + single Pallas kernel whose body decodes AND formats, so the
# formatted output lands directly in the consumer's layout. All the math is
# integer/boolean, so fused output is bit-identical to the two-step path.
#
# Formats opt in through a FUSER registry: ``fn(dec, kmer_k) -> array`` maps
# the padded decode dict to the format's output array with pure jnp ops
# (traceable both inside the vmap jit and inside the Pallas kernel body).
# repro.core.api registers the built-in formats at import; custom formats
# without a fuser transparently fall back to the two-step path.

#: fmt name -> (out_key, fuser fn | None); None = decode IS the format (2bit)
_FORMAT_FUSERS: dict[str, tuple[str, Optional[Callable]]] = {}

#: path kind ("vmap"/"pallas") -> builder of the fused padded-decode runner
_FUSED_DECODERS: dict[str, Callable] = {}


def register_format_fuser(name: str, out_key: str, fn: Optional[Callable] = None) -> None:
    """Register ``fmt``'s fused formatter: ``fn(dec, kmer_k) -> jax.Array``
    over the padded decode dict, pure jnp (it is traced inside the fused
    jit/kernel). ``fn=None`` marks a format whose output is the decode
    itself (2bit)."""
    _FORMAT_FUSERS[name] = (out_key, fn)


def fused_format_supported(name: str) -> bool:
    return name in _FORMAT_FUSERS


def register_fused_decoder(kind: str, build: Callable) -> None:
    """Register a fused decode-path builder: ``build(caps_h, classes_key,
    fixed_len, fmt_name, kmer_k, opts)`` returns a runner mapping
    ``(arrays, padded_ids, valid) -> decode dict + format out_key``, all at
    the padded bucket shape."""
    _FUSED_DECODERS[kind] = build


@functools.partial(
    jax.jit, static_argnames=("caps", "classes", "fixed_len", "fmt_name", "kmer_k")
)
def _fused_vmap_jit(arrays, ids, valid, caps, classes, fixed_len, fmt_name, kmer_k):
    TRACE_COUNTS["fused_vmap"] += 1
    cd = {k: tuple(v) for k, v in classes}
    sub = {k: v[ids] for k, v in arrays.items()}
    sub["valid"] = valid[:, None].astype(jnp.int32)
    out = dict(jax.vmap(
        lambda blk: decode_block_arrays(blk, caps=caps, classes=cd, fixed_len=fixed_len)
    )(sub))
    out_key, fn = _FORMAT_FUSERS[fmt_name]
    if fn is not None:
        out[out_key] = fn(out, kmer_k)
    return out


def _build_vmap_fused(caps_h, classes_key, fixed_len, fmt_name, kmer_k, opts):
    def run(arrays, ids, valid):
        return _fused_vmap_jit(
            arrays, ids, valid, caps=caps_h, classes=classes_key,
            fixed_len=fixed_len, fmt_name=fmt_name, kmer_k=kmer_k,
        )
    return run


register_fused_decoder("vmap", _build_vmap_fused)


def fused_decode_blocks_bucketed(
    db: DeviceBlocks,
    ids: np.ndarray,
    *,
    fmt_name: str,
    kmer_k: Optional[int] = None,
    path_key=None,
) -> dict[str, jax.Array]:
    """Single-dispatch bucketed decode+format — the fused twin of
    ``decode_blocks_bucketed(..., postprocess=apply_format)``.

    Same pad/mask/slice invariants (compiles once per bucket), bit-identical
    outputs; ``path_key`` selects the runner (None = the fused vmap jit;
    ``("pallas", ())`` = the fused Pallas kernel registered
    by repro.kernels.sage_decode)."""
    if fmt_name not in _FORMAT_FUSERS:
        raise KeyError(
            f"format {fmt_name!r} has no registered fuser; "
            f"use the two-step decode path"
        )
    ids = np.asarray(ids, dtype=np.int64)
    if ids.size == 0:
        R, C = db.caps.segs, db.caps.tokens
        out = {"tokens": jnp.zeros((0, C), jnp.int8),
               "n_tokens": jnp.zeros((0,), jnp.int32),
               "n_reads": jnp.zeros((0,), jnp.int32)}
        for k in ("read_pos", "read_rev", "read_start", "read_len", "read_corner"):
            out[k] = jnp.zeros((0, R), jnp.int32)
        out_key, fn = _FORMAT_FUSERS[fmt_name]
        if fn is not None:
            out[out_key] = fn(out, kmer_k)
        return out
    kind, opts = path_key if path_key is not None else ("vmap", ())
    classes_key = tuple(sorted((k, tuple(v)) for k, v in db.classes.items()))
    run = _FUSED_DECODERS[kind](
        _HashableCaps(db.caps), classes_key, db.fixed_len, fmt_name,
        kmer_k, dict(opts),
    )
    padded, valid = pad_block_ids(ids)
    out = dict(run(db.arrays, jnp.asarray(padded, jnp.int32),
                   jnp.asarray(valid, jnp.int32)))
    if padded.size == ids.size:
        return out
    return {k: v[: ids.size] for k, v in out.items()}


class _HashableCaps:
    """Hashable static wrapper around BlockCaps for jit (idempotent: wrapping
    an already-wrapped caps reuses the underlying dataclass)."""

    def __init__(self, caps) -> None:
        if isinstance(caps, _HashableCaps):
            caps = caps._c
        self._c = caps
        self._key = tuple(sorted(dataclasses.asdict(caps).items()))

    def __getattr__(self, k):
        return getattr(self._c, k)

    def __hash__(self) -> int:
        return hash(self._key)

    def __eq__(self, other) -> bool:
        return isinstance(other, _HashableCaps) and self._key == other._key
