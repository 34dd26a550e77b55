"""Batched banded edit-distance DP and traceback for the SAGe_Write mapper
front-end.

One jitted ``lax.scan`` over DP rows, ``vmap``-ed across a batch of reads —
the encode-side sibling of the decode kernels: the paper's co-design
argument (and the GenASM / storage-centric line of work) is that alignment
must be *batched and offloaded*, not looped per read on the host. The
recurrence is row-sequential but each row is a width-(2b+1) vector op, so a
batch of B reads turns L tiny numpy rows into one (B, width) device op per
row. The traceback runs on the device too (one vectorized step per move for
every lane), so the move matrices never leave it: only each lane's edit-op
stream comes back.

Reads of different lengths share a call. Every lane carries its own length
and band: rows past a lane's length leave its DP row unchanged (so the last
row is the row at the lane's own length), columns past its own band width
stay at ``INF``, and its traceback starts at its own length. Lanes in one
call share one padded length from a fixed ladder (:func:`padded_len`) and
the band of that length, so the jit cache holds one entry per (lane bucket,
padded length, band).

Bit-for-bit contract: every lane computes exactly the recurrence of
:func:`repro.genomics.mapper.banded_align` at its own length and band (same
INF arithmetic, same tie-breaking, same band-edge masking) and walks the
same traceback; ``repro.genomics.batch_map`` then reproduces the sequential
mapper's ops verbatim. Tests assert equality against the per-read reference
on every mapped read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bitio import ranges_from_counts
from repro.core.decode_jax import TRACE_COUNTS

INF = 1 << 20  # matches repro.genomics.mapper.banded_align

# DP lengths: each octave of read lengths splits into this many steps (at
# least 8 rows), so padding stays under 1/8 and a read set of any length mix
# compiles a few programs: 145-160 bp reads share 160, 11,265-12,288 share
# 12,288.
LEN_STEPS_PER_OCTAVE = 8


def padded_len(n: int) -> int:
    """The DP length a read of ``n`` bases runs at."""
    step = max(8, 1 << max(n.bit_length() - LEN_STEPS_PER_OCTAVE.bit_length(), 0))
    return -(-n // step) * step


@functools.partial(jax.jit, static_argnames=("width",))
def _align_scan(reads, wins, js0, wlen, n, bw, *, width: int):
    """DP forward pass for a batch of reads padded to one length.

    reads: (B, Lp) int32 base codes, valid up to ``n``; wins: (B, Wmax) int32
    consensus windows (values past ``wlen`` are ignored); js0, wlen, n, bw:
    (B,) int32 window column of band lane 0 at row 1, true window length,
    read length, and band width 2*band+1 (at most ``width``). Returns
    (moves (B, Lp, width) uint8, last_row (B, width) int32): the row at each
    lane's own length; moves past it, or past its band width, are never read."""
    TRACE_COUNTS["align_scan"] += 1
    Lp = reads.shape[1]
    inf = jnp.int32(INF)
    ar = jnp.arange(width, dtype=jnp.int32)

    def lane(read, win, js0, wl, n, bw):
        inband = ar < bw

        def step(prev, x):
            base, i = x
            j = (i - 1) + js0 + ar  # window col consumed on diag
            valid = (j >= 0) & (j < wl)
            cj = jnp.where(valid, j, 0)
            match = (win[cj] == base) & (base < 4) & valid
            diag = prev + jnp.where(match, 0, 1) + jnp.where(valid, 0, inf)
            up = jnp.concatenate([prev[1:], jnp.full((1,), inf, jnp.int32)]) + 1
            cur = jnp.minimum(diag, up)
            mv = jnp.where(up < diag, 1, 0).astype(jnp.uint8)
            # left (deletion) via prefix-min, lanes gated to in-window cols
            b_lo = -i - js0 + 1
            b_hi = wl - i - js0
            y = jnp.where(ar < b_lo - 1, inf, cur - ar)
            lft = jax.lax.cummin(y) + ar
            allowed = (ar >= b_lo) & (ar <= b_hi)
            lft = jnp.where(allowed, lft, cur)
            mv = jnp.where(lft < cur, jnp.uint8(2), mv)
            cur = jnp.where(inband, jnp.minimum(lft, cur), inf)
            return jnp.where(i <= n, cur, prev), mv

        prev0 = jnp.where(inband, 0, inf)  # free start anywhere in band
        xs = (read, jnp.arange(1, Lp + 1, dtype=jnp.int32))
        last, moves = jax.lax.scan(step, prev0, xs)
        return moves, last

    return jax.vmap(lane)(reads, wins, js0, wlen, n, bw)


# traceback step codes: the move of an op the lane emits (0=S 1=I1 2=D1),
# a diagonal match (no op), and a lane that has finished
MATCH, DONE = 3, 4


@jax.jit
def _traceback(moves, last, reads, wins, js0, wlen, n, bw):
    """Traceback of :func:`_align_scan` for every lane, from its own length,
    one vectorized step for all lanes per iteration.

    Returns (ok, b, codes): whether the lane aligned, its band lane at row 0
    (the start column is ``js0 + b``), and (cap, B) uint8 step codes: each
    lane's walk from row ``n`` down, one code per step, then ``DONE``."""
    TRACE_COUNTS["align_traceback"] += 1
    B, Lp, width = moves.shape
    cap = 2 * Lp + width + 2
    lanes = jnp.arange(B)
    b0 = jnp.argmin(last, axis=1).astype(jnp.int32)  # first min, as np.argmin
    ok0 = last[lanes, b0] < INF

    def cond(s):
        t, i, _, ok, _ = s
        return (t < cap) & jnp.any(ok & (i > 0))

    def body(s):
        t, i, b, ok, codes = s
        act = ok & (i > 0)
        r = jnp.maximum(i - 1, 0)
        badb = (b < 0) | (b >= bw)  # off-band walk: impossible when dist<INF
        mv = jnp.where(badb, jnp.uint8(0), moves[lanes, r, jnp.clip(b, 0, width - 1)])
        j = r + js0 + b
        badj = (mv == 0) & ((j < 0) | (j >= wlen))
        base = reads[lanes, r]
        sub = (mv == 0) & ~badj & ((wins[lanes, jnp.where(badj, 0, j)] != base) | (base >= 4))
        code = jnp.where(act, jnp.where(sub | (mv != 0), mv, jnp.uint8(MATCH)), jnp.uint8(DONE))
        codes = jax.lax.dynamic_update_slice(codes, code[None, :], (t, 0))
        i = jnp.where(act, i - (mv != 2), i)
        b = jnp.where(act, b + (mv == 1) - (mv == 2), b)
        ok = jnp.where(act, ok & ~badb & ~badj, ok)
        return t + 1, i, b, ok, codes

    s = (jnp.int32(0), n, b0, ok0, jnp.full((cap, B), DONE, jnp.uint8))
    _, i, b, ok, codes = jax.lax.while_loop(cond, body, s)
    return ok & (i == 0), b, codes


def _op_streams(codes: np.ndarray, n: np.ndarray) -> tuple[np.ndarray, list]:
    """Each lane's ops, in emit order, from its traceback step codes:
    (nops (B,), [(kinds, read offsets)] per lane)."""
    live = codes != DONE
    mv = np.where(codes == MATCH, 0, codes)
    dec = live & (mv != 2)  # every move but a deletion consumes a read base
    i = n[None, :] - np.cumsum(dec, axis=0) + dec  # row before the step
    emit = (live & (codes != MATCH)).T
    lane, step = np.nonzero(emit)
    kinds = codes.T[lane, step]
    offs = np.where(kinds == 2, i.T[lane, step], i.T[lane, step] - 1).astype(np.int64)
    nops = emit.sum(axis=1).astype(np.int64)
    cut = np.cumsum(nops)[:-1]
    return nops, list(zip(np.split(kinds, cut), np.split(offs, cut)))


def _bucket(n: int) -> int:
    return 1 << (max(n, 1) - 1).bit_length()


# Soft cap on one DP call's move-matrix bytes; callers chunk above this so
# long-read batches don't materialize gigabyte intermediates.
MOVES_BUDGET_BYTES = 256 << 20
# Hard cap on lanes per DP call: every full chunk then shares one
# power-of-two bucket shape, so the jit cache stays small (full-chunk
# bucket + at most one tail bucket per padded length).
MAX_CHUNK_LANES = 1024


def dp_inputs(
    reads: list[np.ndarray], cons: np.ndarray, cand: np.ndarray, bands: np.ndarray,
    band_cap: int, Lp: int,
) -> tuple[list[np.ndarray], np.ndarray]:
    """The arguments of :func:`_align_scan` for reads near ``cand`` (B,), each
    at its own band ``bands`` (B,) of at most ``band_cap``, laid out at
    length ``Lp``: ([rows, wins, js0, wlen, n, bw], ws), ``ws`` (B,) being
    each lane's window start on the consensus."""
    B = len(reads)
    lens = np.fromiter((r.size for r in reads), dtype=np.int64, count=B)
    rows = np.zeros((B, Lp), dtype=np.int32)
    rows[np.repeat(np.arange(B), lens), ranges_from_counts(lens)] = np.concatenate(reads)
    cand = np.asarray(cand, dtype=np.int64)
    bands = np.asarray(bands, dtype=np.int64)
    ws = np.maximum(cand - bands, 0)
    we = np.minimum(int(cons.size), cand + lens + bands)
    idx = ws[:, None] + np.arange(Lp + 2 * band_cap, dtype=np.int64)[None, :]
    wins = cons[np.clip(idx, 0, cons.size - 1)].astype(np.int32)
    i32 = lambda x: np.asarray(x, dtype=np.int32)
    return [rows, wins, i32(cand - ws - bands), i32(we - ws), i32(lens),
            i32(2 * bands + 1)], ws


def align_rows(
    reads: list[np.ndarray], cons: np.ndarray, cand: np.ndarray, bands: np.ndarray,
    band_cap: int, stats: dict | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, list]:
    """Banded DP and traceback of every read near ``cand`` (B,), each at its
    own band ``bands`` (B,) of at most ``band_cap``.

    The reads must share one :func:`padded_len`. Host wrapper: lays the
    reads out at that length with their consensus windows
    (:func:`dp_inputs`), pads each chunk of lanes to a power-of-two lane
    bucket (so the jit cache holds one entry per (bucket, Lp, band_cap)),
    chunks oversized batches, and returns (ok, pos, nops, ops): per lane,
    whether it aligned, its consensus start, and its ``nops`` ops as (kinds,
    read offsets) in emit order (the reverse of read order), kind 0=S 1=I1
    2=D1. Lanes whose window is empty (W <= 0) must be filtered by the
    caller beforehand. ``stats`` gains the calls, lanes, rows and padded
    rows it ran."""
    Lp = padded_len(max(r.size for r in reads))
    inputs, ws = dp_inputs(reads, cons, cand, bands, band_cap, Lp)
    lens, js0 = inputs[4], inputs[2]
    width = 2 * band_cap + 1
    chunk = max(1, min(MOVES_BUDGET_BYTES // max(Lp * width, 1), MAX_CHUNK_LANES))
    ok, b, nops, ops = [], [], [], []
    for s in range(0, len(reads), chunk):
        args = [a[s : s + chunk] for a in inputs]
        m = args[0].shape[0]
        nb = _bucket(m)
        if nb != m:  # pad lanes by repeating lane 0; outputs sliced off below
            args = [np.concatenate([a, np.repeat(a[:1], nb - m, axis=0)]) for a in args]
        with jax.profiler.TraceAnnotation("sage.encode.align"):
            args = [jnp.asarray(a) for a in args]
            moves, last = _align_scan(*args, width=width)
            last.block_until_ready()
        with jax.profiler.TraceAnnotation("sage.encode.traceback"):
            ok_c, b_c, codes = (np.asarray(o) for o in _traceback(moves, last, *args))
            nops_c, ops_c = _op_streams(codes[:, :m], lens[s : s + m])
        ok.append(ok_c[:m])
        b.append(b_c[:m])
        nops.append(nops_c)
        ops += ops_c
        if stats is not None:
            stats["align_calls"] = stats.get("align_calls", 0) + 1
            stats["align_lanes"] = stats.get("align_lanes", 0) + m
            stats["align_rows"] = stats.get("align_rows", 0) + int(lens[s : s + m].sum())
            stats["align_rows_padded"] = stats.get("align_rows_padded", 0) + nb * Lp
    pos = ws + js0 + np.concatenate(b)
    return np.concatenate(ok) & (pos >= 0), pos, np.concatenate(nops), ops
