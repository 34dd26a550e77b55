"""Batched mapper front-end for SAGe_Write.

``batch_map_reads(mapper, reads)`` produces the same per-read result as
``[mapper.map_read(r) for r in reads]`` — read for read, op for op — but
runs the hot loop batched, for reads of any length:

* minimizer seeding and diagonal candidate voting are single numpy passes
  over every read at once (both strands), laid end to end with N gaps that
  keep k-mers and minimizer windows inside their read;
* the banded DP and its traceback run for every candidate lane on the
  device (:mod:`repro.kernels.banded_align`). Reads share a call by length
  bucket (``padded_len``: eight steps per octave of length, so padding
  stays under 1/8), and each lane carries its own length and band, so its
  last DP row and its traceback start at its own length.

Only two kinds of read go to ``mapper.map_read``, the sequential mapper,
one at a time: reads holding an N (which it escapes) and reads whose
alignment triggers the chimera-splitting attempt (``n_edits > 0.12 L`` with
a second seed cluster). The fallback IS the sequential mapper, so
equivalence is by construction there; everywhere else it is asserted by
tests/test_encode_batch_parity.py.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from numpy.lib.stride_tricks import sliding_window_view

from repro.genomics.mapper import Alignment, ReadMapper, Segment, _merge_ops, _mix
from repro.genomics.synth import revcomp


def _batch_minimizers(
    reads: list[np.ndarray], k: int, w: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-lane (k, w) minimizers of N-free reads of any length: returns
    flattened (lane_id, qpos, hash) triples, qpos ascending within each lane
    — exactly the per-read ``minimizers()`` selection (windowed argmin
    positions are non-decreasing, so adjacent dedupe equals ``np.unique``).

    The reads are laid end to end, each followed by ``w`` N bases: every
    k-mer that crosses into a gap gets a hash above any real one, so a
    window of a read with fewer than ``w`` k-mers picks among its own."""
    from repro.core.bitio import ranges_from_counts  # genomics must not import core at module scope

    lens = np.fromiter((r.size for r in reads), dtype=np.int64, count=len(reads))
    starts = np.concatenate([[0], np.cumsum(lens + w)])
    flat = np.full(int(starts[-1]) + k + w - 1, 4, dtype=np.uint8)  # >= one window
    if lens.sum():
        flat[np.repeat(starts[:-1], lens) + ranges_from_counts(lens)] = np.concatenate(reads)
    n = flat.size - k + 1
    valid = flat < 4
    s = np.where(valid, flat, 0).astype(np.int64)
    code = np.zeros(n, dtype=np.int64)
    ok = np.ones(n, dtype=bool)
    for i in range(k):
        code |= s[i : i + n] << (2 * (k - 1 - i))
        ok &= valid[i : i + n]
    h = np.where(ok, _mix(code), np.int64(1) << 62)
    nk = lens - k + 1  # k-mers per read; one window when 1 <= nk <= w
    nwin = np.where(nk >= 1, np.maximum(nk - w + 1, 1), 0)
    lane = np.repeat(np.arange(lens.size, dtype=np.int64), nwin)
    wstart = np.repeat(starts[:-1], nwin) + ranges_from_counts(nwin)
    m = sliding_window_view(h, w).argmin(axis=1)[wstart] + wstart
    first = np.ones(m.size, dtype=bool)
    first[1:] = (m[1:] != m[:-1]) | (lane[1:] != lane[:-1])
    lane, m = lane[first], m[first]
    return lane, m - starts[lane], h[m]


NMAX = 4  # clusters ReadMapper._candidates keeps


def _batch_candidates(
    index, reads: list[np.ndarray]
) -> tuple[np.ndarray, np.ndarray]:
    """Seed clusters per lane, replicating ``ReadMapper._candidates``:
    returns (top (B, NMAX, 4), n_clusters (B,)): each lane's best ``NMAX``
    (votes, cand_pos, q_lo, q_hi) clusters in the order the sequential
    mapper's ``clusters.sort(reverse=True)`` leaves them, and how many it
    found (it keeps ``min(n, NMAX)``)."""
    B = len(reads)
    lens = np.fromiter((r.size for r in reads), dtype=np.int64, count=B)
    lane, qp, h = _batch_minimizers(reads, index.k, index.w)
    top = np.zeros((B, NMAX, 4), dtype=np.int64)
    # one lookup for every lane's minimizers — the same hit expansion (and
    # occ_cut semantics) the sequential mapper uses, qidx mapped to lanes
    qi, rpos = index.lookup(h)
    nh = qi.size
    if nh == 0:
        return top, np.zeros(B, dtype=np.int64)
    hit_lane = lane[qi]
    hit_q = qp[qi]
    diag = rpos - hit_q
    order = np.lexsort((diag, hit_lane))  # stable: per-lane diag sort
    ls, d, q = hit_lane[order], diag[order], hit_q[order]
    tol = np.maximum(32, (lens * 0.08).astype(np.int64))  # int(L * 0.08) per lane
    brk = np.ones(nh, dtype=bool)
    brk[1:] = (ls[1:] != ls[:-1]) | ((d[1:] - d[:-1]) > tol[ls[1:]])
    cstart = np.nonzero(brk)[0]
    cend = np.append(cstart[1:], nh)
    votes = cend - cstart
    qlo = np.minimum.reduceat(q, cstart)
    qhi = np.maximum.reduceat(q, cstart)
    # diag is sorted within a cluster, so the median is the middle pair
    med = (d[cstart + (votes - 1) // 2] + d[cstart + votes // 2]) / 2.0
    cand = np.trunc(med).astype(np.int64)  # == int(np.median(...))
    clane = ls[cstart]
    ncl = np.bincount(clane, minlength=B).astype(np.int64)
    # per lane, clusters ascending by (votes, cand, qlo, qhi): rank 0 is the
    # last, the lexicographic max that clusters.sort(reverse=True) puts first
    oc = np.lexsort((qhi, qlo, cand, votes, clane))
    cl_s = clane[oc]
    rank = np.searchsorted(cl_s, cl_s, side="right") - 1 - np.arange(cl_s.size)
    keep = rank < NMAX
    top[cl_s[keep], rank[keep]] = np.stack([votes, cand, qlo, qhi], axis=1)[oc[keep]]
    return top, ncl


def _lane_alignment(
    row: np.ndarray, pos: int, opk: np.ndarray, opp: np.ndarray
) -> Alignment:
    """Materialize one lane's Alignment from its op stream (emit order)."""
    ops = [
        ("S", int(p), int(row[p])) if k == 0 else (("I1", int(p)) if k == 1 else ("D1", int(p)))
        for k, p in zip(opk[::-1].tolist(), opp[::-1].tolist())
    ]
    return Alignment(
        pos=int(pos), rev=False, ops=_merge_ops(ops, row),
        n_edits=len(ops), read_len=int(row.size),
    )


def _align_lanes(
    mapper: ReadMapper, seqs: list[np.ndarray], cand: np.ndarray, alive: np.ndarray,
    stats: Optional[dict],
) -> tuple[np.ndarray, np.ndarray, np.ndarray, list]:
    """``banded_align(seqs[k], cons, cand[k], band)`` for every lane at its
    own band, on the device, one call per padded length: (ok, pos, nops,
    ops), ``ok`` False where ``alive`` is False or the window is empty."""
    from repro.kernels.banded_align import align_rows, padded_len

    n = len(seqs)
    lens = np.fromiter((r.size for r in seqs), dtype=np.int64, count=n)
    band_of = {int(L): mapper._band(int(L)) for L in np.unique(lens)}
    bands = np.fromiter((band_of[int(L)] for L in lens), dtype=np.int64, count=n)
    ws = np.maximum(cand - bands, 0)
    we = np.minimum(int(mapper.cons.size), cand + lens + bands)
    lanes = np.nonzero(alive & (we - ws > 0) & (lens > 0))[0]  # else aln None
    ok = np.zeros(n, dtype=bool)
    pos = np.zeros(n, dtype=np.int64)
    nops = np.zeros(n, dtype=np.int64)
    ops: list = [None] * n  # lane -> op stream
    lp = np.fromiter((padded_len(int(L)) for L in lens[lanes]), dtype=np.int64,
                     count=lanes.size)
    for Lp in np.unique(lp):
        g = lanes[lp == Lp]
        ok[g], pos[g], nops[g], ops_g = align_rows(
            [seqs[k] for k in g], mapper.cons, cand[g], bands[g], mapper._band(int(Lp)),
            stats,
        )
        for k, o in zip(g.tolist(), ops_g):
            ops[k] = o
    return ok, pos, nops, ops


def batch_map_reads(
    mapper: ReadMapper,
    reads: list[np.ndarray],
    *,
    stats: Optional[dict] = None,
) -> list[Optional[list[Segment]]]:
    """Batched equivalent of ``[mapper.map_read(r) for r in reads]``.

    ``stats`` gains ``n_batch_mapped`` (reads decided here), ``n_fallback``
    (reads sent to ``map_read``), ``n_chimera_attempts`` (strands whose
    alignment tried the chimera split) and the DP's ``align_*`` counters."""
    with jax.profiler.TraceAnnotation("sage.encode.map"):
        n = len(reads)
        out: list[Optional[list[Segment]]] = [None] * n
        has_n = [bool(np.any(r == 4)) for r in reads]
        fallback = [i for i in range(n) if has_n[i]]  # map_read escapes them
        idxs = [i for i in range(n) if not has_n[i]]
        B = len(idxs)
        both = [reads[i] for i in idxs] + [revcomp(reads[i]) for i in idxs]
        lens = np.fromiter((r.size for r in both), dtype=np.int64, count=2 * B)
        top, ncl = _batch_candidates(mapper.index, both)
        ok, pos, nops, ops = _align_lanes(mapper, both, top[:, 0, 1], ncl > 0, stats)
        # chimera-splitting attempts, as map_read makes them: every piece of
        # every tried strand in one more round of device DP
        tried = np.nonzero(ok & (nops > 0.12 * lens) & (ncl >= 2))[0].tolist()
        pieces = {}
        for ln in tried:
            cands = [tuple(int(v) for v in c) for c in top[ln, : min(int(ncl[ln]), NMAX)]]
            pieces[ln] = mapper._chimera_pieces(int(lens[ln]), cands)
        subs = [(ln, lo, hi, c) for ln, pcs in pieces.items() if pcs for lo, hi, c in pcs]
        sub_seqs = [both[ln][lo:hi] for ln, lo, hi, _ in subs]
        s_ok, s_pos, _, s_ops = _align_lanes(
            mapper, sub_seqs, np.array([c for *_, c in subs], dtype=np.int64),
            np.ones(len(subs), dtype=bool), stats,
        )
        chim: dict = {}  # strand -> its split, None where a piece did not align
        for k, (ln, lo, hi, _) in enumerate(subs):
            segs = chim.setdefault(ln, [])
            if segs is not None and s_ok[k]:
                segs.append(Segment(lo, hi, _lane_alignment(sub_seqs[k], s_pos[k], *s_ops[k])))
            else:
                chim[ln] = None
        for bidx, ridx in enumerate(idxs):
            best = best_edits = None
            for ln, rev in ((bidx, False), (B + bidx, True)):
                if not ok[ln]:
                    continue
                edits, split = int(nops[ln]), chim.get(ln)
                if split is not None:
                    ch_edits = sum(s.aln.n_edits for s in split)
                    if ch_edits + 8 * len(split) < edits:
                        edits = ch_edits
                    else:
                        split = None
                if best_edits is None or edits < best_edits:
                    best, best_edits = (ln, rev, split), edits
            if best is None:
                continue  # unmappable -> escape
            ln, rev, segs = best
            if segs is None:
                segs = [Segment(0, int(lens[ln]), _lane_alignment(both[ln], pos[ln], *ops[ln]))]
            if best_edits > mapper.max_edit_rate * max(1, int(lens[ln])):
                continue  # out[ridx] stays None
            for s in segs:
                s.aln.rev = rev
            out[ridx] = segs
        for ridx in fallback:
            with jax.profiler.TraceAnnotation("sage.encode.fallback"):
                out[ridx] = mapper.map_read(reads[ridx])
    if stats is not None:
        stats["n_batch_mapped"] = stats.get("n_batch_mapped", 0) + n - len(fallback)
        stats["n_fallback"] = stats.get("n_fallback", 0) + len(fallback)
        stats["n_chimera_attempts"] = stats.get("n_chimera_attempts", 0) + len(tried)
    return out
