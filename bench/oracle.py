"""The plain reference that decides ``correct``.

A store is correct when every block it serves holds reads that were written,
laid out as the read path promises, in the format asked for, when the blocks
together hold the read set, each read once, and when block ``b`` holds block
``b``'s reads. This module checks exactly that against the reads the
benchmark generated, with numpy alone: it imports nothing of the program and
reads nothing the program made but its answers.

Layout of a served block (the decoder's contract): the block's reads back to
back from token 0, PAD (4) after them; ``read_start`` the exclusive running
sum of ``read_len``; ``n_tokens`` their total; zeros past ``n_reads``. The
format arrays follow from the tokens: ``kmer`` packs ``k`` bases per id,
with groups holding an N mapped to the N-block id inside the reads and to
the pad id past them; ``onehot`` marks A, C, G, T.

Which reads block ``b`` holds follows from the encoder's blocking: reads in
order of their mapped position, a block closed once it holds the token
target. The generator knows where it sampled each read, so the same cut over
the sampled positions places every block on the reference without the
program: ``block_offset_spans`` is the largest distance, over the blocks
served, between the median place of a block's reads and of the reads that
cut puts in it, in blocks' spans. A block answered with its neighbour's reads
moves it by about one.

Short reads are placed by sampled position. Reads mapped to a repeat's other
copy or escaped to the end move a block of some 430 of them by a fraction of
a span. A block of long reads holds a few, so a read placed elsewhere than
its sampled position moves the blocks after it by a sizeable share of a
span, and long reads start densely near the reference's start and sparsely
near its end. So long-read blocks are placed by rank (``rank_offsets``),
leaving out the reads the program places itself: chimeras, reads holding an
N, and reads served out of order in their block.

Every other number compared is a count of faults, and its limit is 0.
"""

from __future__ import annotations

from collections import Counter

import numpy as np

PAD = 4
FORMAT_KEY = {"2bit": "tokens", "onehot": "onehot", "kmer": "kmer"}
LIMITS = {
    "unanswered": 0,  # answers due in the window that never came or failed
    "wrong_blocks": 0,  # answers not holding exactly the blocks asked for
    "layout_errors": 0,  # blocks whose tokens and read rows break the layout
    "wrong_reads": 0,  # served reads that are not reads of the set
    "format_errors": 0,  # blocks whose format array differs from the tokens'
    "inconsistent_blocks": 0,  # a block served twice with different reads
    "excess_reads": 0,  # reads served by more blocks than the set holds them
    "missing_reads": 0,  # reads of the set absent from a full cover of blocks
    "block_offset_spans": 0.5,  # how far a served block lies from its place
}


def np_kmer(tokens: np.ndarray, k: int, n_tokens) -> np.ndarray:
    """numpy k-mer pack: groups holding code 4 map to the N-block id inside
    a row's real tokens and to the pad id past them."""
    C = tokens.shape[-1]
    g = tokens[..., : (C // k) * k].reshape(*tokens.shape[:-1], C // k, k).astype(np.int64)
    ids = (np.where(g > 3, 0, g) * 4 ** np.arange(k - 1, -1, -1)).sum(-1)
    has4 = (g == PAD).any(-1)
    in_read = (np.arange(C // k) + 1) * k <= np.asarray(n_tokens)[..., None]
    return np.where(has4, np.where(in_read, 4**k + 2, 4**k), ids).astype(np.int32)


def np_onehot(tokens: np.ndarray) -> np.ndarray:
    return (tokens[..., None] == np.arange(4)).astype(np.float32)


def format_array(tokens: np.ndarray, fmt: str, k, n_tokens) -> np.ndarray:
    if fmt == "kmer":
        return np_kmer(tokens, k, n_tokens)
    if fmt == "onehot":
        return np_onehot(tokens)
    return tokens


def _layout_ok(toks, n_tok: int, n: int, starts, lens) -> bool:
    if not 0 <= n <= lens.size or (lens[:n] <= 0).any():
        return False
    if (starts[n:] != 0).any() or (lens[n:] != 0).any():
        return False
    ends = np.cumsum(lens[:n], dtype=np.int64)
    return (
        n_tok == (int(ends[-1]) if n else 0)
        and np.array_equal(starts[:n], ends - lens[:n])
        and n_tok <= toks.size
        and bool((toks[n_tok:] == PAD).all())
    )


def block_places(reads: list, positions, token_target: int) -> tuple[np.ndarray, float]:
    """Each block's median sampled position, cutting the reads in order of
    sampled position as the encoder cuts them in order of mapped position,
    and the median distance between neighbouring blocks' medians (a span)."""
    positions = np.asarray(positions)
    block, n, tokens = np.zeros(len(reads), np.int64), 0, 0
    for i in np.argsort(positions, kind="stable").tolist():
        if tokens >= token_target:
            n, tokens = n + 1, 0
        block[i] = n
        tokens += reads[i].size
    medians = np.array([np.median(positions[block == b]) for b in range(n + 1)])
    return medians, float(np.median(np.diff(medians))) if n else 1.0


def rank_offsets(served: list, reads: list, n_blocks: int, positions, chimeric=None) -> float:
    """``block_offset_spans`` of long-read blocks ``served`` (block id,
    indices of the set's reads it holds, in row order), placed by rank.

    The reads the oracle vouches for are ranked by sampled position. Block
    ``b`` should hold the ranks after those of the blocks before it, as
    many as it holds: its median rank is compared with that window's, in
    spans of the median block's count. The program places some reads
    itself, and the oracle does not vouch for them: chimeras (two loci),
    reads holding an N (SAGe's corner case), and reads that lie before a
    read ahead of them in their block (escapes, which the encoder writes
    after the mapped reads). An escape that opens a block is not seen: it
    moves the windows after its rank by one, and the last block, where
    escapes go, leaves the ranks below its window out of its median. A
    block is placed once every block before it has been served."""
    vouched = np.array([not (r == PAD).any() for r in reads], bool)
    if chimeric is not None:
        vouched &= ~np.asarray(chimeric, bool)
    rows: dict = {}
    for b, held in served:
        rows.setdefault(b, held)
    for held in rows.values():
        ahead = -1
        for i in [i for i in held if vouched[i]]:
            vouched[i] = positions[i] >= ahead
            ahead = max(ahead, positions[i])
    ids = np.flatnonzero(vouched)
    rank = np.full(len(reads), -1, np.int64)
    rank[ids[np.argsort(positions[ids], kind="stable")]] = np.arange(ids.size)
    counts = {b: sum(bool(vouched[i]) for i in held) for b, held in rows.items()}
    span = float(np.median([c for c in counts.values() if c] or [1]))
    worst = 0
    for b, held in served:
        if any(c not in counts for c in range(b)):
            continue
        start = sum(counts[c] for c in range(b))
        at = [rank[i] for i in held if vouched[i]]
        if b == n_blocks - 1:
            at = [r for r in at if r >= start]
        if at:
            worst = max(worst, float(abs(np.median(at) - start - (len(at) - 1) / 2) / span))
    return worst


def offset_spans(served: list, reads: list, n_blocks: int, positions, token_target: int,
                 kind: str, chimeric=None) -> float:
    """``block_offset_spans`` of the blocks ``served``: (block id, indices
    of the set's reads it holds, in row order)."""
    if kind == "long":
        return rank_offsets(served, reads, n_blocks, positions, chimeric)
    medians, span = block_places(reads, positions, token_target)
    worst = 0
    for b, held in served:
        at = [positions[i] for i in held]
        if at:
            off = abs(np.median(at) - medians[min(b, medians.size - 1)]) / span
            worst = max(worst, float(off))
    return worst


def check(answers: list, reads: list, n_blocks: int, positions, token_target: int,
          kind: str = "short", chimeric=None) -> dict:
    """Count every fault in ``answers`` against the generated ``reads``,
    sampled at ``positions`` and blocked at ``token_target`` tokens: a
    ``kind`` of ``"short"`` or ``"long"`` reads, ``chimeric`` marking the
    reads that join two loci.

    Each answer is a dict with ``want`` (the block ids asked for), ``fmt``
    and ``kmer_k``, and either ``error`` (it failed or never came) or
    ``block_ids`` and ``data`` (host arrays, block axis first).
    ``missing_reads`` is counted only when the answers cover all
    ``n_blocks`` blocks."""
    want_reads = Counter(bytes(r) for r in reads)
    index: dict = {}
    for i, r in enumerate(reads):
        index.setdefault(bytes(r), i)
    out = dict.fromkeys(LIMITS, 0)
    content: dict = {}  # block id -> sorted tuple of its reads
    served = []  # (block id, indices of the set's reads it holds, in row order)
    for a in answers:
        if a.get("error") is not None:
            out["unanswered"] += 1
            continue
        ids = np.asarray(a["block_ids"])
        d, fmt = a["data"], a["fmt"]
        rows = {np.shape(v)[0] for v in d.values()}
        if not np.array_equal(ids, np.asarray(a["want"])) or rows != {ids.size}:
            out["wrong_blocks"] += 1
            continue
        for j, b in enumerate(ids.tolist()):
            toks = np.asarray(d["tokens"][j])
            n_tok, n = int(d["n_tokens"][j]), int(d["n_reads"][j])
            starts, lens = np.asarray(d["read_start"][j]), np.asarray(d["read_len"][j])
            if not _layout_ok(toks, n_tok, n, starts, lens):
                out["layout_errors"] += 1
                continue
            held = [toks[s: s + ln].astype(np.uint8).tobytes()
                    for s, ln in zip(starts[:n].tolist(), lens[:n].tolist())]
            out["wrong_reads"] += sum(r not in want_reads for r in held)
            served.append((b, [index[r] for r in held if r in index]))
            want = format_array(toks, fmt, a["kmer_k"], n_tok)
            got = d.get(FORMAT_KEY[fmt])
            if got is None or not np.array_equal(np.asarray(got[j]).astype(want.dtype), want):
                out["format_errors"] += 1
            block = tuple(sorted(held))
            if content.setdefault(b, block) != block:
                out["inconsistent_blocks"] += 1
    out["block_offset_spans"] = offset_spans(served, reads, n_blocks, np.asarray(positions),
                                             token_target, kind, chimeric)
    in_blocks = Counter()
    for block in content.values():
        in_blocks.update(block)
    out["excess_reads"] = sum(max(0, c - want_reads[r]) for r, c in in_blocks.items()
                              if r in want_reads)
    if len(content) == n_blocks:
        out["missing_reads"] = sum(max(0, c - in_blocks[r]) for r, c in want_reads.items())
    else:
        del out["missing_reads"]
    return out


def collapse_n(answers: list) -> list:
    """The control: the same answers from a store that keeps two bits per
    base, so an N inside a read comes back as A and every format follows
    from those tokens. It breaks the configurations' lossless guarantee."""
    lossy = []
    for a in answers:
        if a.get("error") is not None:
            lossy.append(a)
            continue
        d = dict(a["data"])
        toks = np.asarray(d["tokens"]).copy()
        in_read = np.arange(toks.shape[-1]) < np.asarray(d["n_tokens"])[:, None]
        toks[(toks == PAD) & in_read] = 0
        d["tokens"] = toks
        d[FORMAT_KEY[a["fmt"]]] = format_array(toks, a["fmt"], a["kmer_k"],
                                               np.asarray(d["n_tokens"]))
        lossy.append({**a, "data": d})
    return lossy


def report(checks: dict) -> list:
    """One plain line per number compared, with its limit."""
    return [f"check {name} = {value} (limit {LIMITS[name]})" for name, value in checks.items()]


def passed(checks: dict) -> bool:
    return all(v <= LIMITS[k] for k, v in checks.items())
