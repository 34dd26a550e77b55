"""SAGe_Write's batched mapper on reads of any length.

The device DP padded to a shared length must equal the unpadded call lane
for lane, and ``batch_map_reads`` / ``SageEncoder`` must equal the
sequential mapper on HiFi and ONT read sets (both strands, a chimera, a
read holding an N), chimera-splitting attempts included, sending only
reads that hold an N to it."""

import numpy as np
import pytest

from repro.core.encoder import SageEncoder
from repro.genomics.batch_map import batch_map_reads
from repro.genomics.mapper import ReadMapper
from repro.genomics.synth import ReadSet, make_reference, sample_read_set
from repro.kernels import banded_align as ba


def _scan(reads, cons, cand, bands, band_cap, Lp):
    args, _ = ba.dp_inputs(reads, cons, cand, bands, band_cap, Lp)
    moves, last = ba._align_scan(*args, width=2 * band_cap + 1)
    return np.asarray(moves), np.asarray(last)


def test_padded_len_ladder():
    assert [ba.padded_len(n) for n in (150, 160, 161, 1024, 1025, 12000, 12289)] == [
        160, 160, 176, 1024, 1152, 12288, 13312]
    for n in range(1, 40_000, 37):
        assert n <= ba.padded_len(n) < n + max(8, n / 8)


@pytest.mark.parametrize("edge", [128, 1024])
def test_padded_scan_matches_unpadded(edge):
    """Lanes of lengths ``edge`` and ``edge + 1`` (either side of a padded
    length), a read shorter than its band, and a lane at a narrower band than
    the call's, in one padded call: each lane's moves up to its length and
    band, and its last row, equal its own unpadded call's."""
    rng = np.random.default_rng(edge)
    ref = make_reference(6_000, seed=3)
    lens = [edge, edge + 1, 10, edge - 7]
    bands = np.array([24, 24, 24, 15])
    pos = [2_000, 40, 3_000, 1_000]  # 40: the window starts at the consensus' start
    reads = []
    for p, n in zip(pos, lens):
        r = ref[p : p + n].copy()
        at = rng.integers(0, n, max(1, n // 40))
        r[at] = (r[at] + 1) % 4
        r = np.concatenate([r[: n // 2], r[n // 2 + 3 :], rng.integers(0, 4, 3).astype(np.uint8)])
        reads.append(r)
    cand = np.array(pos) + np.array([5, -3, 0, 2])
    Lp = ba.padded_len(edge + 1)
    assert ba.padded_len(edge) == edge < Lp
    moves, last = _scan(reads, ref, cand, bands, 24, Lp)
    assert moves.shape[1] == Lp
    for k, (r, band) in enumerate(zip(reads, bands)):
        bw = 2 * int(band) + 1
        m1, l1 = _scan([r], ref, cand[k : k + 1], bands[k : k + 1], int(band), r.size)
        assert np.array_equal(moves[k, : r.size, :bw], m1[0]), k
        assert np.array_equal(last[k, :bw], l1[0]), k
        assert (last[k, bw:] == ba.INF).all()


def _same_segments(a, b) -> bool:
    if a is None or b is None:
        return a is None and b is None
    if len(a) != len(b):
        return False
    for sa, sb in zip(a, b):
        if (sa.read_start, sa.read_end, sa.aln.pos, sa.aln.rev, sa.aln.n_edits,
                len(sa.aln.ops)) != (sb.read_start, sb.read_end, sb.aln.pos, sb.aln.rev,
                                     sb.aln.n_edits, len(sb.aln.ops)):
            return False
        for oa, ob in zip(sa.aln.ops, sb.aln.ops):
            if oa[0] != ob[0] or int(oa[1]) != int(ob[1]):
                return False
            if not (np.array_equal(oa[2], ob[2]) if oa[0] == "I" else int(oa[2]) == int(ob[2])):
                return False
    return True


@pytest.fixture(scope="module", params=["hifi", "ont"])
def long_reads(request):
    """A long-read set with both strands, a planted chimera and a read
    holding an N; the sequential mapper's answer for each read, and the
    read of each chimera-splitting attempt it made."""
    ref = make_reference(30_000, seed=11)
    rs = sample_read_set(ref, request.param, depth=1, max_reads=4, seed=12)
    assert len(set(rs.true_rev)) == 2
    chimera = np.concatenate([ref[2_000:5_000], ref[21_000:24_500]])
    with_n = rs.reads[0].copy()
    with_n[with_n.size // 3] = 4
    reads = list(rs.reads) + [chimera, with_n]
    m = ReadMapper(ref)
    tried, real, cur = [], m._chimeric, [0]
    m._chimeric = lambda r, c: (tried.append(cur[0]), real(r, c))[1]
    seq = []
    for i, r in enumerate(reads):
        cur[0] = i
        seq.append(m.map_read(r))
    del m._chimeric
    rs = ReadSet(reads=reads, quals=[np.full(r.size, 40, np.uint8) for r in reads],
                 kind="long", profile=request.param)
    return ref, m, rs, seq, tried


def test_batch_map_matches_sequential_long_reads(long_reads):
    _, m, rs, seq, tried = long_reads
    stats = {}
    bat = batch_map_reads(m, rs.reads, stats=stats)
    assert [i for i, (a, b) in enumerate(zip(seq, bat)) if not _same_segments(a, b)] == []
    chimera, with_n = len(rs.reads) - 2, len(rs.reads) - 1
    assert chimera in tried and seq[with_n] is None
    # chimera-splitting attempts run on the device too: only the N read
    # goes to the sequential mapper
    assert stats["n_chimera_attempts"] == len(tried)
    assert stats["n_fallback"] == 1
    assert stats["n_batch_mapped"] == len(rs.reads) - 1
    assert stats["align_lanes"] >= len(rs.reads) - 1
    assert stats["align_rows_padded"] >= stats["align_rows"]


def test_batched_encoder_long_read_parity(long_reads):
    ref, _, rs, _, _ = long_reads
    sf_ref = SageEncoder(ref, token_target=8192, batched=False).encode(rs)
    sf_bat = SageEncoder(ref, token_target=8192).encode(rs)
    assert sf_ref.diff(sf_bat) == []


class _SplitMapper(ReadMapper):
    """The sequential mapper, with chimera pieces placed on their cluster's
    diagonal (``cand + lo``), so that a planted chimera's split is taken."""

    def _chimera_pieces(self, size, cands):
        pieces = super()._chimera_pieces(size, cands)
        if pieces is None:
            return None
        qlo = sorted(c[2] for c in cands if any(c[1] + (lo - c[2]) == p for lo, _, p in pieces))
        return [(lo, hi, p + q) for (lo, hi, p), q in zip(pieces, qlo)]


def test_batch_map_matches_sequential_on_taken_split():
    ref = make_reference(30_000, seed=11)
    rs = sample_read_set(ref, "hifi", depth=0.5, max_reads=1, seed=13)
    reads = [np.concatenate([ref[2_000:5_000], ref[21_000:24_500]]), rs.reads[0]]
    m = _SplitMapper(ref)
    seq = [m.map_read(r) for r in reads]
    assert [s.aln.pos for s in seq[0]] == [2_000, 20_995]  # the split is taken
    stats = {}
    bat = batch_map_reads(m, reads, stats=stats)
    assert all(_same_segments(a, b) for a, b in zip(seq, bat))
    assert stats["n_chimera_attempts"] >= 1 and stats["n_fallback"] == 0
