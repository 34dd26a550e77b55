"""Batched-vs-reference encoder parity + batched mapper edge cases.

The vectorized SAGe_Write pipeline must be a drop-in for the sequential
reference: same mapping decisions, same stream bits, same directory — at
every opt_level, on datasets that exercise every corner (reverse
complement, chimeric joins, N dropouts, unmappable junk)."""

import numpy as np
import pytest

from repro.core.encoder import SageEncoder
from repro.core import refdec
from repro.genomics.batch_map import batch_map_reads
from repro.genomics.mapper import MinimizerIndex, ReadMapper
from repro.genomics.synth import ReadSet, make_reference, revcomp, sample_read_set

from conftest import multiset


def _mixed_read_set(seed: int, n: int = 24, ref_len: int = 12_000):
    """Reads covering every encoder path: clean, revcomp, edited, chimeric,
    N-containing, and unmappable junk."""
    rng = np.random.default_rng(seed)
    ref = make_reference(ref_len, seed=seed % 5)
    reads = []
    for i in range(n):
        kind = rng.random()
        L = int(rng.integers(80, 240))
        if kind < 0.12:  # junk -> escape
            reads.append(rng.integers(0, 5, L).astype(np.uint8))
            continue
        if kind < 0.24 and L >= 160:  # chimeric join of two loci
            l1 = L // 2
            p1 = int(rng.integers(0, ref_len - l1))
            p2 = int(rng.integers(0, ref_len - (L - l1)))
            r = np.concatenate([ref[p1 : p1 + l1], ref[p2 : p2 + (L - l1)]]).copy()
        else:
            pos = int(rng.integers(0, ref_len - L))
            r = ref[pos : pos + L].copy()
        for _ in range(int(rng.integers(0, 5))):  # random edits
            at = int(rng.integers(0, r.size))
            op = rng.random()
            if op < 0.6:
                r[at] = (r[at] + int(rng.integers(1, 4))) % 4
            elif op < 0.8:
                ins = rng.integers(0, 4, int(rng.integers(1, 5))).astype(np.uint8)
                r = np.concatenate([r[:at], ins, r[at:]])
            else:
                r = np.concatenate([r[:at], r[at + 1 :]])
        if kind < 0.34:  # N dropout -> corner/escape
            r = r.copy()
            r[int(rng.integers(0, r.size))] = 4
        if rng.random() < 0.5:
            r = revcomp(r)
        reads.append(r.astype(np.uint8))
    quals = [np.full(r.size, 60, np.uint8) for r in reads]
    return ref, ReadSet(reads=reads, quals=quals, kind="short", profile="mix")


# --------------------------------------------------------------- mapper
def test_batch_map_matches_sequential_mapper():
    ref = make_reference(20_000, seed=2)
    rs = sample_read_set(ref, "illumina", depth=2, seed=3)
    m = ReadMapper(ref)
    seq = [m.map_read(r) for r in rs.reads]
    bat = batch_map_reads(m, rs.reads)
    assert len(seq) == len(bat)
    for a, b in zip(seq, bat):
        assert (a is None) == (b is None)
        if a is None:
            continue
        assert len(a) == len(b)
        for sa, sb in zip(a, b):
            assert (sa.read_start, sa.read_end) == (sb.read_start, sb.read_end)
            assert sa.aln.pos == sb.aln.pos
            assert sa.aln.rev == sb.aln.rev
            assert sa.aln.n_edits == sb.aln.n_edits
            assert len(sa.aln.ops) == len(sb.aln.ops)
            for oa, ob in zip(sa.aln.ops, sb.aln.ops):
                assert oa[0] == ob[0] and int(oa[1]) == int(ob[1])
                if oa[0] == "I":
                    assert np.array_equal(oa[2], ob[2])
                else:
                    assert int(oa[2]) == int(ob[2])


def test_minimizer_lookup_empty_paths():
    """Regression: empty-hit paths must return empty arrays, not raise."""
    idx = MinimizerIndex.build(make_reference(4000, seed=1))
    q, r = idx.lookup(np.zeros(0, dtype=np.int64))
    assert q.size == 0 and r.size == 0
    # hashes that match nothing
    q, r = idx.lookup(np.asarray([-12345, -99999], dtype=np.int64))
    assert q.size == 0 and r.size == 0
    # index built from a reference shorter than k: empty index
    tiny = MinimizerIndex.build(np.zeros(4, dtype=np.uint8), k=13, w=8)
    assert tiny.hashes.size == 0
    q, r = tiny.lookup(np.asarray([7], dtype=np.int64))
    assert q.size == 0 and r.size == 0
    # all-N reference: every k-mer window is poisoned
    alln = MinimizerIndex.build(np.full(64, 4, dtype=np.uint8))
    q, r = alln.lookup(np.asarray([7], dtype=np.int64))
    assert q.size == 0 and r.size == 0


def test_lookup_matches_bruteforce_expansion():
    idx = MinimizerIndex.build(make_reference(6000, seed=4))
    h = idx.hashes[::17].copy()
    q, r = idx.lookup(h)
    exp_q, exp_r = [], []
    for i, hh in enumerate(h):
        lo = int(np.searchsorted(idx.hashes, hh, side="left"))
        hi = int(np.searchsorted(idx.hashes, hh, side="right"))
        for o in range(min(hi - lo, idx.occ_cut)):
            exp_q.append(i)
            exp_r.append(int(idx.positions[lo + o]))
    assert q.tolist() == exp_q and r.tolist() == exp_r


# -------------------------------------------------------------- encoder
@pytest.mark.parametrize("opt_level", [0, 1, 2, 3, 4])
def test_batched_encoder_bit_identical_all_opt_levels(opt_level):
    ref, rs = _mixed_read_set(seed=7)
    sf_ref = SageEncoder(ref, token_target=4096, batched=False).encode(rs, opt_level=opt_level)
    sf_bat = SageEncoder(ref, token_target=4096).encode(rs, opt_level=opt_level)
    assert sf_ref.diff(sf_bat) == []


def test_batched_encoder_lossless_and_escape_stats():
    ref, rs = _mixed_read_set(seed=11, n=40)
    enc_b = SageEncoder(ref, token_target=4096)
    enc_r = SageEncoder(ref, token_target=4096, batched=False)
    sf_b, sf_r = enc_b.encode(rs), enc_r.encode(rs)
    assert multiset(d.seq for d in refdec.decode_all(sf_b)) == multiset(rs.reads)
    assert enc_b.stats["n_escaped"] == enc_r.stats["n_escaped"]
    assert sf_r.diff(sf_b) == []


def test_batched_encoder_variable_length_fallback_parity():
    """Long reads of every length map through the batched device DP (one
    program per length bucket, each lane at its own length) and pack
    through the columnar path — output must stay identical, and only reads
    holding an N fall back to the sequential mapper."""
    ref = make_reference(40_000, seed=5)
    rs = sample_read_set(ref, "ont", depth=1, max_reads=8, seed=6)
    sf_ref = SageEncoder(ref, token_target=8192, batched=False).encode(rs)
    enc = SageEncoder(ref, token_target=8192)
    sf_bat = enc.encode(rs)
    assert sf_ref.diff(sf_bat) == []
    assert len({r.size for r in rs.reads}) == len(rs.reads)  # every length its own
    assert enc.stats["n_fallback"] == sum(bool((r == 4).any()) for r in rs.reads)
    assert enc.stats["n_batch_mapped"] + enc.stats["n_fallback"] == len(rs.reads)
    assert enc.stats["align_calls"] < len(rs.reads) <= enc.stats["align_lanes"]


def test_batched_encoder_empty_read_set():
    ref = make_reference(4000, seed=1)
    rs = ReadSet(reads=[], quals=[], kind="short", profile="x")
    sf = SageEncoder(ref).encode(rs)
    assert sf.meta.n_blocks == 0 and sf.meta.n_reads == 0


def test_verify_demotes_corrupted_mapping(monkeypatch):
    """If mapping produces a record set that does not decode back to the
    read, the decode round-trip must demote exactly that read to the
    escape stream (the batch analogue of the reference _verify walk)."""
    ref = make_reference(12_000, seed=3)
    rs = sample_read_set(ref, "illumina", depth=1, seed=4)
    enc = SageEncoder(ref, token_target=4096)

    from repro.core import encoder as enc_mod

    real = enc_mod._segment_records

    def corrupt(read, segs, cons, _n=[0]):
        recs = real(read, segs, cons)
        _n[0] += 1
        if _n[0] == 3 and recs and recs[0].length > 1:  # break one read's records
            recs[0].mbb = [(m + 1) % 3 if k == "S" else m for m, k in zip(recs[0].mbb, recs[0].kinds)]
            if not recs[0].mp:
                recs[0].mp = [0]
                recs[0].mbb = [0]
                recs[0].kinds = ["S"]
        return recs

    monkeypatch.setattr(enc_mod, "_segment_records", corrupt)
    sf = enc.encode(rs)
    assert multiset(d.seq for d in refdec.decode_all(sf)) == multiset(rs.reads)
    assert enc.stats["verify_rounds"] >= 2
    assert enc.stats["n_escaped"] >= 1


# ------------------------------------------------------------ property
try:
    import hypothesis  # noqa: F401

    HAVE_HYP = True
except ImportError:  # pragma: no cover
    HAVE_HYP = False

if HAVE_HYP:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    @given(st.integers(0, 2**16))
    @settings(
        max_examples=10, deadline=None,
        suppress_health_check=[HealthCheck.too_slow, HealthCheck.data_too_large],
    )
    def test_property_batched_equals_reference(seed):
        ref, rs = _mixed_read_set(seed=seed, n=14, ref_len=6000)
        for opt in (0, 4):
            sf_ref = SageEncoder(ref, token_target=2048, batched=False).encode(rs, opt_level=opt)
            sf_bat = SageEncoder(ref, token_target=2048).encode(rs, opt_level=opt)
            assert sf_ref.diff(sf_bat) == []
        assert multiset(d.seq for d in refdec.decode_all(sf_bat)) == multiset(rs.reads)
