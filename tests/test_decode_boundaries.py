"""The block decoder at its boundaries, against the numpy oracle.

``decode_block_arrays`` builds the token axis's segment and read structure
from segment boundaries (a scatter at each segment's start, then one prefix
scan) instead of a ``searchsorted`` loop and per-token lookups into
per-segment tables. These tests hold every output of it, masked lanes
included, to ``refdec`` on corpora that reach each boundary case:

- a fixed-length Illumina corpus with N reads (escape segments);
- a variable-length HiFi corpus;
- a planned corpus, written through a mapper that hands the encoder chosen
  alignments: insertions at a segment's first token and running to its last,
  back-to-back insertions, deletions, a deletion and a substitution at one
  read coordinate, chimera continuation segments, zero-length segments and
  reverse-complement reads.

A structural test guards the mechanism: the decoder's jaxpr has no loop and
at most six gathers that return a token-length array (the bit-stream fields,
the consensus and the reverse-complement, whose indices are data).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from repro.core import refdec
from repro.core.bitio import unpack_2bit
from repro.core.decode_jax import (
    PAD_BASE,
    _spread,
    decode_block_arrays,
    decode_blocks_padded,
    decode_file_jax,
    prepare_device_blocks,
)
from repro.core.encoder import SageEncoder
from repro.genomics.mapper import Alignment, Segment
from repro.genomics.synth import ReadSet, make_reference, revcomp, sample_read_set
from repro.kernels.sage_decode import OUT_KEYS, sage_decode_arrays

OUT_ALL = OUT_KEYS + ("n_tokens", "n_reads")


# --------------------------------------------------------------- corpora
class _PlannedMapper:
    """Maps each planned read to the segments it was built from; any other
    read (the N reads) is unmapped and goes to the escape stream."""

    def __init__(self) -> None:
        self.plans: dict[bytes, list[Segment]] = {}

    def map_read(self, read: np.ndarray):
        return self.plans.get(np.asarray(read, np.uint8).tobytes())


def _segment_bases(cons, pos, length, ops):
    """A segment's bases under the decoder's semantics: consensus from
    ``pos``, with ("S", p, k) a substitution by the k-th other base,
    ("I", p, bases) an insertion and ("D", p, n) a deletion."""
    out, cursor, ri = [], pos, 0
    mapper_ops = []
    for kind, p, arg in ops:
        while ri < p:
            out.append(int(cons[cursor]))
            ri += 1
            cursor += 1
        if kind == "S":
            base = (int(cons[cursor]) + 1 + arg) % 4
            out.append(base)
            mapper_ops.append(("S", p, base))
            ri += 1
            cursor += 1
        elif kind == "I":
            out.extend(arg)
            mapper_ops.append(("I", p, np.asarray(arg, np.uint8)))
            ri += len(arg)
        else:
            mapper_ops.append(("D", p, arg))
            cursor += arg
    while ri < length:
        out.append(int(cons[cursor]))
        ri += 1
        cursor += 1
    assert len(out) == length
    return np.asarray(out, np.uint8), mapper_ops


def _planned_corpus():
    cons = make_reference(20_000, seed=71)
    mapper = _PlannedMapper()
    reads = []

    def plan(pieces, rev=False):
        """``pieces``: (consensus pos, length, ops) per segment, in read order."""
        parts, segs, start = [], [], 0
        for pos, length, ops in pieces:
            bases, mops = _segment_bases(cons, pos, length, ops)
            aln = Alignment(pos=pos, rev=rev, ops=mops, n_edits=len(mops), read_len=length)
            segs.append(Segment(read_start=start, read_end=start + length, aln=aln))
            parts.append(bases)
            start += length
        fwd = np.concatenate(parts)
        read = revcomp(fwd) if rev else fwd
        mapper.plans[read.tobytes()] = segs
        reads.append(read)

    for i in range(6):  # several blocks of mixed cases
        o = 3000 * i + 100
        plan([(o, 40, [])])
        plan([(o + 10, 30, [("I", 0, [1, 2, 3])])])  # insertion at the first token
        plan([(o + 20, 30, [("S", 4, 1), ("I", 27, [0, 1, 2])])])  # ... running to the last
        plan([(o + 30, 36, [("D", 0, 2), ("S", 5, 0), ("D", 12, 4), ("I", 20, [3])])], rev=True)
        plan([(o + 40, 32, [("I", 4, [2, 2]), ("D", 6, 3), ("D", 10, 2), ("S", 10, 2),
                            ("I", 15, [1]), ("I", 16, [0, 3, 1])])])  # back to back
        # chimera: three loci, the middle piece empty, insertions at both ends
        # of the continuation segments, on the reverse strand
        plan([(o + 50, 25, [("S", 3, 0), ("I", 22, [1, 1, 1])]),
              (o + 1500, 0, []),
              (o + 900, 20, [("I", 0, [2, 0]), ("D", 7, 1), ("I", 18, [3, 3])])], rev=True)
        plan([(o + 60, 0, []), (o + 2000, 28, [("I", 0, [1])])])  # empty first segment
        plan([(o + 70, 24, [("S", 0, 2), ("S", 23, 1)])], rev=True)
    rng = np.random.default_rng(72)
    for _ in range(5):  # N reads: escape (corner) segments
        read = rng.integers(0, 4, 35).astype(np.uint8)
        read[rng.integers(0, 35)] = 4
        reads.append(read)
    rs = ReadSet(reads=reads, quals=[np.full(r.size, 70, np.uint8) for r in reads],
                 kind="short", profile="planned")
    enc = SageEncoder(cons, token_target=256, mapper=mapper, batched=False)
    sf = enc.encode(rs)
    assert enc.stats["n_escaped"] == 5  # every planned read kept its plan
    return sf


@functools.lru_cache(maxsize=None)
def _corpus(name):
    if name == "planned":
        return _planned_corpus()
    if name == "illumina":
        ref = make_reference(30_000, seed=73)
        rs = sample_read_set(ref, "illumina", depth=2, seed=74)
        # indels make the reads 147-153 bases long: trim them to one length
        rs.reads = [r[:140].copy() for r in rs.reads]
        rs.quals = [q[:140] for q in rs.quals]
        for i in range(0, len(rs.reads), 9):
            rs.reads[i][7] = 4
        sf = SageEncoder(ref, token_target=4096).encode(rs)
        assert sf.meta.fixed_read_len == 140
        return sf
    ref = make_reference(40_000, seed=75)
    rs = sample_read_set(ref, "hifi", depth=1, seed=76, max_reads=4)
    sf = SageEncoder(ref, token_target=8192).encode(rs)
    assert sf.meta.fixed_read_len == 0
    return sf


def _oracle_arrays(sf):
    """Every decoder output, per block, built from the sequential oracle."""
    cons = unpack_2bit(sf.consensus2b, sf.meta.cons_len)
    R, C = sf.meta.caps.segs, sf.meta.caps.tokens
    nb = sf.meta.n_blocks
    exp = {"tokens": np.full((nb, C), PAD_BASE, np.int8),
           "n_tokens": np.zeros(nb, np.int32), "n_reads": np.zeros(nb, np.int32)}
    exp.update({k: np.zeros((nb, R), np.int32)
                for k in ("read_rev", "read_start", "read_len", "read_corner")})
    exp["read_pos"] = np.full((nb, R), -1, np.int32)
    for b in range(nb):
        start = 0
        reads = refdec.decode_block(sf, b, cons)
        for r, d in enumerate(reads):
            exp["tokens"][b, start:start + d.seq.size] = d.seq
            exp["read_pos"][b, r] = d.pos
            exp["read_rev"][b, r] = d.rev
            exp["read_start"][b, r] = start
            exp["read_len"][b, r] = d.seq.size
            exp["read_corner"][b, r] = d.corner
            start += d.seq.size
        exp["n_tokens"][b], exp["n_reads"][b] = start, len(reads)
    return exp


def _assert_equal(got, exp, keys):
    for k in keys:
        np.testing.assert_array_equal(np.asarray(got[k]), exp[k], err_msg=k)


# ---------------------------------------------------- bit identity tests
CORPORA = ("illumina", "hifi", "planned")


@pytest.mark.parametrize("name", CORPORA)
def test_vmap_decode_equals_oracle(name):
    sf = _corpus(name)
    _assert_equal(decode_file_jax(prepare_device_blocks(sf)), _oracle_arrays(sf), OUT_ALL)


@pytest.mark.parametrize("name", CORPORA)
def test_padded_decode_equals_oracle_and_masks_invalid_lanes(name):
    sf = _corpus(name)
    exp = _oracle_arrays(sf)
    db = prepare_device_blocks(sf).to_device()
    nb = db.n_blocks
    # every block once, in reverse, with invalid lanes holding real blocks'
    # streams between them
    ids, valid = [], []
    for i, b in enumerate(range(nb - 1, -1, -1)):
        ids.append(b)
        valid.append(1)
        if i % 2 == 0:
            ids.append((b + 1) % nb)
            valid.append(0)
    ids, valid = np.asarray(ids), np.asarray(valid, np.int32)
    out = jax.tree.map(np.asarray, decode_blocks_padded(db, ids, valid))
    lanes = valid == 1
    for k in OUT_ALL:
        np.testing.assert_array_equal(out[k][lanes], exp[k][ids[lanes]], err_msg=k)
    pad = ~lanes
    assert (out["tokens"][pad] == PAD_BASE).all()
    assert (out["read_pos"][pad] == -1).all()
    for k in ("n_tokens", "n_reads", "read_rev", "read_start", "read_len", "read_corner"):
        assert (out[k][pad] == 0).all(), k


def test_pallas_decode_equals_oracle_on_planned_corpus():
    sf = _corpus("planned")
    db = prepare_device_blocks(sf)
    out = sage_decode_arrays(db.arrays, caps=db.caps, classes=db.classes, fixed_len=db.fixed_len)
    _assert_equal(out, _oracle_arrays(sf), OUT_KEYS)


def test_planned_corpus_reaches_its_boundary_cases():
    """The planned corpus holds what the tests above are meant to reach."""
    sf = _corpus("planned")
    exp = _oracle_arrays(sf)
    db = prepare_device_blocks(sf)
    assert db.n_blocks >= 3 and db.fixed_len == 0
    assert (exp["read_corner"].sum(1) > 0).any()
    assert exp["read_rev"].any()
    assert sf.meta.n_segments > sf.meta.n_reads  # continuation segments
    assert (exp["n_reads"] < db.caps.segs).any()  # masked segments past n_segs


# ------------------------------------------------ the boundary spread
@pytest.mark.parametrize("seed", range(4))
def test_spread_equals_searchsorted_lookup(seed):
    """``_spread`` gives each slot the values ``searchsorted`` + lookup
    would: zero-length segments, segments past the end and ends >= n."""
    rng = np.random.default_rng(seed)
    R, n = int(rng.integers(1, 40)), int(rng.integers(1, 300))
    lens = rng.integers(0, 12, R) * (rng.random(R) < 0.7)
    if seed % 2:  # a masked tail of zero-length segments
        lens[rng.integers(0, R):] = 0
    ends = np.cumsum(lens)
    vals = rng.integers(-2**31, 2**31, (3, R)).astype(np.int32)
    seg = np.minimum(np.searchsorted(ends, np.arange(n), side="right"), R - 1)
    got = jax.jit(_spread, static_argnums=2)(
        jnp.asarray(vals), jnp.asarray((ends - lens).astype(np.int32)), n
    )
    np.testing.assert_array_equal(np.asarray(got), vals[:, seg])


# -------------------------------------------------- the structural guard
def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, jcore.Jaxpr):
                    yield from _eqns(sub)


@pytest.mark.parametrize("name", ("illumina", "hifi"))
def test_decoder_has_no_loop_and_few_token_gathers(name):
    sf = _corpus(name)
    db = prepare_device_blocks(sf)
    C = db.caps.tokens
    blk = {k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype) for k, v in db.arrays.items()}
    classes = {k: tuple(v) for k, v in db.classes.items()}
    jaxpr = jax.make_jaxpr(functools.partial(
        decode_block_arrays, caps=db.caps, classes=classes, fixed_len=db.fixed_len,
    ))(blk)
    eqns = list(_eqns(jaxpr.jaxpr))
    loops = [e.primitive.name for e in eqns if e.primitive.name in ("while", "scan")]
    assert loops == []
    token_gathers = [e for e in eqns
                     if e.primitive.name == "gather" and e.outvars[0].aval.shape == (C,)]
    assert len(token_gathers) <= 6, len(token_gathers)
