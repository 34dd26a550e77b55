"""The reference check on hand-made answers: a sound store passes, and
each kind of fault, and the control, is counted."""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import oracle  # noqa: E402

C, R = 24, 4  # tokens and read rows per block
READS = [np.array(r, np.uint8) for r in
         ([0, 1, 2, 3, 0, 1], [3, 3, 2, 4, 1], [1, 1, 1, 1], [2, 0, 2, 0, 2, 0, 2], [0, 4, 4])]
BLOCKS = [[0, 1], [2, 3], [4]]  # which reads each block holds
AT = [0, 10, 20, 30, 40]  # where each read was sampled
TARGET = 11  # tokens per block: reads 0+1 and 2+3 reach it, read 4 is left


def block(reads, width=C):
    toks = np.full(width, oracle.PAD, np.int8)
    lens = np.zeros(R, np.int32)
    starts = np.zeros(R, np.int32)
    at = 0
    for i, r in enumerate(reads):
        toks[at: at + r.size] = r
        starts[i], lens[i] = at, r.size
        at += r.size
    return {"tokens": toks, "n_tokens": at, "n_reads": len(reads),
            "read_start": starts, "read_len": lens}


def answer(ids, fmt="kmer", k=3):
    rows = [block([READS[i] for i in BLOCKS[b]]) for b in ids]
    data = {key: np.stack([np.asarray(r[key]) for r in rows]) for key in rows[0]}
    key = oracle.FORMAT_KEY[fmt]
    data[key] = oracle.format_array(data["tokens"], fmt, k, data["n_tokens"])
    return {"want": np.array(ids), "block_ids": np.array(ids), "data": data,
            "fmt": fmt, "kmer_k": k if fmt == "kmer" else None}


def sound():
    return [answer([0, 1]), answer([2], "onehot"), answer([1, 2], "2bit")]


def check(answers, reads, n_blocks):
    return oracle.check(answers, reads, n_blocks, AT, TARGET)


def test_np_kmer_by_hand():
    toks = np.array([[0, 1, 2, 3, 4, 0, 4, 4, 4]], np.int8)
    # ACG -> 0*16 + 1*4 + 2; T N A holds an N inside the reads; the last
    # group is past n_tokens=7 and holds N: the pad id 4**3
    assert oracle.np_kmer(toks, 3, np.array([7])).tolist() == [[6, 66, 64]]


def test_sound_answers_pass():
    checks = check(sound(), READS, n_blocks=3)
    assert checks == dict.fromkeys(oracle.LIMITS, 0)
    assert check(sound(), READS, n_blocks=3)["block_offset_spans"] == 0.0
    assert oracle.passed(checks)


def test_missing_reads_only_on_full_cover():
    assert "missing_reads" not in check([answer([0])], READS, n_blocks=3)


def _altered(a):
    d = {k: v.copy() for k, v in a["data"].items()}
    d["tokens"][0, 1] = (d["tokens"][0, 1] + 1) % 4
    return dict(a, data=d)


def _half(a):
    return dict(a, data={k: v[:1] for k, v in a["data"].items()})


def _stale(a):
    return dict(a, data=answer([0, 1])["data"])


@pytest.mark.parametrize("fault,count", [
    (_altered, "wrong_reads"),
    (_half, "wrong_blocks"),
])
def test_fault_counted(fault, count):
    answers = sound()
    answers[0] = fault(answers[0])
    checks = check(answers, READS, n_blocks=3)
    assert checks[count] > 0 and not oracle.passed(checks)


def test_stale_answer_counted():
    answers = sound() + [dict(answer([1, 2]), data=answer([0, 1])["data"])]
    checks = check(answers, READS, n_blocks=3)
    assert checks["inconsistent_blocks"] > 0 and not oracle.passed(checks)


def test_unanswered_and_layout():
    bad = answer([2])
    bad["data"]["n_tokens"] = bad["data"]["n_tokens"] + 1
    answers = sound() + [{"want": np.array([0]), "error": TimeoutError()}, bad]
    checks = check(answers, READS, n_blocks=3)
    assert checks["unanswered"] == 1 and checks["layout_errors"] == 1


def test_format_error():
    a = answer([0, 1])
    a["data"]["kmer"] = a["data"]["kmer"].copy()
    a["data"]["kmer"][1, 0] += 1
    assert check([a], READS, n_blocks=3)["format_errors"] == 1


def test_read_in_two_blocks_is_excess():
    a = answer([0, 1])
    d = {k: v.copy() for k, v in a["data"].items()}
    twin = block([READS[0], READS[0]])  # block 1 claims block 0's first read
    for k in twin:
        d[k][1] = twin[k]
    d["kmer"] = oracle.format_array(d["tokens"], "kmer", 3, d["n_tokens"])
    checks = check([dict(a, data=d), answer([2])], READS, n_blocks=3)
    assert checks["excess_reads"] > 0 and checks["missing_reads"] > 0


def test_control_breaks_losslessness():
    checks = check(oracle.collapse_n(sound()), READS, n_blocks=3)
    assert checks["wrong_reads"] == 3  # the served reads that hold an N
    assert not oracle.passed(checks)
    assert oracle.report(checks)[0] == "check unanswered = 0 (limit 0)"


def test_block_places_by_hand():
    medians, span = oracle.block_places(READS, AT, TARGET)
    assert medians.tolist() == [5.0, 25.0, 40.0] and span == 17.5


def test_block_answered_with_its_neighbour_is_misplaced():
    a = answer([0, 1])
    a["data"] = answer([1, 2])["data"]  # ids 0 and 1 hold blocks 1 and 2
    checks = check([a], READS, n_blocks=3)
    assert checks["block_offset_spans"] == 20 / 17.5 and not oracle.passed(checks)
    assert sum(v for k, v in checks.items() if k != "block_offset_spans") == 0


# Long reads: nine reads of 10 tokens sampled at 0, 10, ..., 80, blocks of
# two. Read 3 is a chimera the encoder maps at its other locus (75), read 5
# holds an N and is escaped to the end of the corpus.
LONG = [np.random.default_rng(i).integers(0, 4, 10).astype(np.uint8) for i in range(9)]
LONG[5][4] = oracle.PAD
LONG_AT = np.array([10 * i for i in range(9)])
CHIMERIC = np.arange(9) == 3
LONG_BLOCKS = [[0, 1], [2, 4], [6, 7], [3, 8], [5]]  # the encoder's cut


def long_answer(ids, contents=None):
    contents = ids if contents is None else contents
    rows = [block([LONG[i] for i in LONG_BLOCKS[c]], 32) for c in contents]
    data = {key: np.stack([np.asarray(r[key]) for r in rows]) for key in rows[0]}
    return {"want": np.array(ids), "block_ids": np.array(ids), "data": data,
            "fmt": "2bit", "kmer_k": None}


def long_check(answers, kind="long", chimeric=CHIMERIC):
    return oracle.check(answers, LONG, 5, LONG_AT, 20, kind, chimeric)


def test_long_read_sound_blocks_pass():
    checks = long_check([long_answer([0, 1, 2, 3]), long_answer([4])])
    assert checks == dict.fromkeys(oracle.LIMITS, 0) and oracle.passed(checks)
    # placed by sampled position, as short reads are, the chimera and the
    # escaped read move the cut after them: a block of two reads in two
    short = long_check([long_answer([0, 1, 2, 3]), long_answer([4])], kind="short")
    assert short["block_offset_spans"] == 1.5 and not oracle.passed(short)


def test_long_read_chimera_not_vouched_for():
    # ranked at its sampled position, the chimera misplaces block 3
    checks = long_check([long_answer([0, 1, 2, 3]), long_answer([4])], chimeric=None)
    assert checks["block_offset_spans"] == 0.75 and not oracle.passed(checks)


def offsets(served, n_blocks=5):
    return oracle.rank_offsets(served, LONG, n_blocks, LONG_AT, CHIMERIC)


def test_long_read_escape_after_a_mapped_read_not_vouched_for():
    # read 2 unmapped: written after read 8, which lies past it
    assert offsets([(0, [0, 1]), (1, [4, 6]), (2, [7, 3]), (3, [8, 2]), (4, [5])]) == 0


def test_long_read_escape_opening_the_last_block():
    # not seen: the windows after its rank move by one read, half a span of
    # two here; the last block leaves it out of its own median
    assert offsets([(0, [0, 1]), (1, [4, 6]), (2, [7, 3, 8]), (3, [2, 5])], 4) == 0.5


@pytest.mark.parametrize("contents,reading", [
    ([1, 2, 3, 4, 0], 1.0),  # every block shifted by one
    ([0, 2, 2, 3, 4], 1.0),  # one block answered with its neighbour's reads
    ([0, 1, 1, 3, 4], 1.0),
    ([1, 0, 2, 3, 4], 1.0),
])
def test_long_read_misplaced_block_fails(contents, reading):
    checks = long_check([long_answer([0, 1, 2, 3, 4], contents)])
    assert checks["block_offset_spans"] == reading and not oracle.passed(checks)
