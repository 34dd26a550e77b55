"""The peaks table and the decode's byte count, against hand-computed sizes."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import peaks  # noqa: E402
import roofline  # noqa: E402


def test_v5e_peaks():
    p = peaks.peaks("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["int8_ops_per_s"]) == (197e12, 393e12)
    assert (p["hbm_bytes_per_s"], p["hbm_bytes"]) == (819e9, 16e9)
    assert "TPU v5e" in p["source"]


def test_unknown_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")


def test_decode_bytes_by_hand():
    # one block of the rs1 corpus's shape: 65,565 int8 tokens, 10,927 int32
    # k-mer ids, 437 read rows of 5 int32 fields, 2 int32 counts
    shapes = {"tokens": ((4, 65565), 1), "kmer": ((4, 10927), 4),
              "n_tokens": ((4,), 4), "n_reads": ((4,), 4)}
    shapes.update({k: ((4, 437), 4) for k in
                   ("read_start", "read_len", "read_pos", "read_rev", "read_corner")})
    per_block = 65565 + 4 * 10927 + 2 * 4 + 5 * 4 * 437
    assert roofline.output_bytes_per_block(shapes) == per_block == 118021
    assert roofline.decode_bytes(3, 5068, shapes) == 3 * (5068 + 118021)


def test_roofline_share():
    assert roofline.roofline_share(819e9, 2.0, 819e9) == 0.5
    assert roofline.roofline_share(10, 0.0, 819e9) is None
