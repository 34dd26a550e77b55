"""The trace reduction on a short trace recorded on a TPU v5e (a window of
the ``rs1.stream-kmer`` cell), against a second, plain reading of the same
file."""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402

TRACE = BENCH / "tests" / "data" / "stream.xplane.pb.gz"


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(TRACE, ("bench.fetch",))


@pytest.fixture(scope="module")
def plain():
    """Device op intervals and the window, read without the reduction."""
    import gzip

    from jax.profiler import ProfileData

    with gzip.open(TRACE) as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    ops, window = [], None
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                    ops.append((e.start_ns, e.end_ns))
                elif plane.name.startswith("/host:") and e.name == "bench.window":
                    window = (e.start_ns, e.end_ns)
    return np.array(ops), window


def test_one_chip_and_window(reduced, plain):
    _, (lo, hi) = plain
    assert reduced.chips == 1
    assert reduced.window_s == pytest.approx((hi - lo) / 1e9)


def test_busy_is_the_union_of_ops(reduced, plain):
    ops, (lo, hi) = plain
    # the union by a sweep over starts with a running maximum of the ends
    ops = np.clip(ops, lo, hi)
    ops = ops[np.argsort(ops[:, 0])]
    reach = np.maximum.accumulate(ops[:, 1])
    begins = np.flatnonzero(np.r_[True, ops[1:, 0] > reach[:-1]])
    ends = np.r_[begins[1:] - 1, len(ops) - 1]
    busy = (reach[ends] - ops[begins, 0]).sum() / 1e9
    assert reduced.busy_s == pytest.approx(busy, rel=1e-9)
    assert 0 < reduced.busy_s < reduced.window_s
    assert 0 < reduced.idle_share < 1


def test_fetch_spans_and_breakdown(reduced):
    fetches = reduced.spans["bench.fetch"]
    assert len(fetches) >= 3 and all(e > s for s, e in fetches)
    assert 0 < len(reduced.device_ops) <= trace_reduce.TOP
    assert 0 < len(reduced.idle_gaps) <= trace_reduce.TOP
    gaps = [s for _, s in reduced.idle_gaps]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= reduced.window_s - reduced.busy_s + 1e-9
