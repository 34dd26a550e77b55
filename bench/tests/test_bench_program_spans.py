"""The reading of the store's ``sage.*`` spans: on hand-built events, and on
a short trace recorded on a TPU v5e (a window of the ``rs1.stream-kmer``
cell with the spans in), against a plain reading of the same file."""

from __future__ import annotations

import sys
from collections import defaultdict
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import program_spans  # noqa: E402
import trace_reduce  # noqa: E402

TRACE = BENCH / "tests" / "data" / "stream_spans.xplane.pb.gz"
NAMES = ("sage.store.gather", "sage.store.group_upload", "sage.read.decode",
         "sage.read.format", "sage.stream.io_wait")


# --------------------------------------------------------------- hand-built
def test_program_nesting_clipping_and_idle():
    a, b = ("host", 0), ("host", 1)
    events = [
        (a, 0, 100, "sage.read.decode"),
        (a, 10, 30, "sage.read.format"),
        (a, 40, 60, "sage.read.format"),
        (a, 120, 200, "sage.store.gather"),  # runs past the window's end
        (b, 5, 95, "sage.stream.io_wait"),  # another line: nests in nothing
        (a, 300, 310, "sage.store.gather"),  # outside the window
    ]
    idle = [[(50, 130)], [(0, 10), (190, 250)]]  # two chips
    got = program_spans.program(events, idle, 0, 150)
    assert set(got) == {"sage.read.decode", "sage.read.format", "sage.store.gather",
                        "sage.stream.io_wait"}
    dec, fmt, gat, wait = (got[k] for k in ("sage.read.decode", "sage.read.format",
                                            "sage.store.gather", "sage.stream.io_wait"))
    assert dec == pytest.approx(dict(count=1, seconds=100e-9, self_seconds=60e-9,
                                     idle_seconds=(50 + 10) / 2 * 1e-9))
    assert fmt == pytest.approx(dict(count=2, seconds=40e-9, self_seconds=40e-9,
                                     idle_seconds=10 / 2 * 1e-9))
    assert gat == pytest.approx(dict(count=1, seconds=30e-9, self_seconds=30e-9,
                                     idle_seconds=10 / 2 * 1e-9))
    assert wait == pytest.approx(dict(count=1, seconds=90e-9, self_seconds=90e-9,
                                      idle_seconds=(45 + 5) / 2 * 1e-9))


def test_nested_child_clipped_to_the_window():
    line = ("host", 0)
    events = [(line, -50, 50, "sage.read.decode"), (line, -40, 20, "sage.read.format")]
    got = program_spans.program(events, [[]], 0, 100)
    assert got["sage.read.decode"]["seconds"] == pytest.approx(50e-9)
    assert got["sage.read.decode"]["self_seconds"] == pytest.approx(30e-9)
    assert got["sage.read.format"]["seconds"] == pytest.approx(20e-9)


def _reading(program, fetches=4, window_s=1.0):
    return {"window_s": window_s, "fetch_s": [0.1] * fetches, "device_idle_s": 0.4,
            "program": program}


def test_per_fetch_reads_each_span():
    span = {"count": 2, "seconds": 0.02, "self_seconds": 0.012, "idle_seconds": 0.015}
    got = program_spans.per_fetch(_reading({n: dict(span) for n in NAMES}))
    assert got == pytest.approx({
        "gather_ms_per_fetch.stream": 5.0,
        "group_upload_ms_per_fetch.stream": 5.0,
        "io_wait_ms_per_fetch.stream": 5.0,
        "decode_dispatch_ms_per_fetch.stream": 3.0,  # self seconds
        "format_ms_per_fetch.stream": 5.0,
        "idle_in_gather_share.stream": 1.5,
    })


def test_per_fetch_is_none_without_the_span_or_a_fetch():
    """A window that never straddles two groups reads no gather, not 0."""
    span = {"count": 1, "seconds": 0.01, "self_seconds": 0.01, "idle_seconds": 0.0}
    got = program_spans.per_fetch(_reading({"sage.read.decode": span}))
    assert got.pop("decode_dispatch_ms_per_fetch.stream") == pytest.approx(2.5)
    assert set(got.values()) == {None}
    got = program_spans.per_fetch(_reading({n: dict(span) for n in NAMES}, fetches=0))
    assert got.pop("idle_in_gather_share.stream") == 0.0
    assert set(got.values()) == {None}


# ----------------------------------------------------------- recorded trace
@pytest.fixture(scope="module")
def pd():
    return trace_reduce.load(TRACE)


@pytest.fixture(scope="module")
def reading():
    return program_spans.reduce(TRACE)


@pytest.fixture(scope="module")
def plain(pd):
    """The window, the chip's op intervals, the fetch spans and each line's
    program spans, read without the reduction."""
    ops, fetches, lines, window = [], [], defaultdict(list), None
    for plane in pd.planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                    ops.append((e.start_ns, e.end_ns))
                elif plane.name.startswith("/host:"):
                    if e.name == "bench.window":
                        window = (e.start_ns, e.end_ns)
                    elif e.name == "bench.fetch":
                        fetches.append((e.start_ns, e.end_ns))
                    elif e.name.startswith("sage."):
                        lines[(plane.name, i)].append((e.name, e.start_ns, e.end_ns))
    return window, np.array(ops), fetches, lines


def _covered(intervals, points):
    """How many of ``intervals`` cover each segment [points[k], points[k+1])."""
    s = np.sort([a for a, _ in intervals])
    e = np.sort([b for _, b in intervals])
    at = points[:-1]
    return np.searchsorted(s, at, "right") - np.searchsorted(e, at, "right")


def test_recorded_trace_holds_every_span(reading):
    assert set(NAMES) <= set(reading["program"])
    assert reading["program"]["sage.store.gather"]["count"] >= 1


def test_program_equals_a_plain_reading(reading, plain):
    (lo, hi), ops, fetches, lines = plain
    assert reading["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert len(reading["fetch_s"]) == sum(e > lo and s < hi for s, e in fetches)
    ops = np.clip(ops, lo, hi)
    want = {}
    for events in lines.values():
        for name, s, e in events:
            if not (e > lo and s < hi):
                continue
            cs, ce = max(s, lo), min(e, hi)
            # the union of the program spans inside this one, by sorting
            inner, nested = sorted((max(a, lo), min(b, hi)) for n, a, b in events
                                   if (n, a, b) != (name, s, e) and s <= a and b <= e
                                   and b > lo and a < hi), 0.0
            reach = -np.inf
            for a, b in inner:
                nested += max(0.0, b - max(a, reach))
                reach = max(reach, b)
            d = want.setdefault(name, {"count": 0, "seconds": 0.0, "self_seconds": 0.0,
                                       "iv": []})
            d["count"] += 1
            d["seconds"] += (ce - cs) / 1e9
            d["self_seconds"] += (ce - cs - nested) / 1e9
            d["iv"].append((cs, ce))
    assert set(want) == set(reading["program"])
    for name, d in want.items():
        # the device idle inside the span: segments that no op covers and it does
        points = np.unique(np.r_[lo, hi, ops.ravel(), np.ravel(d["iv"])])
        free = _covered(ops, points) == 0
        inside = _covered(d["iv"], points) > 0
        d["idle_seconds"] = np.diff(points)[free & inside].sum() / 1e9
        got = reading["program"][name]
        assert got["count"] == d["count"], name
        for k in ("seconds", "self_seconds", "idle_seconds"):
            assert got[k] == pytest.approx(d[k], rel=1e-9, abs=1e-12), (name, k)


def test_program_fits_the_window_and_the_idle_time(reading):
    """Idle time inside a span is never more than the device's idle time,
    and the store's steps on the consumer take no more than its fetches."""
    idle = reading["device_idle_s"]
    assert 0 < idle < reading["window_s"]
    prog = reading["program"]
    assert all(0 <= d["idle_seconds"] <= min(idle, d["seconds"]) + 1e-12
               for d in prog.values())
    per_fetch = program_spans.per_fetch(reading)
    steps = sum(v for k, v in per_fetch.items() if k in program_spans.PER_FETCH)
    fetch_ms = 1e3 * sum(reading["fetch_s"]) / len(reading["fetch_s"])
    assert 0 < steps <= fetch_ms
    assert per_fetch["idle_in_gather_share.stream"] <= 100 * idle / reading["window_s"]


def test_idle_share_agrees_with_the_reduction(reading):
    r = trace_reduce.reduce(TRACE, ("bench.fetch",))
    assert reading["device_idle_s"] == pytest.approx(r.window_s - r.busy_s, rel=1e-9)
    assert len(reading["fetch_s"]) == len(r.spans["bench.fetch"])


def _without_program(pd):
    """The trace's planes with every ``sage.*`` event left out."""
    return SimpleNamespace(planes=[
        SimpleNamespace(name=p.name, lines=[
            SimpleNamespace(name=ln.name, events=[
                e for e in ln.events if not e.name.startswith("sage.")])
            for ln in p.lines])
        for p in pd.planes])


def test_reduction_reads_the_same_without_program_spans(pd, monkeypatch):
    """Busy time, the fetch spans, the op breakdown and the idle gaps' lengths
    do not depend on the program's spans; a gap they cover may be named
    after one of them."""
    with_spans = trace_reduce.reduce(TRACE, ("bench.fetch",))
    monkeypatch.setattr(trace_reduce, "load", lambda path: _without_program(pd))
    without = trace_reduce.reduce(TRACE, ("bench.fetch",))
    for k in ("window_s", "busy_s", "chips", "spans", "device_ops"):
        assert getattr(with_spans, k) == getattr(without, k), k
    assert [s for _, s in with_spans.idle_gaps] == [s for _, s in without.idle_gaps]
    for (a, _), (b, _) in zip(with_spans.idle_gaps, without.idle_gaps):
        assert a == b or a.startswith("sage."), (a, b)
