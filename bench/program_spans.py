"""The store's own spans in a profiler trace of a benchmark window, on the
device trace's clock.

The read path marks its steps with ``jax.profiler.TraceAnnotation`` spans
named ``sage.*``:

    sage.store.gather        a fetch's rows gathered across residency groups
    sage.store.group_upload  one residency miss: host entry, unpack, upload
    sage.read.decode         decode + format dispatched from the host
    sage.read.format         the unfused format, inside sage.read.decode
    sage.stream.io_wait      the consumer blocked on the stream's I/O stage

``reduce`` counts and times each name over the ``bench.window`` span: its
seconds, its self seconds (less the ``sage.*`` spans nested in it on the same
thread line) and the part of it in which the device was idle. ``per_fetch``
turns that into the numbers per ``bench.fetch`` span. On a trace kept by
``bench/run.py --trace 1 --keep-trace DIR``:

    python bench/program_spans.py DIR/rs1.stream-kmer.xplane.pb
"""

from __future__ import annotations

import json
import sys
from collections import defaultdict
from pathlib import Path

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402
from trace_reduce import DEVICE_PLANE, OP_LINE, WINDOW_SPAN, _clip, _union  # noqa: E402

PREFIX = "sage."
FETCH_SPAN = "bench.fetch"
# name -> (span, field): milliseconds of the span's field per fetch
PER_FETCH = {
    "gather_ms_per_fetch.stream": ("sage.store.gather", "seconds"),
    "group_upload_ms_per_fetch.stream": ("sage.store.group_upload", "seconds"),
    "io_wait_ms_per_fetch.stream": ("sage.stream.io_wait", "seconds"),
    "decode_dispatch_ms_per_fetch.stream": ("sage.read.decode", "self_seconds"),
    "format_ms_per_fetch.stream": ("sage.read.format", "seconds"),
}


def reduce(path: Path) -> dict:
    """The window's length, its fetch spans, the device's idle seconds
    (averaged over chips) and each ``sage.*`` name's span totals."""
    pd = trace_reduce.load(path)
    events: list = []  # (thread line, start, end, name) of the program's spans
    window, fetches, devices = [], [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append([(e.start_ns, e.end_ns)
                            for line in plane.lines if line.name == OP_LINE
                            for e in line.events])
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        events.append(((plane.name, i), e.start_ns, e.end_ns, e.name))
                    elif e.name == WINDOW_SPAN:
                        window.append((e.start_ns, e.end_ns))
                    elif e.name == FETCH_SPAN:
                        fetches.append((e.start_ns, e.end_ns))
    if not window:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span")
    if not devices:
        raise ValueError(f"{path}: no device plane")
    lo, hi = window[0]
    idle = []  # per chip: the window's intervals with no op running
    for ops in devices:
        edges = [lo] + [x for iv in _union(_clip(ops, lo, hi)) for x in iv] + [hi]
        idle.append([(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s])
    return {
        "window_s": (hi - lo) / 1e9,
        "fetch_s": [(e - s) / 1e9 for s, e in _clip(fetches, lo, hi)],
        "device_idle_s": sum(e - s for free in idle for s, e in free) / len(idle) / 1e9,
        "program": program(events, idle, lo, hi),
    }


def program(events: list, idle: list, lo: float, hi: float) -> dict:
    """Per span name, over the spans that overlap the window [lo, hi):
    ``count``; ``seconds``, their time clipped to the window;
    ``self_seconds``, less the time of the ``sage.*`` spans nested in them
    on the same thread line; ``idle_seconds``, the union of their intervals
    intersected with each chip's idle intervals, averaged over the chips."""
    nested = defaultdict(float)  # event index -> ns of its direct children
    lines = defaultdict(list)
    for i, ev in enumerate(events):
        lines[ev[0]].append(i)
    for idx in lines.values():
        stack: list = []  # the spans open at the current start, outermost first
        for i in sorted(idx, key=lambda i: (events[i][1], -events[i][2])):
            _, s, e, _ = events[i]
            while stack and events[stack[-1]][2] < e:
                stack.pop()
            if stack:  # one thread's children do not overlap: their sum is their union
                nested[stack[-1]] += max(0.0, min(e, hi) - max(s, lo))
            stack.append(i)
    out: dict = {}
    intervals = defaultdict(list)
    for i, (_, s, e, name) in enumerate(events):
        if e <= lo or s >= hi:
            continue
        ns = min(e, hi) - max(s, lo)
        d = out.setdefault(name, {"count": 0, "seconds": 0.0, "self_seconds": 0.0,
                                  "idle_seconds": 0.0})
        d["count"] += 1
        d["seconds"] += ns / 1e9
        d["self_seconds"] += (ns - nested[i]) / 1e9
        intervals[name].append((max(s, lo), min(e, hi)))
    for name, d in out.items():
        merged = _union(intervals[name])
        d["idle_seconds"] = sum(_overlap(merged, free) for free in idle) / len(idle) / 1e9
    return out


def _overlap(a: list, b: list) -> float:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def per_fetch(r: dict) -> dict:
    """Milliseconds per fetch of each step, and the share of the window in
    which the device idled inside the gather; None where the window holds
    no such span or no fetch."""
    fetches = len(r["fetch_s"])
    out = {}
    for name, (span, fld) in PER_FETCH.items():
        d = r["program"].get(span)
        out[name] = 1e3 * d[fld] / fetches if d and fetches else None
    gather = r["program"].get("sage.store.gather")
    out["idle_in_gather_share.stream"] = (
        100.0 * gather["idle_seconds"] / r["window_s"] if gather else None)
    return out


def main(argv: list) -> int:
    for path in argv:
        r = reduce(Path(path))
        fetch_s = r["fetch_s"]
        print(json.dumps({
            "trace": str(path), "window_s": r["window_s"], "fetches": len(fetch_s),
            "fetch_ms_mean": 1e3 * sum(fetch_s) / len(fetch_s) if fetch_s else None,
            "device_idle_share": 100.0 * r["device_idle_s"] / r["window_s"],
            "program": r["program"], "per_fetch": per_fetch(r),
        }))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
