"""Reduce a profiler trace (``.xplane.pb``) to device busy time, idle gaps and
the benchmark's own host spans, all on the trace's one clock.

The benchmark wraps its measured window in a ``bench.window`` annotation and
each unit of work (a stream fetch, a serving round seen by the client) in a
span of its own. The device planes are ``/device:TPU:<n>``; every event on
their ``XLA Ops`` line is one operation running on that chip. Busy time is
the union of those intervals inside the window, averaged over the chips.
"""

from __future__ import annotations

import gzip
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OP_LINE = "XLA Ops"
TOP = 10  # entries kept in each breakdown list


@dataclass
class Reduction:
    window_s: float
    busy_s: float  # mean over chips of the union of op intervals
    chips: int
    spans: dict = field(default_factory=dict)  # name -> [(start_ns, end_ns)] in window
    device_ops: list = field(default_factory=list)  # [[op name, seconds]], longest first
    idle_gaps: list = field(default_factory=list)  # [[host activity, seconds]], longest gaps

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, found {len(found)}")
    return found[0]


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def load(path: Path):
    """The trace at ``path``: an ``.xplane.pb``, or one compressed with gzip."""
    from jax.profiler import ProfileData

    if str(path).endswith(".gz"):
        with gzip.open(path) as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(str(path))


def reduce(path: Path, span_names=()) -> Reduction:
    """Reduce the trace at ``path`` over its window span."""
    pd = load(path)
    wanted = set(span_names) | {WINDOW_SPAN}
    spans: dict = defaultdict(list)
    host_events: list = []  # (start, end, name) of every other host event
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [(e.start_ns, e.end_ns, e.name)
                   for line in plane.lines if line.name == OP_LINE for e in line.events]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        spans[e.name].append((e.start_ns, e.end_ns))
                    elif e.duration_ns > 0:
                        host_events.append((e.start_ns, e.end_ns, e.name))
    if not spans[WINDOW_SPAN]:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span")
    if not devices:
        raise ValueError(f"{path}: no device plane")
    lo, hi = spans[WINDOW_SPAN][0]
    window_ns = hi - lo
    per_op: dict = defaultdict(float)
    busy = []
    gaps: list = []
    for ops in devices:
        clipped = [(max(s, lo), min(e, hi), n) for s, e, n in ops if e > lo and s < hi]
        for s, e, n in clipped:
            per_op[n] += (e - s) / len(devices)
        merged = _union([(s, e) for s, e, _ in clipped])
        busy.append(sum(e - s for s, e in merged))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(e - s, s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    longest = sorted(gaps, reverse=True)[:TOP]
    return Reduction(
        window_s=window_ns / 1e9,
        busy_s=sum(busy) / len(devices) / 1e9,
        chips=len(devices),
        spans={k: _clip(v, lo, hi) for k, v in spans.items() if k != WINDOW_SPAN},
        device_ops=_top(per_op),
        idle_gaps=[[_host_activity(host_events, s, e), n / 1e9] for n, s, e in longest],
    )


def _host_activity(events: list, s: float, e: float) -> str:
    """The host event that covers most of the gap [s, e); among equal
    covers the shortest, so the innermost call names the gap."""
    best, best_key = "no host event", (0.0, 0.0)
    for hs, he, name in events:
        cover = min(he, e) - max(hs, s)
        if cover > 0 and (cover, hs - he) > best_key:
            best, best_key = name, (cover, hs - he)
    return best


def _top(totals: dict) -> list:
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]
    return [[name, ns / 1e9] for name, ns in ranked]
