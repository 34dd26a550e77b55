"""Benchmark orchestrator: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV: for throughput-model rows the
second column is the modeled per-Mbase preparation time (us), the third the
figure's normalized value (speedup / ratio / bytes)."""

from __future__ import annotations

import sys
import time


def main() -> None:
    from benchmarks import paper_figs, roofline
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    sections = [
        ("fig03", paper_figs.fig03_rows),
        ("fig12", paper_figs.fig12_rows),
        ("fig13", paper_figs.fig13_rows),
        ("fig14", paper_figs.fig14_rows),
        ("fig15", paper_figs.fig15_rows),
        ("fig16", paper_figs.fig16_rows),
        ("tab03", paper_figs.tab03_rows),
        ("fig17", paper_figs.fig17_rows),
        ("tab02", paper_figs.tab02_rows),
        ("decode_speed", paper_figs.decode_speed_rows),
        ("roofline", roofline.rows),
    ]
    print("name,us_per_call,derived")
    for name, fn in sections:
        t0 = time.perf_counter()
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001
            print(f"{name},ERROR,{type(e).__name__}:{e}", file=sys.stderr)
            raise
        dt_us = (time.perf_counter() - t0) * 1e6
        for rname, derived in rows:
            print(f"{rname},{dt_us/max(len(rows),1):.1f},{derived}")


if __name__ == "__main__":
    main()
