"""Run one benchmark cell once and print its result as one JSON line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell (``bench/workloads/<cell>.json``)
names its configuration, traffic and chips; the run builds the
configuration's corpus, draws its traffic from ``--seed``, warms up every
shape that traffic uses, measures for ``--seconds``, checks the window's
answers against the reads it generated, and prints the cell's end-to-end
metrics (``--trace 0``) or per-layer metrics from a profiler trace of the
window (``--trace 1``). It needs a TPU with the cell's chips and exits
non-zero, printing no result, without one. The numbers the check compared,
each with its limit, are the last lines of standard error and the last key
of the result.

``--control`` reports the check's control instead: the same window, with its
answers put through a store that keeps two bits per base. It has to come out
as not correct; the window's own reading is logged before it.
``--keep-trace DIR`` keeps the traced window's ``.xplane.pb``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--keep-trace")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    # every program, the small eager ones too, goes to the persistent cache,
    # so only a checkout's first run of a cell compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    import harness

    manifest = harness.load_json(ROOT / "BENCHMARK.json")
    cell, _ = harness.load_cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform!r} device(s)", file=sys.stderr)
        return 2

    def log(line: str) -> None:
        print(line, file=sys.stderr, flush=True)

    result = harness.run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace), manifest=manifest,
        t_start=T_START, control=args.control, keep_trace=args.keep_trace, log=log,
    )
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
