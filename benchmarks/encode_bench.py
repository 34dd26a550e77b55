"""Encode-throughput benchmark for the SAGe_Write ingest path.

Measures, on the same synthetic dataset:

  reference  the retained sequential encoder (read-at-a-time mapping +
             per-read verify walk + per-record stream packing)
  batched    the vectorized pipeline (batched seeding/voting, vmapped
             lax.scan banded DP, columnar pack, decode-based verify),
             broken down into map / pack / verify phase throughputs

plus the two contracts the tentpole demands:

  parity     batched output is bit-identical to the reference container
             (meta, directory, every stream) at every opt_level 0..4
  lossless   the batched container decodes back to the original reads
             (sequential numpy oracle)

and the compile-once property of the DP kernel: re-encoding the same
dataset must not retrace ``align_scan`` (counts via repro.core
trace_counts). Writes ``BENCH_encode.json`` (see README). ``--smoke``
shrinks everything for CI and exits non-zero on any parity/lossless
failure or if the batched speedup falls below the CI floor.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import jax

from repro.compile_cache import enable_compile_cache
from repro.core import refdec, reset_trace_counts, trace_counts
from repro.core.encoder import SageEncoder
from repro.genomics.synth import ReadSet, make_reference, sample_read_set


def bench_encode(ref: np.ndarray, rs: ReadSet, *, token_target: int, iters: int) -> dict:
    n_bases = rs.n_bases

    # ---- sequential reference (the speedup denominator) -----------------
    # construction (minimizer index build) sits outside the timed region on
    # both paths, so the speedup compares encode() against encode() only
    enc_ref = SageEncoder(ref, token_target=token_target, batched=False)
    t0 = time.perf_counter()
    sf_ref = enc_ref.encode(rs)
    t_ref = time.perf_counter() - t0

    # ---- batched pipeline: steady state = min over iters ----------------
    enc = SageEncoder(ref, token_target=token_target)
    reset_trace_counts()
    sf_bat = enc.encode(rs)  # warmup compiles the DP + decode-verify buckets
    warm = trace_counts()
    best, best_stats = float("inf"), dict(enc.stats)
    for _ in range(iters):
        t0 = time.perf_counter()
        sf_bat = enc.encode(rs)
        dt = time.perf_counter() - t0
        if dt < best:
            best, best_stats = dt, dict(enc.stats)
    steady = trace_counts()

    oracle = sorted(bytes(d.seq) for d in refdec.decode_all(sf_bat))
    lossless = oracle == sorted(bytes(np.asarray(r, np.uint8)) for r in rs.reads)
    diffs = sf_ref.diff(sf_bat)
    t_other = best - sum(best_stats.get(k, 0.0) for k in ("t_map", "t_pack", "t_verify"))
    return {
        "n_reads": rs.n_reads,
        "encoded_bases": n_bases,
        "n_blocks": sf_bat.meta.n_blocks,
        "reference": {"seconds": t_ref, "bases_per_s": n_bases / t_ref},
        "batched": {
            "seconds": best,
            "bases_per_s": n_bases / best,
            "phases": {
                "map": {"seconds": best_stats["t_map"], "bases_per_s": n_bases / max(best_stats["t_map"], 1e-9)},
                "pack": {"seconds": best_stats["t_pack"], "bases_per_s": n_bases / max(best_stats["t_pack"], 1e-9)},
                "verify": {"seconds": best_stats["t_verify"], "bases_per_s": n_bases / max(best_stats["t_verify"], 1e-9)},
                "other_seconds": t_other,
            },
            "n_batch_mapped": best_stats.get("n_batch_mapped", 0),
            "n_fallback": best_stats.get("n_fallback", 0),
            "n_escaped": best_stats.get("n_escaped", 0),
            "verify_rounds": best_stats.get("verify_rounds", 0),
        },
        "speedup_vs_reference": t_ref / best,
        "compiles": {
            "warmup": dict(warm),
            "steady_state": {k: steady.get(k, 0) - warm.get(k, 0) for k in steady},
            "align_scan_steady_state": steady.get("align_scan", 0) - warm.get("align_scan", 0),
        },
        "bit_identical_to_reference": not diffs,
        "diffs": diffs,
        "lossless_on_decode": lossless,
    }


def check_opt_level_parity(ref: np.ndarray, rs: ReadSet, token_target: int) -> dict:
    """Bit-identity batched vs reference at every Fig.17 ablation level."""
    out = {}
    for opt in range(5):
        sf_r = SageEncoder(ref, token_target=token_target, batched=False).encode(rs, opt_level=opt)
        sf_b = SageEncoder(ref, token_target=token_target).encode(rs, opt_level=opt)
        d = sf_r.diff(sf_b)
        out[f"opt{opt}"] = {"bit_identical": not d, "diffs": d}
    out["all_identical"] = all(v["bit_identical"] for k, v in out.items() if k.startswith("opt"))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="tiny dataset, CI mode")
    ap.add_argument("--out", default="BENCH_encode.json")
    ap.add_argument("--ref-len", type=int, default=None)
    ap.add_argument("--depth", type=float, default=None)
    ap.add_argument("--iters", type=int, default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    ref_len = args.ref_len or (12_000 if args.smoke else 120_000)
    depth = args.depth or (2 if args.smoke else 4)
    iters = args.iters or (1 if args.smoke else 3)
    token_target = 2048 if args.smoke else 8192

    ref = make_reference(ref_len, seed=7)
    rs = sample_read_set(ref, "illumina", depth=depth, seed=8)
    # corner coverage for the parity sweep: N-containing + junk reads ride
    # along so escapes and fallbacks are exercised at every opt level
    rng = np.random.default_rng(9)
    reads = list(rs.reads)
    for i in range(0, len(reads), 13):
        reads[i] = reads[i].copy()
        reads[i][3] = 4
    for _ in range(6):
        reads.append(rng.integers(0, 5, 150).astype(np.uint8))
    rs_mixed = ReadSet(
        reads=reads, quals=[np.full(r.size, 60, np.uint8) for r in reads],
        kind="short", profile="illumina",
    )
    if args.smoke:
        parity_rs = rs_mixed
    else:  # a slice (plus the junk tail) keeps the 5x2 parity sweep fast
        p_reads = reads[: max(200, len(reads) // 6)] + reads[-6:]
        parity_rs = ReadSet(
            reads=p_reads, quals=[np.full(r.size, 60, np.uint8) for r in p_reads],
            kind="short", profile="illumina",
        )

    report = {
        "config": {
            "smoke": args.smoke, "ref_len": ref_len, "depth": depth,
            "iters": iters, "token_target": token_target,
            "backend": jax.default_backend(),
        },
        "encode": bench_encode(ref, rs, token_target=token_target, iters=iters),
        "opt_level_parity": check_opt_level_parity(ref, parity_rs, token_target),
    }

    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")

    e = report["encode"]
    par = report["opt_level_parity"]
    print(
        f"encode {e['batched']['bases_per_s']:.3g} bases/s batched vs "
        f"{e['reference']['bases_per_s']:.3g} reference = {e['speedup_vs_reference']:.1f}x | "
        f"map {e['batched']['phases']['map']['bases_per_s']:.3g} / "
        f"pack {e['batched']['phases']['pack']['bases_per_s']:.3g} / "
        f"verify {e['batched']['phases']['verify']['bases_per_s']:.3g} bases/s | "
        f"align_scan retraces steady-state: {e['compiles']['align_scan_steady_state']} | "
        f"bit-identical={e['bit_identical_to_reference']} "
        f"opt0-4={par['all_identical']} lossless={e['lossless_on_decode']} -> {args.out}"
    )
    min_speedup = 2.0 if args.smoke else 10.0  # CI floor is loose: tiny smoke sets amortize poorly
    ok = (
        e["bit_identical_to_reference"]
        and e["lossless_on_decode"]
        and par["all_identical"]
        and e["compiles"]["align_scan_steady_state"] == 0
        and e["speedup_vs_reference"] >= min_speedup
    )
    if not ok:
        print("FAIL: encode parity/lossless/speedup contract violated", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
