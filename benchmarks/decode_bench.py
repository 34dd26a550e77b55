"""Decode-throughput benchmark for the SAGe_Read serving hot path.

Measures, for the vmap and Pallas(interpret) decode paths:

  prepare  host-side packing of a SageFile into block-major arrays (bases/s)
  upload   one-time ``jax.device_put`` of the prepared arrays (bytes/s)
  decode   steady-state full decode throughput (bases/s, blocks/s)
  format   steady-state k-mer formatting on decoded tokens (bases/s)

plus the compile-once contract on a mixed block-range workload: N ranged
reads of varying lengths must compile the decoder at most once per
power-of-two shape bucket (never once per distinct range length), and the
bucketed session read must be bit-identical to the unbucketed vmap
reference and lossless against the sequential numpy oracle.

Writes ``BENCH_decode.json`` (see README "Reading BENCH_decode.json").
``--smoke`` shrinks the dataset and iteration counts for CI and exits
non-zero on any oracle/bit-identity mismatch.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import jax

from repro.compile_cache import enable_compile_cache
from repro.core import SageStore, reset_trace_counts, trace_counts
from repro.core import refdec
from repro.core.decode_jax import (
    bucket_size,
    decode_file_jax,
    prepare_device_blocks,
)
from repro.core.format import D
from repro.genomics.synth import make_reference, sample_read_set


def _block_until_ready(tree) -> None:
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()


def _timed(fn, iters: int) -> tuple[float, object]:
    """Min-of-iters wall time of ``fn()`` (result fully materialized)."""
    best, out = float("inf"), None
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn()
        _block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best, out


def _reads_from_decode(out: dict) -> list[bytes]:
    toks = np.asarray(out["tokens"])
    n_reads = np.asarray(out["n_reads"])
    starts = np.asarray(out["read_start"])
    lens = np.asarray(out["read_len"])
    got = []
    for bi in range(toks.shape[0]):
        for r in range(int(n_reads[bi])):
            s, ln = int(starts[bi][r]), int(lens[bi][r])
            got.append(bytes(toks[bi][s : s + ln].astype(np.uint8)))
    return got


def bench_path(store: SageStore, name: str, *, use_pallas: bool, iters: int) -> dict:
    sess = store.session(use_pallas=use_pallas)
    sf = store.file(name)
    nb = sf.meta.n_blocks
    total_bases = int(np.sum(np.asarray(sf.directory[:, D["n_tokens"]])))

    # prepare (host) — measured on the raw API so upload is excluded
    t_prep, db_host = _timed(lambda: prepare_device_blocks(sf), max(1, iters // 2))
    # upload — one device_put of everything prepare produced
    nbytes = int(sum(np.asarray(v).nbytes for v in db_host.arrays.values()))
    t_up, _ = _timed(lambda: jax.device_put(dict(db_host.arrays)), max(1, iters // 2))

    # decode — steady state full-file session read (first call compiles)
    store.evict(name)
    reset_trace_counts()
    sess.read(name)  # warmup: prepare+upload once, compile the bucket
    warm_counts = trace_counts()
    t_dec, out = _timed(lambda: sess.read(name), iters)
    steady_counts = trace_counts()

    # format — full decode+format read (format-only cost = this minus decode)
    t_fmt_total, _ = _timed(lambda: sess.read(name, fmt="kmer", kmer_k=4), iters)

    return {
        "n_blocks": nb,
        "decoded_bases": total_bases,
        "prepare": {"seconds": t_prep, "bases_per_s": total_bases / t_prep},
        "upload": {"seconds": t_up, "bytes": nbytes, "bytes_per_s": nbytes / t_up},
        "decode": {
            "seconds": t_dec,
            "bases_per_s": total_bases / t_dec,
            "blocks_per_s": nb / t_dec,
            "compiles_warmup": dict(warm_counts),
            "compiles_steady_state": {
                k: steady_counts.get(k, 0) - warm_counts.get(k, 0) for k in steady_counts
            },
        },
        "format_kmer": {
            "seconds": t_fmt_total,
            "bases_per_s": total_bases / t_fmt_total,
        },
    }


def bench_mixed_ranges(store: SageStore, name: str, n_requests: int = 20) -> dict:
    """The acceptance workload: ranged reads of varying lengths must compile
    the decoder at most once per distinct bucket.

    Callers must point this at a dataset whose decoder shapes no other bench
    section has touched (jax's jit cache cannot be reset, so a shared
    dataset would pre-warm buckets and undercount compiles)."""
    nb = store.n_blocks(name)
    rng = np.random.default_rng(0)
    # sweep of distinct lengths (1..L) plus repeats, served in random order —
    # the worst case for a compile-per-length decoder
    L = max(min(nb - 1, 32), 1)
    lengths = [1 + (i % L) for i in range(n_requests)]
    rng.shuffle(lengths)
    store.evict(name)
    sess = store.session()
    reset_trace_counts()
    for ln in lengths:
        lo = int(rng.integers(0, nb - ln + 1))
        sess.read(name, (lo, lo + ln))
    counts = trace_counts()
    distinct_lengths = len(set(lengths))
    distinct_buckets = len({bucket_size(ln) for ln in lengths})
    compiles = counts.get("decode_vmap", 0)
    return {
        "n_requests": n_requests,
        "range_lengths": lengths,
        "distinct_lengths": distinct_lengths,
        "distinct_buckets": distinct_buckets,
        "decoder_compiles": compiles,
        "gather_compiles": counts.get("gather", 0),
        "compile_once_per_bucket": compiles <= distinct_buckets,
        "compile_savings_vs_per_length": distinct_lengths / max(compiles, 1),
    }


def check_correctness(store: SageStore, name: str) -> dict:
    """Bucketed session read vs unbucketed vmap reference (bit-identical) and
    vs the sequential numpy oracle (lossless)."""
    sf = store.file(name)
    ref = decode_file_jax(prepare_device_blocks(sf))
    sess = store.session()
    nb = sf.meta.n_blocks
    out = sess.read(name)
    bit_identical = True
    for key in ("tokens", "n_tokens", "read_pos", "read_rev", "read_start",
                "read_len", "read_corner", "n_reads"):
        if not np.array_equal(np.asarray(out[key]), np.asarray(ref[key])):
            bit_identical = False
    # ranged (bucket-padded) reads against the whole-file slice
    lo, hi = 1, min(4, nb)
    part = sess.read(name, (lo, hi))
    for key in ("tokens", "n_reads", "read_start", "read_len"):
        if not np.array_equal(np.asarray(part[key]), np.asarray(ref[key])[lo:hi]):
            bit_identical = False
    oracle = sorted(bytes(d.seq) for d in refdec.decode_all(sf))
    got = sorted(_reads_from_decode(out))
    return {"bit_identical_to_unbucketed": bit_identical, "oracle_lossless": got == oracle}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="tiny dataset, CI mode")
    ap.add_argument("--out", default="BENCH_decode.json")
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
    store = SageStore(max_prepared=2)
    sf = store.write("bench", rs, ref, token_target=token_target)
    # separate dataset (different token_target -> different decoder shapes)
    # for the compile-count workload: its jit cache entries start cold even
    # though the throughput sections above already compiled theirs
    store.write("bench_mixed", rs, ref, token_target=token_target // 2)

    report = {
        "config": {
            "smoke": args.smoke, "ref_len": ref_len, "depth": depth,
            "iters": iters, "token_target": token_target,
            "n_blocks": sf.meta.n_blocks, "n_reads": sf.meta.n_reads,
            "backend": jax.default_backend(),
        },
        "paths": {
            "vmap": bench_path(store, "bench", use_pallas=False, iters=iters),
            "pallas_interpret": bench_path(store, "bench", use_pallas=True, iters=iters),
        },
        "mixed_range_workload": bench_mixed_ranges(
            store, "bench_mixed", n_requests=20 if args.smoke else 40
        ),
        "correctness": check_correctness(store, "bench"),
    }

    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")

    mixed = report["mixed_range_workload"]
    corr = report["correctness"]
    dec = report["paths"]["vmap"]["decode"]
    print(
        f"decode {dec['bases_per_s']:.3g} bases/s, {dec['blocks_per_s']:.3g} blocks/s | "
        f"mixed ranges: {mixed['decoder_compiles']} compiles for "
        f"{mixed['distinct_lengths']} lengths ({mixed['distinct_buckets']} buckets) | "
        f"bit-identical={corr['bit_identical_to_unbucketed']} "
        f"oracle={corr['oracle_lossless']} -> {args.out}"
    )
    ok = (
        corr["bit_identical_to_unbucketed"]
        and corr["oracle_lossless"]
        and mixed["compile_once_per_bucket"]
    )
    if not ok:
        print("FAIL: decode mismatch or compile-once contract violated", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
