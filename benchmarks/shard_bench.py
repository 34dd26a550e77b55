"""Multi-device decode-throughput benchmark for the sharded SAGe hot path.

Measures, per device count (1/2/4/8 by default), the steady-state full-file
SAGe_Read decode throughput with block-sharded residency + shard_map decode,
the compile counts (warmup vs steady state — the zero-retrace contract must
hold per (per-shard bucket, shard count)), and bit-identity of every format
(``2bit``/``onehot``/``kmer``) x decode path (vmap / Pallas-interpret)
against the single-device reference. Also drives the token pipeline's
host-sync-free fetch path and asserts the transfer contract: one host
transfer per *batch*, never per fetch.

Runs on CPU-only containers by widening the device pool before jax
initializes (``--force-devices`` defaults to 8):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      python benchmarks/shard_bench.py            # or let the script set it

Writes ``BENCH_shard.json`` (see README "Reading BENCH_shard.json").
``--smoke`` shrinks the dataset for CI and exits non-zero on any
bit-identity / retrace / transfer-contract violation.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _force_host_devices(n: int) -> None:
    """Widen the CPU device pool; must run before jax initializes."""
    if "jax" in sys.modules:  # pragma: no cover - defensive
        raise RuntimeError("set device count before importing jax")
    flag = f"--xla_force_host_platform_device_count={n}"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {flag}".strip()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="tiny dataset, CI mode")
    ap.add_argument("--out", default="BENCH_shard.json")
    ap.add_argument("--ref-len", type=int, default=None)
    ap.add_argument("--depth", type=float, default=None)
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--force-devices", type=int, default=8,
                    help="force this many host devices on CPU (0 = don't)")
    ap.add_argument("--shards", type=int, nargs="*", default=None,
                    help="device counts to sweep (default 1 2 4 8)")
    args = ap.parse_args(argv)

    if args.force_devices:
        _force_host_devices(args.force_devices)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    import jax
    import numpy as np

    from repro.core import SageStore, get_format, reset_trace_counts, trace_counts
    from repro.core.format import D
    from repro.data.pipeline import SageTokenPipeline
    from repro.genomics.synth import make_reference, sample_read_set

    ndev = len(jax.devices())
    counts = [s for s in (args.shards or (1, 2, 4, 8)) if s <= ndev]

    ref_len = args.ref_len or (12_000 if args.smoke else 120_000)
    depth = args.depth or (2 if args.smoke else 4)
    iters = args.iters or (1 if args.smoke else 3)
    token_target = 2048 if args.smoke else 8192

    ref = make_reference(ref_len, seed=7)
    rs = sample_read_set(ref, "illumina", depth=depth, seed=8)
    base = SageStore(max_prepared=2)
    sf = base.write("bench", rs, ref, token_target=token_target)
    nb = sf.meta.n_blocks
    total_bases = int(np.sum(np.asarray(sf.directory[:, D["n_tokens"]])))

    def timed(fn, n):
        best, out = float("inf"), None
        for _ in range(n):
            t0 = time.perf_counter()
            out = fn()
            for leaf in jax.tree.leaves(out):
                if hasattr(leaf, "block_until_ready"):
                    leaf.block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best, out

    # single-device reference outputs, per format (the bit-identity oracle)
    ref_sess = base.session()
    ref_outs = {f: ref_sess.read("bench", fmt=f, kmer_k=4)
                for f in ("2bit", "onehot", "kmer")}

    ok = True
    shards_report = {}
    for s in counts:
        store = SageStore(max_prepared=2, shards=s if s > 1 else None)
        store.register("bench", sf)
        sess = store.session()
        reset_trace_counts()
        sess.read("bench")  # warmup: shard residency upload + bucket compile
        warm = trace_counts()
        t_dec, _ = timed(lambda: sess.read("bench"), iters)
        steady = {k: trace_counts().get(k, 0) - warm.get(k, 0) for k in trace_counts()}
        retraces = sum(v for k, v in steady.items() if k.startswith(("decode", "gather")))

        # bit-identity: every format x both decode paths vs single-device ref
        identical = True
        for use_pallas in (False, True):
            ps = store.session(use_pallas=use_pallas)
            for f, ref_out in ref_outs.items():
                out = ps.read("bench", fmt=f, kmer_k=4)
                for key in ("tokens", "n_reads", "n_tokens", "read_start",
                            "read_len", "read_pos", get_format(f).out_key):
                    if not np.array_equal(np.asarray(out[key]), np.asarray(ref_out[key])):
                        identical = False
        ok &= identical and retraces == 0
        shards_report[str(s)] = {
            "devices": s,
            "decode": {
                "seconds": t_dec,
                "bases_per_s": total_bases / t_dec,
                "blocks_per_s": nb / t_dec,
            },
            "compiles_warmup": dict(warm),
            "steady_state_retraces": retraces,
            "bit_identical_to_single_device": identical,
        }
        store.evict()

    base1 = shards_report[str(counts[0])]["decode"]["bases_per_s"]
    for rep in shards_report.values():
        rep["decode"]["speedup_vs_1dev"] = rep["decode"]["bases_per_s"] / base1

    # pipeline transfer contract: one host transfer per batch, none per fetch.
    # seq_len is sized so one batch spans ~3 single-block fetches, making
    # "fetches > transfers" the observable difference from the old per-fetch
    # np.asarray path
    kpb_max = int(np.max(np.asarray(sf.directory[:, D["n_tokens"]])) // 4)
    pipe = SageTokenPipeline(sf, vocab_size=256, batch=2,
                             seq_len=max(16, (3 * kpb_max) // 2),
                             blocks_per_fetch=1,
                             shards=counts[-1] if counts[-1] > 1 else None)
    it = pipe.batches()
    n_batches = 3
    for _ in range(n_batches):
        next(it)
    per_fetch_sync_gone = (
        pipe.transfer_stats["host_transfers"] == n_batches
        and pipe.transfer_stats["fetches"] > n_batches
    )
    ok &= per_fetch_sync_gone

    report = {
        "config": {
            "smoke": args.smoke, "ref_len": ref_len, "depth": depth,
            "iters": iters, "token_target": token_target, "n_blocks": nb,
            "n_reads": sf.meta.n_reads, "decoded_bases": total_bases,
            "backend": jax.default_backend(), "visible_devices": ndev,
            "forced_host_devices": bool(args.force_devices),
        },
        "shards": shards_report,
        "pipeline_async": {
            "shards": counts[-1],
            "batches": n_batches,
            "fetches": pipe.transfer_stats["fetches"],
            "host_transfers": pipe.transfer_stats["host_transfers"],
            "per_fetch_host_sync_gone": per_fetch_sync_gone,
        },
    }

    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")

    line = " | ".join(
        f"{s}dev {rep['decode']['bases_per_s']:.3g} b/s "
        f"(x{rep['decode']['speedup_vs_1dev']:.2f}, retrace={rep['steady_state_retraces']}, "
        f"ident={rep['bit_identical_to_single_device']})"
        for s, rep in shards_report.items()
    )
    print(f"{line} | pipeline transfers {pipe.transfer_stats['host_transfers']}"
          f"/{n_batches} batches over {pipe.transfer_stats['fetches']} fetches"
          f" -> {args.out}")
    if not ok:
        print("FAIL: sharded decode mismatch, steady-state retrace, or "
              "per-fetch host sync detected", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
