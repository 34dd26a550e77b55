"""Container-I/O benchmark: v1 monolithic archive vs v2 block-extent layout.

The v1 container (``np.savez_compressed``) must decompress the WHOLE dataset
to serve any ranged read; the v2 block-extent container (DESIGN.md §7)
opens header-only and serves a k-block range with O(k) coalesced extent
reads. This benchmark quantifies that on a large synthetic dataset:

  open            time + bytes to open each container (v1 = full load)
  ranged_read     cold end-to-end ``session.read`` of k blocks: wall time,
                  disk bytes, and read amplification (bytes read / payload
                  requested) for both layouts
  first_batch     time-to-first-batch of a cold ``SageTokenPipeline`` on a
                  path-registered store, v1 vs v2, plus the v2 pipeline's
                  ``io_stats`` (bounded host cache, no whole-file load)

Scale comes from block tiling: one encoded read set is replicated block-wise
(stream offsets shifted per tile) until the extent payload reaches
``--target-gb``, so a multi-GB container builds in seconds instead of the
hours a real multi-GB encode would take — the on-disk layout and access
pattern are identical to a natively encoded container of that size.

The ``compression`` section (DESIGN.md §11) reports the codec container's
economics: stored vs decoded payload bytes, dedup, and file-size ratios
against both the v1 archive and the raw v2 layout.

Writes ``BENCH_io.json`` (see README "Reading BENCH_io.json"). ``--smoke``
shrinks everything for CI and exits non-zero if ranged decode is not
bit-identical across all three container formats (v1, raw v2, codec v2;
all output formats, both decode paths), the O(k) *compressed* bytes-read
contract is violated, or the codec container exceeds 4x the v1 archive.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

import numpy as np

import jax

from repro.compile_cache import enable_compile_cache
from repro.core import SageStore
from repro.core.format import D, STREAMS, SageFile
from repro.core.layout import SageContainerV2, write_v2
from repro.data.pipeline import SageTokenPipeline
from repro.genomics.synth import make_reference, sample_read_set


def tile_sage_file(sf: SageFile, times: int) -> SageFile:
    """Replicate a container block-wise ``times`` x: streams are tiled and
    each tile's directory offsets shift by the (word-aligned) stream length,
    so every tiled block decodes exactly like its source block. Consensus is
    shared across tiles (reads re-map the same reference), matching how
    depth scales in a real dataset."""
    if times <= 1:
        return sf
    streams = {s: np.tile(sf.streams[s], times) for s in STREAMS}
    tiles = []
    for t in range(times):
        d = sf.directory.copy()
        for s in STREAMS:
            d[:, D[f"off_{s}"]] += t * int(sf.streams[s].size) * 32
        tiles.append(d)
    bits = dict(sf.meta.stream_bits)
    bits.update({s: int(sf.streams[s].size) * 32 * times for s in STREAMS})
    meta = dataclasses.replace(
        sf.meta,
        n_blocks=sf.meta.n_blocks * times,
        n_reads=sf.meta.n_reads * times,
        n_segments=sf.meta.n_segments * times,
        stream_bits=bits,
    )
    return SageFile(meta=meta, consensus2b=sf.consensus2b,
                    directory=np.concatenate(tiles), streams=streams)


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return time.perf_counter() - t0, out


def bench_open(v1_path: str, v2_path: str) -> dict:
    t1, _ = _timed(lambda: SageFile.load(v1_path))
    t2, c = _timed(lambda: SageContainerV2.open(v2_path))
    return {
        "v1": {"seconds": t1, "bytes_read": os.path.getsize(v1_path)},
        "v2": {"seconds": t2, "bytes_read": c.io_stats["header_bytes"]},
        "open_speedup": t1 / max(t2, 1e-9),
    }


def bench_ranged_read(v1_path: str, v2_path: str, k: int, group_blocks: int) -> dict:
    """Cold store -> session.read of k blocks, end to end, per layout.

    Bytes are split into the one-time open cost (v1: decompress the whole
    archive into host RAM; v2: the header) and the per-read cost (v1: zero
    more disk bytes but the whole dataset is already host-resident; v2: the
    covering groups' coalesced extents). ``read_amplification`` is the
    per-read host-materialized bytes over the k requested payloads — the
    number that decides whether out-of-RAM datasets are servable at all."""
    out = {}
    for ver, path in (("v1", v1_path), ("v2", v2_path)):
        store = SageStore(group_blocks=group_blocks)
        store.register("ds", path)
        sess = store.session()
        t, _ = _timed(lambda: jax.block_until_ready(sess.read("ds", (0, k))["tokens"]))
        io = store.io_stats
        if ver == "v1":
            sf = store.file("ds")
            open_bytes = io["container_bytes_loaded"]  # compressed disk bytes
            per_read = sf.compressed_bytes()  # the decompressed resident set
        else:
            open_bytes = io["header_bytes"]
            per_read = io["extent_bytes_read"]
        out[ver] = {
            "seconds_cold": t,
            "open_bytes_read": int(open_bytes),
            "per_read_bytes": int(per_read),
            "extent_reads": io["extent_reads"],
        }
    c = SageContainerV2.open(v2_path)
    # amplification baseline: the k blocks' DECODED payload — what the
    # consumer asked for. With codec extents v2 reads fewer disk bytes than
    # that (amplification < 1), which is the compression win in I/O terms.
    ideal = k * int(c.layout.payload_nbytes)
    for ver in ("v1", "v2"):
        out[ver]["read_amplification"] = out[ver]["per_read_bytes"] / ideal
    out["v2"]["stored_bytes_requested"] = int(c.extents[:k, 1].sum())
    out["blocks_requested"] = k
    out["ideal_payload_bytes"] = ideal
    out["cold_read_speedup"] = out["v1"]["seconds_cold"] / max(out["v2"]["seconds_cold"], 1e-9)
    out["amplification_v1_over_v2"] = (
        out["v1"]["read_amplification"] / max(out["v2"]["read_amplification"], 1e-9)
    )
    return out


def bench_first_batch(v1_path: str, v2_path: str, group_blocks: int, cache_budget: int) -> dict:
    out = {}
    for ver, path in (("v1", v1_path), ("v2", v2_path)):
        store = SageStore(group_blocks=group_blocks, cache_budget=cache_budget)
        store.register("train", path)
        t, _ = _timed(lambda: next(iter(
            SageTokenPipeline("train", 259, 4, 128, store=store).batches()
        )))
        io = store.io_stats
        out[ver] = {"seconds": t, "io_stats": {k: int(v) for k, v in io.items()}}
    out["first_batch_speedup"] = out["v1"]["seconds"] / max(out["v2"]["seconds"], 1e-9)
    return out


def bench_streaming(
    v2_path: str, group_blocks: int, cache_budget: int,
    n_fetches: int, blocks_per_fetch: int,
) -> dict:
    """Steady-state streaming decode: pipelined (background I/O + fused
    decode, ``mode="pipelined"``) vs the sequential-per-fetch baseline
    (``mode="sync"``: fetch, decode, block, repeat). Both run on a cold
    store over the SAME codec v2 container, so the pipelined column's win
    is pure overlap + fusion, not caching.

    Reported per mode: TTFB (first batch materialized), steady-state
    throughput (bases/s and decoded-payload bytes/s, excluding the first
    batch), and for the pipelined run its per-stage stats. The roofline
    bound is computed from the measured stage times (``streaming_roofline``)
    — a perfectly overlapped pipeline runs at the slowest stage's speed."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from roofline import streaming_roofline

    from repro.core.decode_jax import TRACE_COUNTS

    def run(mode: str):
        store = SageStore(group_blocks=group_blocks, cache_budget=cache_budget)
        store.register("ds", v2_path)
        sess = store.session(fused=(mode == "pipelined"))
        stream = sess.read_stream(
            "ds", fmt="2bit", blocks_per_fetch=blocks_per_fetch,
            max_fetches=n_fetches, mode=mode,
        )
        ntok = np.asarray(store.directory("ds")[:, D["n_tokens"]], dtype=np.int64)
        payload_per_block = store.block_nbytes("ds")
        batches, times = [], []
        traces_after_first = None
        t0 = time.perf_counter()
        for sb in stream:
            jax.block_until_ready(sb.data["tokens"])
            times.append(time.perf_counter() - t0)
            batches.append(sb)
            if traces_after_first is None:
                traces_after_first = sum(TRACE_COUNTS.values())
        out = {
            "ttfb_seconds": times[0],
            "total_seconds": times[-1],
            "fetches": len(batches),
        }
        if len(times) >= 2:
            ids = np.concatenate([np.asarray(b.block_ids) for b in batches[1:]])
            dt = times[-1] - times[0]
            out["steady_seconds"] = dt
            out["steady_bases_per_s"] = float(ntok[ids].sum()) / max(dt, 1e-9)
            out["steady_bytes_per_s"] = ids.size * payload_per_block / max(dt, 1e-9)
        if mode == "pipelined":
            out["stream_stats"] = {
                k: round(v, 6) if isinstance(v, float) else v
                for k, v in stream.stats.to_dict().items()
            }
            # all fetches share one shape bucket, so every compile lands at
            # or before batch 0's delivery — steady state must not retrace
            out["steady_retraces"] = sum(TRACE_COUNTS.values()) - traces_after_first
        return out, batches

    # warm the jit caches for BOTH decode paths on a throwaway store so
    # TTFB measures the data path, not first-trace compile time
    warm = SageStore(group_blocks=group_blocks, cache_budget=cache_budget)
    warm.register("ds", v2_path)
    span = (0, blocks_per_fetch)
    jax.block_until_ready(warm.session().read("ds", span)["tokens"])
    jax.block_until_ready(warm.session(fused=True).read("ds", span)["tokens"])
    del warm

    seq, seq_batches = run("sync")
    pipe, pipe_batches = run("pipelined")

    identical = len(seq_batches) == len(pipe_batches)
    for a, b in zip(seq_batches[:4], pipe_batches[:4]):  # bound host bytes
        for key in ("tokens", "n_reads", "n_tokens", "read_start"):
            if not np.array_equal(np.asarray(a.data[key]), np.asarray(b.data[key])):
                identical = False
    s = pipe["stream_stats"]
    store = SageStore(group_blocks=group_blocks)
    store.register("ds", v2_path)
    payload_bytes = pipe["fetches"] * blocks_per_fetch * store.block_nbytes("ds")
    decode_s = s["dispatch_seconds"] + s["consume_seconds"]
    components = {
        "disk": payload_bytes / s["io_seconds"] if s["io_seconds"] > 0 else 0.0,
        "upload": payload_bytes / s["upload_seconds"] if s["upload_seconds"] > 0 else 0.0,
        "decode": payload_bytes / decode_s if decode_s > 0 else 0.0,
    }
    achieved = pipe.get("steady_bytes_per_s", payload_bytes / pipe["total_seconds"])
    # the DERIVED overlap target (not hand-picked): perfect overlap runs the
    # pipeline at its slowest stage, so the achievable speedup over the
    # sequential baseline is bounded by sum(stage)/max(stage) on THIS
    # machine. On a single-core host every stage shares the one CPU and the
    # bound collapses toward 1.0 — the roofline, not a fixed ratio, is what
    # the pipeline is judged against.
    stage_seconds = {"disk": s["io_seconds"], "upload": s["upload_seconds"],
                     "decode": decode_s}
    stage_total = sum(stage_seconds.values())
    # 1 - wall / sum(stage seconds): 0 for a fully serial pipeline
    overlap = 1.0 - s["wall_seconds"] / stage_total if stage_total > 0 else 0.0
    out = {
        "sequential": seq,
        "pipelined": pipe,
        "bit_identical": identical,
        "speedup_vs_sequential": (
            pipe.get("steady_bytes_per_s", 0.0)
            / max(seq.get("steady_bytes_per_s", 1e-9), 1e-9)
        ),
        "ttfb_ratio": pipe["ttfb_seconds"] / max(seq["ttfb_seconds"], 1e-9),
        "overlap_fraction": overlap,
        "overlap_bound_speedup": stage_total / max(max(stage_seconds.values()), 1e-9),
        "host_cpus": os.cpu_count(),
        "roofline": streaming_roofline(components, achieved),
    }
    # gates: bit identity; the stages demonstrably overlapped; first-batch
    # latency did not regress (10% + 50ms timer-noise allowance)
    out["streaming_ok"] = (
        identical
        and overlap > 0
        and pipe["ttfb_seconds"] <= 1.10 * seq["ttfb_seconds"] + 0.05
    )
    return out


def check_identity(
    v1_path: str, v2_path: str, v2_raw_path: str, group_blocks: int, nb: int
) -> dict:
    """Ranged decode of all three container formats (v1, raw v2, codec v2)
    against each other, all output formats x both decode paths. The vmap
    path checks a group-boundary-spanning prefix; the Pallas(interpret)
    path checks a small window across the same boundary (interpret-mode
    decode is minutes/block at full token caps)."""
    s1 = SageStore()
    s1.register("ds", v1_path)
    s2 = SageStore(group_blocks=group_blocks)
    s2.register("ds", v2_path)
    s2r = SageStore(group_blocks=group_blocks)
    s2r.register("ds", v2_raw_path)
    spans = {
        False: (0, min(group_blocks + 2, nb)),
        True: (max(0, min(group_blocks - 2, nb - 2)), min(group_blocks + 2, nb)),
    }
    ok = True
    for use_pallas, (lo, hi) in spans.items():
        a = s1.session(use_pallas=use_pallas)
        others = [
            s2.session(use_pallas=use_pallas),
            s2r.session(use_pallas=use_pallas),
        ]
        for fmt in ("2bit", "onehot", "kmer"):
            x = a.read("ds", (lo, hi), fmt=fmt, kmer_k=4)
            for b in others:
                y = b.read("ds", (lo, hi), fmt=fmt, kmer_k=4)
                for key in ("tokens", "n_reads", "read_start", "read_len",
                            "read_pos",
                            "onehot" if fmt == "onehot" else "tokens",
                            "kmer" if fmt == "kmer" else "tokens"):
                    if not np.array_equal(np.asarray(x[key]), np.asarray(y[key])):
                        ok = False
    return {"v2_bit_identical_to_v1": ok, "spans_checked": list(spans.values())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="tiny dataset, CI mode")
    ap.add_argument("--out", default="BENCH_io.json")
    ap.add_argument("--target-gb", type=float, default=2.0,
                    help="extent payload target for the tiled dataset")
    ap.add_argument("--workdir", default=None, help="container scratch dir")
    ap.add_argument("--k", type=int, default=4, help="ranged-read block count")
    args = ap.parse_args(argv)
    enable_compile_cache()

    ref_len = 12_000 if args.smoke else 120_000
    depth = 2 if args.smoke else 6
    token_target = 2048 if args.smoke else 65536
    group_blocks = 4 if args.smoke else 32

    ref = make_reference(ref_len, seed=7)
    rs = sample_read_set(ref, "illumina", depth=depth, seed=8)
    store = SageStore()
    base = store.write("base", rs, ref, token_target=token_target)

    workdir = args.workdir or tempfile.mkdtemp(prefix="sage_io_bench_")
    os.makedirs(workdir, exist_ok=True)
    v2_path = os.path.join(workdir, "ds.sage2")
    v2_raw_path = os.path.join(workdir, "ds_raw.sage2")
    v1_path = os.path.join(workdir, "ds.sage.npz")

    # size the tile factor off the DECODED per-block payload (the codec
    # compresses extents, so stored stride no longer tracks dataset scale)
    probe = write_v2(base, v2_path)
    times = 1 if args.smoke else max(
        1, int(args.target_gb * 1e9 / (probe["payload_nbytes"] * base.meta.n_blocks))
    )
    sf = tile_sage_file(base, times)
    t_w2, w2 = _timed(lambda: write_v2(sf, v2_path))
    t_w2r, w2r = _timed(lambda: write_v2(sf, v2_raw_path, codec=False))
    t_w1, _ = _timed(lambda: sf.save(v1_path))

    cache_budget = max(64 * probe["payload_nbytes"], 8 << 20)
    report = {
        "config": {
            "smoke": args.smoke, "ref_len": ref_len, "depth": depth,
            "token_target": token_target, "tile_times": times,
            "n_blocks": sf.meta.n_blocks, "group_blocks": group_blocks,
            "cache_budget": cache_budget, "backend": jax.default_backend(),
        },
        "containers": {
            # NOTE: block tiling repeats the same streams, so zlib compresses
            # the v1 archive far beyond any real dataset's ratio — compare
            # disk *traffic* via the decompressed/materialized numbers
            "v1_nbytes": os.path.getsize(v1_path), "v1_write_seconds": t_w1,
            "v1_decompressed_nbytes": sf.compressed_bytes(),
            "v2_nbytes": w2["file_nbytes"], "v2_write_seconds": t_w2,
            "v2_header_nbytes": w2["header_nbytes"],
            "v2_stride_nbytes": w2["stride_nbytes"],
            "v2_payload_nbytes": w2["payload_nbytes"],
            "v2_raw_nbytes": w2r["file_nbytes"], "v2_raw_write_seconds": t_w2r,
        },
        "open": bench_open(v1_path, v2_path),
        "ranged_read": bench_ranged_read(v1_path, v2_path, args.k, group_blocks),
        "first_batch": bench_first_batch(v1_path, v2_path, group_blocks, cache_budget),
        "correctness": check_identity(
            v1_path, v2_path, v2_raw_path, group_blocks, sf.meta.n_blocks
        ),
        "streaming": bench_streaming(
            v2_path, group_blocks, cache_budget,
            n_fetches=max(3, min(8 if args.smoke else 48,
                                 sf.meta.n_blocks // group_blocks)),
            blocks_per_fetch=group_blocks,
        ),
    }

    # compression economics of the codec container (PR 9): stored vs decoded
    # payload, header/table bytes, and the headline file-size ratio against
    # the zlib-packed v1 archive (block tiling repeats streams, which both
    # zlib and the codec's payload dedup collapse — the ratio compares like
    # with like) and against the raw stride-aligned v2 layout it replaces
    v1_nbytes = os.path.getsize(v1_path)
    decoded_payload = w2["n_blocks"] * w2["payload_nbytes"]
    fixed_len = int(sf.meta.fixed_read_len or 0)
    report["compression"] = {
        "v1_nbytes": v1_nbytes,
        "v2_nbytes": w2["file_nbytes"],
        "v2_raw_nbytes": w2r["file_nbytes"],
        "v2_over_v1": w2["file_nbytes"] / max(v1_nbytes, 1),
        "v2_raw_over_v1": w2r["file_nbytes"] / max(v1_nbytes, 1),
        "codec_shrink_vs_raw": w2r["file_nbytes"] / max(w2["file_nbytes"], 1),
        "stored_payload_nbytes": w2["stored_payload_nbytes"],
        "decoded_payload_nbytes": decoded_payload,
        "payload_ratio": decoded_payload / max(w2["stored_payload_nbytes"], 1),
        "dedup_blocks": w2["dedup_blocks"],
        "header_nbytes": w2["header_nbytes"],
        "bytes_per_base": (
            w2["file_nbytes"] / (sf.meta.n_reads * fixed_len)
            if fixed_len else None
        ),
        "ratio_ok": w2["file_nbytes"] <= 4 * v1_nbytes,
    }

    # O(k) contract: past the one-time header, a v2 ranged read may touch
    # only the covering groups' extents — in STORED (compressed) bytes, the
    # sum of those extents' aligned slots, never a whole-container count
    rr = report["ranged_read"]
    groups = -(-args.k // group_blocks)
    c2 = SageContainerV2.open(v2_path)
    cover = np.arange(min(groups * group_blocks, sf.meta.n_blocks))
    a = c2.layout.align
    bound = int(np.sum(-(-c2.extents[cover, 1] // a) * a))
    rr["v2_bytes_bound"] = bound
    # open cost = the header region plus the 24-byte commit footer check
    from repro.core.layout import FOOTER_NBYTES
    rr["v2_bytes_ok"] = (
        rr["v2"]["per_read_bytes"] <= bound
        and rr["v2"]["open_bytes_read"] == w2["header_nbytes"] + FOOTER_NBYTES
    )
    pipe_io = report["first_batch"]["v2"]["io_stats"]
    cache_ok = pipe_io["cache_peak_bytes"] <= cache_budget and pipe_io["container_loads"] == 0
    report["first_batch"]["v2_cache_bounded"] = cache_ok

    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")

    corr = report["correctness"]
    comp = report["compression"]
    strm = report["streaming"]
    print(
        f"open: v1 {report['open']['v1']['seconds']:.3f}s vs v2 "
        f"{report['open']['v2']['seconds']*1e3:.2f}ms | ranged {args.k} blocks: "
        f"{rr['cold_read_speedup']:.1f}x faster cold, amplification v1 "
        f"{rr['v1']['read_amplification']:.1f}x vs v2 "
        f"{rr['v2']['read_amplification']:.2f}x "
        f"(v1/v2 {rr['amplification_v1_over_v2']:.3g}x) | first batch "
        f"{report['first_batch']['first_batch_speedup']:.1f}x faster | "
        f"codec {comp['v2_over_v1']:.2f}x v1 "
        f"({comp['codec_shrink_vs_raw']:.1f}x smaller than raw v2) | "
        f"streaming {strm['speedup_vs_sequential']:.2f}x sequential, overlap "
        f"{strm['overlap_fraction']:.2f}, roofline_frac "
        f"{strm['roofline']['roofline_frac']:.2f} "
        f"(bottleneck {strm['roofline']['bottleneck']}), ttfb "
        f"{strm['ttfb_ratio']:.2f}x | "
        f"bit-identical={corr['v2_bit_identical_to_v1']} -> {args.out}"
    )
    if args.workdir is None:
        for p in (v1_path, v2_path, v2_raw_path):
            os.unlink(p)
        os.rmdir(workdir)
    if not (corr["v2_bit_identical_to_v1"] and rr["v2_bytes_ok"] and cache_ok
            and comp["ratio_ok"]):
        print("FAIL: v2 mismatch, O(k) bytes contract, cache budget, or "
              "compression ratio (> 4x v1) violated", file=sys.stderr)
        return 1
    if not strm["streaming_ok"]:
        print("FAIL: streaming gate — pipelined decode not bit-identical to "
              "sequential, stages did not overlap (overlap_fraction <= 0), "
              "or TTFB regressed past 10%", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
