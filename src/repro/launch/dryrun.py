import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell and
extract the roofline inputs (deliverables e & g).

This module (and ONLY this module) forces 512 placeholder host devices — the
env var is set before any other import so jax locks the device count at the
production size. Never import this from tests or benches.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single|multi|both]

Artifacts: one JSON per cell under benchmarks/artifacts/dryrun/, consumed by
benchmarks/roofline.py and EXPERIMENTS.md.
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs import ARCHS, SHAPES, get_arch, get_shape
from repro.distributed.sharding import Rules, use_rules
from repro.launch.hlo_cost import analyze
from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh
from repro.launch.specs import build_case
from repro.training.steps import TrainOptions


def model_flops(cfg, cell) -> float:
    """MODEL_FLOPS = 6·N·D (train) or 2·N·D (fwd-only), N = active params."""
    n = cfg.n_active_params()
    if cell.kind == "train":
        return 6.0 * n * cell.global_batch * cell.seq_len
    if cell.kind == "prefill":
        return 2.0 * n * cell.global_batch * cell.seq_len
    return 2.0 * n * cell.global_batch  # decode: one token per sequence


def run_cell(arch: str, shape: str, multi_pod: bool, opts: TrainOptions, out_dir: Path, verbose: bool = True, seq_shard: bool = True, tag_suffix: str = "", pure_dp: bool = False, dp_compress: str = "", sage_fused: bool = False):
    cfg = get_arch(arch)
    cell = get_shape(shape)
    if cell.name == "long_500k" and not cfg.sub_quadratic:
        rec = {"arch": arch, "shape": shape, "multi_pod": multi_pod, "status": "skipped",
               "reason": "full-attention arch; 500k decode needs sub-quadratic attention (DESIGN.md §4)"}
        out_dir.mkdir(parents=True, exist_ok=True)
        (out_dir / f"{arch}_{shape}_{'pod2' if multi_pod else 'pod1'}{tag_suffix}.json").write_text(json.dumps(rec, indent=1))
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    # SP (shard activation seq over model) only helps token-parallel steps
    sp = seq_shard and cell.kind in ("train", "prefill") and not pure_dp
    rules = Rules(mesh, data_axes=("pod", "data") if multi_pod else ("data",), seq_shard=sp, pure_dp=pure_dp)
    chips = mesh.devices.size
    t0 = time.time()
    with use_rules(rules):
        if sage_fused:
            from repro.launch.specs import build_sage_fused_case

            fn, specs, donate = build_sage_fused_case(cfg, cell, rules, opts)
        elif dp_compress:
            from repro.launch.specs import build_dp_compressed_case

            fn, specs, donate = build_dp_compressed_case(cfg, cell, rules, opts, dp_compress)
        else:
            fn, specs, donate = build_case(cfg, cell, rules, opts)
        lowered = jax.jit(fn, donate_argnums=donate).lower(*specs)
        t_lower = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t1
    mem = compiled.memory_analysis()
    xla_cost = compiled.cost_analysis() or {}
    cost = analyze(compiled.as_text())  # trip-count-aware walker

    flops_dev = float(cost.flops)
    bytes_dev = float(cost.bytes)
    coll_bytes_dev = float(cost.collective_bytes)
    coll = {k: float(v) for k, v in cost.coll.items()}
    coll.update({f"n_{k}": float(v) for k, v in cost.coll_n.items()})
    mf = model_flops(cfg, cell)

    rec = {
        "arch": arch, "shape": shape, "multi_pod": multi_pod, "chips": chips,
        "status": "ok",
        "t_lower_s": round(t_lower, 2), "t_compile_s": round(t_compile, 2),
        # memory (per device)
        "arg_bytes": mem.argument_size_in_bytes,
        "out_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "peak_hbm_gb": round((mem.argument_size_in_bytes + mem.output_size_in_bytes
                              + mem.temp_size_in_bytes - mem.alias_size_in_bytes) / 2**30, 3),
        # cost (per device program; trip-count-aware HLO walk)
        "hlo_flops_dev": flops_dev,
        "hlo_bytes_dev": bytes_dev,
        "collective_bytes_dev": coll_bytes_dev,
        "collectives": coll,
        "xla_flops_raw": float(xla_cost.get("flops", 0.0)),
        # roofline terms (seconds)
        "t_compute": flops_dev / PEAK_FLOPS_BF16,
        "t_memory": bytes_dev / HBM_BW,
        "t_collective": coll_bytes_dev / ICI_BW,
        # model-flops accounting
        "model_flops_total": mf,
        "model_flops_dev": mf / chips,
        "useful_flops_frac": (mf / chips) / flops_dev if flops_dev else 0.0,
    }
    terms = {"compute": rec["t_compute"], "memory": rec["t_memory"], "collective": rec["t_collective"]}
    rec["bottleneck"] = max(terms, key=terms.get)
    # fraction of the dominant-term-bounded step time that is USEFUL model
    # math at peak — the score we hillclimb in EXPERIMENTS.md §Perf
    useful_t = (mf / chips) / PEAK_FLOPS_BF16
    rec["roofline_frac"] = useful_t / max(max(terms.values()), 1e-30)
    if verbose:
        print(f"[{arch} × {shape} × {'2pod' if multi_pod else '1pod'}] "
              f"compile={t_compile:.1f}s peak_hbm={rec['peak_hbm_gb']}GB "
              f"flops/dev={flops_dev:.3g} bneck={rec['bottleneck']} "
              f"useful={rec['useful_flops_frac']:.2f}")
        print("  memory_analysis:", mem)
    rec["seq_shard"] = sp
    rec["options"] = {"grad_compress": opts.grad_compress, "microbatch": opts.microbatch,
                      "chunk": opts.chunk, "remat_policy": opts.remat_policy,
                      "pure_dp": pure_dp, "dp_compress": dp_compress, "sage_fused": sage_fused}
    out_dir.mkdir(parents=True, exist_ok=True)
    tag = f"{arch}_{shape}_{'pod2' if multi_pod else 'pod1'}{tag_suffix}.json"
    (out_dir / tag).write_text(json.dumps(rec, indent=1))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--out", default="benchmarks/artifacts/dryrun")
    ap.add_argument("--compress", default=None, help="grad compression: bf16|int16_ef")
    ap.add_argument("--microbatch", type=int, default=4, help="grad-accumulation steps (train cells)")
    ap.add_argument("--chunk", type=int, default=1024)
    ap.add_argument("--no-seq-shard", action="store_true", help="disable SP (baseline ablation)")
    ap.add_argument("--remat-policy", default="nothing", choices=["nothing", "dots"])
    ap.add_argument("--tag", default="", help="artifact filename suffix (perf iterations)")
    ap.add_argument("--pure-dp", action="store_true", help="fold model axis into DP (small models)")
    ap.add_argument("--dp-compress", default="", help="explicit shard_map DP step: int16_ef|bf16")
    ap.add_argument("--sage-fused", action="store_true", help="fuse on-device SAGe decode into train_step")
    args = ap.parse_args()

    opts = TrainOptions(grad_compress=args.compress, microbatch=args.microbatch, chunk=args.chunk,
                        remat_policy=args.remat_policy)
    out = Path(args.out)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    cells: list[tuple[str, str]]
    if args.all:
        cells = [(a, s) for a in ARCHS for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    failures = []
    for arch, shape in cells:
        for mp in meshes:
            try:
                run_cell(arch, shape, mp, opts, out, seq_shard=not args.no_seq_shard, tag_suffix=args.tag,
                         pure_dp=args.pure_dp, dp_compress=args.dp_compress, sage_fused=args.sage_fused)
            except Exception as e:  # noqa: BLE001 — record, continue sweep
                traceback.print_exc()
                failures.append((arch, shape, mp, str(e)))
                tag = f"{arch}_{shape}_{'pod2' if mp else 'pod1'}{args.tag}.json"
                (out / tag).write_text(json.dumps({
                    "arch": arch, "shape": shape, "multi_pod": mp,
                    "status": "failed", "error": str(e)[:2000],
                }, indent=1))
            jax.clear_caches()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nall cells OK")


if __name__ == "__main__":
    main()
