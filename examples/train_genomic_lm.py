"""End-to-end driver: train a genomic LM on SAGe-prepared tokens.

Default runs a CPU-feasible reduced model for a few hundred steps with
checkpointing + resume; ``--full`` selects the real architecture config
(for TPU hardware). This is deliverable (b)'s end-to-end trainer.

  PYTHONPATH=src python examples/train_genomic_lm.py --steps 300
"""

import argparse
import os
import shutil
import sys
import tempfile

sys.path.insert(0, "src")

import dataclasses

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_arch
from repro.core import SageStore
from repro.data.pipeline import SageTokenPipeline
from repro.genomics.synth import make_reference, sample_read_set
from repro.training.optimizer import AdamWConfig
from repro.training.steps import TrainOptions, init_train_state
from repro.training.trainer import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--full", action="store_true", help="full config (TPU scale)")
    ap.add_argument("--dmodel", type=int, default=256, help="reduced width")
    ap.add_argument("--layers", type=int, default=4, help="reduced depth")
    ap.add_argument("--ckpt-dir", default="/tmp/genomic_lm_ckpt")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if not args.full:
        cfg = dataclasses.replace(
            cfg.reduced(),
            n_layers=args.layers, d_model=args.dmodel, n_heads=8, n_kv_heads=2,
            head_dim=args.dmodel // 8, d_ff=args.dmodel * 3, vocab=4**4 + 3,
        )
    opts = TrainOptions(chunk=min(512, args.seq), adamw=AdamWConfig(lr=1e-3, total_steps=args.steps, warmup_steps=20))
    params, opt = init_train_state(jax.random.PRNGKey(0), cfg, opts)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"training {cfg.name}: {n_params/1e6:.1f}M params on SAGe-prepared genomic tokens")

    # small genome + deep coverage => the LM sees each locus many times
    # per epoch and measurably learns it within a few hundred CPU steps
    ref = make_reference(24_000, seed=1)
    rs = sample_read_set(ref, "illumina", depth=10, seed=2)
    # out-of-core data path: SAGe_Write to a v2 block-extent container and
    # train from the lazy path — the pipeline streams block groups through
    # a bounded host cache instead of materializing the dataset
    store = SageStore(group_blocks=8)
    v2_path = os.path.join(tempfile.mkdtemp(prefix="sage_lm_"), "train.sage2")
    sf = store.write("train", rs, ref, token_target=16384,
                     layout="v2", path=v2_path)
    pipe = SageTokenPipeline("train", cfg.vocab, args.batch, args.seq, store=store)
    ratio = rs.n_bases / sf.compressed_bytes(include_consensus=False)
    print(f"data: {rs.n_bases/1e6:.1f} Mbases, SAGe ratio {ratio:.1f}x, k={pipe.k}, "
          f"container {v2_path}")

    tc = TrainerConfig(total_steps=args.steps, ckpt_every=max(args.steps // 3, 50),
                       log_every=20, ckpt_dir=args.ckpt_dir)
    trainer = Trainer(tc, cfg, opts, params, opt, iter(pipe.prefetched()))
    trainer.install_signal_handler()
    if trainer.maybe_resume(pipe):
        print(f"resumed from step {trainer.step}")
    hist = trainer.run(pipeline=pipe)
    l0, l1 = hist[0]["loss"], hist[-1]["loss"]
    print(f"loss {l0:.3f} -> {l1:.3f} over {trainer.step} steps")
    io = pipe.io_stats
    print(f"io_stats: {io['extent_reads']} ranged reads, "
          f"{io['extent_bytes_read']/1e6:.2f} MB extents read, host cache peak "
          f"{io['cache_peak_bytes']/1e6:.2f} MB, whole-file loads: {io['container_loads']}")
    shutil.rmtree(os.path.dirname(v2_path), ignore_errors=True)
    assert l1 < l0, "training must reduce loss"


if __name__ == "__main__":
    main()
