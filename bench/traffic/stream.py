"""Traffic kind ``stream``: one consumer pulls the whole dataset through the
pipelined stream (``SageReadSession.read_stream(mode="pipelined")``),
wrapping around it for as long as the window lasts, and waits on the device
until each fetch's formatted output is ready there. The seed picks the block
the stream starts at.

Parameters (the cell file's ``params``): ``fmt``, ``kmer_k`` (for
``kmer``), ``blocks_per_fetch``, and ``warm_fetches``, the fetches pulled
before the window so that it opens on a full pipeline.

The check sees the window's first pass over every block, so that the blocks
together must hold the read set, and a sample of the later fetches drawn
from the seed (``CHECK_SHARE``), so that holding them for the check does not
fill the device.
"""

from __future__ import annotations

import time

import jax
import numpy as np

SPANS = ("bench.fetch",)
CHECK_SHARE = 0.125  # of the fetches after the first pass


class Run:
    def __init__(self, config: dict, params: dict, corpus, seed: int) -> None:
        self.config, self.params, self.corpus = config, params, corpus
        self.start = seed % corpus.n_blocks
        self.rng = np.random.default_rng(seed)
        self.kept: list = []  # (fetch index, StreamBatch) the check will see
        self.n_tokens: list = []  # every window fetch's per-block token counts
        self.obs: dict = {}

    def setup(self) -> None:
        from repro.core import SageStore

        p, name = self.params, self.corpus.name
        self.store = SageStore(**self.config["store"])
        self.store.register(name, self.corpus.path)
        sess = self.store.session()
        nb, bpf = self.corpus.n_blocks, p["blocks_per_fetch"]
        self.out_key = {"2bit": "tokens"}.get(p["fmt"], p["fmt"])
        # a fetch's blocks split over residency groups in as many ways as it
        # has blocks: read each split once so that every shape the stream
        # meets is compiled before the window
        g = self.store.group_blocks
        for first in sorted({(g - off) % nb for off in range(bpf)}):
            ids = (first + np.arange(bpf)) % nb
            sess.read(name, ids, p["fmt"], kmer_k=p.get("kmer_k"))[self.out_key].block_until_ready()
        self.it = sess.read_stream(
            name, fmt=p["fmt"], kmer_k=p.get("kmer_k"), blocks_per_fetch=bpf,
            wrap=True, mode="pipelined", start_block=self.start,
        )
        self.pulled = 0
        for _ in range(p["warm_fetches"]):
            next(self.it).data[self.out_key].block_until_ready()
            self.pulled += 1

    def _counters(self) -> dict:
        s = self.it.stats
        return {"io_seconds": s.io_seconds, "upload_seconds": s.upload_seconds,
                "fetches": s.fetches}

    def measure(self, seconds: float) -> None:
        first_pass = -(-self.corpus.n_blocks // self.params["blocks_per_fetch"])
        fetches, ends, waits = 0, [], []  # waits: on the device, after next()
        before = self._counters()
        t0 = time.perf_counter()
        deadline = t0 + seconds
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                with jax.profiler.TraceAnnotation("bench.fetch"):
                    batch = next(self.it)
                    t_next = time.perf_counter()
                    batch.data[self.out_key].block_until_ready()
                ends.append(time.perf_counter())
                waits.append(ends[-1] - t_next)
                self.n_tokens.append(batch.data["n_tokens"])
                if fetches < first_pass or self.rng.random() < CHECK_SHARE:
                    self.kept.append((self.pulled, batch))
                fetches += 1
                self.pulled += 1
                t = time.perf_counter()
                if t >= deadline:
                    break
        after = self._counters()
        gaps = 1e3 * np.diff([t0] + ends)
        self.obs.update(
            window_log="fetch ms p50/p90/p99/max: " + " ".join(
                f"{x:.2f}" for x in np.percentile(gaps, [50, 90, 99, 100]))
            + f" over_2x_median={int((gaps > 2 * np.median(gaps)).sum())}"
            + f" slowest: in next() {gaps.max() - 1e3 * waits[gaps.argmax()]:.2f}"
            f" on the device {1e3 * waits[gaps.argmax()]:.2f}",
            window_s=t - t0,
            fetches=fetches,
            blocks_per_fetch=self.params["blocks_per_fetch"],
            counters={k: after[k] - before[k] for k in after},
        )

    def close(self) -> list:
        """Stop the stream, bring the window's output to the host, free the
        store, and return the answers for the check."""
        self.it.close()
        self.obs["resident_bytes_per_block"] = self.store.block_nbytes(self.corpus.name)
        nb, bpf = self.corpus.n_blocks, self.params["blocks_per_fetch"]
        answers = []
        for i, batch in self.kept:
            data = {k: np.asarray(v) for k, v in batch.data.items() if k != "block_ids"}
            answers.append({
                "want": (self.start + i * bpf + np.arange(bpf)) % nb,
                "block_ids": np.asarray(batch.block_ids),
                "data": data, "fmt": self.params["fmt"], "kmer_k": self.params.get("kmer_k"),
            })
        first = self.kept[0][1].data
        self.obs["output_shapes"] = {k: (tuple(v.shape), v.dtype.itemsize)
                                     for k, v in first.items() if k != "block_ids"}
        self.obs["bases"] = int(sum(np.asarray(n).sum() for n in self.n_tokens))
        self.obs["attempted"] = self.obs["fetches"]
        del self.kept, self.n_tokens, self.it, self.store
        return answers
