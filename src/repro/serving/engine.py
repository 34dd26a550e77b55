"""Serving entry point: the SAGe production frontend + the LM engine.

Two layers live here:

:class:`ServingEngine` — the model-side executor: padded-slot prefill +
jitted decode loop over the model zoo (greedy or temperature sampling,
one compile per batch bucket).

:class:`SageServer` — the front door the ROADMAP's "millions of users"
item asks for, wiring the whole serving subsystem together::

        submit()            Scheduler (serving/scheduler.py)
    client ──────> waiting queue ──admit──> running set
                                             │ continuous batches
                                             v
                   ContinuousBatcher (serving/batching.py)
                     fused bucketed SAGe_Read / consensus / ISP chunks
                     + padded-batch LM generation
                                             │
                   SessionPool (serving/session_pool.py)
                     one shared SageStore: block-granular device LRU,
                     host extent cache, per-decode-path sessions
                                             │
    client <──── ResponseHandle.chunks() ────┘  (streaming, abortable,
                                                 backpressured)

The paper's interface contract — "send each read to the analysis system as
soon as it is decoded" (§5.1) — becomes a multi-tenant one: every decoded
chunk flows to its requesting tenant as soon as its fused batch lands,
and hot datasets stay device-resident across all of them.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.api import get_format, pick_k
from repro.core.store import SageReadSession, SageStore
from repro.models import lm
from repro.serving.batching import ContinuousBatcher
from repro.serving.scheduler import (
    Request,
    RequestState,
    ResponseHandle,
    Scheduler,
)
from repro.serving.session_pool import SessionPool


def prompts_from_store(
    session: SageReadSession,
    name: str,
    *,
    vocab: int,
    n_prompts: int = 8,
    max_prompt: int = 64,
    kmer_k: Optional[int] = None,
    block_range=None,
) -> list[np.ndarray]:
    """SAGe_Read -> serving prompt feed: decoded reads of a stored dataset as
    k-mer token prompts (the paper's "send each read to the analysis system
    as soon as it is decoded" contract, §5.1).

    Walks the requested block range in order and emits one prompt per read
    (its k-mer token prefix, folded into ``vocab``) until ``n_prompts``.
    Fewer than ``n_prompts`` reads yields fewer prompts; reads shorter than
    one k-mer are skipped (a range of only those yields ``[]``); prompts
    truncate to their first ``max_prompt`` k-mers — the same prefix
    :meth:`ServingEngine.generate` keeps when a prompt overflows its slot,
    so pre-truncation here and slot truncation there agree."""
    k = kmer_k if kmer_k is not None else pick_k(vocab)
    out = session.read(name, block_range, fmt="kmer", kmer_k=k)
    km = out["kmer"]  # stays on device (sharded under a session mesh)
    starts, lens = np.asarray(out["read_start"]), np.asarray(out["read_len"])
    n_reads = np.asarray(out["n_reads"])
    # one batched gather over (read_start, read_len): enumerate real reads in
    # (block, read) order, apply the n_prompts cutoff, and pull every prompt's
    # k-mer span out of the device array in a single fancy-indexed gather —
    # the only host transfer is the gathered prompt tokens themselves
    n_r = np.minimum(n_reads, starts.shape[1])
    keep = np.arange(starts.shape[1])[None, :] < n_r[:, None]
    keep &= lens // k > 0  # zero-k-mer reads are skipped, not emitted
    bi, ri = np.nonzero(keep)  # row-major == the loop's (block, read) order
    bi, ri = bi[:n_prompts], ri[:n_prompts]
    if bi.size == 0:
        return []
    starts_k = starts[bi, ri] // k
    spans = np.minimum(lens[bi, ri] // k, max_prompt)
    ends = np.cumsum(spans)
    offs = ends - spans
    row = np.repeat(bi, spans)
    col = starts_k.repeat(spans) + np.arange(ends[-1]) - offs.repeat(spans)
    flat = np.asarray(km[jnp.asarray(row), jnp.asarray(col)] % vocab).astype(np.int32)
    return [flat[o:e] for o, e in zip(offs, ends)]


@dataclasses.dataclass
class ServeConfig:
    max_prompt: int = 512
    max_new: int = 64
    temperature: float = 0.0  # 0 => greedy
    seed: int = 0


class ServingEngine:
    """Padded-slot prefill + decode loop over one model config.

    Each engine owns its own :class:`ServeConfig` (``sc=None`` constructs a
    per-instance default — a shared default instance would alias sampling
    state across every engine in the process)."""

    def __init__(self, cfg: ArchConfig, params, sc: Optional[ServeConfig] = None) -> None:
        self.cfg = cfg
        self.params = params
        self.sc = sc if sc is not None else ServeConfig()
        self._prefill = jax.jit(self._prefill_impl, static_argnums=(2,))
        self._step = jax.jit(self._step_impl)

    def _prefill_impl(self, tokens, frames, max_len: int):
        kw = {}
        if self.cfg.family == "encdec":
            kw["frames"] = frames
        if self.cfg.family == "vlm":
            kw["patch_embeds"] = frames
        return lm.prefill(self.params, self.cfg, tokens, max_len=max_len, **kw)

    def _sample(self, lg: jax.Array, key) -> jax.Array:
        """Next-token selection — the ONE temperature guard both prefill
        sampling and the decode loop share (greedy at 0; the 1e-6 floor
        keeps a denormal temperature from blowing up the logit scale)."""
        if self.sc.temperature > 0:
            nxt = jax.random.categorical(
                key, lg / max(self.sc.temperature, 1e-6), axis=-1
            )
        else:
            nxt = jnp.argmax(lg, axis=-1)
        return nxt.astype(jnp.int32)

    def _step_impl(self, tok, cache, idx, key):
        logits, cache = lm.decode_step(self.params, self.cfg, tok, cache, idx)
        lg = logits[:, -1].astype(jnp.float32)
        return self._sample(lg, key)[:, None], cache

    def generate(self, prompts: list[np.ndarray], frames: Optional[np.ndarray] = None) -> list[np.ndarray]:
        """prompts: list of int32 token arrays (longer than ``max_prompt``
        keeps the first ``max_prompt`` tokens — prefix truncation, matching
        ``prompts_from_store``)."""
        B = len(prompts)
        if B == 0:
            return []
        P = self.sc.max_prompt
        toks = np.zeros((B, P), np.int32)
        for i, p in enumerate(prompts):
            p = p[:P]
            toks[i, -len(p) :] = p  # left-pad (keeps last token at P-1)
        max_len = P + self.sc.max_new + 1
        if frames is None and self.cfg.family in ("encdec", "vlm"):
            frames = np.zeros((B, P, self.cfg.d_model), np.float32)
        logits, cache = self._prefill(jnp.asarray(toks), None if frames is None else jnp.asarray(frames), max_len)
        key = jax.random.PRNGKey(self.sc.seed)
        lg = logits[:, -1].astype(jnp.float32)
        cur = self._sample(lg, key)[:, None]
        outs = [np.asarray(cur)]
        for t in range(self.sc.max_new - 1):
            key, sub = jax.random.split(key)
            cur, cache = self._step(cur, cache, jnp.int32(P + t), sub)
            outs.append(np.asarray(cur))
        gen = np.concatenate(outs, axis=1)
        return [gen[i] for i in range(B)]


class SageServer:
    """The serving frontend: ingestion + scheduling + continuous batching
    over one shared SageStore.

    ``policy`` picks admission order (``"cache_aware"`` default,
    ``"fcfs"``); ``max_waiting`` bounds the ingestion queue (backpressure);
    ``max_batch_requests``/``max_batch_bytes``/``max_union_blocks`` shape
    the batcher's rounds. Drive it synchronously (``step`` /
    ``run_until_idle`` — deterministic, what the tests and benches use) or
    in the background (``start``/``stop`` or a ``with`` block) so clients
    block only on their own handles."""

    def __init__(
        self,
        pool: Optional[SessionPool] = None,
        *,
        store: Optional[SageStore] = None,
        engine: Optional[ServingEngine] = None,
        policy: str = "cache_aware",
        max_waiting: int = 64,
        max_batch_requests: int = 16,
        max_batch_bytes: int = 64 << 20,
        max_union_blocks: int = 64,
        use_pallas: bool = False,
    ) -> None:
        if pool is not None and store is not None:
            raise ValueError("pass pool= or store=, not both")
        self.pool = pool if pool is not None else SessionPool(store=store)
        self.engine = engine
        self.scheduler = Scheduler(
            policy=policy, max_waiting=max_waiting,
            residency=self.pool.request_residency,
        )
        self.batcher = ContinuousBatcher(
            self.pool, self.scheduler, engine=engine,
            max_batch_requests=max_batch_requests,
            max_batch_bytes=max_batch_bytes,
            max_union_blocks=max_union_blocks,
            use_pallas=use_pallas,
        )
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    # ------------------------------------------------------------- ingestion
    def submit(
        self, request: Union[Request, dict], *, timeout: Optional[float] = None
    ) -> ResponseHandle:
        """Validate + enqueue a request; returns its streaming handle.

        Validation is submission-time so a bad request fails its OWN
        caller: unknown dataset, unknown/k-less format, or a generate
        request on an engine-less server all raise here, never inside the
        batch loop."""
        if isinstance(request, dict):
            request = Request(**request)
        req = request
        if req.kind == "generate":
            if self.engine is None:
                raise ValueError("this server has no ServingEngine; generate unavailable")
            if req.prompt is None and not req.dataset:
                raise ValueError("generate needs prompt= or dataset=")
        if req.dataset:
            if req.dataset not in self.pool.store.names():
                raise KeyError(
                    f"dataset {req.dataset!r} not registered; have {self.pool.store.names()}"
                )
        if req.kind in ("read", "isp"):
            spec = get_format(req.fmt)
            if spec.requires_k and req.kmer_k is None:
                raise ValueError(f"format {spec.name!r} needs kmer_k=")
        return self.scheduler.submit(req, timeout=timeout)

    # convenience constructors -------------------------------------------------
    def read(self, dataset: str, block_range=None, fmt="2bit", *,
             kmer_k: Optional[int] = None, priority: int = 0, **kw) -> ResponseHandle:
        return self.submit(Request(
            kind="read", dataset=dataset, block_range=block_range, fmt=fmt,
            kmer_k=kmer_k, priority=priority), **kw)

    def consensus(self, dataset: str, block_range=None, *, priority: int = 0,
                  **kw) -> ResponseHandle:
        return self.submit(Request(
            kind="consensus", dataset=dataset, block_range=block_range,
            priority=priority), **kw)

    def stream(self, dataset: str, block_range=None, fmt="2bit", *,
               kmer_k: Optional[int] = None, blocks_per_fetch: int = 4,
               max_fetches: Optional[int] = None, priority: int = 0,
               stream_buffer: Optional[int] = None, **kw) -> ResponseHandle:
        return self.submit(Request(
            kind="isp", dataset=dataset, block_range=block_range, fmt=fmt,
            kmer_k=kmer_k, blocks_per_fetch=blocks_per_fetch,
            max_fetches=max_fetches, priority=priority,
            stream_buffer=stream_buffer), **kw)

    def generate(self, prompt: Optional[np.ndarray] = None, *, dataset: str = "",
                 block_range=None, max_prompt: int = 64, kmer_k: Optional[int] = None,
                 priority: int = 0, **kw) -> ResponseHandle:
        return self.submit(Request(
            kind="generate", prompt=prompt, dataset=dataset,
            block_range=block_range, max_prompt=max_prompt, kmer_k=kmer_k,
            priority=priority), **kw)

    # -------------------------------------------------------------- execution
    def step(self) -> int:
        """One synchronous admission + fused-batch round."""
        return self.batcher.step()

    def run_until_idle(self, **kw) -> int:
        return self.batcher.run_until_idle(**kw)

    def start(self) -> "SageServer":
        """Serve in a background thread until :meth:`stop`."""
        if self._thread is not None:
            return self
        self._stop.clear()

        def loop() -> None:
            while not self._stop.is_set():
                if self.batcher.step() == 0:
                    time.sleep(0.002)

        self._thread = threading.Thread(target=loop, daemon=True, name="sage-server")
        self._thread.start()
        return self

    def stop(self) -> None:
        self.batcher.close()  # ISP host-prefetch worker, if one was started
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join()
        self._thread = None

    def __enter__(self) -> "SageServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ---------------------------------------------------------- observability
    def stats(self) -> dict:
        return {
            "scheduler": dict(self.scheduler.stats),
            "batcher": dict(self.batcher.stats),
            "pool": self.pool.stats(),
            "waiting": len(self.scheduler.waiting),
            "running": len(self.scheduler.running),
        }

    def health(self, dataset: Optional[str] = None) -> dict:
        """Integrity health of the backing store (see ``SageStore.health``):
        which datasets have quarantined block groups. A quarantined group
        fails only the requests touching it — this is the operator's view
        of what degraded and what a repair + ``clear_quarantine`` (or
        re-register) would restore."""
        return self.pool.store.health(dataset)


__all__ = [
    "prompts_from_store",
    "ServeConfig",
    "ServingEngine",
    "SageServer",
    "Request",
    "RequestState",
    "ResponseHandle",
]
