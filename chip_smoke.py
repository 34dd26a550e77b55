#!/usr/bin/env python3
"""Drive the SAGe store's read path once on a TPU, end to end, and check it.

    python chip_smoke.py [--seed 0] [--ref-len 800000]
    python chip_smoke.py --chips 4 [--seed 0] [--ref-len 800000]

One chip (the default), in order:

1. device check: the platform must be ``tpu`` (no CPU fallback);
2. corpus: a seeded reference (800 kb by default, cut from 2 Mb so the
   host-bound encode stays near 3 minutes on a one-chip host), a 30x
   Illumina-like read set (150 bp, the RS1-RS3 profile) encoded at the default ``token_target`` and written as a
   codec v2 container with XOR parity, plus a small HiFi set over the same
   reference (so the long-read capacities compile too);
3. ranged reads in ``2bit``, ``onehot`` and ``kmer``, one range crossing a
   residency-group boundary, bit-identical to the numpy oracle
   (``repro.core.refdec``) on the same blocks;
4. the whole dataset through ``read_stream(mode="pipelined")``: k-mer
   output matches a numpy k-mer pack of the 2-bit pass, the 2-bit pass is
   lossless against the generated reads, and a second k-mer pass traces
   nothing new;
5. the k-mer token feed (``SageTokenPipeline``) matches the stream and
   resumes at a restored cursor with the same batch;
6. ``SageServer`` read and stream requests match direct session reads.

``--chips 4`` runs only the block-sharded store (``SageStore(shards=4)``):
a ranged read and a pipelined stream, bit-identical to the single-device
decode of the same blocks, with residency spread over all four devices.

Everything is generated from ``--seed``; the container lives in a
temporary directory. Any mismatch raises and exits non-zero. Lines starting
with ``info:`` are informational wall-clock readings of this host. The last
line of standard output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

import jax
import numpy as np

NAME = "rs1"  # the short-read dataset
HIFI = "hifi"
HIFI_READS = 16  # long reads encode at about 2 s each on the host
KMER_K = 6
GROUP_BLOCKS = 32  # the store's default residency group
FULL_REF_LEN = 2_000_000  # the reference the smoke is sized against
# 30x over 2 Mb is 60 Mbases; the host encode runs at about 7 s per Mbase
# on a one-chip v5e host, so the default reference is cut to 800 kb
DEFAULT_REF_LEN = 800_000
PAD = 4  # PAD_BASE: tail padding token (also the N code)


def device_check(chips: int) -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    print(f"device: platform={dev['platform']} kind={dev['kind']} count={dev['count']}")
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found {dev['platform']!r}")
    if dev["count"] < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} devices, found {dev['count']}")
    return dev


def _import_repo() -> None:
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))


# ----------------------------------------------------------------- oracle
def oracle_block(sf, bi: int, cons: np.ndarray) -> dict:
    """The numpy oracle's decode of block ``bi`` in the decoder's layout:
    reads back to back from token 0, PAD after them."""
    from repro.core import refdec

    reads = refdec.decode_block(sf, bi, cons)
    caps = sf.meta.caps
    lens = np.array([r.seq.size for r in reads], np.int32)
    n_tok = int(lens.sum())
    tokens = np.full(caps.tokens, PAD, np.int8)
    if reads:
        tokens[:n_tok] = np.concatenate([r.seq for r in reads])
    rows = {k: np.zeros(caps.segs, np.int32) for k in
            ("read_start", "read_len", "read_rev", "read_corner")}
    rows["read_pos"] = np.full(caps.segs, -1, np.int32)
    n = len(reads)
    rows["read_len"][:n] = lens
    rows["read_start"][:n] = np.cumsum(lens) - lens
    rows["read_rev"][:n] = [r.rev for r in reads]
    rows["read_corner"][:n] = [r.corner for r in reads]
    rows["read_pos"][:n] = [r.pos for r in reads]
    return dict(tokens=tokens, n_tokens=n_tok, n_reads=n, **rows)


def np_kmer(tokens: np.ndarray, k: int, n_tokens) -> np.ndarray:
    """numpy k-mer pack: groups holding code 4 map to the N-block id inside
    a row's real tokens and to the pad id past them."""
    C = tokens.shape[-1]
    g = tokens[..., : (C // k) * k].reshape(*tokens.shape[:-1], C // k, k).astype(np.int64)
    ids = (np.where(g > 3, 0, g) * 4 ** np.arange(k - 1, -1, -1)).sum(-1)
    has4 = (g == PAD).any(-1)
    in_read = (np.arange(C // k) + 1) * k <= np.asarray(n_tokens)[..., None]
    return np.where(has4, np.where(in_read, 4**k + 2, 4**k), ids).astype(np.int32)


def np_onehot(tokens: np.ndarray) -> np.ndarray:
    return (tokens[..., None] == np.arange(4)).astype(np.float32)


def require_equal(what: str, got, want) -> None:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or not np.array_equal(got, want):
        bad = "shape" if got.shape != want.shape else int(np.sum(got != want))
        raise AssertionError(f"{what}: mismatch ({bad}); got {got.shape}, want {want.shape}")


DECODE_KEYS = ("tokens", "n_tokens", "n_reads", "read_start", "read_len",
               "read_rev", "read_corner", "read_pos")


def check_against_oracle(what: str, out: dict, ids, oracle: dict, fmt: str) -> None:
    for j, bi in enumerate(np.asarray(ids)):
        o = oracle[int(bi)]
        for key in DECODE_KEYS:
            require_equal(f"{what} block {bi} {key}", np.asarray(out[key])[j], o[key])
        if fmt == "onehot":
            got = np.asarray(out["onehot"][j]).astype(np.float32)
            require_equal(f"{what} block {bi} onehot", got, np_onehot(o["tokens"]))
        elif fmt == "kmer":
            want = np_kmer(o["tokens"], KMER_K, o["n_tokens"])
            require_equal(f"{what} block {bi} kmer", out["kmer"][j], want)


def reads_of(tokens, n_reads, starts, lens) -> list[bytes]:
    return [
        bytes(tokens[b, starts[b, r]: starts[b, r] + lens[b, r]].astype(np.uint8))
        for b in range(tokens.shape[0]) for r in range(int(n_reads[b]))
    ]


def multiset(reads) -> list[bytes]:
    return sorted(bytes(np.asarray(r, dtype=np.uint8)) for r in reads)


# ----------------------------------------------------------------- phases
def _crossing(nb: int, before: int = 3, after: int = 2) -> tuple[int, int]:
    """A block range across the first residency-group boundary (or the
    middle of a dataset too small to have one)."""
    edge = GROUP_BLOCKS if nb > GROUP_BLOCKS else nb // 2
    return max(0, edge - before), min(nb, edge + after)


def build_corpus(args, tmp: Path, *, hifi_set: bool) -> dict:
    from repro.core.encoder import SageEncoder
    from repro.core.layout import write_v2
    from repro.genomics.synth import make_reference, sample_read_set

    t0 = time.perf_counter()
    ref = make_reference(args.ref_len, seed=args.seed)
    rs = sample_read_set(ref, "illumina", depth=30, seed=args.seed + 1)
    t1 = time.perf_counter()
    sf = SageEncoder(ref).encode(rs)
    t2 = time.perf_counter()
    c = dict(rs=rs, sf=sf, path=tmp / f"{NAME}.sage2")
    sets = [("illumina", rs, sf, c["path"])]
    if hifi_set:
        hifi = sample_read_set(ref, "hifi", depth=1.5, seed=args.seed + 2,
                               max_reads=HIFI_READS)
        c["sf_hifi"] = SageEncoder(ref).encode(hifi)
        c["hpath"] = tmp / f"{HIFI}.sage2"
        sets.append(("hifi", hifi, c["sf_hifi"], c["hpath"]))
        print(f"corpus: hifi depth 1.5 capped at {HIFI_READS} reads")
    t3 = time.perf_counter()
    for tag, r, s, p in sets:
        write_v2(s, p, parity="xor")
        m = s.meta
        print(f"corpus {tag}: reads={r.n_reads} bases={r.n_bases} blocks={m.n_blocks} "
              f"caps.tokens={m.caps.tokens} container_bytes={p.stat().st_size}")
    cut = f" (cut from {FULL_REF_LEN})" if args.ref_len < FULL_REF_LEN else ""
    print(f"corpus: reference={args.ref_len} bases{cut}, seed={args.seed}")
    print(f"info: synth_seconds={t1 - t0} encode_seconds={t2 - t1} "
          f"hifi_synth_encode_seconds={t3 - t2}")
    return c


def phase_ranged_reads(store, c: dict) -> None:
    from repro.core.bitio import unpack_2bit

    sess = store.session()
    for name, sf in ((NAME, c["sf"]), (HIFI, c["sf_hifi"])):
        nb = sf.meta.n_blocks
        cons = unpack_2bit(sf.consensus2b, sf.meta.cons_len)
        ranges = sorted({(0, min(3, nb)), _crossing(nb), (max(0, nb - 2), nb)})
        ids = sorted({b for lo, hi in ranges for b in range(lo, hi)})
        oracle = {b: oracle_block(sf, b, cons) for b in ids}
        for fmt in ("2bit", "onehot", "kmer"):
            for lo, hi in ranges:
                out = sess.read(name, (lo, hi), fmt, kmer_k=KMER_K)
                check_against_oracle(f"read {name} [{lo},{hi}) {fmt}", out,
                                     range(lo, hi), oracle, fmt)
        print(f"ranged reads {name}: ranges={ranges} formats=2bit,onehot,kmer "
              f"blocks_checked={len(ids)} bit-identical to refdec")


def phase_stream(store, c: dict) -> dict:
    from repro.core import trace_counts

    sf, rs = c["sf"], c["rs"]
    nb = sf.meta.n_blocks
    sess = store.session()
    kmer: dict[int, np.ndarray] = {}

    def keep(batch):
        km = np.asarray(batch.data["kmer"])
        for j, b in enumerate(np.asarray(batch.block_ids)):
            kmer[int(b)] = km[j]

    t0 = time.perf_counter()
    sess.read_stream(NAME, keep, fmt="kmer", kmer_k=KMER_K, mode="pipelined")
    t1 = time.perf_counter()
    if sorted(kmer) != list(range(nb)):
        raise AssertionError(f"kmer stream covered {len(kmer)} of {nb} blocks")

    reads: list[bytes] = []
    n_tok = np.zeros(nb, np.int64)

    def check(batch):
        out = {k: np.asarray(batch.data[k]) for k in ("tokens", "n_tokens", "n_reads",
                                                       "read_start", "read_len")}
        for j, b in enumerate(np.asarray(batch.block_ids)):
            n_tok[b] = out["n_tokens"][j]
            want = np_kmer(out["tokens"][j], KMER_K, out["n_tokens"][j])
            require_equal(f"stream block {b} kmer vs 2bit", kmer[int(b)], want)
        reads.extend(reads_of(out["tokens"], out["n_reads"], out["read_start"],
                              out["read_len"]))

    sess.read_stream(NAME, check, fmt="2bit", mode="pipelined")
    if sorted(reads) != multiset(rs.reads):
        raise AssertionError("2bit stream is not lossless against the generated reads")

    before = trace_counts()
    store.reset_io_stats()
    t2 = time.perf_counter()
    sess.read_stream(NAME, lambda b: b.data["kmer"].block_until_ready(),
                     fmt="kmer", kmer_k=KMER_K, mode="pipelined")
    t3 = time.perf_counter()
    io = store.io_stats
    # a read of 4 resident blocks: the decode + format alone, no container I/O
    sess.read(NAME, (0, 4), "kmer", kmer_k=KMER_K)["kmer"].block_until_ready()
    t4 = time.perf_counter()
    for _ in range(10):
        sess.read(NAME, (0, 4), "kmer", kmer_k=KMER_K)["kmer"].block_until_ready()
    t_read4 = (time.perf_counter() - t4) / 10
    new = {k: v - before.get(k, 0) for k, v in trace_counts().items()
           if v != before.get(k, 0)}
    if new:
        raise AssertionError(f"second stream pass retraced: {new}")
    bases = int(n_tok.sum())
    print(f"stream: blocks={nb} reads={len(reads)} lossless, kmer == numpy pack of "
          f"2bit, second pass new traces=0 (trace counts {before})")
    resident = sum(int(a.nbytes) for a in jax.live_arrays())
    print(f"corpus: device_bytes_per_block={store.block_nbytes(NAME)} "
          f"device_bytes_all_blocks={nb * store.block_nbytes(NAME)} "
          f"live_device_bytes={resident}")
    print(f"info: first_stream_seconds={t1 - t0} (compiles included) "
          f"second_stream_seconds={t3 - t2} bases_per_second={bases / (t3 - t2)}")
    stages = ("io", "upload", "dispatch", "consume", "wall")
    print("info: second pass stage seconds "
          + " ".join(f"{k}={io[f'stream_{k}_seconds']}" for k in stages)
          + f"; resident 4-block kmer read seconds={t_read4}")
    return dict(kmer=kmer, n_tok=n_tok)


def phase_token_feed(store, streamed: dict) -> None:
    from repro.core.api import kmer_vocab_size
    from repro.data.pipeline import SageTokenPipeline

    kmer, n_tok = streamed["kmer"], streamed["n_tok"]
    flat = np.concatenate([kmer[b][: n_tok[b] // KMER_K] for b in range(len(n_tok))])
    batch, seq = 8, 2048
    need = batch * (seq + 1)

    def want(i):
        chunk = flat[i * need: (i + 1) * need].reshape(batch, seq + 1)
        return chunk[:, :-1], chunk[:, 1:]

    vocab = kmer_vocab_size(KMER_K)
    pipe = SageTokenPipeline(NAME, vocab, batch, seq, store=store)
    it = pipe.batches()
    got = [next(it)]
    state = pipe.state()
    got += [next(it), next(it)]
    pipe.close()
    for i, b in enumerate(got):
        tok, lab = want(i)
        require_equal(f"token feed batch {i} tokens", b["tokens"], tok)
        require_equal(f"token feed batch {i} labels", b["labels"], lab)
    resumed = SageTokenPipeline(NAME, vocab, batch, seq, store=store)
    resumed.restore(state)
    again = next(resumed.batches())
    resumed.close()
    require_equal("token feed after restore", again["tokens"], got[1]["tokens"])
    require_equal("token feed labels after restore", again["labels"], got[1]["labels"])
    print(f"token feed: k={KMER_K} batches=3 of {batch}x{seq} match the stream; "
          f"restore at cursor {state['cursor']} replays batch 1")


def phase_server(store, c: dict) -> None:
    from repro.serving import SageServer

    nb = c["sf"].meta.n_blocks
    sess = store.session()
    reads = [((0, 4), "2bit"), (_crossing(nb), "kmer"), ((nb - 3, nb), "onehot"),
             ((5, 9), "kmer")]
    srv = SageServer(store=store)
    try:
        hs = [srv.read(NAME, r, fmt, kmer_k=KMER_K) for r, fmt in reads]
        hstream = srv.stream(NAME, (8, 24), fmt="kmer", kmer_k=KMER_K, blocks_per_fetch=4)
        srv.run_until_idle()
        for h, (r, fmt) in zip(hs, reads):
            direct = sess.read(NAME, r, fmt, kmer_k=KMER_K)
            got = h.result()["data"]
            for key, v in direct.items():
                if key != "block_ids":
                    require_equal(f"server read {r} {fmt} {key}", got[key], v)
        chunks = list(hstream.chunks(timeout=0))
        if [c_["fetch"] for c_ in chunks] != [0, 1, 2, 3]:
            raise AssertionError(f"server stream chunks {[c_['fetch'] for c_ in chunks]}")
        for ch in chunks:
            direct = sess.read(NAME, ch["block_ids"], "kmer", kmer_k=KMER_K)
            require_equal(f"server stream {ch['block_ids']} kmer", ch["data"]["kmer"],
                          direct["kmer"])
    finally:
        srv.stop()
    print(f"server: {len(reads)} reads + 1 stream ({len(chunks)} chunks) match direct "
          f"session reads; batcher {srv.batcher.stats['rounds']} rounds")


def phase_sharded(c: dict) -> None:
    """Block-sharded store over four devices vs the single-device decode."""
    from repro.core import SageStore

    sharded = SageStore(shards=4)
    single = SageStore()
    for s in (sharded, single):
        s.register(NAME, c["path"])
    nb = c["sf"].meta.n_blocks
    keys = DECODE_KEYS + ("kmer",)
    lo, hi = _crossing(nb, before=5, after=11)
    a = sharded.session().read(NAME, (lo, hi), "kmer", kmer_k=KMER_K)
    b = single.session().read(NAME, (lo, hi), "kmer", kmer_k=KMER_K)
    for k in keys:
        require_equal(f"sharded read [{lo},{hi}) {k}", a[k], b[k])
    spread = {len(a[k].sharding.device_set) for k in keys}
    db, _ = sharded.prepared_for(NAME, np.arange(min(nb, GROUP_BLOCKS)))
    resident = {len(v.sharding.device_set) for v in db.arrays.values()}
    if spread != {4} or resident != {4}:
        raise AssertionError(f"sharded output on {spread}, residency on {resident} devices")

    want: dict[int, dict] = {}

    def keep(batch):
        out = {k: np.asarray(batch.data[k]) for k in keys}
        for j, bi in enumerate(np.asarray(batch.block_ids)):
            want[int(bi)] = {k: out[k][j] for k in keys}

    single.session().read_stream(NAME, keep, fmt="kmer", kmer_k=KMER_K, mode="pipelined")
    seen = set()

    def compare(batch):
        out = {k: np.asarray(batch.data[k]) for k in keys}
        for j, bi in enumerate(np.asarray(batch.block_ids)):
            for k in keys:
                require_equal(f"sharded stream block {bi} {k}", out[k][j], want[int(bi)][k])
            seen.add(int(bi))

    t0 = time.perf_counter()
    sharded.session().read_stream(NAME, compare, fmt="kmer", kmer_k=KMER_K, mode="pipelined")
    t1 = time.perf_counter()
    if seen != set(range(nb)):
        raise AssertionError(f"sharded stream covered {len(seen)} of {nb} blocks")
    print(f"sharded: read [{lo},{hi}) and pipelined stream of {nb} blocks bit-identical "
          f"to single-device decode; residency and output on 4 devices")
    print(f"info: sharded_stream_seconds={t1 - t0} (compiles included)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ref-len", type=int, default=DEFAULT_REF_LEN,
                    help="reference length in bases (30x Illumina reads over it)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the block-sharded store over four chips")
    args = ap.parse_args(argv)
    dev = device_check(args.chips)
    _import_repo()
    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    run(args)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


def run(args) -> None:
    """Every phase of the smoke on the current backend (no device check)."""
    from repro.core import SageStore

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        c = build_corpus(args, Path(tmp), hifi_set=args.chips == 1)
        if args.chips == 4:
            phase_sharded(c)
            return
        store = SageStore()
        store.register(NAME, c["path"])
        store.register(HIFI, c["hpath"])
        phase_ranged_reads(store, c)
        streamed = phase_stream(store, c)
        phase_token_feed(store, streamed)
        phase_server(store, c)


if __name__ == "__main__":
    sys.exit(main())
