"""Quickstart: compress a read set with SAGe into an out-of-core v2
block-extent container, decode it on-device through a SageStore session,
verify losslessness, and show the ranged-I/O win via ``io_stats``.

  PYTHONPATH=src python examples/quickstart.py
"""

import shutil
import sys
import tempfile
import time
import zlib
from pathlib import Path

sys.path.insert(0, "src")

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import SageStore
from repro.genomics.synth import make_reference, sample_read_set


def main() -> None:
    enable_compile_cache()
    print("=== SAGe quickstart ===")
    ref = make_reference(80_000, seed=7)
    rs = sample_read_set(ref, "illumina", depth=8, seed=8)
    raw = sum(r.size for r in rs.reads)
    print(f"read set: {rs.n_reads} reads, {raw/1e6:.2f} Mbases")

    store = SageStore(group_blocks=8, max_prepared=8)
    path = Path(tempfile.mkdtemp(prefix="sage_qs_")) / "quickstart.sage2"
    t0 = time.time()
    # SAGe_Write straight to the v2 block-extent container: the store
    # registers the *path*, so every read below is lazy ranged I/O
    sf = store.write("quickstart", rs, ref, token_target=16384,
                     layout="v2", path=path)
    comp = sf.compressed_bytes(include_consensus=False)
    gz = len(zlib.compress(b"".join(r.tobytes() for r in rs.reads), 9))
    print(f"compressed in {time.time()-t0:.1f}s -> {comp/1e3:.1f} KB "
          f"({raw/comp:.1f}x vs sequence bytes; zlib-9: {raw/gz:.1f}x) -> {path.name}")

    session = store.session()
    t0 = time.time()
    out = session.read("quickstart", fmt="kmer", kmer_k=4)  # SAGe_Read
    jax.block_until_ready(out["tokens"])
    t_c = time.time() - t0
    t0 = time.time()
    out = session.read("quickstart", fmt="kmer", kmer_k=4)
    jax.block_until_ready(out["tokens"])
    print(f"device decode: {raw/1e6/(time.time()-t0):.0f} Mbases/s "
          f"(first call incl. compile: {t_c:.2f}s)")

    # a ranged SAGe_Read returns exactly the whole-file slice — and on a
    # COLD store it reads only the covering extents, never the container
    nb = store.n_blocks("quickstart")
    cold = SageStore(group_blocks=2)
    cold.register("quickstart", path)
    part = cold.session().read("quickstart", (1, min(3, nb)))
    np.testing.assert_array_equal(
        np.asarray(part["tokens"]), np.asarray(out["tokens"])[1 : min(3, nb)]
    )
    io = cold.io_stats
    print(f"ranged read (1, {min(3, nb)}) matches whole-file decode")
    print(f"io_stats: header {io['header_bytes']/1e3:.1f} KB + "
          f"{io['extent_reads']} ranged read(s) = {io['extent_bytes_read']/1e3:.1f} KB "
          f"of {path.stat().st_size/1e3:.1f} KB container "
          f"({io['extent_bytes_read']/path.stat().st_size:.0%} touched)")

    # verify losslessness
    toks = np.asarray(out["tokens"])
    got = []
    for bi in range(nb):
        for r in range(int(np.asarray(out["n_reads"])[bi])):
            st = int(np.asarray(out["read_start"])[bi][r])
            ln = int(np.asarray(out["read_len"])[bi][r])
            got.append(toks[bi, st : st + ln].astype(np.uint8).tobytes())
    ok = sorted(got) == sorted(r.tobytes() for r in rs.reads)
    print(f"lossless roundtrip: {ok}")
    print(f"k-mer tokens ready for the model zoo: shape {out['kmer'].shape}")
    shutil.rmtree(path.parent, ignore_errors=True)
    assert ok


if __name__ == "__main__":
    main()
