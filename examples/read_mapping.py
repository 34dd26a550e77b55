"""End-to-end genome analysis: SAGe-prepared reads -> in-framework read
mapper through the store's SAGe_ISP stream (the paper's integration
scenario: decompression feeds an analysis accelerator, with an
in-storage-filter-style exact-match pruning stage).

  PYTHONPATH=src python examples/read_mapping.py
"""

import sys
import time

sys.path.insert(0, "src")

from repro.compile_cache import enable_compile_cache
from repro.core import SageStore
from repro.genomics.mapper import map_store_reads
from repro.genomics.synth import make_reference, sample_read_set


def main() -> None:
    enable_compile_cache()
    print("=== SAGe -> read-mapping pipeline ===")
    ref = make_reference(60_000, seed=21)
    rs = sample_read_set(ref, "illumina", depth=3, seed=22)
    store = SageStore()
    store.write("mapping", rs, ref, token_target=16384)  # SAGe_Write
    session = store.session()

    t0 = time.time()
    out = session.read("mapping")  # whole-file SAGe_Read (warms the decoder)
    n_decoded = int(out["n_reads"].sum())
    print(f"decoded {n_decoded} reads in {time.time()-t0:.2f}s")

    # SAGe_ISP: stream decoded blocks into the mapper; reads whose decode
    # already carries an exact match position skip the expensive mapper
    # (GenStore-EM-style pruning)
    t0 = time.time()
    rep = map_store_reads(session, "mapping", ref, blocks_per_fetch=1)
    dt = time.time() - t0
    print(f"filter pruned {rep.pruned}/{rep.total} reads ({rep.pruned/rep.total:.0%}) — "
          f"mapper handled {rep.mapped}, unmapped {rep.unmapped}, in {dt:.1f}s")
    assert rep.total == n_decoded
    assert rep.pruned + rep.mapped > 0.9 * rep.total


if __name__ == "__main__":
    main()
