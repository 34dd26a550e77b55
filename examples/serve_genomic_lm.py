"""Multi-tenant serving: mixed SAGe traffic through the SageServer frontend.

The paper's SAGe_Read/SAGe_ISP contract — decoded reads flow straight from
the store into the analysis system — served to many concurrent tenants:
ranged decodes, consensus windows, a streaming analysis feed, and genomic
LM continuations all share one scheduler, one continuous-batch loop, and
one device-resident store.

  PYTHONPATH=src python examples/serve_genomic_lm.py
"""

import sys
import time

sys.path.insert(0, "src")

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import get_arch
from repro.genomics.synth import make_reference, sample_read_set
from repro.models import lm
from repro.serving import SageServer, ServeConfig, ServingEngine, SessionPool


def main() -> None:
    enable_compile_cache()
    cfg = get_arch("qwen2-1.5b").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, ServeConfig(max_prompt=48, max_new=16))

    ref = make_reference(30_000, seed=31)
    rs = sample_read_set(ref, "illumina", depth=1, seed=32, max_reads=64)
    pool = SessionPool()
    pool.write("serve", rs, ref, token_target=8192)  # SAGe_Write
    srv = SageServer(pool, engine=eng)
    nb = pool.store.n_blocks("serve")

    # a mixed-tenant burst: decodes + consensus + a stream + 4 generations
    t0 = time.time()
    reads = [srv.read("serve", (0, 2), fmt="kmer", kmer_k=4) for _ in range(4)]
    cons = srv.consensus("serve")
    isp = srv.stream("serve", blocks_per_fetch=1, max_fetches=min(3, nb))
    gens = [
        srv.generate(dataset="serve", block_range=(b % nb, b % nb + 1),
                     max_prompt=48, kmer_k=3)
        for b in range(4)
    ]
    srv.run_until_idle()
    dt = time.time() - t0

    n_new = sum(g.result()["tokens"].size for g in gens)
    n_chunks = sum(1 for _ in isp.chunks(timeout=0))
    st = srv.stats()
    print(
        f"served {st['scheduler']['finished']} requests in {dt:.2f}s "
        f"(incl. compile): {len(reads)} reads, 1 consensus "
        f"({cons.result()['windows'].shape[0]} windows), {n_chunks} stream "
        f"chunks, {len(gens)} generations / {n_new} new tokens"
    )
    print(
        f"fused {st['batcher']['fused_read_requests']} read requests into "
        f"{st['batcher']['fused_reads']} decodes; prepared-LRU "
        f"{st['pool']['cache']['total']}"
    )

    # steady state: the same burst again — everything is resident + compiled
    t0 = time.time()
    for _ in range(4):
        srv.read("serve", (0, 2), fmt="kmer", kmer_k=4)
    srv.stream("serve", blocks_per_fetch=1, max_fetches=min(3, nb))
    srv.run_until_idle()
    print(f"steady-state burst: {time.time() - t0:.3f}s")


if __name__ == "__main__":
    main()
