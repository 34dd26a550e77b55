"""SAGe_Write's profiler spans and counters: a small HiFi encode, with a
planted chimera and a read holding an N, traced with ``jax.profiler.trace``
and read back from the host plane of the ``.xplane.pb``."""

from collections import Counter

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.encoder import SageEncoder
from repro.genomics.synth import ReadSet, make_reference, sample_read_set

SPANS = ("sage.encode.map", "sage.encode.align", "sage.encode.traceback",
         "sage.encode.fallback", "sage.encode.pack", "sage.encode.verify")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The encoder's stats, and how many times each host span was recorded."""
    ref = make_reference(10_000, seed=80)
    rs = sample_read_set(ref, "hifi", depth=1, max_reads=3, seed=81)
    with_n = rs.reads[0].copy()
    with_n[7] = 4
    reads = list(rs.reads) + [np.concatenate([ref[1_000:3_000], ref[6_000:8_000]]), with_n]
    rs = ReadSet(reads=reads, quals=[np.full(r.size, 40, np.uint8) for r in reads],
                 kind="long", profile="hifi")
    enc = SageEncoder(ref, token_target=8192)
    tmp = tmp_path_factory.mktemp("encode_spans")
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1  # annotations, not every op the DP's loops run
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    with jax.profiler.trace(str(tmp / "trace"), profiler_options=opts):
        enc.encode(rs)
    (xplane,) = (tmp / "trace").glob("plugins/profile/*/*.xplane.pb")
    counts = Counter(e.name for plane in ProfileData.from_file(str(xplane)).planes
                     if plane.name.startswith("/host:") for line in plane.lines
                     for e in line.events)
    return enc.stats, counts, len(reads)


def test_every_encode_span_is_recorded(traced):
    stats, counts, _ = traced
    assert all(counts[s] >= 1 for s in SPANS), {s: counts[s] for s in SPANS}
    assert counts["sage.encode.map"] == 1
    assert counts["sage.encode.pack"] == counts["sage.encode.verify"] == stats["verify_rounds"]


def test_span_counts_match_the_counters(traced):
    stats, counts, _ = traced
    assert counts["sage.encode.align"] == counts["sage.encode.traceback"] == stats["align_calls"]
    assert counts["sage.encode.fallback"] == stats["n_fallback"] == 1  # the N read


def test_counters_add_up(traced):
    stats, _, n_reads = traced
    assert stats["n_batch_mapped"] + stats["n_fallback"] == n_reads
    assert stats["align_lanes"] >= stats["n_batch_mapped"]
    assert stats["align_rows_padded"] >= stats["align_rows"] > 0
    assert stats["n_chimera_attempts"] >= 1  # the planted chimera
