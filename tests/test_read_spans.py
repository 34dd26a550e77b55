"""The read path's profiler spans: a pipelined stream over a lazy v2 store
with 8-block residency groups and 4-block fetches, traced with
``jax.profiler.trace`` and read back from the host plane of the
``.xplane.pb``."""

from collections import defaultdict
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import SageStore
from repro.core.encoder import SageEncoder
from repro.core.layout import write_v2
from repro.genomics.synth import make_reference, sample_read_set

GROUP_BLOCKS = 8
BLOCKS_PER_FETCH = 4
START = 2  # every other fetch (blocks 6-9, 14-17, ...) straddles two groups
SPANS = ("sage.store.gather", "sage.store.group_upload", "sage.read.decode",
         "sage.read.format", "sage.stream.io_wait")
CONSUMER = "test.consume"


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The stream's fetched block ids, and each host line's events as
    (name, start_ns, end_ns)."""
    ref = make_reference(30_000, seed=70)
    rs = sample_read_set(ref, "illumina", depth=3, seed=71)
    sf = SageEncoder(ref, token_target=2048).encode(rs)
    tmp = tmp_path_factory.mktemp("spans")
    write_v2(sf, tmp / "ds.sage2", align=512)
    assert sf.meta.n_blocks >= START + 3 * GROUP_BLOCKS
    store = SageStore(group_blocks=GROUP_BLOCKS)
    store.register("ds", str(tmp / "ds.sage2"))
    sess = store.session()
    fetched = []
    with jax.profiler.trace(str(tmp / "trace")):
        with jax.profiler.TraceAnnotation(CONSUMER):
            for batch in sess.read_stream("ds", fmt="kmer", kmer_k=4, start_block=START,
                                          blocks_per_fetch=BLOCKS_PER_FETCH,
                                          max_fetches=6, mode="pipelined"):
                batch.data["kmer"].block_until_ready()
                fetched.append(np.asarray(batch.block_ids))
    (xplane,) = (tmp / "trace").glob("plugins/profile/*/*.xplane.pb")
    lines = []
    for plane in ProfileData.from_file(str(xplane)).planes:
        if plane.name.startswith("/host:"):
            lines += [[(e.name, e.start_ns, e.end_ns) for e in line.events]
                      for line in plane.lines]
    return fetched, lines


def _by_name(events):
    out = defaultdict(list)
    for name, s, e in events:
        out[name].append((s, e))
    return out


def test_every_span_is_recorded(traced):
    _, lines = traced
    names = {name for events in lines for name, _, _ in events}
    assert set(SPANS) <= names


def test_one_gather_per_straddling_fetch(traced):
    fetched, lines = traced
    straddling = sum(len(set(ids // GROUP_BLOCKS)) > 1 for ids in fetched)
    gathers = sum(name == "sage.store.gather" for events in lines for name, _, _ in events)
    assert len(fetched) == 6 and straddling == 3
    assert gathers == straddling


def test_format_nests_in_decode_on_one_line(traced):
    _, lines = traced
    formats = 0
    for events in lines:
        spans = _by_name(events)
        for s, e in spans["sage.read.format"]:
            assert any(ds <= s and e <= de for ds, de in spans["sage.read.decode"])
            formats += 1
    assert formats > 0


def test_io_wait_is_on_the_consumer_line(traced):
    """The consumer's line is the one the test's own loop annotation is on;
    the stream's I/O worker thread never enters it."""
    _, lines = traced
    (consumer,) = [events for events in lines if any(n == CONSUMER for n, _, _ in events)]
    waits = [events for events in lines
             if any(n == "sage.stream.io_wait" for n, _, _ in events)]
    assert waits == [consumer]
    spans = _by_name(consumer)
    (outer,) = spans[CONSUMER]
    for name in SPANS:
        for s, e in spans[name]:
            assert outer[0] <= s and e <= outer[1], name
