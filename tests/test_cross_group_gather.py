"""Reads across residency groups: a lazy v2 store with 2-block groups
gathers the requested rows out of the covering groups in one jitted
dispatch, in request order. Every such read must be bit-identical to the
same read from the whole-file residency of an eager store and to the numpy
oracle, and the gather must compile once per (covering groups, bucket)."""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import SageStore, refdec
from repro.core.bitio import unpack_2bit
from repro.core.decode_jax import TRACE_COUNTS, _gather_group_rows_jit
from repro.core.encoder import SageEncoder
from repro.core.format import D
from repro.core.layout import write_v2
from repro.genomics.synth import make_reference, sample_read_set

GROUP_BLOCKS = 2
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (ids, covering groups): ragged lengths 1, 3 and 5, in and out of order
ID_SETS = [
    ([4], 1),
    ([1, 2, 3], 2),
    ([5, 0, 3], 3),
    ([3, 4, 5, 6, 7], 3),
    ([9, 8, 1, 0, 9], 2),
]
KEYS = ("tokens", "n_tokens", "n_reads", "read_pos", "read_rev", "read_start",
        "read_len", "read_corner", "kmer")


@pytest.fixture(scope="module")
def ds(tmp_path_factory):
    ref = make_reference(30_000, seed=70)
    rs = sample_read_set(ref, "illumina", depth=3, seed=71)
    sf = SageEncoder(ref, token_target=2048).encode(rs)
    path = tmp_path_factory.mktemp("xgroup") / "ds.sage2"
    write_v2(sf, path, align=512)
    assert sf.meta.n_blocks >= 8 * GROUP_BLOCKS
    eager = SageStore()
    eager.register("ds", sf)
    return sf, str(path), eager


def lazy_store(path, group_blocks=GROUP_BLOCKS):
    store = SageStore(group_blocks=group_blocks)
    store.register("ds", path)
    return store


def assert_matches_oracle(sf, ids, out):
    cons = unpack_2bit(sf.consensus2b, sf.meta.cons_len)
    for i, b in enumerate(ids):
        want = refdec.decode_block(sf, int(b), cons)
        n = int(out["n_reads"][i])
        assert n == len(want), b
        for r, w in enumerate(want):
            s, length = int(out["read_start"][i][r]), int(out["read_len"][i][r])
            np.testing.assert_array_equal(out["tokens"][i][s : s + length], w.seq)
            assert int(out["read_pos"][i][r]) == w.pos


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("ids,n_groups", ID_SETS)
def test_cross_group_read_matches_whole_file_and_oracle(ds, ids, n_groups, fused):
    sf, path, eager = ds
    store = lazy_store(path)
    assert len({b // GROUP_BLOCKS for b in ids}) == n_groups
    got = jax.tree.map(np.asarray, store.session(fused=fused).read(
        "ds", ids, fmt="kmer", kmer_k=4))
    want = jax.tree.map(np.asarray, eager.session(fused=fused).read(
        "ds", ids, fmt="kmer", kmer_k=4))
    for k in KEYS:
        assert got[k].shape[0] == len(ids), k
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)
    np.testing.assert_array_equal(got["block_ids"], ids)
    assert_matches_oracle(sf, ids, got)
    assert store.io_stats["cross_group_gathers"] == (n_groups > 1)


@pytest.mark.parametrize("ids,n_groups", ID_SETS)
def test_cross_group_residency_is_request_ordered_and_bucketed(ds, ids, n_groups):
    """The returned residency holds the bucket's rows, request order first,
    and the local rows are the first ``len(ids)`` of them."""
    sf, path, _ = ds
    db, local = lazy_store(path).prepared_for("ds", ids)
    if n_groups == 1:
        np.testing.assert_array_equal(local, np.asarray(ids) % GROUP_BLOCKS)
        return
    assert db.n_blocks == 1 << (len(ids) - 1).bit_length()
    np.testing.assert_array_equal(local, np.arange(len(ids)))
    starts = np.asarray(db.arrays["dir"])[:, D["cons_start"]]
    np.testing.assert_array_equal(starts[: len(ids)], sf.directory[ids, D["cons_start"]])
    assert (starts[len(ids):] == sf.directory[ids[0], D["cons_start"]]).all()


@pytest.mark.parametrize("ids,n_groups", ID_SETS)
def test_cross_group_consensus_windows(ds, ids, n_groups):
    sf, path, eager = ds
    store = lazy_store(path)
    wins, starts = store.consensus_windows("ds", ids)
    want_wins, want_starts = eager.consensus_windows("ds", ids)
    np.testing.assert_array_equal(wins, want_wins)
    np.testing.assert_array_equal(starts, want_starts)
    np.testing.assert_array_equal(starts, sf.directory[ids, D["cons_start"]])
    assert store.io_stats["cross_group_gathers"] == (n_groups > 1)


def test_ragged_ranges_share_one_compile_per_bucket(ds):
    _, path, _ = ds
    store = lazy_store(path)
    sess = store.session()
    _gather_group_rows_jit.clear_cache()
    before = TRACE_COUNTS["group_gather"]
    for ids in ([1, 2, 3], [2, 3, 4, 5], [7, 8], [6, 5, 4]):  # 2 groups, bucket 2-4
        sess.read("ds", ids, fmt="2bit")["tokens"].block_until_ready()
    assert TRACE_COUNTS["group_gather"] - before == 2  # buckets 4 and 2
    sess.read("ds", [9, 10, 11, 12, 13], fmt="2bit")["tokens"].block_until_ready()
    assert TRACE_COUNTS["group_gather"] - before == 3  # 3 groups, bucket 8
    assert store.io_stats["cross_group_gathers"] == 5


def test_wrapped_stream_traces_gather_once_per_shape(ds):
    """Three wrapped passes of a pipelined stream: the gather traces once
    per (covering groups, bucket) in the first pass and never again, and
    ``cross_group_gathers`` counts exactly the fetches that span groups."""
    sf, path, eager = ds
    nb, bpf, g = sf.meta.n_blocks, 3, 4  # some fetches inside one group
    store = lazy_store(path, group_blocks=g)
    sess = store.session()
    _gather_group_rows_jit.clear_cache()
    before = TRACE_COUNTS["group_gather"]
    first_pass = -(-nb // bpf)
    fetched, traced_after_first = [], None
    stream = sess.read_stream("ds", fmt="kmer", kmer_k=4, blocks_per_fetch=bpf,
                              wrap=True, mode="pipelined", start_block=nb - 4,
                              max_fetches=3 * first_pass)
    for i, batch in enumerate(stream):
        fetched.append(np.asarray(batch.block_ids))
        if i == first_pass - 1:
            traced_after_first = TRACE_COUNTS["group_gather"]
    assert len(fetched) * bpf >= 3 * nb
    shapes = {(len(set(ids // g)), 1 << (len(ids) - 1).bit_length())
              for ids in fetched if len(set(ids // g)) > 1}
    assert traced_after_first - before == len(shapes)
    assert TRACE_COUNTS["group_gather"] == traced_after_first
    straddling = sum(len(set(ids // g)) > 1 for ids in fetched)
    assert 0 < straddling < len(fetched)
    assert store.io_stats["cross_group_gathers"] == straddling
    # the stream's output is the eager store's on the same ids
    last = jax.tree.map(np.asarray, eager.session().read(
        "ds", fetched[-1], fmt="kmer", kmer_k=4))
    np.testing.assert_array_equal(np.asarray(batch.data["kmer"]), last["kmer"])


MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import sys
    import jax
    import numpy as np
    from repro.core import SageStore
    from repro.core.encoder import SageEncoder
    from repro.core.layout import write_v2
    from repro.genomics.synth import make_reference, sample_read_set

    assert len(jax.devices()) == 2
    ref = make_reference(12_000, seed=70)
    rs = sample_read_set(ref, "illumina", depth=3, seed=71)
    sf = SageEncoder(ref, token_target=2048).encode(rs)
    path = os.path.join(sys.argv[1], "ds.sage2")
    write_v2(sf, path, align=512)
    eager = SageStore()
    eager.register("ds", sf)
    store = SageStore(shards=2, group_blocks=3)  # groups pad to a stride of 4
    store.register("ds", path)
    for ids in ([4], [1, 2, 3], [2, 3, 6], [5, 0, 3, 7, 8], [2, 3, 4, 5, 6]):
        db, local = store.prepared_for("ds", ids)
        if len({b // 3 for b in ids}) > 1:
            assert db.n_blocks % 2 == 0 and list(local) == list(range(len(ids)))
            for v in db.arrays.values():
                assert len(v.sharding.device_set) == 2, v.sharding
        for fused in (False, True):
            got = store.session(fused=fused).read("ds", ids, fmt="kmer", kmer_k=4)
            want = eager.session(shards=1).read("ds", ids, fmt="kmer", kmer_k=4)
            for k in ("tokens", "n_reads", "read_pos", "read_start", "read_len", "kmer"):
                assert np.array_equal(np.asarray(got[k]), np.asarray(want[k])), (ids, k)
        wins, starts = store.consensus_windows("ds", ids)
        want_wins, want_starts = eager.consensus_windows("ds", ids)
        assert np.array_equal(wins, want_wins) and np.array_equal(starts, want_starts)
    # 4 id sets span groups, each read by prepared_for, 2 sessions and
    # consensus_windows
    assert store.io_stats["cross_group_gathers"] == 16, store.io_stats
    print("OK")
""")


def test_cross_group_read_on_a_two_device_mesh(tmp_path):
    """A block-sharded store (2 forced host devices, in a child process):
    the gathered residency stays evenly sharded and reads match the eager
    single-device store, fused and unfused, and in ``consensus_windows``."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"), JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", MESH_SCRIPT, str(tmp_path)],
                         capture_output=True, text=True, env=env, cwd=ROOT,
                         timeout=600)
    assert out.returncode == 0 and out.stdout.strip().endswith("OK"), out.stderr[-3000:]
