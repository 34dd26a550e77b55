"""Codec primitives (DESIGN.md §11): property-based round-trips per layer,
deterministic golden bytes (format drift detection), and bit-identity of
the three decoders (numpy reference, jit/vmap, Pallas) on the same packed
payloads.

The hypothesis-based tests deepen the seeded ones in CI (where hypothesis
is installed); the seeded tests always run, so every property keeps local
coverage too."""

import numpy as np
import pytest

from repro.core import codec as C
from repro.core.bitio import (
    pack_bits,
    unpack_fields,
    zigzag_decode,
    zigzag_encode,
)
from repro.core.errors import IntegrityError
from repro.core.format import D, STREAMS
from repro.core.layout import SageContainerV2, crc32c, write_v2

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # container image ships without it; CI installs it
    HAVE_HYPOTHESIS = False

    def given(*a, **k):  # decorators must still evaluate; tests get skipped
        return lambda f: f

    settings = given

    class _St:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _St()

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS, reason="hypothesis not installed"
)


# --------------------------------------------------------------- primitives
def test_zigzag_roundtrip_seeded():
    rng = np.random.default_rng(11)
    vals = rng.integers(-(1 << 62), 1 << 62, 1000, dtype=np.int64)
    vals[:4] = (0, -1, 1, -(1 << 62))
    np.testing.assert_array_equal(zigzag_decode(zigzag_encode(vals)), vals)
    # small magnitudes get small codes (what makes delta coding pay off)
    assert list(zigzag_encode(np.array([0, -1, 1, -2, 2]))) == [0, 1, 2, 3, 4]


def test_pack_bits_roundtrip_seeded():
    rng = np.random.default_rng(12)
    for w in (1, 3, 7, 13, 31, 32):
        m = 257
        vals = rng.integers(0, 1 << w, m, dtype=np.uint64)
        words, nbits = pack_bits(vals, w)
        assert nbits == m * w
        starts = w * np.arange(m, dtype=np.int64)
        got = unpack_fields(words, starts, np.full(m, w, dtype=np.int64))
        np.testing.assert_array_equal(got, vals)


@needs_hypothesis
@settings(max_examples=50, deadline=None)
@given(
    st.lists(
        st.integers(min_value=-(1 << 62), max_value=1 << 62), max_size=200
    )
)
def test_zigzag_roundtrip_property(vals):
    arr = np.asarray(vals, dtype=np.int64)
    np.testing.assert_array_equal(zigzag_decode(zigzag_encode(arr)), arr)


@needs_hypothesis
@settings(max_examples=50, deadline=None)
@given(
    st.integers(min_value=1, max_value=32),
    st.lists(st.integers(min_value=0, max_value=(1 << 63) - 1), max_size=64),
)
def test_pack_bits_roundtrip_property(w, raw):
    vals = np.asarray(raw, dtype=np.uint64) & np.uint64((1 << w) - 1)
    words, _ = pack_bits(vals, w)
    starts = w * np.arange(vals.size, dtype=np.int64)
    got = unpack_fields(words, starts, np.full(vals.size, w, dtype=np.int64))
    np.testing.assert_array_equal(got, vals)


# ----------------------------------------------------------- binary tables
def test_i64_table_roundtrip_seeded():
    rng = np.random.default_rng(13)
    for n, c in ((0, 3), (1, 1), (57, 4)):
        tbl = rng.integers(-(1 << 40), 1 << 40, (n, c), dtype=np.int64)
        enc = C.encode_i64_table(tbl)
        np.testing.assert_array_equal(C.decode_i64_table(enc, n, c), tbl)
    # a column whose zigzag deltas exceed 32 bits takes the raw fallback
    wide = np.array([[0, 0], [1 << 40, 1], [3 << 40, 2]], dtype=np.int64)
    enc = C.encode_i64_table(wide)
    assert enc[12] == C._RAW64  # first column tag
    np.testing.assert_array_equal(C.decode_i64_table(enc, 3, 2), wide)


@needs_hypothesis
@settings(max_examples=40, deadline=None)
@given(
    st.integers(min_value=0, max_value=20),
    st.integers(min_value=1, max_value=5),
    st.data(),
)
def test_i64_table_roundtrip_property(n, c, data):
    flat = data.draw(
        st.lists(
            st.integers(min_value=-(1 << 62), max_value=1 << 62),
            min_size=n * c,
            max_size=n * c,
        )
    )
    tbl = np.asarray(flat, dtype=np.int64).reshape(n, c)
    np.testing.assert_array_equal(
        C.decode_i64_table(C.encode_i64_table(tbl), n, c), tbl
    )


def test_i64_table_golden_bytes():
    """Byte-exact encoding of a fixed table — catches silent format drift
    that round-trip tests cannot see (writer+reader drifting together)."""
    tbl = np.array(
        [[0, 512], [640, 512], [1280, 1024], [2304, 512]], dtype=np.int64
    )
    assert C.encode_i64_table(tbl).hex() == (
        "5347544204000000020000000c000000000000000000055000080000000b0002"
        "0000000000000000e0ff00000000"
    )
    big = np.array([[0], [1 << 40], [3 << 40]], dtype=np.int64)
    assert C.encode_i64_table(big).hex() == (
        "534754420300000001000000ff00000000000000000000000000010000000000"
        "0000030000"
    )


def test_i64_table_rejects_corruption():
    tbl = np.arange(12, dtype=np.int64).reshape(6, 2)
    enc = C.encode_i64_table(tbl)
    with pytest.raises(ValueError, match="bad magic"):
        C.decode_i64_table(b"XXXX" + enc[4:], 6, 2)
    with pytest.raises(ValueError, match="shape mismatch"):
        C.decode_i64_table(enc, 5, 2)
    with pytest.raises(ValueError, match="trailing"):
        C.decode_i64_table(enc + b"\x00", 6, 2)


# ------------------------------------------------------------ used words
def test_used_words_counts_and_fallback():
    widths = {s: 4 for s in STREAMS}
    nb = 3
    directory = np.zeros((nb, len(D)), dtype=np.int64)
    stream_bits = {}
    for s in STREAMS:
        # blocks own [0, 33), [33, 64), [64, 64) bits of each stream
        directory[:, D[f"off_{s}"]] = (0, 33, 64)
        stream_bits[s] = 64
    u = C.used_words(directory, stream_bits, widths)
    # 33 bits from 0 -> 2 words; 31 bits from 33 -> words 1..1 -> 1; empty -> 0
    np.testing.assert_array_equal(u[:, 0], (2, 1, 0))
    # non-monotonic offsets (never produced by the encoder) fall back to
    # the full row width — always safe for the masked decoder
    directory[1, D[f"off_{STREAMS[0]}"]] = 999999
    u = C.used_words(directory, stream_bits, widths)
    assert u[1, 0] == 4


# ------------------------------------------------- block payload round trip
def _random_case(seed, n):
    rng = np.random.default_rng(seed)
    widths, rows = {}, {}
    for i, s in enumerate(STREAMS):
        W = int(rng.integers(1, 7))
        widths[s] = W
        r = rng.integers(0, 1 << 32, (n, W), dtype=np.uint64).astype(np.uint32)
        if i % 2 == 0:  # half the streams get dictionary-friendly bytes
            r &= np.uint32(0x03030303)
        rows[s] = r
    used = np.stack(
        [rng.integers(0, widths[s] + 1, n) for s in STREAMS], axis=1
    ).astype(np.int64)
    dicts = C.build_stream_dicts({s: rows[s].ravel() for s in STREAMS})
    return widths, rows, used, dicts


def _pad_payloads(words, starts, nwords):
    n = nwords.size
    cap = int(nwords.max()) if n else C.DESC_WORDS
    packed = np.zeros((n, cap), dtype=np.uint32)
    for i in range(n):
        packed[i, : nwords[i]] = words[starts[i] : starts[i] + nwords[i]]
    return packed


def _assert_blocks_roundtrip(widths, rows, used, dicts):
    words, starts, nwords = C.encode_blocks(
        rows, used, C.nibble_luts(dicts)
    )
    assert np.all(nwords >= C.DESC_WORDS)
    packed = _pad_payloads(words, starts, nwords)
    dec = C.decode_blocks(packed, widths, dicts)
    for si, s in enumerate(STREAMS):
        m = np.arange(widths[s])[None, :] < used[:, si][:, None]
        np.testing.assert_array_equal(
            np.where(m, rows[s], 0), dec[s], err_msg=s
        )
        assert np.all(dec[s][~m] == 0), s  # tails decode to zero
    return packed


def test_encode_decode_blocks_roundtrip_seeded():
    both_modes = False
    for seed in range(5):
        packed = _assert_blocks_roundtrip(*_random_case(seed, 7))
        modes = (packed[:, : C.N_STREAMS] >> 20) & 3
        both_modes |= bool(modes.any() and (modes == 0).any())
    assert both_modes  # the seeds exercise both raw and nibble sections


@needs_hypothesis
@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=0, max_value=2**31), st.integers(1, 5))
def test_encode_decode_blocks_roundtrip_property(seed, n):
    _assert_blocks_roundtrip(*_random_case(seed, n))


def test_encode_blocks_golden():
    """Fixed input -> exact packed words (CRC-pinned) + section offsets."""
    n = 3
    rows = {
        s: (
            (np.arange(n * 4, dtype=np.uint32).reshape(n, 4)
             * np.uint32(si + 1) * np.uint32(2654435761)) & np.uint32(0x0F0F0F0F)
        )
        for si, s in enumerate(STREAMS)
    }
    used = np.tile(
        np.array([[4, 3, 2, 1, 0, 4, 3, 2, 1, 0, 4, 3, 2, 1]], np.int64),
        (n, 1),
    )
    dicts = C.build_stream_dicts({s: rows[s].ravel() for s in STREAMS})
    assert crc32c(dicts) == 0x750CD0A4
    words, starts, nwords = C.encode_blocks(rows, used, C.nibble_luts(dicts))
    assert starts.tolist() == [0, 47, 93]
    assert nwords.tolist() == [47, 46, 51]
    assert crc32c(words) == 0x3C47CFD9


# ------------------------------------- three decoders, one packed payload
def test_jit_and_pallas_decoders_match_host_reference():
    from repro.core.decode_jax import unpack_block_rows
    from repro.kernels.sage_decode import sage_unpack_pallas

    widths, rows, used, dicts = _random_case(99, 6)
    words, starts, nwords = C.encode_blocks(rows, used, C.nibble_luts(dicts))
    packed = _pad_payloads(words, starts, nwords)
    host = C.decode_blocks(packed, widths, dicts)
    jit = unpack_block_rows(packed, dicts, widths)
    pal = sage_unpack_pallas(packed, dicts, widths)
    for s in STREAMS:
        np.testing.assert_array_equal(host[s], np.asarray(jit[s]), err_msg=s)
        np.testing.assert_array_equal(host[s], np.asarray(pal[s]), err_msg=s)


# ------------------------------------------- consensus windows by reference
def test_consensus_window_corruption_detected(tmp_path):
    """Codec extents carry no consensus copy — a flipped byte in the shared
    section is caught by the per-window CRCs on gather (one re-read, then
    IntegrityError), not silently decoded into wrong bases."""
    from repro.core.encoder import SageEncoder
    from repro.genomics.synth import make_reference, sample_read_set

    ref = make_reference(12_000, seed=90)
    rs = sample_read_set(ref, "illumina", depth=2, seed=91)
    sf = SageEncoder(ref, token_target=2048).encode(rs)
    path = tmp_path / "ds.sage2"
    write_v2(sf, path)
    c = SageContainerV2.open(path)
    want = c.gather_consensus_windows(np.arange(2))
    w0 = int(c.directory[0, D["cons_start"]] // 16)
    off = c._cons_offset + 4 * w0 + 1
    pristine = path.read_bytes()
    data = bytearray(pristine)
    data[off] ^= 0x20
    path.write_bytes(bytes(data))
    c2 = SageContainerV2.open(path)
    with pytest.raises(IntegrityError, match="consensus window"):
        c2.gather_consensus_windows(np.arange(2))
    assert c2.io_stats["checksum_retries"] == 1
    # undamaged container decodes the same windows bit-identically
    path.write_bytes(pristine)
    np.testing.assert_array_equal(
        SageContainerV2.open(path).gather_consensus_windows(np.arange(2)),
        want,
    )
