"""Fused gather+unpack+reformat decode (ISSUE 10 tentpole tail).

A fused session (``store.session(fused=True)``) runs SAGe_Read as ONE
dispatch — gather, decode, and output formatting traced together (vmap) or
emitted as a single Pallas kernel — instead of the legacy two-step
decode-then-apply_format path. Contract: bit-identical results across all
registered formats x both decode paths x eager and codec-v2 sources, one
trace per shape bucket, and graceful fallback (custom formats without a
fuser, mesh-sharded sessions) to the two-step path.
"""

import numpy as np
import pytest

import repro.core.api as api
from repro.core import SageStore
from repro.core.api import FormatSpec, register_format
from repro.core.decode_jax import (
    TRACE_COUNTS,
    _FORMAT_FUSERS,
    fused_format_supported,
)
from repro.core.encoder import SageEncoder
from repro.core.layout import write_v2
from repro.genomics.synth import make_reference, sample_read_set

GROUP_BLOCKS = 2


@pytest.fixture(scope="module")
def sources(tmp_path_factory):
    """The same dataset as an eager SageFile and a codec v2 container."""
    ref = make_reference(24_000, seed=80)
    rs = sample_read_set(ref, "illumina", depth=3, seed=81)
    sf = SageEncoder(ref, token_target=2048).encode(rs)
    path = tmp_path_factory.mktemp("fused") / "ds.sage2"
    write_v2(sf, path, align=512)
    return sf, str(path)


def _store(src):
    store = SageStore(group_blocks=GROUP_BLOCKS)
    store.register("ds", src)
    return store


COMPARE_KEYS = {
    "2bit": ("tokens", "n_reads", "n_tokens", "read_start", "read_len", "read_pos"),
    "onehot": ("tokens", "n_reads", "n_tokens", "onehot"),
    "kmer": ("tokens", "n_reads", "n_tokens", "kmer"),
}


# ------------------------------------------------------------- bit identity
@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("fmt", ["2bit", "onehot", "kmer"])
@pytest.mark.parametrize("source", ["eager", "v2"])
def test_fused_matches_two_step(sources, source, fmt, use_pallas):
    sf, path = sources
    src = sf if source == "eager" else path
    span = (1, min(GROUP_BLOCKS + 3, sf.meta.n_blocks))  # straddles a group
    two = _store(src).session(use_pallas=use_pallas).read(
        "ds", span, fmt=fmt, kmer_k=4
    )
    fused = _store(src).session(use_pallas=use_pallas, fused=True).read(
        "ds", span, fmt=fmt, kmer_k=4
    )
    for key in COMPARE_KEYS[fmt]:
        a, b = np.asarray(two[key]), np.asarray(fused[key])
        assert a.dtype == b.dtype, key
        np.testing.assert_array_equal(a, b, err_msg=key)
    np.testing.assert_array_equal(two["block_ids"], fused["block_ids"])


# ------------------------------------------------------------- compile once
@pytest.mark.parametrize("use_pallas,counter",
                         [(False, "fused_vmap"), (True, "fused_pallas")])
def test_fused_compiles_once_per_bucket(sources, use_pallas, counter):
    sf, _ = sources
    sess = _store(sf).session(use_pallas=use_pallas, fused=True)
    sess.read("ds", (0, 2), fmt="kmer", kmer_k=4)  # warm this bucket
    before = TRACE_COUNTS[counter]
    sess.read("ds", (2, 4), fmt="kmer", kmer_k=4)  # same bucket, new ids
    sess.read("ds", (1, 3), fmt="kmer", kmer_k=4)
    assert TRACE_COUNTS[counter] == before


# ----------------------------------------------------------------- fallback
def test_unregistered_format_falls_back_to_two_step(sources):
    """A custom FormatSpec without a fuser must still work on a fused
    session — via the legacy two-step path — and match a plain session."""
    sf, _ = sources

    def apply_rc(tokens, *, kmer_k=None, use_pallas=False, n_tokens=None):
        return tokens[..., ::-1]

    register_format(FormatSpec("revtok", "revtok", apply_rc, doc="test-only"))
    try:
        assert not fused_format_supported("revtok")
        plain = _store(sf).session().read("ds", (0, 2), fmt="revtok")
        fused = _store(sf).session(fused=True).read("ds", (0, 2), fmt="revtok")
        np.testing.assert_array_equal(
            np.asarray(plain["revtok"]), np.asarray(fused["revtok"])
        )
    finally:
        api._FORMATS.pop("revtok", None)
        _FORMAT_FUSERS.pop("revtok", None)


def test_fused_requires_k_error_matches_two_step(sources):
    sf, _ = sources
    with pytest.raises(ValueError, match="requires kmer_k"):
        _store(sf).session(fused=True).read("ds", (0, 2), fmt="kmer")
    with pytest.raises(ValueError, match="requires kmer_k"):
        _store(sf).session().read("ds", (0, 2), fmt="kmer")


def test_builtin_formats_have_fusers():
    for fmt in ("2bit", "onehot", "kmer"):
        assert fused_format_supported(fmt)
