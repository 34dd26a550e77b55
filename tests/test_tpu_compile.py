"""Ahead-of-time compiles of the read path for a described TPU v5e.

The TPU compiler is installed even where no chip is attached, so these
tests lower and compile the main-path programs for one chip of a described
``v5e:2x2`` topology at real widths: 16Ki-token blocks from a real encode,
bucket sizes 8 and 64. A compile that passes here is not a chip run; it
proves only that the chip's compiler accepts the program and that it fits.

The XLA programs (vmap decode, fused vmap decode + k-mer format, codec
unpack, the store's cross-group gather) must compile. Every Pallas kernel is a strict xfail that records the
reason Mosaic refuses it today; a kernel that starts to compile turns its
xfail into an XPASS, which fails the suite until the test becomes a pass.
Only one-hot compiles, and only at one block per call.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.decode_jax import (
    DeviceBlocks,
    _HashableCaps,
    _gather_group_rows_jit,
    decode_blocks_bucketed,
    fused_decode_blocks_bucketed,
    prepare_block_arrays,
    unpack_block_rows,
)
from repro.core.encoder import SageEncoder
from repro.core.format import STREAMS
from repro.core.layout import SageContainerV2, write_v2
from repro.genomics.synth import make_reference, sample_read_set
from repro.kernels.reformat import _build_kmer_pack, _build_one_hot
from repro.kernels.sage_decode import (
    _build_fused_gather_decode,
    _build_pallas_decode,
    _build_pallas_unpack,
)

HBM_BYTES = 16 * 2**30  # one v5e chip
RESIDENT = 64  # resident blocks the gathers index into
KMER_K = 6
BUCKETS = (8, 64)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """A real short-read encode at 16Ki-token blocks, written as a codec v2
    container: its capacities, classes, row widths and codec geometry are
    the shapes the compiles use."""
    ref = make_reference(20_000, seed=41)
    rs = sample_read_set(ref, "illumina", depth=30, seed=42)
    sf = SageEncoder(ref, token_target=16384).encode(rs)
    path = tmp_path_factory.mktemp("tpu_compile") / "c.sage2"
    write_v2(sf, path)
    r = SageContainerV2(path)
    assert sf.meta.caps.tokens >= 16384
    return sf, r


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype), sharding=sharding)


def _resident_specs(sf, sharding, rows=RESIDENT):
    """Shapes of the device-resident block arrays (streams, cons, dir)."""
    host = prepare_block_arrays(sf, np.arange(1))
    return {k: _spec((rows,) + v.shape[1:], v.dtype, sharding) for k, v in host.items()}


def _device_blocks(sf, arrays):
    m = sf.meta
    return DeviceBlocks(
        arrays=arrays, caps=m.caps, classes=m.classes, fixed_len=m.fixed_read_len,
        n_blocks=RESIDENT, on_device=True,
    )


def _classes_key(sf):
    return tuple(sorted((k, tuple(v)) for k, v in sf.meta.classes.items()))


def _check_fits(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used} bytes do not fit one v5e chip"


# ------------------------------------------------------- XLA: must compile
@pytest.mark.parametrize("bucket", BUCKETS)
def test_vmap_decode_compiles(corpus, one_chip, bucket):
    sf, _ = corpus
    ids = np.arange(bucket - 3)  # pads up to ``bucket`` lanes, then slices

    def run(arrays):
        return decode_blocks_bucketed(_device_blocks(sf, arrays), ids)

    compiled = jax.jit(run).lower(_resident_specs(sf, one_chip)).compile()
    _check_fits(compiled)


@pytest.mark.parametrize("bucket", BUCKETS)
def test_fused_kmer_decode_compiles(corpus, one_chip, bucket):
    sf, _ = corpus
    ids = np.arange(bucket - 3)

    def run(arrays):
        return fused_decode_blocks_bucketed(
            _device_blocks(sf, arrays), ids, fmt_name="kmer", kmer_k=KMER_K,
        )

    compiled = jax.jit(run).lower(_resident_specs(sf, one_chip)).compile()
    _check_fits(compiled)


@pytest.mark.parametrize("bucket", BUCKETS)
def test_codec_unpack_compiles(corpus, one_chip, bucket):
    _, r = corpus
    widths = dict(r.layout.widths)

    def run(packed, dicts):
        return unpack_block_rows(packed, dicts, widths)

    compiled = jax.jit(run).lower(
        _spec((bucket, r._cap_words), np.uint32, one_chip),
        _spec(np.shape(r._codec_dicts), np.uint8, one_chip),
    ).compile()
    _check_fits(compiled)


@pytest.mark.parametrize("n_groups,bucket", [(2, 4), (3, 8)])
def test_cross_group_gather_compiles(corpus, one_chip, n_groups, bucket):
    """The store's one-dispatch gather across resident 8-block groups."""
    sf, _ = corpus
    groups = tuple(_resident_specs(sf, one_chip, rows=8) for _ in range(n_groups))
    where = _spec((2, bucket), np.int32, one_chip)
    compiled = _gather_group_rows_jit.lower(groups, where, mesh=None).compile()
    _check_fits(compiled)


# --------------------------------------- Pallas: Mosaic refuses each today
# Each refusal is matched by what Mosaic says, so a refusal for another
# reason fails the test outright instead of counting as the recorded xfail.
# At 8 blocks every kernel hits the (1, w) row BlockSpec; at one block per
# call the next refusal in each kernel body shows.
ROW_BLOCK = "last two dimensions of your block shape are divisible by 8 and 128"
GATHER_1D = "Only 2D gather is supported"
CUMSUM = "Unimplemented primitive in Pallas TPU lowering .*cumsum"
SHAPE_CAST = "unsupported shape cast"


class _MosaicRefusal(Exception):
    """The recorded refusal: what each strict xfail below expects."""


def _compiles(compile_fn, refusal) -> None:
    if refusal is None:
        compile_fn()
        return
    try:
        compile_fn()
    except Exception as e:  # noqa: BLE001 - re-raised, or turned into a failure
        if not re.search(refusal, str(e)):
            raise AssertionError(f"Mosaic refused for a new reason: {e}") from e
        raise _MosaicRefusal(str(e)) from e


def _grid(nb, refusal):
    """A (blocks per call, expected refusal) case; refused ones xfail."""
    if refusal is None:
        return pytest.param(nb, None, id=f"{nb}blk")
    mark = pytest.mark.xfail(
        strict=True, raises=_MosaicRefusal, reason=f"Mosaic: {refusal}"
    )
    return pytest.param(nb, refusal, marks=mark, id=f"{nb}blk")


@pytest.mark.parametrize("nb,refusal", [_grid(8, ROW_BLOCK), _grid(1, GATHER_1D)])
def test_pallas_decode_kernel_compiles(corpus, one_chip, nb, refusal):
    sf, _ = corpus
    host = prepare_block_arrays(sf, np.arange(1))
    names = list(STREAMS) + ["cons", "dir", "valid"]
    host["valid"] = np.ones((1, 1), np.int32)
    specs = [_spec((nb, host[n].shape[1]), host[n].dtype, one_chip) for n in names]
    run = _build_pallas_decode(
        _HashableCaps(sf.meta.caps), _classes_key(sf), sf.meta.fixed_read_len, nb,
        tuple(s.shape[1] for s in specs), tuple(names), False,
    )
    _compiles(lambda: run.lower(*specs).compile(), refusal)


@pytest.mark.parametrize("nb,refusal", [_grid(8, ROW_BLOCK), _grid(1, GATHER_1D)])
def test_pallas_fused_kmer_kernel_compiles(corpus, one_chip, nb, refusal):
    sf, _ = corpus
    names = list(STREAMS) + ["cons", "dir", "valid"]
    arrays = _resident_specs(sf, one_chip)
    shapes = tuple(arrays[n].shape[1] for n in names if n != "valid") + (1,)
    run = _build_fused_gather_decode(
        _HashableCaps(sf.meta.caps), _classes_key(sf), sf.meta.fixed_read_len, nb,
        shapes, tuple(names), "kmer", KMER_K, False,
    )
    ids = _spec((nb,), np.int32, one_chip)
    _compiles(lambda: run.lower(arrays, ids, ids).compile(), refusal)


@pytest.mark.parametrize("nb,refusal", [_grid(8, ROW_BLOCK), _grid(1, CUMSUM)])
def test_pallas_unpack_kernel_compiles(corpus, one_chip, nb, refusal):
    _, r = corpus
    wmap = dict(r.layout.widths)
    wt = tuple((s, int(wmap[s])) for s in STREAMS)
    run = _build_pallas_unpack(wt, r._cap_words, nb, False)
    _compiles(
        lambda: run.lower(
            _spec((nb, r._cap_words), np.uint32, one_chip),
            _spec((len(wt), 16), np.uint8, one_chip),
        ).compile(),
        refusal,
    )


@pytest.mark.parametrize("nb,refusal", [_grid(8, ROW_BLOCK), _grid(1, SHAPE_CAST)])
def test_pallas_kmer_kernel_compiles(corpus, one_chip, nb, refusal):
    sf, _ = corpus
    C = sf.meta.caps.tokens
    run = _build_kmer_pack(nb, C, KMER_K, True, False)
    _compiles(
        lambda: run.lower(
            _spec((nb, C), np.int8, one_chip), _spec((nb, 1), np.int32, one_chip),
        ).compile(),
        refusal,
    )


@pytest.mark.parametrize("nb,refusal", [_grid(8, ROW_BLOCK), _grid(1, None)])
def test_pallas_one_hot_kernel_compiles(corpus, one_chip, nb, refusal):
    sf, _ = corpus
    C = sf.meta.caps.tokens
    run = _build_one_hot(nb, C, False)
    _compiles(lambda: run.lower(_spec((nb, C), np.int8, one_chip)).compile(), refusal)
