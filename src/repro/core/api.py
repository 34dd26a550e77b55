"""SAGe interface commands (§5.3 analogue) + the output-format registry.

The paper exposes three NVMe commands; our TPU framework exposes them as a
session-based streaming API (:mod:`repro.core.store`):

  SAGe_Write -> ``SageStore.write`` / ``SageReadSession.write``
  SAGe_Read  -> ``SageReadSession.read(name, block_range, fmt)`` — ranged,
                batched decode to any registered :class:`FormatSpec`
  SAGe_ISP   -> ``SageReadSession.read_stream(name, consumer)`` — decoded
                blocks are handed to an analysis-side consumer as soon as
                they are ready (mapper / filter / LM pipeline / serving)

This module holds the pieces that are *format math*, the pluggable
:class:`FormatSpec` registry, and the one-shot ``sage_write``/``sage_read``
convenience wrappers. Multi-dataset state, ranged reads, and streaming live
in :class:`repro.core.store.SageStore`; all consumers outside ``core/`` go
through the store, never through the raw decoders.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.decode_jax import (
    PAD_BASE,
    DeviceBlocks,
    decode_blocks_bucketed,
    prepare_device_blocks,
    register_format_fuser,
)
from repro.core.encoder import SageEncoder
from repro.core.format import SageFile
from repro.genomics.synth import ReadSet


class OutputFormat(enum.Enum):
    """Legacy closed enum — retained as an alias set over the open
    :class:`FormatSpec` registry (``get_format`` accepts either)."""

    TOKENS_2BIT = "2bit"  # int8 base codes 0..3 (PAD_BASE padding)
    ONE_HOT = "onehot"  # (.., 4) bfloat16 one-hot (paper cites [106])
    KMER = "kmer"  # packed k-mer LM token ids (maps onto arch vocabs)


# -- k-mer token space ------------------------------------------------------
def kmer_vocab_size(k: int) -> int:
    return 4**k + 3  # + PAD, BOS, NBLK


def kmer_special_ids(k: int) -> dict[str, int]:
    return {"pad": 4**k, "bos": 4**k + 1, "nblk": 4**k + 2}


def pick_k(vocab_size: int, max_k: int = 8) -> int:
    """Largest k with 4^k + specials <= vocab (how arch vocabs map to DNA)."""
    k = 1
    while k < max_k and kmer_vocab_size(k + 1) <= vocab_size:
        k += 1
    return k


def kmer_pack(tokens: jax.Array, k: int, n_tokens: Optional[jax.Array] = None) -> jax.Array:
    """Pack base tokens (.., C) into k-mer ids (.., C//k).

    Code 4 is both PAD (the token axis past each row's real length) and N
    (dropouts inside escape reads). ``n_tokens`` — the per-row real-token
    count from the decode dict, shape ``tokens.shape[:-1]`` — disambiguates:
    a 4-containing group entirely inside ``n_tokens`` maps to the N-block
    id, while groups at or past the boundary map to the pad id. Pad ids are
    therefore confined to each row's tail and exactly ``n_tokens // k``
    leading groups are real — the deterministic per-block k-mer count the
    streaming pipeline's cursor math and device-side PAD filter rely on.

    Without ``n_tokens`` the two cases are indistinguishable and every
    4-containing group maps to the pad id (legacy one-shot behavior).
    Pure-jnp reference for the reformat kernel."""
    C = tokens.shape[-1]
    g = tokens[..., : (C // k) * k].reshape(*tokens.shape[:-1], C // k, k).astype(jnp.int32)
    weights = (4 ** jnp.arange(k, dtype=jnp.int32))[::-1]
    ids = jnp.sum(jnp.where(g > 3, 0, g) * weights, axis=-1)
    sp = kmer_special_ids(k)
    has4 = jnp.any(g == PAD_BASE, axis=-1)  # PAD_BASE == 4 == N code
    if n_tokens is None:
        return jnp.where(has4, sp["pad"], ids)
    gi = jnp.arange(C // k, dtype=jnp.int32)
    in_read = (gi + 1) * k <= jnp.asarray(n_tokens, jnp.int32)[..., None]
    return jnp.where(has4, jnp.where(in_read, sp["nblk"], sp["pad"]), ids)


def one_hot_bases(tokens: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    """(.., C) -> (.., C, 4); PAD rows are all-zero."""
    t = tokens.astype(jnp.int32)
    return (t[..., None] == jnp.arange(4, dtype=jnp.int32)).astype(dtype)


# -- output-format registry -------------------------------------------------
@dataclasses.dataclass(frozen=True)
class FormatSpec:
    """One SAGe_Read output format.

    ``apply(tokens, *, kmer_k, use_pallas, n_tokens)`` converts
    decoded base tokens into the format's array (``n_tokens`` is the decode
    dict's per-row real-token count, for formats that must tell tail PAD
    from in-read N); ``None`` means the raw 2-bit tokens are already the
    answer. New formats register via :func:`register_format`."""

    name: str  # registry key (the ``fmt=`` string)
    out_key: str  # key the formatted array appears under in the read result
    apply: Optional[Callable[..., jax.Array]] = None
    requires_k: bool = False
    doc: str = ""


def _apply_one_hot(tokens, *, kmer_k=None, use_pallas=False, n_tokens=None):
    if use_pallas:
        from repro.kernels.reformat import one_hot_pallas

        return one_hot_pallas(tokens)
    return one_hot_bases(tokens)


def _apply_kmer(tokens, *, kmer_k, use_pallas=False, n_tokens=None):
    if use_pallas:
        from repro.kernels.reformat import kmer_pack_pallas

        return kmer_pack_pallas(tokens, kmer_k, n_tokens)
    return kmer_pack(tokens, kmer_k, n_tokens)


_FORMATS: dict[str, FormatSpec] = {}


def register_format(spec: FormatSpec, *, replace: bool = False) -> FormatSpec:
    """Register an output format; returns the spec.

    A name collision raises ``ValueError`` unless ``replace=True`` — silent
    replacement would let a plugin shadow a built-in format and change the
    meaning of every consumer's ``fmt=`` string."""
    if spec.name in _FORMATS and not replace:
        raise ValueError(
            f"output format {spec.name!r} is already registered; pass "
            f"replace=True to override it (registered: {available_formats()})"
        )
    _FORMATS[spec.name] = spec
    return spec


def available_formats() -> tuple[str, ...]:
    return tuple(sorted(_FORMATS))


def get_format(fmt) -> FormatSpec:
    """Resolve ``fmt`` — a registry name, :class:`FormatSpec`, or legacy
    :class:`OutputFormat` member — to its spec."""
    if isinstance(fmt, FormatSpec):
        return fmt
    key = fmt.value if isinstance(fmt, OutputFormat) else str(fmt)
    if key not in _FORMATS:
        raise ValueError(f"unknown output format {key!r}; registered: {available_formats()}")
    return _FORMATS[key]


def apply_format(
    out: dict[str, jax.Array],
    fmt,
    *,
    kmer_k: Optional[int] = None,
    use_pallas: bool = False,
    context: str = "sage_read",
) -> dict[str, jax.Array]:
    """Attach ``fmt``'s array to a decode result dict (in place) and return it."""
    spec = get_format(fmt)
    if spec.requires_k and kmer_k is None:
        raise ValueError(
            f"{context}: format {spec.name!r} requires kmer_k "
            f"(registered formats: {available_formats()})"
        )
    if spec.apply is not None:
        out[spec.out_key] = spec.apply(
            out["tokens"], kmer_k=kmer_k, use_pallas=use_pallas,
            n_tokens=out.get("n_tokens"),
        )
    return out


register_format(FormatSpec("2bit", "tokens", None, doc="int8 base codes 0..3, PAD=4"))
register_format(FormatSpec("onehot", "onehot", _apply_one_hot, doc="(.., C, 4) bf16 one-hot"))
register_format(FormatSpec("kmer", "kmer", _apply_kmer, requires_k=True, doc="packed k-mer LM ids"))

# fusers for the single-dispatch decode+format path (fused sessions): pure
# jnp over the padded decode dict, traced inside the fused jit/kernel —
# same expressions as the two-step appliers above, so output is
# bit-identical. Custom registered formats without a fuser simply take the
# two-step path.
register_format_fuser("2bit", "tokens", None)
register_format_fuser("onehot", "onehot", lambda dec, kmer_k: one_hot_bases(dec["tokens"]))
register_format_fuser("kmer", "kmer", lambda dec, kmer_k: kmer_pack(dec["tokens"], kmer_k, dec["n_tokens"]))


# -- one-shot commands (compat wrappers; consumers use SageStore) -----------
def sage_write(
    rs: ReadSet,
    consensus: np.ndarray,
    token_target: int = 65536,
    **enc_kwargs,
) -> SageFile:
    """Compress a read set against a consensus (SAGe_Write)."""
    enc = SageEncoder(consensus, token_target=token_target, **enc_kwargs)
    return enc.encode(rs)


def sage_read(
    sf_or_db: SageFile | DeviceBlocks,
    fmt="2bit",
    kmer_k: Optional[int] = None,
) -> dict[str, jax.Array]:
    """Decode all blocks to the requested format (SAGe_Read, one-shot form).

    Kept for core-internal and throwaway use; persistent consumers open a
    :class:`repro.core.store.SageReadSession` instead. Routes through the
    same power-of-two shape buckets as the store sessions, so one-shot and
    session reads share jit cache entries."""
    db = sf_or_db if isinstance(sf_or_db, DeviceBlocks) else prepare_device_blocks(sf_or_db)
    db = db.to_device()
    out = decode_blocks_bucketed(db, np.arange(db.n_blocks, dtype=np.int64))
    return apply_format(dict(out), fmt, kmer_k=kmer_k)
