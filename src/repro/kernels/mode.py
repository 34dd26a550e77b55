"""How the Pallas kernels run, chosen from the JAX backend.

The CPU backend has no Mosaic compiler, so there every kernel runs in
Pallas interpret mode: the validation bar that the test suite holds each
kernel to against its jnp twin. On any other backend a kernel has to lower
through Mosaic, and Mosaic refuses every kernel of the read path today
(``tests/test_tpu_compile.py`` records each refusal as a strict xfail). So a
session, batcher or store that asks for a Pallas path off the CPU raises
:class:`PallasUnavailableError` when it is built. It neither interprets on
the host nor switches to the jnp path unasked.

This module is the only place that decides interpret mode.
"""

from __future__ import annotations

from typing import Sequence

import jax

# the read path's Pallas kernels, by the names errors and the compile test use
DECODE = "sage_decode"  # kernels.sage_decode.sage_decode_arrays
FUSED = "sage_fused_decode"  # kernels.sage_decode._build_fused_gather_decode
UNPACK = "sage_unpack"  # kernels.sage_decode.sage_unpack_pallas
KMER = "kmer_pack"  # kernels.reformat.kmer_pack_pallas
ONE_HOT = "one_hot"  # kernels.reformat.one_hot_pallas


class PallasUnavailableError(RuntimeError):
    """A Pallas kernel path was requested on a backend it cannot compile for."""

    def __init__(self, kernels: Sequence[str], backend: str, requested_by: str) -> None:
        self.kernels = tuple(kernels)
        self.backend = backend
        super().__init__(
            f"{requested_by} needs the Pallas kernel(s) {', '.join(self.kernels)}, "
            f"which Mosaic refuses to compile for the {backend!r} backend "
            "(tests/test_tpu_compile.py records each refusal as a strict "
            "xfail); build it without the Pallas option to run the XLA path"
        )


def interpret_mode() -> bool:
    """True where Pallas kernels run in interpret mode: the CPU backend."""
    return jax.default_backend() == "cpu"


def require_pallas(kernels: Sequence[str], requested_by: str) -> None:
    """Raise :class:`PallasUnavailableError` unless ``kernels`` can run on
    the current backend (today: only in interpret mode, on the CPU)."""
    if not interpret_mode():
        raise PallasUnavailableError(kernels, jax.default_backend(), requested_by)
