"""Kernel mode from the backend, the typed Pallas refusal, and where the
compile cache goes.

The backend query is steered with monkeypatch: these tests run on the CPU,
and what a session does on a chip is decided by ``jax.default_backend()``.
"""

import types
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import compile_cache
from repro.core import SageStore
from repro.data.pipeline import SageTokenPipeline
from repro.genomics.synth import make_reference, sample_read_set
from repro.kernels import mode
from repro.serving import SageServer, SessionPool
from repro.serving.batching import ContinuousBatcher
from repro.serving.scheduler import Scheduler

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def store():
    ref = make_reference(8_000, seed=61)
    rs = sample_read_set(ref, "illumina", depth=1, seed=62)
    s = SageStore()
    s.write("ds", rs, ref, token_target=2048)
    return s


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


# ------------------------------------------------------------- the resolver
def test_interpret_mode_only_on_cpu(monkeypatch):
    assert jax.default_backend() == "cpu"
    assert mode.interpret_mode() is True
    mode.require_pallas([mode.DECODE], "a CPU session")  # no refusal on the CPU
    for backend in ("tpu", "gpu"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert mode.interpret_mode() is False


def test_pallas_kernels_still_run_interpreted_on_cpu(store):
    """On the CPU a use_pallas session builds and decodes bit-identically
    to the XLA path (the kernels pick interpret mode themselves)."""
    got = store.session(use_pallas=True).read("ds", (0, 2), fmt="kmer", kmer_k=4)
    want = store.session().read("ds", (0, 2), fmt="kmer", kmer_k=4)
    np.testing.assert_array_equal(np.asarray(got["kmer"]), np.asarray(want["kmer"]))


# ------------------------------------------- the typed refusal off the CPU
MAKERS = {
    "session": lambda s: s.session(use_pallas=True),
    "fused_session": lambda s: s.session(use_pallas=True, fused=True),
    "pool_session": lambda s: SessionPool(store=s).session(use_pallas=True),
    "batcher": lambda s: ContinuousBatcher(
        SessionPool(store=s), Scheduler(), use_pallas=True
    ),
    "server": lambda s: SageServer(store=s, use_pallas=True),
    "token_pipeline": lambda s: SageTokenPipeline(
        "ds", 259, 2, 16, store=s, use_pallas_decode=True
    ),
    "pallas_unpack_store": lambda s: SageStore(unpack_impl="pallas"),
}


@pytest.mark.parametrize("maker", sorted(MAKERS))
def test_use_pallas_off_cpu_is_refused_when_built(store, on_tpu, maker):
    with pytest.raises(mode.PallasUnavailableError) as ei:
        MAKERS[maker](store)
    err = ei.value
    assert err.backend == "tpu"
    assert err.kernels and all(k in str(err) for k in err.kernels)
    assert "tests/test_tpu_compile.py" in str(err)
    expected = {
        "fused_session": mode.FUSED, "pallas_unpack_store": mode.UNPACK,
    }.get(maker, mode.DECODE)
    assert expected in err.kernels


def test_xla_path_is_not_refused_off_cpu(store, on_tpu):
    sess = store.session()
    assert sess.use_pallas is False
    SageServer(store=store)
    SessionPool(store=store).session()


# ------------------------------------------------------------ compile cache
@pytest.fixture
def config_calls(monkeypatch):
    """Record what the helper would set, without turning the cache on in
    the test process."""
    calls = []
    fake = types.SimpleNamespace(
        config=types.SimpleNamespace(update=lambda *a: calls.append(a))
    )
    monkeypatch.setattr(compile_cache, "jax", fake)
    return calls


def test_compile_cache_placed_from_outside_sets_nothing(monkeypatch, config_calls):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert config_calls == []


def test_compile_cache_defaults_to_fixed_ignored_path(monkeypatch, config_calls):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert compile_cache.enable_compile_cache() == first  # fixed, not per run
    assert config_calls == [("jax_compilation_cache_dir", first)] * 2
    path = Path(first)
    assert path.parent == REPO
    ignored = (REPO / ".gitignore").read_text().split()
    assert f"{path.name}/" in ignored
