"""CPU rehearsal of the benchmark: every traffic kind runs in-process on a
tiny configuration, below the harness's look for a chip, and the check has
to see each fault planted in the timed path.

The tiny cells, their configurations, a traffic kind and a metric live under
``bench/tests/data``; the test lays them beside a copy of ``bench/`` and
adds their manifest entries, editing no file of the benchmark. Each
configuration's corpus is built once per module: a long read takes seconds
of host encode.
"""

from __future__ import annotations

import dataclasses
import json
import shutil
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

SEED = 5  # the traffic's seed; the tiny corpus (seed 1) holds a read with an N
KEYS = {"correct", "attempted", "failed", "metrics", "device"}
CELLS = ["tiny.stream-kmer", "tiny.restream-2bit", "tiny-hifi.stream-kmer",
         "tiny-ont.stream-kmer"]


@pytest.fixture(scope="module")
def built_once(tmp_path_factory):
    """``corpus.build`` with each configuration's container built once and
    copied into every run's work directory."""
    built = {}
    build = harness.corpus_mod.build

    def cached(config, workdir):
        name = config["name"]
        if name not in built:
            built[name] = build(config, tmp_path_factory.mktemp(name))
        path = Path(workdir) / built[name].path.name
        shutil.copy(built[name].path, path)
        return dataclasses.replace(built[name], path=path)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness.corpus_mod, "build", cached)
        yield


@pytest.fixture(scope="module")
def tree(tmp_path_factory, built_once):
    """A copy of bench/ with the test data added as new files, and the
    manifest with the test cells' entries added."""
    root = tmp_path_factory.mktemp("bench")
    shutil.copytree(BENCH, root, dirs_exist_ok=True, ignore=shutil.ignore_patterns("tests"))
    for sub in ("configs", "workloads", "metrics", "traffic"):
        for f in (DATA / sub).iterdir():
            assert not (root / sub / f.name).exists(), f"{f.name} would edit a benchmark file"
            shutil.copy(f, root / sub / f.name)
    manifest = harness.load_json(BENCH.parent / "BENCHMARK.json")
    extra = harness.load_json(DATA / "manifest_extra.json")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:  # the registered cells are all streams
            m["workloads"] += [c for c, k in extra["workloads"].items() if k == "stream"]
    manifest["end_to_end"] += extra["end_to_end"]
    manifest["per_layer"] += extra["per_layer"]
    return root, manifest


def run(tree, cell, **kw):
    root, manifest = tree
    lines = []
    res = harness.run_cell(cell, SEED, 1.0, False, manifest=manifest,
                           t_start=time.perf_counter(), bench=root, log=lines.append, **kw)
    json.dumps(res)  # the result line is plain JSON
    return res, lines


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct(tree, cell):
    res, lines = run(tree, cell)
    assert KEYS <= set(res) and list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"stream_bases_per_s", "container_bits_per_base", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["count"] == 1 and res["device"]["platform"] == "cpu"
    assert any("compiles_in_window=0 " in ln for ln in lines), lines
    assert lines[-len(res["checks"]):] == [
        f"check {k} = {v['value']} (limit {v['limit']})" for k, v in res["checks"].items()]


def test_added_kind_and_metric_are_found(tree):
    root, manifest = tree
    lines = []
    res = harness.run_cell("tiny.restream-2bit", SEED, 0.5, False, manifest=manifest,
                           t_start=time.perf_counter(), bench=root, log=lines.append)
    assert res["correct"] is True and "stream_bases_per_s" in res["metrics"]
    assert "window_fetches" in {m["name"] for m in
                                harness.metrics_for(manifest, "tiny.restream-2bit", "per_layer")}
    fetches = harness.load_module(root / "metrics" / "window_fetches.py").read(
        {"fetches": res["attempted"]})
    assert fetches == res["attempted"]


def test_control_is_not_correct(tree):
    res, _ = run(tree, "tiny.stream-kmer", control=True)
    assert res["correct"] is False
    assert res["checks"]["wrong_reads"]["value"] > 0


def _alter_token(out):
    t = np.asarray(out["tokens"]).copy()
    t[0, 0] = (t[0, 0] + 1) % 4
    return dict(out, tokens=jnp.asarray(t))


def _half_the_blocks(out):
    n = max(1, np.shape(out["tokens"])[0] // 2)
    return {k: v[:n] for k, v in out.items()}


def _neighbours(out):
    """Each block of a fetch answered with the next block's output."""
    return {k: jnp.roll(v, -1, axis=0) for k, v in out.items()}


@pytest.fixture
def fault(monkeypatch):
    """Plant a fault where the store's read path produces its output, armed
    when the measured window opens, so that set-up runs clean."""
    import jax

    from repro.core.store import SageReadSession

    armed = []
    annotation = jax.profiler.TraceAnnotation

    class Arming(annotation):
        def __init__(self, name, **kw):
            if name == "bench.window":
                armed.append(True)
            super().__init__(name, **kw)

    def plant(kind):
        real = SageReadSession._decode_prepared
        seen = []

        def broken(self, *a, **kw):
            out = real(self, *a, **kw)
            if not armed:
                return out
            if kind == "stale":  # every read returns the window's first output
                seen.append(out)
                return seen[0]
            return {"token": _alter_token, "half": _half_the_blocks,
                    "neighbour": _neighbours}[kind](out)

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Arming)
        monkeypatch.setattr(SageReadSession, "_decode_prepared", broken)

    return plant


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("kind", ["token", "half", "stale", "neighbour"])
def test_fault_is_not_correct(tree, fault, cell, kind):
    fault(kind)
    res, _ = run(tree, cell)
    assert res["correct"] is False, res["checks"]
