"""The PacBio HiFi configuration (SAGe's read set RS5) and its cell are well
formed: the cell loads, the corpus is the hifi profile's, every setting cut
from its default is listed under ``reduced``, and ``BENCHMARK.json`` reports
the cell's stream rate and the stream's per-layer metrics."""

from __future__ import annotations

import inspect
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import corpus  # noqa: E402
import harness  # noqa: E402

CELL, CONFIG = "rs5.stream-kmer", "rs5-hifi-30x"
STREAM_METRICS = ("stream_bases_per_s", "device_idle_share.stream", "device_ms_per_block.stream",
                  "decode_roofline.stream", "host_ms_per_fetch.stream")


def test_cell_loads_the_hifi_configuration():
    cell, config = harness.load_cell(CELL)
    assert (cell["config"], cell["chips"], cell["kind"]) == (CONFIG, 1, "stream")
    assert config["name"] == CONFIG and "RS5" in config["source"]
    c = config["corpus"]
    assert c["profile"] == "hifi" and c["read_length"] == corpus.PROFILES["hifi"]["read_len"]
    # the rest of the deployment is rs1's: depth, SNP rate, blocks, store, guarantees
    rs1 = harness.load_json(BENCH / "configs" / "rs1-illumina-30x.json")
    same = ("seed", "depth", "snp_rate", "token_target")
    assert {k: c[k] for k in same} == {k: rs1["corpus"][k] for k in same}
    assert (config["store"], config["guarantees"]) == (rs1["store"], rs1["guarantees"])
    assert cell["params"] == harness.load_json(BENCH / "workloads" / "rs1.stream-kmer.json")["params"]


def test_every_cut_is_listed_under_reduced():
    from repro.core import SageStore

    _, config = harness.load_cell(CELL)
    reduced = config["reduced"]
    defaults = {k: p.default for k, p in inspect.signature(SageStore).parameters.items()}
    cut = {k for k, v in config["store"].items() if v != defaults[k]} | {"reference_length"}
    assert set(reduced) == cut
    for k, r in reduced.items():
        run = config["corpus"][k] if k in config["corpus"] else config["store"][k]
        assert r["run"] == run and r["why"]
        if k in config["store"]:
            assert r["default"] == defaults[k]
    manifest = harness.load_json(BENCH.parent / "BENCHMARK.json")
    (entry,) = [e for e in manifest["configs"] if e["name"] == CONFIG]
    assert entry["file"] == f"bench/configs/{CONFIG}.json"
    assert sorted(entry["reduced"]) == sorted(reduced)


def test_benchmark_reports_the_cell():
    manifest = harness.load_json(BENCH.parent / "BENCHMARK.json")
    (w,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, "stream-kmer", 1)
    metrics = {m["name"]: m for m in manifest["end_to_end"] + manifest["per_layer"]}
    for name in STREAM_METRICS:
        assert metrics[name]["workloads"][-1] == CELL
    reported = {m["name"] for s in ("end_to_end", "per_layer")
                for m in harness.metrics_for(manifest, CELL, s)}
    assert reported == set(STREAM_METRICS) | {"container_bits_per_base", "setup_s"}
