"""The benchmark's corpus: the registered configurations' corpora stay
bit-for-bit what they were, and a configuration can name any of the three
sequencing profiles."""

from __future__ import annotations

import hashlib
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import corpus  # noqa: E402
import harness  # noqa: E402

# SHA-256 of the reads, qualities, sampled positions and container bytes,
# recorded before the long-read profiles were added: at the registered
# reference length (sampling only) and at 20 kb (built through the encoder)
FROZEN = {
    "rs1-illumina-30x": {
        "full": ("abff8c25526d89232a067d4f96b3c4b9cf97335eba06638f14c2bd73c2c6e2b3",
                 "3a985b259b91d832f428b0e13b0eb8070ce320b8b7e0dd295f8c097df1e33659",
                 "f545a5f515eeb6e5d02e8ca1e9ccd42f85474d6117576b164b571e216b92379e"),
        "20kb": ("6dbd189af29732fd0b3fea96c6d97db45609692fa69a158dc7c52b043917ee12",
                 "488e3d98484090e145181cd8bd0f1378a15c171cdf40b48a1a5523230995d2f2",
                 "2b77a16a36fd0f52a0ffa82ecaba158be2a42c27a180d5847fe9cfcac948818b",
                 "37a539695852315e9f3b5155ff2544f79a1ee13ca1b6619c3aa7fc0e5f38d746"),
    },
    "rs3-divergent-30x": {
        "full": ("9aaa5166eac101b705cedf8e2ac6a31823ae8dd5f78631dd4f9431cb3fed7953",
                 "106a46e7958f1e1f077b26510ce576cb668a56902d6919272df02dfa18171d7f",
                 "30a295723081ee8353b75f38c4794fd7c111d461b469fe2219bcf19779a5bdb4"),
        "20kb": ("e33112381aac135856bfd44dd70f70848389b1e5ee30722c8bd5a24ec69327cb",
                 "ad8aa28141e1baf039ed81e4dde13fc4c5902c7815006de1b28c070979be1a91",
                 "ec74ef95cd47d0dafbe8bec4d8b92b38d73b3f5dcdb5ed03441862a468e74c4f",
                 "4fb31c0a531e451c130d78df3fb8e1a546d3a0cd3e9fcf8ac44c3e1046c52dc5"),
    },
}


def digest(arrays) -> str:
    return hashlib.sha256(b"".join(len(a).to_bytes(4, "little") + a.tobytes()
                                   for a in arrays)).hexdigest()


def sampled(c: dict) -> tuple:
    ref = corpus.make_reference(c["reference_length"], c["seed"])
    reads, quals, positions, chimeric = corpus.sample_reads(
        ref, c["profile"], c["depth"], c["seed"] + 1, c["snp_rate"])
    assert not chimeric.any()  # short reads are never chimeric
    return (digest(reads), digest(quals),
            hashlib.sha256(positions.astype("<i8").tobytes()).hexdigest())


@pytest.mark.parametrize("name", sorted(FROZEN))
def test_registered_corpus_is_frozen(name, tmp_path):
    config = harness.load_json(BENCH / "configs" / f"{name}.json")
    assert sampled(config["corpus"]) == FROZEN[name]["full"]
    config["corpus"]["reference_length"] = 20_000
    built = corpus.build(config, tmp_path)
    assert built.kind == "short"
    container = hashlib.sha256(built.path.read_bytes()).hexdigest()
    assert sampled(config["corpus"]) + (container,) == FROZEN[name]["20kb"]
    assert digest(built.reads) == FROZEN[name]["20kb"][0]


@pytest.mark.parametrize("profile,qual", [("hifi", 30), ("ont", 14)])
def test_long_read_profile(profile, qual):
    p = corpus.PROFILES[profile]
    ref = corpus.make_reference(400_000, 3)
    reads, quals, positions, chimeric = corpus.sample_reads(ref, profile, 4, 4, 0.001)
    lengths = np.array([r.size for r in reads])
    # lengths drawn from a normal clipped to [200, 4 x mean], then the errors
    assert abs(np.median(lengths) - p["read_len"]) < 0.1 * p["read_len"]
    assert lengths.std() > 0.5 * p["read_len_sd"] and lengths.max() < 4.2 * p["read_len"]
    assert 0 < chimeric.sum() < 4 * p["chimera_rate"] * len(reads)
    assert abs(np.mean(np.concatenate(quals)) - 33 - qual) < 1  # phred, truncated
    assert all(0 <= x <= ref.size for x in positions)


def test_long_read_corpus_is_long(tmp_path):
    from repro.core.layout import open_container

    config = harness.load_json(BENCH / "tests" / "data" / "configs" / "tiny-hifi.json")
    config["corpus"].update(reference_length=3_000, depth=1)
    built = corpus.build(config, tmp_path)
    assert built.kind == "long" and open_container(built.path).meta.read_kind == "long"


def test_read_length_is_the_profile_mean(tmp_path):
    config = harness.load_json(BENCH / "configs" / "rs1-illumina-30x.json")
    config["corpus"]["read_length"] = 250
    with pytest.raises(ValueError, match="read_length 250"):
        corpus.build(config, tmp_path)
