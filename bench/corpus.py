"""The benchmark's corpus: a seeded genome, a read set sampled from it, and
the codec v2 container the store under test serves.

The read generator is a copy of the program's ``genomics/synth.py``
(reference with dispersed repeats, a donor with clustered SNPs, reads of the
sequencing profile's length distribution with its substitutions, indels,
bursts, N dropouts and chimeras), so the benchmark's inputs do not move when
the program's generator does. The three profiles are the sequencing
technologies of the SAGe paper's read sets (arXiv:2504.03732, Table 3):
Illumina short reads, PacBio HiFi and ONT long reads. The reads are the
ground truth the correctness check compares served output against.
"""

from __future__ import annotations

import dataclasses
import os
import time
from pathlib import Path

import numpy as np

# sequencing profiles (genomics/synth.py PROFILES, and its _qual_for's
# quality means): read length mean and sd, rates of substitution, insertion
# and deletion, geometric indel length, N dropouts, chimeras, error bursts,
# the ReadSet kind and the phred quality mean
PROFILES = {
    "illumina": dict(read_len=150, read_len_sd=0, sub_rate=0.001, ins_rate=0.0001,
                     del_rate=0.0001, indel_len_p=0.7, n_rate=0.0015, chimera_rate=0.0005,
                     burst_rate=0.002, burst_len=10, burst_sub_rate=0.15, kind="short",
                     qual=38),
    "hifi": dict(read_len=12000, read_len_sd=2500, sub_rate=0.004, ins_rate=0.003,
                 del_rate=0.003, indel_len_p=0.55, n_rate=0.001, chimera_rate=0.01,
                 burst_rate=0.0005, burst_len=20, burst_sub_rate=0.2, kind="long", qual=30),
    "ont": dict(read_len=8000, read_len_sd=3000, sub_rate=0.03, ins_rate=0.025,
                del_rate=0.025, indel_len_p=0.45, n_rate=0.002, chimera_rate=0.02,
                burst_rate=0.001, burst_len=30, burst_sub_rate=0.35, kind="long", qual=14),
}


def revcomp(codes: np.ndarray) -> np.ndarray:
    out = codes[::-1].copy()
    acgt = out < 4
    out[acgt] = 3 - out[acgt]
    return out


def make_reference(length: int, seed: int, repeat_fraction: float = 0.15,
                   repeat_unit: int = 300) -> np.ndarray:
    """Random genome with dispersed, lightly diverged repeat copies."""
    rng = np.random.default_rng(seed)
    ref = rng.integers(0, 4, length, dtype=np.int8).astype(np.uint8)
    for _ in range(int(length * repeat_fraction / max(repeat_unit, 1))):
        src = int(rng.integers(0, max(1, length - repeat_unit)))
        dst = int(rng.integers(0, max(1, length - repeat_unit)))
        seg = ref[src: src + repeat_unit].copy()
        nmut = rng.binomial(seg.size, 0.02)
        if nmut:
            at = rng.integers(0, seg.size, nmut)
            seg[at] = (seg[at] + rng.integers(1, 4, nmut)) % 4
        ref[dst: dst + seg.size] = seg
    return ref


def _donor(ref: np.ndarray, rng: np.random.Generator, snp_rate: float) -> np.ndarray:
    donor = ref.copy()
    for c in rng.integers(0, ref.size, max(1, int(ref.size * snp_rate / 3))):
        k = 1 + rng.geometric(0.45)
        idx = np.clip(c + np.unique(rng.integers(-60, 61, k)), 0, ref.size - 1)
        donor[idx] = (donor[idx] + rng.integers(1, 4, idx.size)) % 4
    return donor


def _apply_errors(seq: np.ndarray, p: dict, rng: np.random.Generator) -> np.ndarray:
    n = seq.size
    sub_p = np.full(n, p["sub_rate"])
    for s in rng.integers(0, max(1, n - p["burst_len"]), rng.binomial(n, p["burst_rate"])):
        sub_p[s: s + p["burst_len"]] = p["burst_sub_rate"]
    sub = rng.random(n) < sub_p
    out = seq.copy()
    if sub.any():
        out[sub] = (out[sub] + rng.integers(1, 4, int(sub.sum()))) % 4
    n_ins, n_del = rng.binomial(n, p["ins_rate"]), rng.binomial(n, p["del_rate"])
    events = [(int(rng.integers(1, max(2, n - 1))), "I", int(rng.geometric(p["indel_len_p"])))
              for _ in range(n_ins)]
    events += [(int(rng.integers(1, max(2, n - 1))), "D", int(rng.geometric(p["indel_len_p"])))
               for _ in range(n_del)]
    pieces, cursor = [], 0
    for pos, kind, length in sorted(events):
        if pos <= cursor:
            continue
        pieces.append(out[cursor:pos])
        if kind == "I":
            pieces.append(rng.integers(0, 4, min(length, 40)).astype(np.uint8))
            cursor = pos
        else:
            cursor = min(n, pos + min(length, 40))
    pieces.append(out[cursor:])
    res = np.concatenate(pieces)
    if rng.random() < p["n_rate"] and res.size > 4:
        res = res.copy()
        res[rng.integers(0, res.size, 1 + rng.geometric(0.5))] = 4
    return res


def sample_reads(ref: np.ndarray, profile: str, depth: float, seed: int, snp_rate: float
                 ) -> tuple[list[np.ndarray], list[np.ndarray], np.ndarray, np.ndarray]:
    """Reads (coded 0..4), phred+33 qualities, the reference position each
    read was sampled at, and which reads are chimeric (two segments from
    different loci, placed at the first), at ``depth`` over ``ref``."""
    p = PROFILES[profile]
    rng = np.random.default_rng(seed)
    donor = _donor(ref, rng, snp_rate)
    reads, quals, positions, chimeric, got = [], [], [], [], 0
    while got < int(ref.size * depth):
        L = p["read_len"] if p["read_len_sd"] == 0 else int(
            np.clip(rng.normal(p["read_len"], p["read_len_sd"]), 200, 4 * p["read_len"]))
        L = min(L, ref.size - 1)
        # the chimera draw is made for every read: short reads never take one
        chimera = rng.random() < p["chimera_rate"] and L >= 400
        if chimera:
            l1 = int(rng.integers(L // 4, 3 * L // 4))
            pos = int(rng.integers(0, ref.size - l1))
            p2 = int(rng.integers(0, ref.size - (L - l1)))
            frag = np.concatenate([donor[pos: pos + l1], donor[p2: p2 + (L - l1)]])
        else:
            pos = int(rng.integers(0, ref.size - L))
            frag = donor[pos: pos + L]
        if rng.random() < 0.5:
            frag = revcomp(frag)
        read = _apply_errors(frag, p, rng)
        if read.size < 20:
            continue
        reads.append(read)
        positions.append(pos)
        chimeric.append(chimera)
        quals.append(np.clip(rng.normal(p["qual"], 3, read.size), 2, 41).astype(np.uint8) + 33)
        got += read.size
    return reads, quals, np.asarray(positions, np.int64), np.asarray(chimeric, bool)


@dataclasses.dataclass
class Corpus:
    name: str
    path: Path  # the codec v2 container
    reads: list  # ground truth, coded uint8 arrays
    positions: np.ndarray  # the reference position each read was sampled at
    chimeric: np.ndarray  # which reads join two loci (placed at the first)
    kind: str  # "short" or "long" reads
    token_target: int  # the encoder's tokens per block
    bases: int
    container_bytes: int
    n_blocks: int
    seconds: dict  # host clock of each build step


def build(config: dict, workdir: Path) -> Corpus:
    """Generate the configuration's reads from its corpus seed and write them
    through the program's encoder and container writer, with the guarantees
    the configuration states (CRC32C on every extent, XOR parity, codec on).

    The corpus is the deployment's dataset, the same in every run: the
    encoder sizes every block's capacities, and with them every program's
    shapes, from the data, so a corpus drawn anew per run would compile anew
    and change the device's work per block. A run's seed draws its traffic."""
    from repro.core.encoder import SageEncoder
    from repro.core.layout import write_v2
    from repro.genomics.synth import ReadSet

    c = config["corpus"]
    g = config["guarantees"]
    p = PROFILES[c["profile"]]
    if c["read_length"] != p["read_len"]:
        raise ValueError(f"{config['name']}: corpus.read_length {c['read_length']} is not "
                         f"the {c['profile']} profile's mean read length {p['read_len']}")
    t0 = time.perf_counter()
    ref = make_reference(c["reference_length"], c["seed"])
    reads, quals, positions, chimeric = sample_reads(ref, c["profile"], c["depth"],
                                                     c["seed"] + 1, c["snp_rate"])
    t1 = time.perf_counter()
    rs = ReadSet(reads=reads, quals=quals, kind=p["kind"], profile=c["profile"])
    sf = SageEncoder(ref, token_target=c["token_target"]).encode(rs)
    t2 = time.perf_counter()
    path = workdir / f"{config['name']}.sage2"
    write_v2(sf, path, integrity=g["crc32c"], parity=g["parity"],
             parity_group=g["parity_group"], codec=g["codec"])
    t3 = time.perf_counter()
    return Corpus(
        name=config["name"], path=path, reads=reads, positions=positions, chimeric=chimeric,
        kind=p["kind"],
        token_target=c["token_target"],
        bases=int(sum(r.size for r in reads)),
        container_bytes=os.path.getsize(path), n_blocks=sf.meta.n_blocks,
        seconds={"synth": t1 - t0, "encode": t2 - t1, "write": t3 - t2},
    )
