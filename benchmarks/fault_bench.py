"""Fault-tolerance benchmark: detection rate, recovery latency, goodput.

Measures the integrity layer (DESIGN.md §9) end-to-end on a checksummed
v2 container using the ``repro.testing.faults`` harness:

  detection  N reversible single-bit-flip trials at random (block, byte,
             bit) extent offsets: every corrupted read must RAISE
             IntegrityError, and after undoing the flip the same range
             must decode bit-identically — corruption is never silently
             served. Gate: detection_rate == 1.0, silent wrong decodes == 0.
  recovery   per-read latency with one injected transient EIO (bounded
             retry re-opens + re-reads) vs fault-free, both cold-cache —
             the added milliseconds are the price of riding through a
             flaky medium. Gate: every faulted read recovers bit-identically.
  goodput    multi-tenant serving with ONE block group corrupted at rest:
             requests touching it abort with the typed error, everyone
             else completes with parity (goodput = finished/submitted
             == healthy fraction); then repair + re-register restores
             goodput to 1.0. Transient EIO during serving stays invisible
             (goodput 1.0, zero isolated failures).
  self-healing  the same trials on a PARITY container (DESIGN.md §10):
             every single-extent at-rest corruption is reconstructed in
             flight (zero failed requests, goodput 1.0, bit-identical —
             and ``clear_quarantine`` is never called) and the scrubber
             durably heals the medium; multi-extent damage beyond the
             parity budget still fails ONLY its tenants with the typed
             error and quarantines; the parity space overhead and the
             scrubber's rate-limit adherence are reported. Gates:
             repair_rate == 1.0, failed_requests == 0, unrecoverable
             damage quarantined + typed, scrub within its byte budget.

Contracts above are checked in --smoke (CI) and full mode alike; any
violation exits non-zero. Writes ``BENCH_fault.json`` (see README).
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

import jax

from repro.compile_cache import enable_compile_cache
from repro.core import SageStore, Scrubber
from repro.core.encoder import SageEncoder
from repro.core.errors import IntegrityError, SageIOError
from repro.core.layout import SageContainerV2, write_v2
from repro.genomics.synth import make_reference, sample_read_set
from repro.serving import SageServer, SessionPool
from repro.testing.faults import (
    FaultPlan,
    corrupt_extent,
    corrupt_extents,
    inject,
)


def pctl(xs, q):
    return float(np.percentile(np.asarray(xs, dtype=np.float64), q)) if xs else 0.0


def fresh_store(path: str, group_blocks: int) -> SageStore:
    store = SageStore(group_blocks=group_blocks)
    store.register("ds", path)
    return store


def read_range(store: SageStore, rng) -> np.ndarray:
    return np.asarray(store.session().read("ds", rng)["tokens"])


# ----------------------------------------------------------------- detection
def bench_detection(path: str, nb: int, gb: int, trials: int) -> dict:
    """Reversible bit-flip trials: flip -> read must raise -> undo ->
    read must be bit-identical to the pristine baseline."""
    rng = np.random.default_rng(7)
    baseline = read_range(fresh_store(path, gb), None)
    detected = silent_wrong = 0
    errors: dict[str, int] = {}
    for _ in range(trials):
        block = int(rng.integers(0, nb))
        undo = corrupt_extent(
            path, block, byte=int(rng.integers(0, 256)), bit=int(rng.integers(0, 8))
        )
        store = fresh_store(path, gb)
        group = block // gb
        try:
            got = read_range(store, (group * gb, min(nb, (group + 1) * gb)))
            want = baseline[group * gb : min(nb, (group + 1) * gb)]
            silent_wrong += not np.array_equal(got, want)
        except SageIOError as e:
            detected += 1
            errors[type(e).__name__] = errors.get(type(e).__name__, 0) + 1
        finally:
            undo()
        # repaired medium serves the full dataset bit-identically again
        if not np.array_equal(read_range(fresh_store(path, gb), None), baseline):
            silent_wrong += 1
    return {
        "trials": trials,
        "detected": detected,
        "detection_rate": detected / trials,
        "silent_wrong_decodes": silent_wrong,
        "errors_raised": errors,
    }


# ------------------------------------------------------------------ recovery
def bench_recovery(path: str, gb: int, trials: int) -> dict:
    """Cold-cache read latency, fault-free vs one transient EIO per read
    (a fresh store per trial defeats the host extent cache, so every trial
    really hits disk; ``meta`` primes the header open outside the timer)."""

    def timed_read(plan=None):
        store = fresh_store(path, gb)
        store.meta("ds")  # header open is not in the retry scope
        t0 = time.perf_counter()
        if plan is None:
            out = read_range(store, None)
        else:
            with inject(plan):
                out = read_range(store, None)
        return time.perf_counter() - t0, out, store.io_stats

    timed_read()  # warm the decode compile cache
    clean_s, baseline, _ = zip(*[timed_read() for _ in range(trials)])
    recovered, faulted_s, retries = 0, [], 0
    for _ in range(trials):
        dt, out, io = timed_read(FaultPlan(eio_reads=frozenset({0})))
        faulted_s.append(dt)
        recovered += np.array_equal(out, baseline[0])
        retries += io["read_retries"]
    p50_clean, p50_fault = pctl(clean_s, 50), pctl(faulted_s, 50)
    return {
        "trials": trials,
        "recovered": recovered,
        "read_retries": retries,
        "clean_read_p50_ms": 1e3 * p50_clean,
        "faulted_read_p50_ms": 1e3 * p50_fault,
        "recovery_overhead_ms": 1e3 * (p50_fault - p50_clean),
    }


# ------------------------------------------------------------------- goodput
def bench_goodput(path: str, nb: int, gb: int, tmp: Path) -> dict:
    """Serving throughput under damage: one corrupted group fails only its
    own tenants; repair restores full goodput; transient EIO costs nothing."""
    work = str(tmp / "goodput.sage2")
    shutil.copy(path, work)
    n_groups = nb // gb
    bad_group = 1
    undo = corrupt_extent(work, bad_group * gb, byte=9, bit=6)

    def serve(container: str, plan=None) -> tuple[int, int, SageServer]:
        pool = SessionPool(max_prepared=4, group_blocks=gb)
        pool.store.register("ds", container)
        pool.store.meta("ds")
        srv = SageServer(pool)
        hs = [srv.read("ds", (g * gb, (g + 1) * gb)) for g in range(n_groups)]
        if plan is None:
            srv.run_until_idle()
        else:
            with inject(plan):
                srv.run_until_idle()
        ok = bad = 0
        for h in hs:
            try:
                ok += h.result() is not None
            except SageIOError:
                bad += 1
        return ok, bad, srv

    clean = read_range(fresh_store(path, gb), None)
    ok, bad, srv = serve(work)
    parity = np.array_equal(
        np.asarray(srv.pool.session().read("ds", (0, gb))["tokens"]), clean[:gb]
    )
    degraded = {
        "submitted": n_groups,
        "finished": ok,
        "failed_typed": bad,
        "goodput": ok / n_groups,
        "expected_goodput": (n_groups - 1) / n_groups,
        "isolated_failures": srv.batcher.stats["isolated_failures"],
        "quarantined_groups": list(srv.health("ds")["quarantined_groups"]),
        "healthy_parity": bool(parity),
    }

    undo()  # repair + re-register -> full goodput again
    ok2, bad2, _ = serve(work)
    eio = FaultPlan(eio_reads=frozenset({0, 3}))
    ok3, bad3, srv3 = serve(work, plan=eio)
    return {
        "degraded": degraded,
        "after_repair": {"finished": ok2, "failed": bad2, "goodput": ok2 / n_groups},
        "transient_eio": {
            "finished": ok3, "failed": bad3, "goodput": ok3 / n_groups,
            "read_retries": srv3.pool.store.io_stats["read_retries"],
            "isolated_failures": srv3.batcher.stats["isolated_failures"],
        },
    }


# -------------------------------------------------------------- self-healing
def bench_self_healing(sf, nb: int, gb: int, tmp: Path, trials: int) -> dict:
    """ISSUE 8 acceptance: the same at-rest damage on a PARITY container.

    Single-extent trials serve with ZERO failed requests (in-flight
    reconstruction) and the scrubber then heals the medium durably —
    ``clear_quarantine`` is never called anywhere in this function.
    Multi-extent damage in one parity group (beyond the xor budget) still
    quarantines and fails only its own tenants with the typed error."""
    path = str(tmp / "healing.sage2")
    stats = write_v2(sf, path, align=512, parity="xor", parity_group=4)
    n_groups = -(-nb // gb)
    rng = np.random.default_rng(11)
    baseline = read_range(fresh_store(path, gb), None)

    def serve(container: str) -> tuple[int, int, SageServer]:
        pool = SessionPool(max_prepared=4, group_blocks=gb)
        pool.store.register("ds", container)
        srv = SageServer(pool)
        hs = [
            srv.read("ds", (g * gb, min(nb, (g + 1) * gb)))
            for g in range(n_groups)
        ]
        srv.run_until_idle()
        ok = bad = 0
        for h in hs:
            try:
                ok += h.result() is not None
            except SageIOError:
                bad += 1
        return ok, bad, srv

    healed = failed_requests = reconstructions = 0
    for _ in range(trials):
        block = int(rng.integers(0, nb))
        corrupt_extent(
            path, block, byte=int(rng.integers(0, 256)), bit=int(rng.integers(0, 8))
        )
        ok, bad, srv = serve(path)
        failed_requests += bad
        identical = np.array_equal(
            np.asarray(srv.pool.session().read("ds", None)["tokens"]), baseline
        )
        reconstructions += srv.pool.store.io_stats["reconstructions"]
        # the background sweep durably rewrites the damaged extent
        Scrubber(srv.pool.store, chunk_blocks=8).run_once()
        clean = SageContainerV2.open(path).verify_blocks() == []
        healed += (
            ok == n_groups and identical and clean
            and srv.health("ds")["ok"]
        )
    single = {
        "trials": trials,
        "healed": healed,
        "repair_rate": healed / trials,
        "failed_requests": failed_requests,
        "reconstructions": reconstructions,
        "clear_quarantine_calls": 0,  # structurally: never invoked here
    }

    # damage beyond the xor budget: two extents of parity group 0 (store
    # groups 0 and 1) — exactly those two tenants fail, typed + quarantined
    work = str(tmp / "healing_multi.sage2")
    shutil.copy(path, work)
    corrupt_extents(work, [0, 2], byte=9, bit=6)
    ok, bad, srv = serve(work)
    err_type = None
    try:
        srv.pool.session().read("ds", (0, gb))
    except SageIOError as e:
        err_type = type(e).__name__
    unrecoverable = {
        "submitted": n_groups,
        "finished": ok,
        "failed_typed": bad,
        "typed_error": err_type,
        "quarantined_groups": list(srv.health("ds")["quarantined_groups"]),
        "repair_attempts": srv.batcher.stats["repair_attempts"],
        "auto_repairs": srv.batcher.stats["auto_repairs"],
    }

    # scrub pacing on the (healed) container: a rate budget sized for a
    # ~0.15 s sweep must actually bound the effective bandwidth
    sweep_bytes = nb * SageContainerV2.open(path).stride_nbytes
    rate = sweep_bytes / 0.15
    scrub = Scrubber(fresh_store(path, gb), rate_bps=rate, chunk_blocks=4)
    sweep = scrub.run_once()
    scrub_rate = {
        "rate_budget_bps": rate,
        "bytes_scanned": sweep["bytes_scanned"],
        "elapsed_s": sweep["elapsed_s"],
        "effective_bps": sweep["effective_bps"],
        "within_budget": sweep["effective_bps"] <= 1.25 * rate,
        "complete": sweep["complete"],
        "findings": len(sweep["findings"]),
    }

    return {
        "parity": {
            "scheme": stats["parity"],
            "shards_per_group": stats["parity_shards"],
            "group_blocks": stats["parity_group"],
            "overhead": stats["parity_overhead"],
            "file_nbytes": stats["file_nbytes"],
        },
        "single_extent": single,
        "unrecoverable": unrecoverable,
        "scrub_rate": scrub_rate,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="tiny dataset, CI mode")
    ap.add_argument("--out", default="BENCH_fault.json")
    ap.add_argument("--trials", type=int, default=None)
    ap.add_argument("--ref-len", type=int, default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    ref_len = args.ref_len or (12_000 if args.smoke else 40_000)
    trials = args.trials or (6 if args.smoke else 25)
    gb = 2

    ref = make_reference(ref_len, seed=31)
    rs = sample_read_set(ref, "illumina", depth=3, seed=32)
    sf = SageEncoder(ref, token_target=2048).encode(rs)
    nb = sf.meta.n_blocks
    with tempfile.TemporaryDirectory() as td:
        tmp = Path(td)
        path = str(tmp / "fault.sage2")
        stats = write_v2(sf, path, align=512)
        report = {
            "config": {
                "smoke": args.smoke, "ref_len": ref_len, "trials": trials,
                "n_blocks": nb, "group_blocks": gb,
                "file_nbytes": stats["file_nbytes"],
                "checksum_nbytes": stats["checksum_nbytes"],
                "backend": jax.default_backend(),
            },
            "detection": bench_detection(path, nb, gb, trials),
            "recovery": bench_recovery(path, gb, trials),
            "goodput": bench_goodput(path, nb, gb, tmp),
            "self_healing": bench_self_healing(sf, nb, gb, tmp, trials),
        }

    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)

    d, r, g = report["detection"], report["recovery"], report["goodput"]
    print(
        f"detection x{d['trials']}: {100 * d['detection_rate']:.0f}% raised "
        f"({d['errors_raised']}), {d['silent_wrong_decodes']} silent wrong decodes"
    )
    print(
        f"recovery x{r['trials']}: {r['recovered']} recovered via "
        f"{r['read_retries']} retries; clean p50 {r['clean_read_p50_ms']:.1f}ms, "
        f"faulted p50 {r['faulted_read_p50_ms']:.1f}ms "
        f"(+{r['recovery_overhead_ms']:.1f}ms)"
    )
    gd = g["degraded"]
    print(
        f"goodput: degraded {gd['finished']}/{gd['submitted']} "
        f"({100 * gd['goodput']:.0f}%, quarantined {gd['quarantined_groups']}), "
        f"after repair {100 * g['after_repair']['goodput']:.0f}%, "
        f"under transient EIO {100 * g['transient_eio']['goodput']:.0f}%"
    )
    sh = report["self_healing"]
    se, un, sr = sh["single_extent"], sh["unrecoverable"], sh["scrub_rate"]
    print(
        f"self-healing x{se['trials']} ({sh['parity']['scheme']} parity, "
        f"+{100 * sh['parity']['overhead']:.1f}% space): "
        f"{100 * se['repair_rate']:.0f}% healed, {se['failed_requests']} failed "
        f"requests, {se['reconstructions']} in-flight reconstructions; "
        f"beyond-budget damage -> {un['failed_typed']}/{un['submitted']} typed "
        f"failures, quarantined {un['quarantined_groups']}; scrub "
        f"{sr['effective_bps'] / 1e6:.2f} MB/s vs budget "
        f"{sr['rate_budget_bps'] / 1e6:.2f} MB/s"
    )
    print(f"wrote {args.out}")

    ok = (
        d["detection_rate"] == 1.0
        and d["silent_wrong_decodes"] == 0
        and r["recovered"] == r["trials"]
        and gd["goodput"] == gd["expected_goodput"]
        and gd["isolated_failures"] >= 1
        and gd["healthy_parity"]
        and g["after_repair"]["goodput"] == 1.0
        and g["transient_eio"]["goodput"] == 1.0
        and g["transient_eio"]["isolated_failures"] == 0
        # --- self-healing gates (ISSUE 8) ---
        and se["repair_rate"] == 1.0
        and se["failed_requests"] == 0
        and se["clear_quarantine_calls"] == 0
        and un["failed_typed"] == 2  # exactly the two damaged store groups
        and un["finished"] == un["submitted"] - 2
        and un["typed_error"] == IntegrityError.__name__
        and len(un["quarantined_groups"]) >= 1
        and un["auto_repairs"] == 0  # beyond budget: nothing falsely healed
        and sr["within_budget"]
        and sr["complete"]
    )
    if not ok:
        print("GATE FAILURE", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
