"""One run of one cell: build the corpus, set up the traffic, measure the
window, check the answers, and compute the cell's metrics.

Everything that belongs to one cell, configuration, traffic kind or metric
sits in a file of its own, found by name:

    bench/workloads/<cell>.json   configuration, traffic kind and its params
    bench/configs/<config>.json   the deployment: corpus, store, guarantees
    bench/traffic/<kind>.py       ``Run(config, params, corpus, seed)`` with
                                  ``setup()``, ``measure(seconds)``, ``close()``
    bench/metrics/<metric>.py     ``read(m)``: the metric from the run's
                                  observations ``m``, or None where it finds
                                  nothing to read

Which metrics a cell reports comes from ``BENCHMARK.json``: the end-to-end
ones without ``--trace``, the per-layer ones with it.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import resource
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import corpus as corpus_mod  # noqa: E402
import oracle  # noqa: E402
import peaks  # noqa: E402
import trace_reduce  # noqa: E402


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    name = f"bench_{path.parent.name}_{path.stem}".replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, bench: Path = BENCH) -> tuple[dict, dict]:
    """The cell's file and its configuration's file."""
    cell = load_json(bench / "workloads" / f"{name}.json")
    return cell, load_json(bench / "configs" / f"{cell['config']}.json")


def metrics_for(manifest: dict, cell: str, section: str) -> list:
    """The entries of ``section`` that ``cell`` reports."""
    e2e = {m["name"] for m in manifest["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]}
    out = []
    for m in manifest[section]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


class GcLog:
    """Host times of the interpreter's garbage collections, so that a pause
    inside the window shows."""

    def __init__(self) -> None:
        self.pauses: list = []  # (start, seconds, generation)
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((self._t0, time.perf_counter() - self._t0, info["generation"]))

    def between(self, t0: float, t1: float) -> list:
        return [(s, g) for t, s, g in self.pauses if t0 <= t <= t1]

    def close(self) -> None:
        gc.callbacks.remove(self._on)


class CompileLog:
    """Host times of the program compilations JAX asks for (persistent cache
    loads included), so that any inside the window shows."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax

        self.times: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, fun_name: str = "?", **_) -> None:
        if event == self.EVENT:
            self.times.append((time.perf_counter(), fun_name))

    def between(self, t0: float, t1: float) -> list:
        return [name for t, name in self.times if t0 <= t <= t1]


def _device(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    stats = [d.memory_stats() or {} for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
            "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0)) for s in stats)}


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host spans come from annotations alone
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False  # the reduction reads events only
    return opts


def run_cell(name: str, seed: int, seconds: float, trace: bool, *, manifest: dict,
             t_start: float, bench: Path = BENCH, control: bool = False,
             keep_trace=None, log=print) -> dict:
    """Run cell ``name`` once and return its result line as a dict."""
    import jax

    compiles = CompileLog()
    gcs = GcLog()
    cell, config = load_cell(name, bench)
    kind = load_module(bench / "traffic" / f"{cell['kind']}.py")
    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        corpus = corpus_mod.build(config, Path(tmp))
        t_built = time.perf_counter()
        run = kind.Run(config, cell["params"], corpus, seed)
        run.setup()
        t_setup = time.perf_counter()
        trace_dir = Path(tmp) / "trace"
        if trace:
            jax.profiler.start_trace(str(trace_dir), profiler_options=_profile_options())
        t_window = time.perf_counter()
        ru = resource.getrusage(resource.RUSAGE_SELF)
        run.measure(seconds)
        t_closed = time.perf_counter()
        ru_end = resource.getrusage(resource.RUSAGE_SELF)
        if trace:
            jax.profiler.stop_trace()
        device = _device(cell["chips"])
        answers = run.close()
        obs = run.obs
        t_check = time.perf_counter()
        truth = (corpus.reads, corpus.n_blocks, corpus.positions, corpus.token_target,
                 corpus.kind, corpus.chimeric)
        checks = oracle.check(answers, *truth)
        if control:  # the sound reading of this window, then the control's
            sound = checks
            checks = oracle.check(oracle.collapse_n(answers), *truth)
        del answers
        t_checked = time.perf_counter()
        reduction = None
        if trace:
            xplane = trace_reduce.find_xplane(trace_dir)
            if keep_trace is not None:
                Path(keep_trace).mkdir(parents=True, exist_ok=True)
                (Path(keep_trace) / f"{name}.xplane.pb").write_bytes(xplane.read_bytes())
            reduction = trace_reduce.reduce(xplane, kind.SPANS)
    m = dict(obs, setup_s=t_setup - t_start, container_bytes=corpus.container_bytes,
             corpus_bases=corpus.bases, trace=reduction, peaks=peaks.peaks(device["kind"])
             if trace else None)
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for entry in metrics_for(manifest, name, section):
        value = load_module(bench / "metrics" / f"{entry['name']}.py").read(m)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
    log(f"corpus: blocks={corpus.n_blocks} bases={corpus.bases} reads={len(corpus.reads)} "
        f"container_bytes={corpus.container_bytes}")
    log("setup seconds: " + " ".join(f"{k}={v}" for k, v in corpus.seconds.items())
        + f" store_and_warmup={t_setup - t_built} total={t_setup - t_start} "
        f"compiles={len(compiles.between(0, t_setup))}")
    late = compiles.between(t_window, t_closed)
    pauses = gcs.between(t_window, t_closed)
    gcs.close()
    log(f"window: seconds={t_closed - t_window} compiles_in_window={len(late)} "
        f"{sorted(set(late))} counters={obs.get('counters')}")
    if "window_log" in obs:
        log(f"window: {obs['window_log']}")
    # host time in the window: this process's CPU time and its garbage
    # collections, so that a stall on the host can be told from them
    log(f"window host: user_s={ru_end.ru_utime - ru.ru_utime} "
        f"sys_s={ru_end.ru_stime - ru.ru_stime} "
        f"gc_pauses={len(pauses)} gc_s={sum(s for s, _ in pauses)} "
        f"gc_max={max(pauses, default=(0.0, None))}")
    log(f"check seconds={t_checked - t_check} control={control}")
    if control:
        log(f"sound checks of this window: {sound} correct={oracle.passed(sound)}")
    result = {
        "correct": oracle.passed(checks),
        "attempted": obs["attempted"],
        "failed": obs.get("failed", 0),
        "metrics": metrics,
        "device": device,
    }
    if reduction is not None:
        result["breakdown"] = {"device_ops": reduction.device_ops,
                               "idle_gaps": reduction.idle_gaps}
    result["checks"] = {k: {"value": v, "limit": oracle.LIMITS[k]} for k, v in checks.items()}
    for line in oracle.report(checks):
        log(line)
    return result
