#!/usr/bin/env python3
"""Chip benchmark of ULEEN: one cell of `BENCHMARK.json`, one run.

    python3 benchmarks/chip/run.py --workload uln-l.serve-max \
        --seed 7 --seconds 10 --trace 0

A cell names a configuration (`configs/<name>.json`) and a traffic mix
(`traffic/<name>.json`); the traffic file names the served path
(`paths/<path>.py`), which builds the system under test from the seed,
warms up every shape the window uses, and gives the window's loop. The
run measures `--seconds` of it, then checks every answer it compares
against the plain reference (`reference/`), and prints one JSON line:
with `--trace 0` the cell's end-to-end metrics (`end_to_end/<name>.py`),
with `--trace 1` its per-layer metrics (`layer_metrics/<name>.py`), read
from a `jax.profiler` trace of the window and the program's own spans.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result. JAX's persistent compilation cache is
`$JAX_COMPILATION_CACHE_DIR` where set, else `.jax_cache/` at the root
of the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    chips: int


@dataclasses.dataclass
class LayerInputs:
    """What a per-layer metric's reader may read."""
    cell: Cell
    window: object            # drive.Window
    spans: list               # repro.obs spans opened in the window
    trace: object             # trace_reduce.Reduction
    peaks: dict               # work.PEAKS row of this chip


def load_module(kind: str, name: str):
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(bench: dict, workload: str, seed: int, seconds: float) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    return Cell(name=workload, config=config, traffic=traffic, seed=seed,
                seconds=seconds, chips=int(w["chips"]))


def metrics_of(bench: dict, key: str, workload: str) -> list:
    return [m for m in bench[key]
            if workload in m.get("workloads", [workload])]


def configure_jax() -> None:
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int) -> dict:
    """The devices as JAX reports them; raises NoChip off a TPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds "
                     f"{len(devices)}")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": chips}


def memory_peak(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


class CompileCounter:
    """Notes the host time of every trace and backend compilation."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.times = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event in self.EVENTS:
            self.times.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.times)


class GcPauses:
    """Notes the host time and length of every full collection."""

    def __init__(self):
        self.full = []
        self._t0 = None
        gc.callbacks.append(self.on)

    def on(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.full.append((self._t0, time.perf_counter() - self._t0))

    def between(self, t0: float, t1: float) -> list:
        return [d for t, d in self.full if t0 <= t <= t1]


def run_cell(bench: dict, cell: Cell, *, trace: bool, device: dict,
             t_start: float, path=None) -> dict:
    """One run of `cell`: set up, measure, check, report. `path` stands
    in for the cell's served-path module (tests pass a broken one)."""
    import jax
    import numpy as np

    import trace_reduce
    import work
    from repro.obs import registry

    path = path or load_module("paths", cell.traffic["path"])
    counter = CompileCounter()
    served = path.setup(cell)
    pauses = GcPauses()
    rec, log_dir = None, None

    def begin():
        """Opens the traced window: spans and the profiler."""
        nonlocal rec, log_dir
        rec = registry.Recorder(max_spans=10 ** 7)
        registry.set_recorder(rec)
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(log_dir, profiler_options=opts)

    win = served.run(cell.seconds, traced=trace,
                     begin=begin if trace else None)
    setup_s = win.t0 - t_start
    out_device = dict(device)
    reduction = None
    if trace:
        jax.profiler.stop_trace()
        registry.set_recorder(registry.NullRecorder())
        reduction = trace_reduce.reduce_dir(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)
        out_device["busy_s"] = reduction.busy_s
        out_device["window_s"] = reduction.window_s
    if device["platform"] == "tpu":
        out_device["memory_peak_bytes"] = memory_peak(cell.chips)
    gc.callbacks.remove(pauses.on)
    checks = served.check(win)
    counts = {k: v for k, (v, limit) in checks.items() if limit is None}
    checks = {k: c for k, c in checks.items() if c[1] is not None}
    correct = all(value <= limit for value, limit in checks.values())

    metrics = {}
    if trace:
        inputs = LayerInputs(cell=cell, window=win, spans=list(rec.spans),
                             trace=reduction,
                             peaks=work.peaks(device["kind"]))
        for m in metrics_of(bench, "per_layer", cell.name):
            value = load_module("layer_metrics", m["name"]).read(inputs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in metrics_of(bench, "end_to_end", cell.name):
            value = (setup_s if m["name"] == "setup_s" else
                     load_module("end_to_end", m["name"]).read(win))
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    info = {"window_s": win.seconds,
            "completed_in_window": win.completed_in_window(),
            "compiles_in_window": counter.between(win.t0, win.t1),
            "full_gc_ms": [1e3 * d for d in pauses.between(win.t0, win.t1)],
            "longest_steps": [[1e3 * (b - a), a - win.t0] for a, b, _ in
                              sorted(win.steps, key=lambda x: x[0] - x[1])[:3]],
            "queued_at_close": win.queued_at_close,
            "latency_ms": {f"p{q}": 1e3 * float(np.quantile(
                win.latencies(), q / 100)) for q in (50, 95, 99, 100)
                if len(win.rids)},
            "setup_s": setup_s, **counts}
    result = {
        "correct": bool(correct),
        "attempted": int(len(win.rids)),
        "failed": int(checks.get("unanswered", (0,))[0]),
        "metrics": metrics,
        "device": out_device,
        "info": info,
    }
    if trace:
        result["breakdown"] = reduction.breakdown()
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import repro  # noqa: F401  (the system under test: without it, no run)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = load_cell(bench, args.workload, args.seed, args.seconds)
    configure_jax()
    try:
        device = device_info(cell.chips)
    except NoChip as e:
        print(f"run.py: {e}; no result", file=sys.stderr)
        return 2
    result = run_cell(bench, cell, trace=bool(args.trace), device=device,
                      t_start=T_START)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
