"""JAX-specific observability signals (DESIGN §12).

The only obs module that imports jax — `metrics`/`trace`/`registry`
stay stdlib-only so numpy-only layers (`core/export.py`,
`train/fault.py`) can record into the global recorder without pulling
jax into their import graph.

Importing it puts every span of a real `Recorder` on the profiler's host
timeline: each `Recorder.span(name)` also opens a
`jax.profiler.TraceAnnotation(name)`, a cheap no-op while no trace runs.
The `NullRecorder` opens none.

Three signals:

* `counted(fn, counts, key)` — retrace counting. A function wrapped in
  `jax.jit` runs its Python body once per *trace*; bumping a counter in
  that body therefore counts (re)compilations, not calls. This
  generalizes the ad-hoc `trace_counts[...] += 1` lines the scheduler's
  recompile-guard tests pin: the wrapper bumps the caller's local dict
  (the tests' contract) AND mirrors into the global recorder as
  `jax.trace.<key>`. `key` may be a callable of the traced arguments
  for shape-dependent keys (`prefill_{width}`).
* `record_device_memory()` — live-buffer count/bytes gauges from
  `jax.live_arrays()`, plus per-device `bytes_in_use` where the backend
  exposes `memory_stats()` (CPU backends often don't; absent stats are
  skipped, never zero-filled).
* `profile_trace(log_dir)` — opt-in `jax.profiler` trace context behind
  `serve.py --profile` / `train.py --profile`. Never on by default: the
  profiler's own overhead would contaminate the latency histograms.
"""
from __future__ import annotations

import contextlib
import functools

import jax

from repro.obs import registry as _registry

_registry.set_annotation(jax.profiler.TraceAnnotation)


def counted(fn, counts, key, *, prefix: str = "jax.trace", agg_key=None):
    """Wrap `fn` (pre-`jax.jit`) so every trace of its Python body bumps
    `counts[key]` and the global recorder counter `{prefix}.{key}`.
    `key` may be a callable evaluated on the traced call's arguments
    (shape-dependent keys); pass `agg_key` to additionally bump a stable
    `{prefix}.{agg_key}` aggregate across all dynamic keys (the Engine's
    per-width prefills roll up into `jax.trace.prefill`).

    The bump happens at trace time only — it reads no traced values and
    adds nothing to the lowered program, so wrapped and unwrapped cells
    are bit-exact (the parity suites run over wrapped functions).
    """
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        k = key(*args, **kwargs) if callable(key) else key
        counts[k] += 1
        rec = _registry.get_recorder()
        rec.counter(f"{prefix}.{k}").inc()
        if agg_key is not None and agg_key != k:
            rec.counter(f"{prefix}.{agg_key}").inc()
        return fn(*args, **kwargs)
    return wrapped


def record_device_memory(rec=None) -> None:
    """Set live-buffer and device-memory gauges on `rec` (default: the
    global recorder — a no-op when observability is off)."""
    rec = rec if rec is not None else _registry.get_recorder()
    if not rec.enabled:
        return
    try:
        arrs = jax.live_arrays()
    except Exception:
        arrs = []
    rec.gauge("jax.live_buffers").set(float(len(arrs)))
    rec.gauge("jax.live_bytes").set(
        float(sum(getattr(a, "nbytes", 0) or 0 for a in arrs)))
    for dev in jax.local_devices():
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if stats and "bytes_in_use" in stats:
            rec.gauge(f"jax.device{dev.id}.bytes_in_use").set(
                float(stats["bytes_in_use"]))


@contextlib.contextmanager
def profile_trace(log_dir, *, enabled: bool = True):
    """Wrap a region in a `jax.profiler` trace written to `log_dir`
    (viewable in TensorBoard/Perfetto). With `enabled=False` or a falsy
    `log_dir` this is a zero-cost no-op, so call sites can pass the CLI
    flag straight through."""
    if not enabled or not log_dir:
        yield None
        return
    jax.profiler.start_trace(str(log_dir))
    try:
        yield str(log_dir)
    finally:
        jax.profiler.stop_trace()
