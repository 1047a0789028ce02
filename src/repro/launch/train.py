"""Distributed training driver (LM archs + the executed ULEEN trainer).

The same driver runs the production mesh on a fleet and the 1-device CPU
mesh in this container (examples/tests use smoke configs). Demonstrated
fault-tolerance path: step-atomic checkpoints (keep-N), `--restore auto`
restart, SIGTERM preemption handling, straggler monitoring, elastic
restart (checkpoints are mesh-agnostic logical arrays).

    PYTHONPATH=src python -m repro.launch.train --arch llama3p2_3b --smoke \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

`--arch uleen` runs the paper's own multi-shot STE trainer distributed
(DESIGN §10): deterministic blocked gradient reduction under shard_map on
a real multi-device mesh, bit-identical to the single-device
`core/multi_shot.py` reference, with optional int8 cross-pod gradient
compression. The SIGTERM kill-and-resume drill in
tests/test_distributed_training.py drives exactly this entry point:

    PYTHONPATH=src python -m repro.launch.train --arch uleen \
        --mesh pod=2,data=4 --steps 12 --batch 256 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import contextlib
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ARCH_IDS, get_config
from repro.data.synth import make_lm_tokens
from repro.dist import sharding as sh
from repro.launch import specs, steps
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import (make_host_mesh, make_mesh,
                               make_production_mesh)
from repro.models import transformer
from repro.obs import jaxhooks as obs_jaxhooks
from repro.obs import registry as obs_registry
from repro.train import checkpoint, fault
from repro.train import optimizer as opt_lib


def data_iterator(cfg, batch: int, seq: int, seed: int, *,
                  start_step: int = 0):
    """Deterministic synthetic LM stream; restart-safe (seeded by step)."""
    n_tok = batch * (seq + 1)
    step = start_step
    while True:
        key = jax.random.PRNGKey(seed * 1_000_003 + step)
        toks = make_lm_tokens(key, cfg.vocab_size, n_tok).reshape(
            batch, seq + 1)
        out = {"tokens": jnp.asarray(toks[:, :-1]),
               "labels": jnp.asarray(toks[:, 1:])}
        if cfg.encoder_layers:
            out["frames"] = jax.random.normal(
                key, (batch, cfg.encoder_frames, cfg.d_model),
                jnp.float32) * 0.02
        if cfg.patch_tokens:
            out["patches"] = jax.random.normal(
                key, (batch, cfg.patch_tokens, cfg.d_model),
                jnp.float32) * 0.02
        yield step, out
        step += 1


def train(cfg, *, steps_total: int, batch: int, seq: int,
          lr: float = 3e-4, microbatches: int = 1, seed: int = 0,
          mesh=None, ckpt_dir: str | None = None, ckpt_every: int = 20,
          restore: str = "auto", compute_dtype=jnp.bfloat16,
          log_every: int = 10, guard: fault.PreemptionGuard | None = None,
          verbose: bool = True) -> dict:
    mesh = mesh or make_host_mesh()
    rules = sh.TRAIN_RULES
    optimizer = opt_lib.chain_clip(
        opt_lib.adamw(opt_lib.warmup_cosine_schedule(lr, 10, steps_total)),
        1.0)
    step_fn = steps.make_train_step(cfg, optimizer,
                                    microbatches=microbatches,
                                    compute_dtype=compute_dtype)

    pshard = specs.param_shardings(cfg, mesh, rules)
    with sh.use_mesh(mesh, rules):
        params = jax.jit(
            lambda k: transformer.init_params(cfg, k),
            out_shardings=pshard)(jax.random.PRNGKey(seed))
        opt_state = jax.jit(optimizer.init,
                            out_shardings=specs.opt_shardings(
                                cfg, optimizer, mesh, rules))(params)

    rec = obs_registry.get_recorder()
    start = 0
    if ckpt_dir and restore == "auto":
        with rec.span("ckpt.restore"):
            restored, at = checkpoint.restore_latest(
                ckpt_dir, (params, opt_state))
        if restored is not None:
            params, opt_state = restored
            start = at
            if verbose:
                print(f"[train] restored step {at} from {ckpt_dir}")

    bshard = None
    jit_step = jax.jit(step_fn, donate_argnums=(0, 1))
    monitor = fault.StragglerMonitor()
    it = data_iterator(cfg, batch, seq, seed, start_step=start)
    history = []
    preempted = False

    with sh.use_mesh(mesh, rules):
        for step, data in it:
            if step >= steps_total:
                break
            monitor.start()
            params, opt_state, metrics = jit_step(params, opt_state, data)
            metrics = {k: float(v) for k, v in metrics.items()}
            ev = monitor.stop(step)   # observes train.step_s (DESIGN §12)
            rec.counter("train.steps").inc()
            history.append({"step": step, **metrics})
            if verbose and (step % log_every == 0 or step == steps_total - 1):
                print(f"[train] step {step}: loss={metrics['loss']:.4f} "
                      f"gnorm={metrics['grad_norm']:.3f}"
                      + (f" STRAGGLER x{ev.ratio:.1f}" if ev else ""))
            want_ckpt = ckpt_dir and (step + 1) % ckpt_every == 0
            if guard is not None and guard.preempted:
                want_ckpt, preempted = bool(ckpt_dir), True
            if want_ckpt:
                with rec.span("ckpt.save", step=step + 1):
                    checkpoint.save(ckpt_dir, step + 1, (params, opt_state))
            if preempted:
                if verbose:
                    print(f"[train] preempted; checkpointed step {step + 1}")
                break
    if ckpt_dir and not preempted:
        with rec.span("ckpt.save", step=min(steps_total,
                                            start + len(history))):
            checkpoint.save(ckpt_dir, min(steps_total, start + len(history)),
                            (params, opt_state))
    return {"params": params, "opt_state": opt_state, "history": history,
            "preempted": preempted,
            "straggler_events": len(monitor.events)}


# ---------------------------------------------------------------------------
# Executed distributed ULEEN training (DESIGN §10)
# ---------------------------------------------------------------------------

def uleen_smoke_problem(seed: int = 0, n_train: int = 2048):
    """(spec, statics, bits, labels) — the deterministic smoke problem.

    Data and model init depend only on `seed`, never on wall clock or
    device layout, so two processes (e.g. the SIGTERM drill's killed and
    resumed runs) reconstruct byte-identical inputs.
    """
    from repro.core.encoding import fit_gaussian_thermometer
    from repro.core.model import init_static
    from repro.data.synth import make_mnist_like
    from repro.launch.uleen_cell import ULEEN_EXEC_SPEC

    spec = ULEEN_EXEC_SPEC
    data = make_mnist_like(jax.random.PRNGKey(seed), n_train=n_train,
                           n_test=256, hw=16)
    enc = fit_gaussian_thermometer(data.x_train, 2)
    bits = np.asarray(enc.encode(data.x_train))
    labels = np.asarray(data.y_train)
    statics = init_static(jax.random.PRNGKey(seed + 1), spec)
    return spec, statics, bits, labels


def uleen_batch_indices(seed: int, step: int, n: int, batch: int) -> np.ndarray:
    """Batch row indices of `step` — a pure function of (seed, step), so a
    restored run replays the exact sample order of the run it resumes."""
    rng = np.random.default_rng(seed * 1_000_003 + step)
    return rng.integers(0, n, size=batch)


def train_uleen(spec, statics, bits_train, labels_train, *,
                steps_total: int, global_batch: int = 256,
                lr: float = 1e-3, grad_blocks: int = 8,
                compress: bool = False, seed: int = 0, mesh=None,
                ckpt_dir: str | None = None, ckpt_every: int = 5,
                keep: int = 3, restore: str = "auto",
                guard: fault.PreemptionGuard | None = None,
                monitor: fault.StragglerMonitor | None = None,
                on_step=None, step_delay: float = 0.0,
                verbose: bool = True) -> dict:
    """Executed distributed multi-shot ULEEN training (DESIGN §10).

    Every source of nondeterminism is pinned to (seed, step): model init
    to `seed`, step s's dropout rng to fold_in(PRNGKey(seed), s), its
    batch rows to `uleen_batch_indices(seed, s, ...)`. Combined with the
    deterministic blocked reduction in the step function and logical
    (unsharded) checkpoints, a run killed at any step boundary and
    resumed — on the same mesh or a smaller one — reaches final params
    byte-identical to the uninterrupted run. Tests assert exactly that.

    on_step(step, params): test hook called after each optimizer step
    (the request()-based preemption drill injects there). step_delay:
    per-step sleep, widening the window the SIGTERM drill aims at.

    Spans, one tree per step: `train.step` (attr `step`) over
    `train.feed` (batch rows, their `device_put`s, the step's rng) and
    `train.sync` (loss and accuracy back on the host). The recorder is
    fetched per step, so one installed mid-run (from `on_step`) records
    the steps after.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.model import init_params
    from repro.launch import uleen_cell

    mesh = mesh or make_mesh((1,), ("data",))
    optimizer = opt_lib.adam(lr)
    params = init_params(jax.random.PRNGKey(seed), spec, init_scale=0.1)
    opt_state = optimizer.init(params)

    rep = NamedSharding(mesh, P())
    rep_tree = lambda t: jax.tree.map(lambda _: rep, t)
    start = 0
    if ckpt_dir and restore == "auto":
        with obs_registry.get_recorder().span("ckpt.restore"):
            restored, at = checkpoint.restore_latest(
                ckpt_dir, (params, opt_state),
                shardings=(rep_tree(params), rep_tree(opt_state)))
        if restored is not None:
            params, opt_state = restored
            start = at
            if verbose:
                print(f"[train] restored step {at} from {ckpt_dir}")

    dshard = uleen_cell.uleen_dist_specs(spec, mesh, global_batch)
    step_fn = uleen_cell.make_uleen_dist_train_step(
        spec, optimizer, mesh, grad_blocks=grad_blocks, compress=compress)
    statics_t = tuple((np.asarray(st.perm), np.asarray(st.h3))
                      for st in statics)
    jit_step = jax.jit(
        step_fn,
        in_shardings=(rep_tree(params), rep_tree(opt_state),
                      rep_tree(statics_t), dshard["bits"], dshard["labels"],
                      rep),
        donate_argnums=(0, 1))

    bits_train = np.asarray(bits_train)
    labels_train = np.asarray(labels_train)
    n = bits_train.shape[0]
    base_rng = jax.random.PRNGKey(seed)
    monitor = monitor or fault.StragglerMonitor()
    history = []
    preempted = False
    last = start

    for step in range(start, steps_total):
        rec = obs_registry.get_recorder()
        with rec.span("train.step", step=step):
            with rec.span("train.feed"):
                idx = uleen_batch_indices(seed, step, n, global_batch)
                bits_b = jax.device_put(bits_train[idx], dshard["bits"])
                labels_b = jax.device_put(labels_train[idx],
                                          dshard["labels"])
                rng = jax.random.fold_in(base_rng, step)
            monitor.start()
            params, opt_state, loss, acc = jit_step(
                params, opt_state, statics_t, bits_b, labels_b, rng)
            with rec.span("train.sync"):
                loss, acc = float(loss), float(acc)
            ev = monitor.stop(step)   # observes train.step_s + EWMA gauge
            rec.counter("train.steps").inc()
            if step_delay:
                time.sleep(step_delay)
            history.append({"step": step, "loss": loss, "acc": acc})
            last = step + 1
            if verbose and (step % 5 == 0 or step == steps_total - 1):
                print(f"[train] step {step}: loss={loss:.4f} acc={acc:.4f}"
                      + (f" STRAGGLER x{ev.ratio:.1f}" if ev else ""))
            if on_step is not None:
                on_step(step, params)
            want_ckpt = ckpt_dir and (step + 1) % ckpt_every == 0
            if guard is not None and guard.preempted:
                want_ckpt, preempted = bool(ckpt_dir), True
            if want_ckpt:
                with obs_registry.get_recorder().span("ckpt.save",
                                                      step=step + 1):
                    checkpoint.save(ckpt_dir, step + 1, (params, opt_state),
                                    keep=keep)
        if preempted:
            if verbose:
                print(f"[train] preempted; checkpointed step {step + 1}")
            break
    if ckpt_dir and not preempted and last > start:
        with obs_registry.get_recorder().span("ckpt.save", step=last):
            checkpoint.save(ckpt_dir, last, (params, opt_state), keep=keep)
    return {"params": params, "opt_state": opt_state, "history": history,
            "preempted": preempted, "resumed_from": start,
            "straggler_events": len(monitor.events)}


def uleen_reference_params(spec, statics, bits_train, labels_train, *,
                           steps: int, global_batch: int = 256,
                           lr: float = 1e-3, grad_blocks: int = 8,
                           seed: int = 0):
    """Params of the single-device blocked reference
    (`multi_shot.make_train_step(grad_blocks=)`) after `steps` steps of the
    same (seed, step) schedule `train_uleen` runs — the oracle the
    distributed trainer is bit-exact against (DESIGN §10)."""
    from repro.core import multi_shot
    from repro.core.model import compute_hashes, init_params

    optimizer = opt_lib.adam(lr)
    params = init_params(jax.random.PRNGKey(seed), spec, init_scale=0.1)
    opt_state = optimizer.init(params)
    ref_step = jax.jit(multi_shot.make_train_step(
        spec, optimizer, grad_blocks=grad_blocks))
    base = jax.random.PRNGKey(seed)
    for s in range(steps):
        idx = uleen_batch_indices(seed, s, bits_train.shape[0], global_batch)
        h = compute_hashes(spec, statics, jnp.asarray(bits_train[idx]))
        params, opt_state, _, _ = ref_step(
            params, opt_state, h, jnp.asarray(labels_train[idx]),
            jax.random.fold_in(base, s))
    return params


def max_param_diff(a, b) -> float:
    """Max |Δ| over two param pytrees, compared on the host: the operands
    may live on different meshes, which jnp refuses to mix."""
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def uleen_parity_probe(mesh, *, steps: int = 2, global_batch: int = 256,
                       grad_blocks: int = 8, seed: int = 0,
                       n_train: int = 1024) -> float:
    """Max |Δparam| between the distributed (uncompressed) trainer on
    `mesh` and the single-device blocked reference after `steps` identical
    steps. 0.0 means bit-exact — the dryrun train_host_exec cell gates on
    exactly that (tests/test_distributed_training.py asserts it per-step
    over 10 steps; this is the same check sized for a smoke)."""
    spec, statics, bits, labels = uleen_smoke_problem(seed, n_train=n_train)
    out = train_uleen(spec, statics, bits, labels, steps_total=steps,
                      global_batch=global_batch, grad_blocks=grad_blocks,
                      seed=seed, mesh=mesh, verbose=False)
    ref = uleen_reference_params(spec, statics, bits, labels, steps=steps,
                                 global_batch=global_batch,
                                 grad_blocks=grad_blocks, seed=seed)
    return max_param_diff(out["params"], ref)


def parse_mesh(text: str):
    """'pod=2,data=4' -> mesh. Needs prod(sizes) <= len(jax.devices())."""
    axes, shape = [], []
    for part in text.split(","):
        name, _, size = part.partition("=")
        axes.append(name.strip())
        shape.append(int(size))
    return make_mesh(tuple(shape), tuple(axes))


def _main_uleen(args) -> int:
    mesh = parse_mesh(args.mesh)
    spec, statics, bits, labels = uleen_smoke_problem(args.seed)
    with fault.PreemptionGuard() as guard:
        out = train_uleen(
            spec, statics, bits, labels, steps_total=args.steps,
            global_batch=args.batch, lr=args.lr,
            grad_blocks=args.grad_blocks, compress=args.compress,
            seed=args.seed, mesh=mesh, ckpt_dir=args.ckpt_dir,
            ckpt_every=args.ckpt_every, restore=args.restore, guard=guard,
            step_delay=args.step_delay)
    losses = [h["loss"] for h in out["history"]]
    if losses:
        print(f"[train] done: first loss {losses[0]:.4f} -> "
              f"last {losses[-1]:.4f} over {len(losses)} steps"
              + (" (preempted)" if out["preempted"] else ""))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list(ARCH_IDS) + ["uleen"],
                    required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=5)
    ap.add_argument("--restore", choices=["auto", "none"], default="auto")
    ap.add_argument("--production-mesh", action="store_true",
                    help="16x16 mesh (needs 256 devices; dry-run only here)")
    # --arch uleen (executed distributed trainer, DESIGN §10)
    ap.add_argument("--mesh", default="data=1",
                    help="uleen mesh, e.g. pod=2,data=4 (device count must "
                         "fit XLA_FLAGS --xla_force_host_platform_device_"
                         "count)")
    ap.add_argument("--grad-blocks", type=int, default=8)
    ap.add_argument("--compress", action="store_true",
                    help="int8 cross-pod gradient compression (needs a "
                         "pod axis in --mesh)")
    ap.add_argument("--step-delay", type=float, default=0.0,
                    help="per-step sleep (the SIGTERM drill's kill window)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="wrap the run in a jax.profiler trace written to "
                         "DIR (TensorBoard/Perfetto viewable; DESIGN §12)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write an obsmetrics/v1 METRICS.json snapshot of "
                         "the run (step-time histogram, checkpoint spans, "
                         "straggler EWMA) to PATH")
    args = ap.parse_args(argv)
    enable_compile_cache()

    def _run() -> int:
        if args.arch == "uleen":
            return _main_uleen(args)
        cfg = get_config(args.arch, smoke=args.smoke)
        mesh = (make_production_mesh() if args.production_mesh
                else make_host_mesh())
        with fault.PreemptionGuard() as guard:
            out = train(cfg, steps_total=args.steps, batch=args.batch,
                        seq=args.seq, lr=args.lr,
                        microbatches=args.microbatches, mesh=mesh,
                        ckpt_dir=args.ckpt_dir, restore=args.restore,
                        guard=guard)
        losses = [h["loss"] for h in out["history"]]
        if losses:
            print(f"[train] done: first loss {losses[0]:.4f} -> "
                  f"last {losses[-1]:.4f} over {len(losses)} steps")
        return 0

    if args.arch == "uleen" and args.lr == 3e-4:
        args.lr = 1e-3               # LM default; uleen's paper value

    with contextlib.ExitStack() as stack:
        rec = None
        if args.metrics_out:
            rec = stack.enter_context(obs_registry.recording())
        stack.enter_context(obs_jaxhooks.profile_trace(args.profile))
        rc = _run()
        if rec is not None:
            obs_jaxhooks.record_device_memory(rec)
            rec.write(args.metrics_out)
            print(f"[train] metrics: {len(rec.spans)} spans, "
                  f"{sum(c.value for c in rec.counters.values())} counter "
                  f"events -> {args.metrics_out}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
