#!/usr/bin/env python3
"""Chip smoke: the ULEEN train -> export -> serve path, once, on TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the cross-chip paths, four chips

One chip runs five phases in one process:

    device    the first JAX device must be a TPU; there is no CPU path
    train     `train_uleen` on ULN-L (784 px x 7-bit Gaussian thermometer),
              global batch 1024, 10 steps; loss finite and falling, params
              after 2 steps bit-exact against the single-device blocked
              reference
    serve     export the trained model, serve the test rows through
              `WnnBatcher` (default backend: the packed Pallas kernel on
              TPU); the compiled batch program holds a `tpu_custom_call`,
              scores are int32-equal to `ref.packed_wnn_ref` on the chip,
              one trace
    serve-xl  the same on a seeded random ULN-XL artifact (W = 1024 words)
    tenants   `WnnTenantBatcher` over 64 ULN-S artifacts at capacity 16
              under a skewed stream; scores equal each tenant's own
              `WnnBatcher`, one scores trace and one install trace

`--chips 4` runs only the paths that exist across chips, each beside its
one-chip reference: class-sharded `WnnBatcher` on ULN-XL-ensemble (M=32)
over (data=1, model=4) against the unsharded batcher, and `train_uleen` on
(pod=2, data=2) against the single-device reference, plus 3 `--compress`
steps.

Data is `data/synth.make_mnist_like(hw=28)` and seeded artifacts, from
`--seed`. Every phase prints one line of numbers; a failed check raises
and the script exits non-zero. The last line of stdout is
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import export  # noqa: E402
from repro.core.encoding import fit_gaussian_thermometer  # noqa: E402
from repro.core.model import init_static  # noqa: E402
from repro.data.synth import make_mnist_like  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.launch import train as train_mod  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.scheduler import WnnBatcher, WnnTenantBatcher  # noqa: E402
from repro.launch.uleen_cell import (ULN_L_SPEC, ULN_S_SPEC,  # noqa: E402
                                     ULN_XL_ENSEMBLE_SPEC, ULN_XL_SPEC)

GLOBAL_BATCH = 1024
GRAD_BLOCKS = 8
TRAIN_STEPS = 10
PARITY_STEPS = 2
COMPRESS_STEPS = 3
N_TRAIN = 8192
N_TEST = 512
SLOTS = 128
TENANTS = 64
CAPACITY = 16
TENANT_REQUESTS = 1024


def log(phase: str, **numbers) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in numbers.items()),
          flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def mnist_bits(seed: int, bits_per_input: int):
    """(train bits, train labels, test bits, test labels) of the 28x28
    synthetic digits under a Gaussian thermometer of `bits_per_input`."""
    data = make_mnist_like(jax.random.PRNGKey(seed), n_train=N_TRAIN,
                           n_test=N_TEST, hw=28)
    enc = fit_gaussian_thermometer(data.x_train, bits_per_input)
    return (np.asarray(enc.encode(data.x_train)), np.asarray(data.y_train),
            np.asarray(enc.encode(data.x_test)), np.asarray(data.y_test))


def random_artifact(spec, seed: int) -> export.InferenceArtifact:
    """A seeded deployable artifact at `spec`'s geometry: random H3
    statics, bitplanes a quarter set, 90% of filters surviving."""
    rng = np.random.default_rng(seed)
    m = spec.num_classes
    subs = []
    for sm, st in zip(spec.submodels,
                      init_static(jax.random.PRNGKey(seed), spec)):
        shape = (m, spec.num_filters(sm), max(1, sm.entries // 32))
        words = (rng.integers(0, 2 ** 32, shape, dtype=np.uint32)
                 & rng.integers(0, 2 ** 32, shape, dtype=np.uint32))
        subs.append(export.SubmodelArtifact(
            packed=words, mask=rng.random(shape[:2]) < 0.9,
            perm=np.asarray(st.perm), h3=np.asarray(st.h3),
            entries=sm.entries, inputs_per_filter=sm.inputs_per_filter,
            num_hashes=sm.num_hashes))
    return export.InferenceArtifact(
        submodels=subs, bias=rng.integers(-4, 5, m).astype(np.int32),
        num_classes=m, total_bits=spec.total_bits,
        bits_per_input=spec.bits_per_input)


@jax.jit
def reference_scores(pt, bits):
    """The XLA oracle of the packed serve path: `ref.packed_wnn_ref` per
    submodel plus the bias — no Pallas kernel."""
    zero = jnp.zeros_like(pt.bias)
    scores = jnp.broadcast_to(pt.bias[None], (bits.shape[0],
                                              pt.num_classes))
    for words, mask, perm, h3 in zip(pt.words, pt.masks, pt.perms, pt.h3s):
        tuples = bits[:, perm].astype(jnp.int8)
        scores = scores + ref.packed_wnn_ref(tuples, h3, words, mask, zero)
    return scores


def serve_rows(batcher, bits) -> tuple[np.ndarray, float]:
    t0 = time.perf_counter()
    for row in bits:
        batcher.submit(row)
    results = batcher.drain()
    wall = time.perf_counter() - t0
    return np.stack([r.scores for r in results]), wall


def batch_program_text(batcher) -> str:
    """Optimised HLO of the batcher's one compiled batch program."""
    bits = jnp.zeros((batcher.slots, batcher.total_bits), jnp.uint8)
    return batcher._scores.lower(batcher._prep, bits).compile().as_text()


def served_and_checked(phase: str, artifact, bits, **numbers) -> np.ndarray:
    """Serve `bits` through a fresh `WnnBatcher` on the default backend
    and hold it to the chip contract; returns the scores."""
    batcher = WnnBatcher(artifact, slots=SLOTS)
    scores, wall = serve_rows(batcher, bits)
    traces = batcher.stats()["traces"]
    check(traces == 1, f"{phase}: {traces} traces of the batch program")
    kernel = "tpu_custom_call" in batch_program_text(batcher)
    check(kernel, f"{phase}: no tpu_custom_call in the batch program")
    expect = np.asarray(reference_scores(batcher._prep, jnp.asarray(bits)))
    mismatched = int(np.sum(scores != expect))
    check(mismatched == 0,
          f"{phase}: {mismatched} scores differ from ref.packed_wnn_ref")
    log(phase, rows=len(bits), batches=batcher.batches, traces=traces,
        tpu_custom_call=kernel, score_mismatches=mismatched,
        **numbers, wall_s=f"{wall:.3f}")
    return scores


def phase_device(chips: int) -> dict:
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: the first JAX device is {platform!r}, not a "
                 "TPU; there is no CPU path")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX sees {len(devices)}")
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    log("device", platform=platform, kind=repr(device["kind"]),
        count=len(devices),
        compile_cache=jax.config.jax_compilation_cache_dir)
    return device


def train(seed: int, mesh, *, steps: int, compress: bool = False):
    """`train_uleen` on ULN-L; returns (statics, run output, params after
    PARITY_STEPS, losses, wall seconds)."""
    spec = ULN_L_SPEC
    bits, labels, _, _ = mnist_bits(seed, spec.bits_per_input)
    statics = init_static(jax.random.PRNGKey(seed + 1), spec)
    snaps = {}

    def on_step(step, params):
        if step == PARITY_STEPS - 1:
            snaps["params"] = jax.tree.map(np.asarray, params)

    t0 = time.perf_counter()
    out = train_mod.train_uleen(
        spec, statics, bits, labels, steps_total=steps,
        global_batch=GLOBAL_BATCH, grad_blocks=GRAD_BLOCKS,
        compress=compress, seed=seed, mesh=mesh, on_step=on_step,
        verbose=False)
    wall = time.perf_counter() - t0
    losses = [h["loss"] for h in out["history"]]
    check(bool(np.all(np.isfinite(losses))), f"non-finite loss: {losses}")
    parity = None
    if not compress:
        expect = train_mod.uleen_reference_params(
            spec, statics, bits, labels, steps=PARITY_STEPS,
            global_batch=GLOBAL_BATCH, grad_blocks=GRAD_BLOCKS, seed=seed)
        parity = train_mod.max_param_diff(snaps["params"], expect)
        check(parity == 0.0, f"distributed vs reference params differ by "
                             f"{parity} after {PARITY_STEPS} steps")
    return statics, out, parity, losses, wall


def phase_train(seed: int):
    spec = ULN_L_SPEC
    statics, out, parity, losses, wall = train(
        seed, make_mesh((1,), ("data",)), steps=TRAIN_STEPS)
    check(losses[-1] < losses[0], f"loss not falling: {losses}")
    log("train", spec="ULN-L", total_bits=spec.total_bits,
        batch=GLOBAL_BATCH, steps=len(losses), loss_first=losses[0],
        loss_last=losses[-1], parity_steps=PARITY_STEPS,
        parity_max_abs=parity, wall_s=f"{wall:.3f}")
    return statics, out["params"]


def phase_serve(seed: int, statics, params) -> None:
    spec = ULN_L_SPEC
    _, _, bits, labels = mnist_bits(seed, spec.bits_per_input)
    artifact = export.export_model(spec, statics, params)
    scores = served_and_checked("serve", artifact, bits, spec="ULN-L")
    log("serve", accuracy=float(np.mean(scores.argmax(1) == labels)))


def phase_serve_xl(seed: int) -> None:
    spec = ULN_XL_SPEC
    _, _, bits, _ = mnist_bits(seed, spec.bits_per_input)
    served_and_checked("serve-xl", random_artifact(spec, seed + 2), bits,
                       spec="ULN-XL", max_words=max(
                           sm.entries // 32 for sm in spec.submodels))


def phase_tenants(seed: int) -> None:
    spec = ULN_S_SPEC
    _, _, bits, _ = mnist_bits(seed, spec.bits_per_input)
    artifacts = [random_artifact(spec, seed * 1000 + 3 + t)
                 for t in range(TENANTS)]
    fleet = WnnTenantBatcher(capacity=CAPACITY, slots=SLOTS)
    for artifact in artifacts:
        fleet.add_tenant(artifact)
    # Zipf popularity whose hot set moves halfway through the stream
    rng = np.random.default_rng(seed)
    pop = 1.0 / np.arange(1, TENANTS + 1) ** 1.1
    tids = rng.choice(TENANTS, size=TENANT_REQUESTS, p=pop / pop.sum())
    half = TENANT_REQUESTS // 2
    tids[half:] = rng.permutation(TENANTS)[tids[half:]]
    rows = bits[np.arange(TENANT_REQUESTS) % len(bits)]
    t0 = time.perf_counter()
    for tid, row in zip(tids, rows):
        fleet.submit(int(tid), row)
    results = fleet.drain()
    wall = time.perf_counter() - t0
    stats = fleet.stats()
    check(stats["traces"] == 1, f"{stats['traces']} scores traces")
    check(stats["install_traces"] == 1,
          f"{stats['install_traces']} install traces")
    check(stats["evictions"] > 0, "the stream forced no eviction")
    got = np.stack([r.scores for r in results])
    mismatched = 0
    for tid in np.unique(tids):
        own = np.flatnonzero(tids == tid)
        expect, _ = serve_rows(WnnBatcher(artifacts[tid], slots=SLOTS),
                               rows[own])
        mismatched += int(np.sum(got[own] != expect))
    check(mismatched == 0,
          f"{mismatched} tenant scores differ from the tenants' batchers")
    log("tenants", tenants=TENANTS, capacity=CAPACITY,
        requests=TENANT_REQUESTS, distinct_tenants=len(np.unique(tids)),
        batches=stats["batches"], admissions=stats["admissions"],
        evictions=stats["evictions"], hits=stats["hits"],
        misses=stats["misses"], traces=stats["traces"],
        install_traces=stats["install_traces"],
        score_mismatches=mismatched, wall_s=f"{wall:.3f}")


def devices_of(array) -> int:
    return len({s.device for s in array.addressable_shards})


def phase_sharded_serve(seed: int) -> None:
    spec = ULN_XL_ENSEMBLE_SPEC
    _, _, bits, _ = mnist_bits(seed, spec.bits_per_input)
    artifact = random_artifact(spec, seed + 4)
    mesh = make_mesh((1, 4), ("data", "model"))
    single = WnnBatcher(artifact, slots=SLOTS)
    sharded = WnnBatcher(artifact, slots=SLOTS, mesh=mesh)
    expect, wall_1 = serve_rows(single, bits)
    got, wall_4 = serve_rows(sharded, bits)
    words = sharded._prep.words
    check(sharded.class_shards == 4,
          f"{sharded.class_shards} class shards, expected 4")
    check(all(devices_of(w) == 4 and
              w.addressable_shards[0].data.shape[0] == spec.num_classes // 4
              for w in words),
          "class-sharded words do not sit M/4 classes per chip on 4 chips")
    traces = (single.stats()["traces"], sharded.stats()["traces"])
    check(traces == (1, 1), f"traces {traces}")
    mismatched = int(np.sum(got != expect))
    check(mismatched == 0,
          f"{mismatched} class-sharded scores differ from unsharded")
    log("sharded-serve", spec="ULN-XL-ensemble", classes=spec.num_classes,
        class_shards=sharded.class_shards,
        word_devices=[devices_of(w) for w in words],
        rows=len(bits), score_mismatches=mismatched,
        wall_1chip_s=f"{wall_1:.3f}", wall_4chip_s=f"{wall_4:.3f}")


def phase_sharded_train(seed: int) -> None:
    mesh = make_mesh((2, 2), ("pod", "data"))
    _, out, parity, losses, wall = train(seed, mesh, steps=PARITY_STEPS)
    leaves = jax.tree.leaves((out["params"], out["opt_state"]))
    check(all(devices_of(x) == 4 for x in leaves),
          "trained state is not replicated over the 4 chips")
    log("sharded-train", mesh="pod=2,data=2", steps=len(losses),
        parity_steps=PARITY_STEPS, parity_max_abs=parity,
        state_devices=devices_of(leaves[0]), wall_s=f"{wall:.3f}")
    _, _, _, losses, wall = train(seed, mesh, steps=COMPRESS_STEPS,
                                  compress=True)
    log("sharded-train-compress", steps=len(losses),
        loss_first=losses[0], loss_last=losses[-1], wall_s=f"{wall:.3f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    device = phase_device(args.chips)
    if args.chips == 4:
        phase_sharded_serve(args.seed)
        phase_sharded_train(args.seed)
    else:
        statics, params = phase_train(args.seed)
        phase_serve(args.seed, statics, params)
        phase_serve_xl(args.seed)
        phase_tenants(args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
