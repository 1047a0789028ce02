"""E8 — WNN kernel benchmarks at the paper geometries (ULN-S/M/L/XL).

Sweeps every submodel shape of the model zoo (`benchmarks/model_zoo.py`
ZOO, the paper's Table I scaled to the 256-px synthetic task) plus the
ULN-XL stress geometry through the backend-dispatched inference pipeline
(`repro.kernels.ops.wnn_scores`), timing the fused int8 Pallas
formulation, the packed uint32-bitplane formulation, and the gather
formulation, and emitting machine-readable rows to BENCH_kernel.json.

On TPU all backends are compiled and the fused/packed-over-gather ratios
are the adoption argument; on CPU the gather timing is the real serving
number and the kernels run in interpret mode (bit-exact kernel-body
execution — a correctness cost, not a TPU projection), so each row
carries its execution `mode`. Structural numbers for the TPU target
(VMEM per block) are derived analytically; the fused backend is
*skipped* — recorded as absent with `fused_fits_vmem: false` on the
geometry's other rows — where its int8 table block cannot fit the
16 MiB VMEM at any useful tile, which is
exactly the regime the packed kernel exists for (DESIGN §2 "Packed
layout").

    python benchmarks/kernel_bench.py                  # full sweep
    python benchmarks/kernel_bench.py --smoke          # two geometries (CI)
    python benchmarks/kernel_bench.py --check BENCH_kernel.json
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import zlib

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit
from benchmarks.model_zoo import ZOO
from repro.kernels import fused_wnn, ops, packed_wnn, ref

SCHEMA = "kernel_bench/v3"
# v3: every row carries `interpret` (bool) and `platform` (the jax
# backend that actually ran it) so interpret-mode-on-CPU Pallas numbers
# can never be silently compared against real-hardware rows.
ROW_KEYS = ("model", "submodel", "backend", "mode", "interpret", "platform",
            "b", "n_f", "n", "m", "entries", "k", "wall_us", "vmem_kib",
            "fused_fits_vmem")
FEATURES = 256               # benchmark task: 16x16 synthetic MNIST-like
# per-core VMEM on the TPU target — the same hard limit the kernels'
# `vmem_plan` and the wnnlint vmem-budget rule evaluate against
VMEM_LIMIT = fused_wnn.VMEM_LIMIT

# ULN-XL stress geometry (launch/uleen_cell.py::ULN_XL_SPEC, largest
# submodel): E = 2^15 overflows the fused kernel's VMEM blocking — only
# the packed bitplane layout can hold it on-chip.
XL_GEOMS = [("uln-xl", 0, math.ceil(FEATURES * 8 / 32), 32, 2 ** 15)]


def zoo_geometries():
    """Yields (model, submodel_idx, n_f, n, entries) for every ZOO submodel;
    batch/classes/hashes are `bench_geometry` defaults."""
    for name, (bits, subs, _prune) in ZOO.items():
        total_bits = FEATURES * bits
        for i, (n, log2e) in enumerate(subs):
            yield (name, i, math.ceil(total_bits / n), n, 2 ** log2e)


def fused_vmem_kib(b: int, n: int, m: int, e: int) -> float:
    return fused_wnn.vmem_plan(b, n, m, e)["vmem_bytes"] / 1024.0


def packed_vmem_kib(b: int, n: int, m: int, e: int) -> float:
    return packed_wnn.vmem_plan(b, n, m, e)["vmem_bytes"] / 1024.0


def bench_geometry(model: str, sm_idx: int, n_f: int, n: int, e: int, *,
                   b: int = 256, m: int = 10, k: int = 2) -> list[dict]:
    key = jax.random.PRNGKey(zlib.crc32(f"{model}.{sm_idx}".encode()))
    ks = jax.random.split(key, 4)
    tuples = jax.random.bernoulli(ks[0], 0.5, (b, n_f, n)).astype(jnp.int8)
    params = jax.random.randint(ks[1], (k, n), 0, e, dtype=jnp.int32)
    table = jax.random.bernoulli(ks[2], 0.3, (m, n_f, e)).astype(jnp.int8)
    mask = jax.random.bernoulli(ks[3], 0.8, (m, n_f)).astype(jnp.int8)
    bias = jnp.zeros((m,), jnp.int32)
    from repro.packed import pack_words
    words = pack_words(table.astype(jnp.uint32))

    on_tpu = jax.default_backend() == "tpu"
    fits = fused_vmem_kib(b, n, m, e) * 1024 <= VMEM_LIMIT
    vmem = {"fused": fused_vmem_kib(b, n, m, e),
            "packed": packed_vmem_kib(b, n, m, e), "gather": 0.0}
    rows = []
    backends = (["fused"] if fits else []) + ["gather", "packed"]
    for backend in backends:
        if backend == "packed":
            fn = lambda *a: ops.wnn_scores(*a, backend="packed", entries=e)
            args = (tuples, params, words, mask, bias)
        else:
            fn = lambda *a, _be=backend: ops.wnn_scores(*a, backend=_be)
            args = (tuples, params, table, mask, bias)
        us = timeit(fn, *args, iters=5, warmup=1)
        mode = ("tpu" if on_tpu else
                "interpret" if backend in ("fused", "packed") else "xla-cpu")
        rows.append(dict(model=model, submodel=sm_idx, backend=backend,
                         mode=mode, interpret=mode == "interpret",
                         platform=jax.default_backend(),
                         b=b, n_f=n_f, n=n, m=m, entries=e, k=k,
                         wall_us=round(us, 1),
                         vmem_kib=round(vmem[backend], 1),
                         fused_fits_vmem=fits))
        emit(f"kernel.wnn.{model}.sm{sm_idx}.{backend}_us", f"{us:.0f}",
             f"Nf={n_f} n={n} E={e} mode={mode}")
    by = {r["backend"]: r["wall_us"] for r in rows}
    for kernel in ("fused", "packed"):
        if kernel in by:
            emit(f"kernel.wnn.{model}.sm{sm_idx}.{kernel}_over_gather",
                 f"{by[kernel] / max(by['gather'], 1e-9):.2f}",
                 "ratio < 1 means the kernel wins (TPU target; interpret "
                 "mode on CPU)")
    if not fits:
        emit(f"kernel.wnn.{model}.sm{sm_idx}.fused_skipped", "over-vmem",
             f"int8 table block {vmem['fused']:.0f} KiB > "
             f"{VMEM_LIMIT // 1024} KiB; packed block "
             f"{vmem['packed']:.0f} KiB")
    return rows


def structural_report() -> None:
    """Analytical TPU-target numbers for the kernels (no hardware)."""
    b, n_f, n, m, e, k = 256, 131, 12, 10, 64, 2   # ULN-S SM0-like
    plan = fused_wnn.vmem_plan(b, n, m, e)
    emit("kernel.fused_wnn.vmem_kib_per_block",
         f"{plan['vmem_bytes'] / 1024:.0f}",
         f"block=({plan['block_b']},{plan['block_f']}) fits 16MiB VMEM: "
         f"{plan['fits']}")
    pplan = packed_wnn.vmem_plan(b, n, m, e)
    emit("kernel.packed_wnn.vmem_kib_per_block",
         f"{pplan['vmem_bytes'] / 1024:.0f}",
         f"block=({pplan['block_b']},{pplan['block_f']}) "
         f"W={packed_wnn.word_count(e)} words; table bytes 8x denser")
    # the headline: largest submodel VMEM at the ULN-XL entry count
    e_xl = XL_GEOMS[0][4]
    emit("kernel.packed_wnn.uln_xl_vmem_kib",
         f"{packed_vmem_kib(256, 32, 10, e_xl):.0f}",
         f"E=2^15 packed block; int8 would need "
         f"{fused_vmem_kib(256, 32, 10, e_xl):.0f} KiB (> VMEM)")

    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 2)
    tuples = jax.random.bernoulli(ks[0], 0.5, (b, n_f, n)).astype(jnp.int8)
    params = jax.random.randint(ks[1], (k, n), 0, e, dtype=jnp.int32)
    jit_h3 = jax.jit(ref.h3_hash_ref)
    us = timeit(jit_h3, tuples, params, iters=10)
    emit("kernel.h3.oracle_us", f"{us:.0f}", f"{b * n_f * k} hashes")
    emit("kernel.h3.hashes_per_us", f"{b * n_f * k / max(us, 1e-9):.0f}",
         "CPU oracle rate")


def check(path: str) -> int:
    """Validate a BENCH_kernel.json: schema, row keys, backend coverage.

    Every geometry needs a gather + packed pair; fused is additionally
    required exactly when the geometry's rows claim it fits VMEM
    (`fused_fits_vmem`). Returns 0 when well-formed; prints the defect
    and returns 1 otherwise. The CI benchmark-smoke step runs this after
    the --smoke sweep.
    """
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as exc:
        print(f"[check] {path}: unreadable/malformed: {exc}")
        return 1
    if doc.get("schema") != SCHEMA:
        print(f"[check] {path}: schema {doc.get('schema')!r} != {SCHEMA!r}")
        return 1
    rows = doc.get("rows")
    if not isinstance(rows, list) or not rows:
        print(f"[check] {path}: no rows")
        return 1
    backends_seen: dict[tuple, set] = {}
    fits_seen: dict[tuple, bool] = {}
    for i, row in enumerate(rows):
        missing = [kk for kk in ROW_KEYS if kk not in row]
        if missing:
            print(f"[check] {path}: row {i} missing keys {missing}")
            return 1
        if not (isinstance(row["wall_us"], (int, float))
                and row["wall_us"] > 0):
            print(f"[check] {path}: row {i} wall_us={row['wall_us']!r}")
            return 1
        if not isinstance(row["interpret"], bool):
            print(f"[check] {path}: row {i} interpret="
                  f"{row['interpret']!r} (must be bool)")
            return 1
        if row["interpret"] != (row["mode"] == "interpret") \
                or (row["interpret"] and row["platform"] == "tpu"):
            print(f"[check] {path}: row {i} inconsistent provenance: "
                  f"mode={row['mode']!r} interpret={row['interpret']!r} "
                  f"platform={row['platform']!r}")
            return 1
        g = (row["model"], row["submodel"])
        backends_seen.setdefault(g, set()).add(row["backend"])
        fits_seen[g] = bool(row["fused_fits_vmem"])
    bad = []
    for g, bs in sorted(backends_seen.items()):
        need = {"gather", "packed"} | ({"fused"} if fits_seen[g] else set())
        if not need <= bs:
            bad.append((g, sorted(need - bs)))
        if not fits_seen[g] and "fused" in bs:
            bad.append((g, ["fused row despite fused_fits_vmem=false"]))
    if bad:
        print(f"[check] {path}: backend coverage defects: {bad}")
        return 1
    print(f"[check] {path}: ok ({len(rows)} rows, "
          f"{len(backends_seen)} geometries, "
          f"{sum(not v for v in fits_seen.values())} over-VMEM for fused)")
    return 0


def main(smoke: bool = False, out: str = "BENCH_kernel.json") -> None:
    rows = []
    geoms = list(zoo_geometries()) + XL_GEOMS
    if smoke:
        # CI smoke: one zoo geometry + the over-VMEM XL geometry, so the
        # packed rows AND the fused-skip path are both exercised.
        geoms = geoms[:1] + XL_GEOMS
    for model, sm_idx, n_f, n, e in geoms:
        rows.extend(bench_geometry(model, sm_idx, n_f, n, e,
                                   b=64 if smoke else 256))
    structural_report()
    with open(out, "w") as f:
        json.dump({"schema": SCHEMA,
                   "backend": jax.default_backend(),
                   "rows": rows}, f, indent=1)
    emit("kernel.wnn.bench_rows", str(len(rows)), f"written to {out}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="two geometries only (CI benchmark-smoke step)")
    ap.add_argument("--out", default="BENCH_kernel.json")
    ap.add_argument("--check", metavar="PATH",
                    help="validate an existing BENCH_kernel.json and exit")
    args = ap.parse_args()
    if args.check:
        sys.exit(check(args.check))
    main(smoke=args.smoke, out=args.out)
