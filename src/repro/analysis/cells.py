"""CellProgram builders: one evidence bundle per lowered cell.

This is where each cell's *intent* becomes lintable configuration — which
shapes would be an unpacked table, what the collective budget is, which
kernel geometries must block inside VMEM, and the byte thresholds the
sharding-coverage rule gates on. The builders mirror
`launch/uleen_cell.py` exactly (same step functions, same spec/sharding
resolution), trace the jaxpr with `jax.make_jaxpr` (cheap — no compile),
and either reuse an already-compiled executable (`dryrun --analyze`
passes the one it just built) or compile one themselves (the standalone
`scripts/lint_programs.py` on the host mesh).

Thresholds are derived from the geometry, not hand-tuned:

* `big_param_bytes` = half the smallest packed words-plane's *global*
  bytes — every legitimately-replicated input (perms, H3 params, bias)
  sits orders of magnitude below it, while a words plane whose class
  sharding regressed to replication lands above it at full size;
* `max_intermediate_bytes` = 3x the largest per-device intermediate the
  serve formulations legitimately materialize (the (B_loc, M_loc, N_f, k)
  addressed-bits tensor of the packed oracle dominates). Losing the
  class sharding inflates that tensor by the class-shard degree (>= 4 on
  every sharded mesh), clearing the 3x headroom.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.analysis.registry import CellProgram, KernelGeometry
from repro.dist import sharding as sh
from repro.launch import uleen_cell
from repro.packed.layout import word_count

# shape name -> (spec, kind) — mirrors launch/dryrun.py::run_uleen_cell
ULEEN_CELLS = {
    "train_mnist_scale": (uleen_cell.ULN_L_SPEC, "train"),
    "train_host_exec": (uleen_cell.ULEEN_EXEC_SPEC, "train"),
    "infer_mnist_scale": (uleen_cell.ULN_L_SPEC, "infer"),
    "infer_packed_scale": (uleen_cell.ULN_XL_SPEC, "infer"),
    "infer_sharded_scale": (uleen_cell.ULN_XL_ENSEMBLE_SPEC, "infer"),
    "infer_multitenant_scale": (uleen_cell.ULN_S_SPEC, "infer"),
}


def unpacked_table_shapes(spec) -> frozenset:
    """The (M, N_f, E) extents that must never appear as an aval in this
    geometry's packed-path program."""
    return frozenset((spec.num_classes, spec.num_filters(sm), sm.entries)
                     for sm in spec.submodels)


def kernel_geometries(spec, batch: int, backend: str) -> tuple:
    """One KernelGeometry per submodel for the Pallas kernel this cell
    launches on the deployment target ("fused" for int8 tables, "packed"
    for bitplanes) — what the vmem-budget rule evaluates analytically."""
    return tuple(
        KernelGeometry(backend=backend, batch=batch,
                       n_f=spec.num_filters(sm), n=sm.inputs_per_filter,
                       m=spec.num_classes, entries=sm.entries,
                       label=f"submodel[{i}]")
        for i, sm in enumerate(spec.submodels))


def _coverage_thresholds(spec, mesh, batch: int) -> tuple:
    """(big_param_bytes, max_intermediate_bytes) for the sharded cell on
    `mesh` — see the module docstring for the derivation."""
    m = spec.num_classes
    words_bytes = [m * spec.num_filters(sm) * word_count(sm.entries) * 4
                   for sm in spec.submodels]
    big_param = min(words_bytes) // 2

    _entry, class_deg = sh.class_partition(mesh, m, sh.SERVE_RULES)
    batch_entry = sh.SERVE_RULES.resolve(("batch",), mesh, shape=(batch,))[0]
    b_loc = batch // sh.spec_degree(mesh, batch_entry)
    m_loc = -(-m // class_deg)
    legit = max(max(
        b_loc * spec.num_filters(sm) * sm.inputs_per_filter,   # tuples int8
        b_loc * m_loc * spec.num_filters(sm) * sm.num_hashes * 4,  # oracle
        b_loc * spec.total_bits,                               # bits shard
    ) for sm in spec.submodels)
    return float(big_param), float(3 * legit)


def _mt_coverage_thresholds(spec, mesh, batch: int, tenants: int) -> tuple:
    """(big_param_bytes, max_intermediate_bytes) for the multi-tenant
    fleet cell. Every table leaf is tenant-sharded, so the threshold is
    half the smallest *stacked* words plane's global bytes: a shard
    arrives at global/degree (well under), a regression to replication at
    full size (well over). The dominant legit per-device intermediate is
    the (B_loc, N_f, k, M) int32 per-hash lookup tensor of the tenant
    oracle (`kernels.ref.packed_wnn_tenant_ref`); the local bitcast
    words view and the batch shard trail it."""
    m = spec.num_classes
    words_bytes = [tenants * m * spec.num_filters(sm)
                   * word_count(sm.entries) * 4 for sm in spec.submodels]
    big_param = min(words_bytes) // 2

    _entry, deg = sh.tenant_partition(mesh, tenants, sh.SERVE_RULES)
    t_loc = tenants // deg
    batch_entry = sh.SERVE_RULES.resolve(("batch",), mesh, shape=(batch,))[0]
    b_loc = batch // sh.spec_degree(mesh, batch_entry)
    legit = max(max(
        b_loc * spec.num_filters(sm) * sm.num_hashes * m * 4,     # lookups
        b_loc * spec.num_filters(sm) * sm.inputs_per_filter * 4,  # perm rows
        t_loc * m * spec.num_filters(sm) * word_count(sm.entries) * 4,
        b_loc * spec.total_bits,                                  # bits shard
    ) for sm in spec.submodels)
    return float(big_param), float(3 * legit)


def uleen_cell_program(shape: str, mesh, *,
                       global_batch: Optional[int] = None,
                       backend: str = "auto",
                       compiled=None,
                       with_hlo: bool = True) -> CellProgram:
    """The CellProgram for one uleen dryrun shape on `mesh`.

    `compiled` reuses an executable the caller already built (dryrun);
    otherwise the cell is compiled here when `with_hlo` (the train cell
    defaults to jaxpr-only — none of its rules read HLO, and compiling
    the full Adam step is the slow part of a lint run).
    """
    if shape not in ULEEN_CELLS:
        raise ValueError(f"unknown uleen shape {shape!r}; "
                         f"known: {tuple(ULEEN_CELLS)}")
    spec, kind = ULEEN_CELLS[shape]
    train = kind == "train"
    batch = global_batch if global_batch is not None else (
        uleen_cell.GLOBAL_BATCH if train else uleen_cell.INFER_BATCH)
    rules = sh.TRAIN_RULES if train else sh.SERVE_RULES

    prog = CellProgram(name=f"uleen.{shape}", kind=kind,
                       serving=not train)

    if shape == "train_host_exec":
        # The executed distributed step (DESIGN §10). Its home is the
        # 8-device (pod=2, data=4) exec mesh — lint CLI meshes have no
        # `pod` axis, so the cell builds its own (the program is a
        # function of the mesh; linting it on a pod-less mesh would lint
        # a different program than the one dryrun runs).
        from repro.launch.mesh import make_mesh
        from repro.train import optimizer as opt_lib
        if "pod" not in mesh.axis_names:
            mesh = make_mesh((2, 4), ("pod", "data"))
        batch = (global_batch if global_batch is not None
                 else uleen_cell.EXEC_BATCH)
        optimizer = opt_lib.adam(1e-3)
        step = uleen_cell.make_uleen_dist_train_step(
            spec, optimizer, mesh, compress=True)
        ins, _sh = uleen_cell.uleen_cell_specs(spec, mesh,
                                               global_batch=batch)
        opt_spec = jax.eval_shape(optimizer.init, ins["params"])
        with sh.use_mesh(mesh, rules):
            prog.jaxpr = jax.make_jaxpr(step)(
                ins["params"], opt_spec, ins["statics"], ins["bits"],
                ins["labels"], ins["rng"])
            if with_hlo and compiled is None:
                compiled = uleen_cell.lower_uleen_dist_cell(
                    mesh, global_batch=batch, compress=True)
        prog.hlo_text = compiled.as_text() if compiled is not None else None
        return prog

    if shape == "train_mnist_scale":
        from repro.train import optimizer as opt_lib
        optimizer = opt_lib.adam(1e-3)
        step = uleen_cell.make_uleen_train_step(spec, optimizer)
        ins, _sh = uleen_cell.uleen_cell_specs(spec, mesh,
                                               global_batch=batch)
        opt_spec = jax.eval_shape(optimizer.init, ins["params"])
        rng = jax.ShapeDtypeStruct((2,), "uint32")
        with sh.use_mesh(mesh, rules):
            prog.jaxpr = jax.make_jaxpr(step)(
                ins["params"], opt_spec, ins["statics"], ins["bits"],
                ins["labels"], rng)
            if with_hlo and compiled is None and batch == \
                    uleen_cell.GLOBAL_BATCH:
                compiled = uleen_cell.lower_uleen_cell(mesh, spec=spec)
        prog.hlo_text = compiled.as_text() if compiled is not None else None
        return prog

    if shape == "infer_mnist_scale":
        step = uleen_cell.make_uleen_infer_step(spec, backend=backend)
        ins, _sh = uleen_cell.uleen_infer_specs(spec, mesh,
                                                global_batch=batch)
        args = (ins["tables"], ins["masks"], ins["bias"], ins["statics"],
                ins["bits"])
        lower = lambda: uleen_cell.lower_uleen_infer_cell(
            mesh, global_batch=batch, spec=spec, backend=backend)
        # the int8-table cell deploys the fused (select-scan) kernel
        prog.kernel_geometries = kernel_geometries(spec, batch, "fused")
    elif shape == "infer_multitenant_scale":
        tenants = uleen_cell.MULTITENANT_TENANTS
        ins, _sh2 = uleen_cell.uleen_multitenant_infer_specs(
            spec, mesh, tenants=tenants, global_batch=batch)
        step = uleen_cell.make_uleen_multitenant_infer_step(
            ins["st"], mesh, batch, backend=backend)
        args = (ins["st"], ins["bits"], ins["tids"])
        lower = lambda: uleen_cell.lower_uleen_multitenant_infer_cell(
            mesh, tenants=tenants, global_batch=batch, spec=spec,
            backend=backend)
        prog.packed = True
        # neither the per-tenant (M, N_f, E) table nor its stacked
        # (T, M, N_f, E) fleet form may ever materialize
        prog.unpacked_table_shapes = (
            unpacked_table_shapes(spec)
            | frozenset((tenants,) + s for s in
                        unpacked_table_shapes(spec)))
        prog.kernel_geometries = kernel_geometries(spec, batch, "packed")
        _entry, degree = sh.tenant_partition(mesh, tenants,
                                             sh.SERVE_RULES)
        if degree > 1:   # a trivial mesh has nothing to cover
            prog.sharded = True
            # the ONE psum of ownership-masked partials (DESIGN §11)
            prog.collective_budget = {"all-reduce": 1}
            (prog.big_param_bytes,
             prog.max_intermediate_bytes) = _mt_coverage_thresholds(
                 spec, mesh, batch, tenants)
    else:
        packed_cell = shape == "infer_packed_scale"
        step = (uleen_cell.make_uleen_packed_infer_step(backend=backend)
                if packed_cell
                else uleen_cell.make_uleen_sharded_infer_step(
                    backend=backend))
        specs_fn = (uleen_cell.uleen_packed_infer_specs if packed_cell
                    else uleen_cell.uleen_sharded_infer_specs)
        ins, _sh = specs_fn(spec, mesh, global_batch=batch)
        args = (ins["ptables"], ins["bits"])
        lower = lambda: (
            uleen_cell.lower_uleen_packed_infer_cell if packed_cell
            else uleen_cell.lower_uleen_sharded_infer_cell)(
                mesh, global_batch=batch, spec=spec, backend=backend)
        prog.packed = True
        prog.unpacked_table_shapes = unpacked_table_shapes(spec)
        prog.kernel_geometries = kernel_geometries(spec, batch, "packed")
        if not packed_cell:
            _entry, degree = sh.class_partition(mesh, spec.num_classes,
                                                sh.SERVE_RULES)
            if degree > 1:   # a trivial mesh has nothing to cover
                prog.sharded = True
                prog.collective_budget = {"all-gather": 1}
                (prog.big_param_bytes,
                 prog.max_intermediate_bytes) = _coverage_thresholds(
                     spec, mesh, batch)

    with sh.use_mesh(mesh, rules):
        prog.jaxpr = jax.make_jaxpr(step)(*args)
        if with_hlo and compiled is None:
            compiled = lower()
    prog.hlo_text = compiled.as_text() if compiled is not None else None
    return prog


def hlo_cell_program(name: str, kind: str, hlo_text: str) -> CellProgram:
    """HLO-only program for the LLM cells (train/prefill/decode): the
    jaxpr-side rules stay silent; no-f64 and no-host-callback read the
    compiled module directly."""
    return CellProgram(name=name, kind=kind, hlo_text=hlo_text,
                       serving=kind != "train")
