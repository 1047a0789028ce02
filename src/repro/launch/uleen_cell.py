"""The paper's own technique as a production-mesh dry-run cell.

Distributed ULEEN multi-shot training step (ULN-L geometry at MNIST scale:
784 features × 7 thermometer bits, 6 Bloom submodels): hashing (H3), the
continuous-Bloom STE forward/backward gather/scatter, cross-entropy, and
the Adam update — pjit-sharded batch over (pod, data), tables replicated
(the whole continuous ensemble is ~20 MiB: WNN state is tiny; the batch is
what scales). This is how the paper's PyTorch/GPU trainer maps onto a TPU
fleet, and the §Perf cell where the technique itself is hill-climbed
(gradient compression, hash recompute-vs-store).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import model as uleen
from repro.core import multi_shot
from repro.core.model import SubmodelSpec, UleenSpec
from repro.core.multi_shot import cross_entropy
from repro.dist import sharding as sh
from repro.train import optimizer as opt_lib

# ULN-L geometry (paper Table I), 784 px × 7 bits.
# dropout_shared_classes: §Perf it.5 — per-(sample, class, filter) RNG was
# the cell's dominant HBM traffic; one mask per (sample, filter) is the
# fleet-scale configuration.
ULN_L_SPEC = UleenSpec(
    num_classes=10, total_bits=784 * 7,
    submodels=(SubmodelSpec(12, 6), SubmodelSpec(16, 7),
               SubmodelSpec(20, 7), SubmodelSpec(24, 8),
               SubmodelSpec(28, 8), SubmodelSpec(32, 9)),
    bits_per_input=7, dropout_shared_classes=True, bf16_tables=True)

GLOBAL_BATCH = 131072      # fleet-scale data parallelism
INFER_BATCH = 65536        # fleet-scale serving batch (binary model)

# The *executed* trainer cell's geometry (DESIGN §10): the tiny 2-submodel
# ensemble every in-container execution surface shares (dryrun
# train_host_exec, the --arch uleen CLI, tests). 16x16 mnist-like at
# 2 thermometer bits = 512 total bits; small enough that a real 10-step
# distributed run + its single-device parity reference fit in a CI smoke.
ULEEN_EXEC_SPEC = UleenSpec(
    num_classes=10, total_bits=512,
    submodels=(SubmodelSpec(12, 6), SubmodelSpec(16, 6)),
    bits_per_input=2)
EXEC_BATCH = 256           # global batch of the executed host-mesh cell

# ULN-XL: an ensemble past the int8 kernel's VMEM blocking — E up to 2^15
# means the fused int8 table block (M × E × 128 filters) overflows the
# 16 MiB VMEM at any useful block, while the packed bitplane kernel holds
# the same tables in E/8 bytes per filter and blocks comfortably
# (DESIGN §2 "Packed layout"). 784 px × 8 thermometer bits.
ULN_XL_SPEC = UleenSpec(
    num_classes=10, total_bits=784 * 8,
    submodels=(SubmodelSpec(16, 11), SubmodelSpec(24, 13),
               SubmodelSpec(32, 15)),
    bits_per_input=8, dropout_shared_classes=True)

# ULN-XL ensemble: the class-sharded serving target (DESIGN §7) — the XL
# geometry grown to a 32-way label space (a multi-task edge deployment:
# several datasets' discriminators served as one ensemble, the scaling
# regime BTHOWeN/DWN motivate). Replicating its packed tables costs
# ~36 MiB per device; sharded over `model` by class each device holds
# M/16 discriminators' tables and the only cross-device traffic is the
# final (B, M) score gather.
ULN_XL_ENSEMBLE_SPEC = UleenSpec(
    num_classes=32, total_bits=784 * 8,
    submodels=(SubmodelSpec(16, 11), SubmodelSpec(24, 13),
               SubmodelSpec(32, 15)),
    bits_per_input=8, dropout_shared_classes=True)

# ULN-S: the paper's smallest MNIST ensemble — the KB-scale artifact the
# multi-tenant fleet stacks by the thousand (DESIGN §11). 784 px × 2
# thermometer bits, 3 submodels, E=64: ~24 KiB of packed words per
# tenant, so even 2048 tenants are ~50 MiB of global tables (~3 MiB per
# device at 16-way `model` sharding) — tenancy, not size, is what this
# cell scales.
ULN_S_SPEC = UleenSpec(
    num_classes=10, total_bits=784 * 2,
    submodels=(SubmodelSpec(12, 6), SubmodelSpec(16, 6),
               SubmodelSpec(20, 6)),
    bits_per_input=2, dropout_shared_classes=True)

# Fleet size of the infer_multitenant_scale cell: ≥1024 per the roadmap
# acceptance bar, divisible by the production `model` degree (16) and the
# CI lint mesh's (4).
MULTITENANT_TENANTS = 2048


def make_uleen_train_step(spec: UleenSpec, optimizer: opt_lib.Optimizer):
    def train_step(params, opt_state, statics, bits, labels, rng):
        statics = [uleen.SubmodelStatic(*s) for s in statics]

        def loss_fn(p):
            hashes = uleen.compute_hashes(spec, statics, bits)
            scores = uleen.forward(spec, p, hashes, train=True, rng=rng)
            return cross_entropy(scores, labels)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = opt_lib.apply_updates(params, updates)
        params = params._replace(tables=tuple(
            jnp.clip(t, -1.0, 1.0) for t in params.tables))
        return params, opt_state, loss

    return train_step


def make_uleen_dist_train_step(spec: UleenSpec, optimizer: opt_lib.Optimizer,
                               mesh, *, grad_blocks: int = 8,
                               compress: bool = False,
                               clip_table: float = 1.0,
                               smoothing: float = 0.0):
    """The *executed* distributed multi-shot step (DESIGN §10).

    Deterministic blocked batch reduction: the global batch splits into a
    FIXED number of blocks S=`grad_blocks` (mesh-independent), each block's
    gradient is computed whole on one device, and the block gradients are
    all-gathered and left-folded in global block order. Because both the
    block boundaries and the fold order are functions of S alone, the
    result is bit-identical to `core.multi_shot.make_train_step(...,
    grad_blocks=S)` on one device — and to itself across mesh shapes
    ((pod, data), (data,), single device), which is what makes the
    elastic 8→4→1 restart drill byte-reproducible.

    compress=True routes the cross-pod hop through `compressed_psum`
    (int8 wire): block sums reduce in fp32 over `data` (intra-pod ICI),
    the per-pod mean crosses `pod` as int8. Divergence from the exact
    path is bounded by the quantisation step — asserted per-step in
    tests/test_distributed_training.py (max |Δparam| ≤ lr·(t+1)·1.25
    for Adam, whose per-step update magnitude is capped ≈ lr).

    Step: (params, opt_state, statics, bits, labels, rng) ->
    (params, opt_state, loss, acc), jit-able with batch sharded over all
    mesh axes and everything else replicated (`uleen_dist_specs`).
    """
    from repro.train.compression import compressed_psum

    axes = tuple(mesh.axis_names)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ndev = 1
    for d in mesh.devices.shape:
        ndev *= d
    s = grad_blocks
    if s % ndev:
        raise ValueError(f"grad_blocks {s} not divisible by {ndev} devices")
    bpd = s // ndev                      # blocks per device
    npods = sizes.get("pod", 1)
    if compress and "pod" not in sizes:
        raise ValueError("compress=True needs a `pod` mesh axis")

    loss_fn = multi_shot.make_loss_fn(spec, smoothing)

    def local(params, statics_t, bits_l, labels_l, rng):
        sts = [uleen.SubmodelStatic(*st) for st in statics_t]
        # Linear device index in mesh order == global block order: device
        # (i_pod, i_data) holds blocks [dev*bpd, (dev+1)*bpd) of the
        # S-block global batch, matching the all_gather concatenation
        # order below, so the fold visits blocks 0..S-1 exactly as the
        # single-device reference does.
        dev = jnp.int32(0)
        for a in axes:
            dev = dev * sizes[a] + jax.lax.axis_index(a)
        hashes = uleen.compute_hashes(spec, sts, bits_l)
        gs, ls, accs = multi_shot.block_grads(
            loss_fn, params, hashes, labels_l, rng, bpd, dev * bpd)

        if compress:
            # fp32 intra-pod (ICI), int8 cross-pod (the scarce link).
            gsum = jax.tree.map(lambda x: jnp.sum(x, 0), gs)
            gpod = jax.tree.map(
                lambda x: jax.lax.psum(x, "data") * (npods / s)
                if "data" in sizes else x * (npods / s), gsum)
            g, _ = compressed_psum(gpod, "pod")
            loss = jax.lax.pmean(jnp.mean(ls), axes)
            acc = jax.lax.pmean(jnp.mean(accs), axes)
            return g, loss, acc

        # Exact path: gather the per-block stacks (bit-preserving — no
        # arithmetic on the wire) and left-fold in global block order.
        gall = jax.tree.map(
            lambda x: jax.lax.all_gather(x, axes).reshape(s, *x.shape[1:]),
            gs)
        lall = jax.lax.all_gather(ls, axes).reshape(s)
        aall = jax.lax.all_gather(accs, axes).reshape(s)
        return multi_shot.fold_blocks(gall, lall, aall)

    bspec = P(axes if len(axes) > 1 else axes[0])
    grads_fn = sh.shard_map(
        local, mesh,
        in_specs=(P(), P(), bspec, bspec, P()),
        out_specs=P())

    def train_step(params, opt_state, statics, bits, labels, rng):
        statics_t = tuple(tuple(st) for st in statics)
        grads, loss, acc = grads_fn(params, statics_t, bits, labels, rng)
        params, opt_state = multi_shot.apply_step(optimizer, params,
                                                  opt_state, grads,
                                                  clip_table)
        return params, opt_state, loss, acc

    return train_step


def uleen_dist_specs(spec: UleenSpec, mesh, global_batch: int):
    """NamedShardings for the executed distributed step: batch over every
    mesh axis, params/opt/statics/rng replicated (the continuous ensemble
    is ~MBs — batch is what scales, module docstring)."""
    from jax.sharding import NamedSharding
    axes = tuple(mesh.axis_names)
    bspec = P(axes if len(axes) > 1 else axes[0])
    rep = NamedSharding(mesh, P())
    return dict(rep=rep,
                bits=NamedSharding(mesh, bspec),
                labels=NamedSharding(mesh, bspec))


def uleen_cell_specs(spec: UleenSpec, mesh, *, global_batch: int = GLOBAL_BATCH):
    """(abstract inputs, shardings) for the dry-run lowering."""
    rules = sh.TRAIN_RULES
    rep = sh.named_sharding(mesh, rules, ())

    def table_spec(sm):
        n_f = spec.num_filters(sm)
        return jax.ShapeDtypeStruct((spec.num_classes, n_f, sm.entries),
                                    jnp.float32)

    params = uleen.UleenParams(
        tables=tuple(table_spec(sm) for sm in spec.submodels),
        bias=jax.ShapeDtypeStruct((spec.num_classes,), jnp.float32),
        masks=tuple(jax.ShapeDtypeStruct(
            (spec.num_classes, spec.num_filters(sm)), jnp.float32)
            for sm in spec.submodels))
    statics = tuple(
        (jax.ShapeDtypeStruct((spec.num_filters(sm), sm.inputs_per_filter),
                              jnp.int32),
         jax.ShapeDtypeStruct((sm.num_hashes, sm.inputs_per_filter),
                              jnp.uint32))
        for sm in spec.submodels)
    bits = jax.ShapeDtypeStruct((global_batch, spec.total_bits), jnp.bool_)
    labels = jax.ShapeDtypeStruct((global_batch,), jnp.int32)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)

    rep_tree = lambda t: jax.tree.map(lambda _: rep, t)
    shardings = dict(
        params=rep_tree(params),
        statics=rep_tree(statics),
        bits=sh.named_sharding(mesh, rules, ("batch", None),
                               shape=bits.shape),
        labels=sh.named_sharding(mesh, rules, ("batch",),
                                 shape=labels.shape),
        rng=rep)
    return dict(params=params, statics=statics, bits=bits, labels=labels,
                rng=rng), shardings


def make_uleen_infer_step(spec: UleenSpec, *, backend: str = "auto"):
    """Deployed binary-model inference step, backend-dispatched.

    backend threads through `core.model.forward_binary_fused` into
    `kernels.ops.wnn_scores` (DESIGN §2 "Adoption"): "fused" lowers one
    Pallas kernel per submodel; "gather" the take_along_axis formulation;
    "auto" picks per platform (gather on this CPU host, fused on TPU).
    """
    def infer_step(tables_bin, masks, bias, statics, bits):
        statics = [uleen.SubmodelStatic(*s) for s in statics]
        return uleen.forward_binary_fused(spec, statics, tables_bin, masks,
                                          bias, bits, backend=backend)

    return infer_step


def uleen_infer_specs(spec: UleenSpec, mesh, *,
                      global_batch: int = INFER_BATCH):
    """(abstract inputs, shardings) for the inference-cell lowering."""
    rules = sh.SERVE_RULES
    rep = sh.named_sharding(mesh, rules, ())
    tables = tuple(jax.ShapeDtypeStruct(
        (spec.num_classes, spec.num_filters(sm), sm.entries), jnp.int8)
        for sm in spec.submodels)
    masks = tuple(jax.ShapeDtypeStruct(
        (spec.num_classes, spec.num_filters(sm)), jnp.float32)
        for sm in spec.submodels)
    bias = jax.ShapeDtypeStruct((spec.num_classes,), jnp.float32)
    statics = tuple(
        (jax.ShapeDtypeStruct((spec.num_filters(sm), sm.inputs_per_filter),
                              jnp.int32),
         jax.ShapeDtypeStruct((sm.num_hashes, sm.inputs_per_filter),
                              jnp.uint32))
        for sm in spec.submodels)
    bits = jax.ShapeDtypeStruct((global_batch, spec.total_bits), jnp.bool_)
    rep_tree = lambda t: jax.tree.map(lambda _: rep, t)
    shardings = dict(
        tables=rep_tree(tables), masks=rep_tree(masks), bias=rep,
        statics=rep_tree(statics),
        bits=sh.named_sharding(mesh, rules, ("batch", None),
                               shape=bits.shape))
    return dict(tables=tables, masks=masks, bias=bias, statics=statics,
                bits=bits), shardings


def lower_uleen_infer_cell(mesh, *, global_batch: int = INFER_BATCH,
                           spec: UleenSpec = ULN_L_SPEC,
                           backend: str = "auto"):
    """AOT lower + compile the deployed inference step on `mesh`."""
    step = make_uleen_infer_step(spec, backend=backend)
    ins, shard = uleen_infer_specs(spec, mesh, global_batch=global_batch)
    with sh.use_mesh(mesh, sh.SERVE_RULES):
        fn = jax.jit(step, in_shardings=(
            shard["tables"], shard["masks"], shard["bias"],
            shard["statics"], shard["bits"]))
        lowered = fn.lower(ins["tables"], ins["masks"], ins["bias"],
                           ins["statics"], ins["bits"])
        return lowered.compile()


def make_uleen_packed_infer_step(*, backend: str = "auto"):
    """Deployed packed-domain inference step (DESIGN §2 "Packed layout").

    The whole model arrives as one `repro.packed.PackedTables` pytree —
    uint32 bitplanes, masks, perms, H3 parameters, bias — and the step is
    `packed.packed_scores`: the traced program contains no int8 table and
    no unpack. backend="packed" pins the bitplane Pallas kernel;
    "auto" keeps the packed domain but picks the platform formulation.
    """
    from repro.packed import runtime

    def infer_step(ptables, bits):
        return runtime.packed_scores(ptables, bits, backend=backend)

    return infer_step


def packed_table_specs(spec: UleenSpec):
    """Abstract `PackedTables` (ShapeDtypeStructs) for a geometry — the
    deployable model the packed/sharded inference cells lower against."""
    from repro.packed import layout
    m = spec.num_classes
    return layout.PackedTables(
        words=tuple(jax.ShapeDtypeStruct(
            (m, spec.num_filters(sm), layout.word_count(sm.entries)),
            jnp.uint32) for sm in spec.submodels),
        masks=tuple(jax.ShapeDtypeStruct((m, spec.num_filters(sm)), jnp.int8)
                    for sm in spec.submodels),
        perms=tuple(jax.ShapeDtypeStruct(
            (spec.num_filters(sm), sm.inputs_per_filter), jnp.int32)
            for sm in spec.submodels),
        h3s=tuple(jax.ShapeDtypeStruct(
            (sm.num_hashes, sm.inputs_per_filter), jnp.int32)
            for sm in spec.submodels),
        bias=jax.ShapeDtypeStruct((m,), jnp.int32),
        entries=tuple(sm.entries for sm in spec.submodels),
        num_classes=m)


def uleen_packed_infer_specs(spec: UleenSpec, mesh, *,
                             global_batch: int = INFER_BATCH):
    """(abstract inputs, shardings) for the packed inference-cell lowering."""
    rules = sh.SERVE_RULES
    rep = sh.named_sharding(mesh, rules, ())
    ptables = packed_table_specs(spec)
    bits = jax.ShapeDtypeStruct((global_batch, spec.total_bits), jnp.bool_)
    shardings = dict(
        ptables=jax.tree.map(lambda _: rep, ptables),
        bits=sh.named_sharding(mesh, rules, ("batch", None),
                               shape=bits.shape))
    return dict(ptables=ptables, bits=bits), shardings


def lower_uleen_packed_infer_cell(mesh, *, global_batch: int = INFER_BATCH,
                                  spec: UleenSpec = ULN_XL_SPEC,
                                  backend: str = "auto"):
    """AOT lower + compile the packed-domain inference step on `mesh`."""
    step = make_uleen_packed_infer_step(backend=backend)
    ins, shard = uleen_packed_infer_specs(spec, mesh,
                                          global_batch=global_batch)
    with sh.use_mesh(mesh, sh.SERVE_RULES):
        fn = jax.jit(step, in_shardings=(shard["ptables"], shard["bits"]))
        lowered = fn.lower(ins["ptables"], ins["bits"])
        return lowered.compile()


def make_uleen_sharded_infer_step(*, backend: str = "auto"):
    """Class-sharded packed inference step (DESIGN §7).

    `packed.packed_predict`: per-device partial score columns over the
    class-partitioned bitplane tables, one (B, M) score gather, argmax.
    Returns (scores, predictions) — the serve path's full answer.
    """
    from repro.packed import runtime

    def infer_step(ptables, bits):
        return runtime.packed_predict(ptables, bits, backend=backend)

    return infer_step


def uleen_sharded_infer_specs(spec: UleenSpec, mesh, *,
                              global_batch: int = INFER_BATCH):
    """(abstract inputs, shardings) for the class-sharded inference cell:
    tables partitioned over `model` by class, batch over (pod, data)."""
    rules = sh.SERVE_RULES
    ptables = packed_table_specs(spec)
    bits = jax.ShapeDtypeStruct((global_batch, spec.total_bits), jnp.bool_)
    shardings = dict(
        ptables=ptables.class_shardings(mesh, rules),
        bits=sh.named_sharding(mesh, rules, ("batch", None),
                               shape=bits.shape))
    return dict(ptables=ptables, bits=bits), shardings


def lower_uleen_sharded_infer_cell(mesh, *, global_batch: int = INFER_BATCH,
                                   spec: UleenSpec = ULN_XL_ENSEMBLE_SPEC,
                                   backend: str = "auto"):
    """AOT lower + compile the class-sharded inference step on `mesh`.

    The scaling configuration the ROADMAP calls for once geometries
    outgrow MNIST: per-device table bytes fall to replicated/degree
    (degree = the `model`-axis shard count `dist.sharding.class_partition`
    reports), and serve throughput scales with the `data` axis instead of
    being capped by single-device VMEM/HBM.
    """
    step = make_uleen_sharded_infer_step(backend=backend)
    ins, shard = uleen_sharded_infer_specs(spec, mesh,
                                           global_batch=global_batch)
    with sh.use_mesh(mesh, sh.SERVE_RULES):
        fn = jax.jit(step, in_shardings=(shard["ptables"], shard["bits"]))
        lowered = fn.lower(ins["ptables"], ins["bits"])
        return lowered.compile()


def stacked_table_specs(spec: UleenSpec, tenants: int):
    """Abstract `StackedPackedTables` (ShapeDtypeStructs): `tenants`
    same-geometry deployable models along the leading fleet axis."""
    from repro.packed import layout
    pt = packed_table_specs(spec)
    lead = lambda x: jax.ShapeDtypeStruct((tenants,) + x.shape, x.dtype)
    return layout.StackedPackedTables(
        words=tuple(lead(w) for w in pt.words),
        masks=tuple(lead(m) for m in pt.masks),
        perms=tuple(lead(p) for p in pt.perms),
        h3s=tuple(lead(h) for h in pt.h3s),
        bias=lead(pt.bias),
        entries=pt.entries, num_classes=pt.num_classes,
        num_tenants=tenants)


def make_uleen_multitenant_infer_step(st_spec, mesh, global_batch: int, *,
                                      backend: str = "auto"):
    """Tenant-sharded fleet inference step (DESIGN §11).

    `packed.runtime.make_tenant_sharded_predict`: the fleet's stacked
    bitplane tables partition over `model` by tenant, each shard scores
    the rows it owns, and the masked partials cross the mesh in one psum.
    Returns (scores, predictions) for every (bits row, tenant id) pair.
    """
    from repro.packed import runtime
    return runtime.make_tenant_sharded_predict(
        st_spec, mesh, sh.SERVE_RULES, global_batch, backend=backend)


def uleen_multitenant_infer_specs(spec: UleenSpec, mesh, *,
                                  tenants: int = 0,
                                  global_batch: int = INFER_BATCH):
    """(abstract inputs, shardings) for the multi-tenant inference cell:
    stacked tables partitioned over `model` by tenant, batch + tenant-id
    vector over the batch axes."""
    rules = sh.SERVE_RULES
    tenants = tenants or MULTITENANT_TENANTS
    st = stacked_table_specs(spec, tenants)
    bits = jax.ShapeDtypeStruct((global_batch, spec.total_bits), jnp.bool_)
    tids = jax.ShapeDtypeStruct((global_batch,), jnp.int32)
    shardings = dict(
        st=st.tenant_shardings(mesh, rules),
        bits=sh.named_sharding(mesh, rules, ("batch", None),
                               shape=bits.shape),
        tids=sh.named_sharding(mesh, rules, ("batch",), shape=tids.shape))
    return dict(st=st, bits=bits, tids=tids), shardings


def lower_uleen_multitenant_infer_cell(mesh, *,
                                       tenants: int = 0,
                                       global_batch: int = INFER_BATCH,
                                       spec: UleenSpec = None,
                                       backend: str = "auto"):
    """AOT lower + compile the multi-tenant fleet inference step on `mesh`.

    The N-thousand-artifact serving regime (ROADMAP "multi-tenant
    serving"): `tenants` ULN-S models — each a KB-scale edge artifact —
    stacked along the fleet axis and partitioned over `model`, so the
    whole fleet lowers as ONE fixed-shape scores launch (no per-tenant
    program, no recompile as tenants come and go; the `WnnTenantBatcher`
    hot-cache is the dynamic-admission front end of the same dataflow).
    Per-device table bytes are global/degree; the only cross-device
    traffic is the single (B, M) psum of ownership-masked partials.
    """
    spec = spec if spec is not None else ULN_S_SPEC
    tenants = tenants or MULTITENANT_TENANTS
    ins, shard = uleen_multitenant_infer_specs(
        spec, mesh, tenants=tenants, global_batch=global_batch)
    step = make_uleen_multitenant_infer_step(ins["st"], mesh, global_batch,
                                             backend=backend)
    with sh.use_mesh(mesh, sh.SERVE_RULES):
        fn = jax.jit(step, in_shardings=(shard["st"], shard["bits"],
                                         shard["tids"]))
        lowered = fn.lower(ins["st"], ins["bits"], ins["tids"])
        return lowered.compile()


def lower_uleen_dist_cell(mesh, *, global_batch: int = EXEC_BATCH,
                          spec: UleenSpec = ULEEN_EXEC_SPEC,
                          grad_blocks: int = 8, compress: bool = False,
                          lr: float = 1e-3):
    """AOT lower + compile the *executed* distributed train step on `mesh`
    (the dryrun train_host_exec cell's memory/roofline artifact — the same
    program `train.train_uleen` jits and actually runs)."""
    optimizer = opt_lib.adam(lr)
    step = make_uleen_dist_train_step(spec, optimizer, mesh,
                                      grad_blocks=grad_blocks,
                                      compress=compress)
    ins, shard = uleen_cell_specs(spec, mesh, global_batch=global_batch)
    opt_spec = jax.eval_shape(optimizer.init, ins["params"])
    rep = sh.named_sharding(mesh, sh.TRAIN_RULES, ())
    opt_shard = jax.tree.map(lambda _: rep, opt_spec)
    with sh.use_mesh(mesh, sh.TRAIN_RULES):
        fn = jax.jit(step, in_shardings=(
            shard["params"], opt_shard, shard["statics"], shard["bits"],
            shard["labels"], shard["rng"]), donate_argnums=(0, 1))
        lowered = fn.lower(ins["params"], opt_spec, ins["statics"],
                           ins["bits"], ins["labels"], ins["rng"])
        return lowered.compile()


def lower_uleen_cell(mesh, *, global_batch: int = GLOBAL_BATCH,
                     spec: UleenSpec = ULN_L_SPEC):
    optimizer = opt_lib.adam(1e-3)
    step = make_uleen_train_step(spec, optimizer)
    ins, shard = uleen_cell_specs(spec, mesh, global_batch=global_batch)
    opt_spec = jax.eval_shape(optimizer.init, ins["params"])
    opt_shard = jax.tree.map(lambda _: shard["params"].tables[0]
                             if False else sh.named_sharding(
                                 mesh, sh.TRAIN_RULES, ()), opt_spec)
    with sh.use_mesh(mesh, sh.TRAIN_RULES):
        fn = jax.jit(step, in_shardings=(
            shard["params"], opt_shard, shard["statics"], shard["bits"],
            shard["labels"], shard["rng"]), donate_argnums=(0, 1))
        lowered = fn.lower(ins["params"], opt_spec, ins["statics"],
                           ins["bits"], ins["labels"], ins["rng"])
        return lowered.compile()
