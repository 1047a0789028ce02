"""wnnlint battery (DESIGN §8 "Program invariants").

Two halves:

* negative cases — every rule in the registry must fire on a
  deliberately broken program (an int8 unpack in the packed path, an
  injected f64, an extra all-reduce, a host callback, an over-VMEM
  BlockSpec, a replicated big array in a sharded cell);
* clean cells — every uleen dryrun shape, built by the same
  `repro.analysis.cells` builders the CI lint uses, analyzes to zero
  error-severity findings on the forced 8-device mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import (CellProgram, KernelGeometry, RULES,
                            all_jaxprs, analyze_program, aval_shapes,
                            primitive_names, report_json, summarize)
from repro.analysis import cells
from repro.launch.mesh import make_mesh
from repro.packed import layout


def _mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs the forced 8-device host mesh (conftest.py)")
    return make_mesh((2, 4), ("data", "model"))


def _errors(findings, rule=None):
    return [f for f in findings
            if f.severity == "error" and (rule is None or f.rule == rule)]


# ---------------------------------------------------------------------------
# the walker reaches Pallas kernel bodies (the old test_packed.py walker
# did not — pallas_call's "jaxpr" param is a raw Jaxpr, not a ClosedJaxpr)
# ---------------------------------------------------------------------------

def test_walker_descends_into_pallas_kernel_bodies():
    from repro.kernels.packed_wnn import packed_wnn
    b, n_f, n, m, e = 8, 8, 4, 3, 64
    tuples = jnp.zeros((b, n_f, n), jnp.int8)
    params = jnp.zeros((2, n), jnp.int32)
    words = jnp.zeros((m, n_f, layout.word_count(e)), jnp.uint32)
    mask = jnp.ones((m, n_f), jnp.int8)
    bias = jnp.zeros((m,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda *a: packed_wnn(*a, interpret=True))(tuples, params, words,
                                                   mask, bias)
    subs = list(all_jaxprs(jaxpr))
    assert len(subs) > 1, "kernel body not reached"
    prims = primitive_names(jaxpr)
    assert "pallas_call" in prims
    # program_id exists ONLY inside a kernel body (the grid position) —
    # visible iff the walker descended into it
    assert "program_id" in prims


# ---------------------------------------------------------------------------
# negative battery: every rule fires on a broken program
# ---------------------------------------------------------------------------

def test_no_unpacked_table_fires_on_unpack_in_packed_path():
    m, n_f, e = 4, 8, 64
    words = jax.ShapeDtypeStruct((m, n_f, layout.word_count(e)),
                                 jnp.uint32)

    def broken(w):   # the 32x expansion the packed runtime exists to avoid
        table = layout.unpack_words(w, e)
        return jnp.sum(table.astype(jnp.int32))

    prog = CellProgram(name="broken.unpack", packed=True,
                       jaxpr=jax.make_jaxpr(broken)(words),
                       unpacked_table_shapes=frozenset({(m, n_f, e)}))
    hits = _errors(analyze_program(prog), "no-unpacked-table")
    assert hits and hits[0].detail["shape"] == [m, n_f, e]


def test_no_f64_fires_on_injected_float64():
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(lambda x: jnp.sum(x * 2.0))(
            jax.ShapeDtypeStruct((16,), jnp.float64))
    prog = CellProgram(name="broken.f64", jaxpr=jaxpr)
    assert _errors(analyze_program(prog), "no-f64")


def test_no_f64_fires_on_hlo_side():
    with jax.enable_x64(True):
        hlo = jax.jit(lambda x: jnp.sum(x * 2.0)).lower(
            jax.ShapeDtypeStruct((16,), jnp.float64)).compile().as_text()
    prog = CellProgram(name="broken.f64hlo", hlo_text=hlo)
    assert _errors(analyze_program(prog), "no-f64")


def test_collective_budget_fires_on_extra_all_reduce():
    mesh = _mesh()
    x = jax.ShapeDtypeStruct((256, 128), jnp.float32)
    hlo = jax.jit(jnp.sum,
                  in_shardings=NamedSharding(mesh, P("data", "model"))
                  ).lower(x).compile().as_text()
    prog = CellProgram(name="broken.allreduce", sharded=True, hlo_text=hlo,
                       collective_budget={"all-gather": 1})
    hits = _errors(analyze_program(prog), "collective-budget")
    assert hits and any(f.detail["kind"] == "all-reduce" for f in hits)


def test_collective_budget_fires_past_the_gather_allowance():
    mesh = _mesh()
    x = jax.ShapeDtypeStruct((256, 128), jnp.float32)
    # batch-sharded in, replicated out: GSPMD must all-gather — with a
    # zero-gather budget even the one gather is a finding
    hlo = jax.jit(lambda v: v * 2,
                  in_shardings=NamedSharding(mesh, P("data", None)),
                  out_shardings=NamedSharding(mesh, P())
                  ).lower(x).compile().as_text()
    prog = CellProgram(name="broken.gather", sharded=True, hlo_text=hlo,
                       collective_budget={})
    hits = _errors(analyze_program(prog), "collective-budget")
    assert hits and any(f.detail["kind"] == "all-gather" for f in hits)


def test_no_host_callback_fires_on_pure_callback():
    def broken(x):
        return jax.pure_callback(
            lambda v: np.asarray(v),
            jax.ShapeDtypeStruct((4,), jnp.float32), x)

    prog = CellProgram(name="broken.callback", serving=True,
                       jaxpr=jax.make_jaxpr(broken)(
                           jax.ShapeDtypeStruct((4,), jnp.float32)))
    hits = _errors(analyze_program(prog), "no-host-callback")
    assert hits and hits[0].detail["primitive"] == "pure_callback"


def test_no_host_callback_fires_on_hlo_custom_call():
    hlo = jax.jit(lambda x: jax.pure_callback(
        lambda v: np.asarray(v),
        jax.ShapeDtypeStruct((4,), jnp.float32), x)).lower(
            jax.ShapeDtypeStruct((4,), jnp.float32)).compile().as_text()
    prog = CellProgram(name="broken.callbackhlo", serving=True,
                       hlo_text=hlo)
    assert _errors(analyze_program(prog), "no-host-callback")


def test_vmem_budget_fires_on_over_vmem_fused_blockspec():
    # ULN-XL's largest submodel: E = 2^15 — the int8 one-hot block
    # overflows 16 MiB VMEM at any useful tile (why the packed kernel
    # exists), while the packed plan for the same geometry fits
    geo = KernelGeometry(backend="fused", batch=256, n_f=196, n=32,
                         m=32, entries=2 ** 15, label="uln-xl.sm2")
    prog = CellProgram(name="broken.vmem", kernel_geometries=(geo,))
    hits = _errors(analyze_program(prog), "vmem-budget")
    assert hits and hits[0].detail["vmem_bytes"] > 16 * 2 ** 20

    from repro.kernels import packed_wnn
    assert packed_wnn.vmem_plan(256, 32, 32, 2 ** 15)["fits"]

    ok = CellProgram(name="ok.vmem", kernel_geometries=(
        KernelGeometry(backend="packed", batch=256, n_f=196, n=32,
                       m=32, entries=2 ** 15),))
    assert not analyze_program(ok, rules=["vmem-budget"])


def test_sharding_coverage_fires_on_replicated_big_param():
    mesh = _mesh()
    x = jax.ShapeDtypeStruct((1 << 20,), jnp.float32)   # 4 MiB
    hlo = jax.jit(lambda v: v * 2,
                  in_shardings=NamedSharding(mesh, P())).lower(
                      x).compile().as_text()
    prog = CellProgram(name="broken.coverage", sharded=True, hlo_text=hlo,
                       big_param_bytes=float(1 << 20))
    assert _errors(analyze_program(prog), "sharding-coverage")


def test_sharding_coverage_fires_on_oversized_intermediate():
    mesh = _mesh()
    x = jax.ShapeDtypeStruct((1 << 18,), jnp.float32)   # 1 MiB sharded in
    hlo = jax.jit(lambda v: v * 2,
                  in_shardings=NamedSharding(mesh, P("data")),
                  out_shardings=NamedSharding(mesh, P())   # gathered out
                  ).lower(x).compile().as_text()
    prog = CellProgram(name="broken.interior", sharded=True, hlo_text=hlo,
                       big_param_bytes=float(1 << 30),
                       max_intermediate_bytes=float(1 << 19))
    hits = _errors(analyze_program(prog), "sharding-coverage")
    assert hits and any("intermediate" in f.message for f in hits)


# ---------------------------------------------------------------------------
# registry mechanics
# ---------------------------------------------------------------------------

def test_registry_has_all_core_rules_at_error_severity():
    expected = {"no-unpacked-table", "no-f64", "collective-budget",
                "no-host-callback", "vmem-budget", "sharding-coverage"}
    assert expected <= set(RULES)
    for name in expected:
        assert RULES[name].severity == "error"
        assert RULES[name].established.startswith("PR ")


def test_report_json_document_shape():
    prog = CellProgram(name="broken.vmem", kernel_geometries=(
        KernelGeometry(backend="fused", batch=256, n_f=196, n=32, m=32,
                       entries=2 ** 15),))
    findings = analyze_program(prog)
    doc = report_json({"broken.vmem": summarize(findings),
                       "clean.cell": summarize([])})
    assert doc["schema"] == "wnnlint/v1"
    assert doc["errors"] == len(findings) > 0
    assert doc["cells"]["clean.cell"]["errors"] == 0
    f0 = doc["cells"]["broken.vmem"]["findings"][0]
    assert {"rule", "severity", "cell", "message", "detail"} <= set(f0)


def test_rules_do_not_apply_outside_their_domain():
    # a train cell is not a serving program and has no collective budget:
    # only the dtype rule should even apply
    prog = CellProgram(name="train.cell", kind="train", serving=False,
                       jaxpr=jax.make_jaxpr(lambda x: x * 2)(
                           jax.ShapeDtypeStruct((4,), jnp.float32)))
    applicable = [r.name for r in RULES.values() if r.applies(prog)]
    assert applicable == ["no-f64"]


# ---------------------------------------------------------------------------
# clean cells: every dryrun shape lints to zero errors
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("shape", sorted(cells.ULEEN_CELLS))
def test_uleen_cells_lint_clean(shape):
    mesh = _mesh()
    prog = cells.uleen_cell_program(shape, mesh, global_batch=2048)
    findings = analyze_program(prog)
    assert not _errors(findings), \
        f"{shape} should lint clean: {[f.message for f in findings]}"
    # the serve cells must actually exercise the program-level rules
    if not shape.startswith("train"):
        assert prog.hlo_text is not None
        applicable = {r.name for r in RULES.values() if r.applies(prog)}
        assert "no-host-callback" in applicable
        assert "vmem-budget" in applicable
    if shape == "train_host_exec":
        # the executed train cell compiles (DESIGN §10) but is not a
        # serving program: the host-callback rule must stay silent on it
        assert prog.hlo_text is not None
        applicable = {r.name for r in RULES.values() if r.applies(prog)}
        assert "no-host-callback" not in applicable
    if shape == "infer_sharded_scale":
        assert prog.sharded and prog.collective_budget == {"all-gather": 1}
