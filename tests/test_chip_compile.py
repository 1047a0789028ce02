"""Described-chip compiles: the main path's programs, compiled for a TPU
v5e that is described and not attached (no chip needed).

Interpret-mode tests cannot see what Mosaic refuses (misaligned blocks,
MXU operand types, VMEM overflow) nor what XLA cannot fit on the device.
These compiles can, in seconds each: `packed_wnn` at the largest ULN-S,
ULN-L and ULN-XL submodels, `fused_wnn` at the largest ULN-S and ULN-L
submodels, the jitted ULN-L distributed train step, and on the described
2x2 the two serve cells whose kernels run under a mesh — the int8 ULN-L
infer cell and the class-sharded packed ULN-XL-ensemble cell (GSPMD
cannot partition a Mosaic kernel, so `ops.wnn_scores` runs it per shard).
A kernel compile must contain the Mosaic kernel (`tpu_custom_call`), and
every program must fit the chip's 16 GiB.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU compiler library, so a
description made while pytest-xdist workers import this file would fail
in all but one of them.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.model import init_params
from repro.kernels import ops
from repro.kernels.fused_wnn import fused_wnn
from repro.kernels.packed_wnn import packed_wnn
from repro.launch import uleen_cell
from repro.packed.layout import word_count
from repro.train import optimizer as opt_lib

HBM_BYTES = 16 * 2 ** 30
BATCH = 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        # the TPU compiler logs under /tmp unless told otherwise
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described-chip compile cannot be read back from the persistent
        # cache without a chip: keep these compiles out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def kernels_for_tpu(monkeypatch):
    """Trace `ops.wnn_scores` as on a TPU: the described chip is the
    compile target while the host platform is the CPU. Traces made
    either way are dropped, so none is reused across the switch."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _largest(spec):
    return max(spec.submodels, key=lambda sm: spec.num_filters(sm)
               * sm.entries)


def _fits(compiled):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return 0 < total <= HBM_BYTES


def _kernel_args(spec, sharding, table_dtype):
    sm = _largest(spec)
    m, n_f, n = spec.num_classes, spec.num_filters(sm), sm.inputs_per_filter
    last = word_count(sm.entries) if table_dtype == jnp.uint32 else sm.entries
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=sharding)
    return (sds((BATCH, n_f, n), jnp.int8),
            sds((sm.num_hashes, n), jnp.int32),
            sds((m, n_f, last), table_dtype),
            sds((m, n_f), jnp.int8),
            sds((m,), jnp.int32))


@pytest.mark.parametrize("spec", [uleen_cell.ULN_S_SPEC,
                                  uleen_cell.ULN_L_SPEC,
                                  uleen_cell.ULN_XL_SPEC],
                         ids=["uln_s", "uln_l", "uln_xl"])
def test_packed_wnn_compiles_for_v5e(one_chip, spec):
    compiled = jax.jit(packed_wnn).lower(
        *_kernel_args(spec, one_chip, jnp.uint32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert "%packed_wnn" in compiled.as_text()     # the kernel's name
    assert _fits(compiled)


@pytest.mark.parametrize("spec", [uleen_cell.ULN_S_SPEC,
                                  uleen_cell.ULN_L_SPEC],
                         ids=["uln_s", "uln_l"])
def test_fused_wnn_compiles_for_v5e(one_chip, spec):
    compiled = jax.jit(fused_wnn).lower(
        *_kernel_args(spec, one_chip, jnp.int8)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert "%fused_wnn" in compiled.as_text()      # the kernel's name
    assert _fits(compiled)


def test_uln_l_train_step_compiles_for_v5e(topo):
    """The step `train_uleen` jits on a one-chip mesh, at the global batch
    and width `chip_smoke.py` trains with."""
    spec = uleen_cell.ULN_L_SPEC
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    optimizer = opt_lib.adam(1e-3)
    step = uleen_cell.make_uleen_dist_train_step(spec, optimizer, mesh)
    rep = NamedSharding(mesh, P())
    batch = NamedSharding(mesh, P("data"))
    sds = lambda x, s=rep: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), spec))
    opt_state = jax.eval_shape(optimizer.init, params)
    statics = tuple(
        (jax.ShapeDtypeStruct((spec.num_filters(sm), sm.inputs_per_filter),
                              jnp.int32, sharding=rep),
         jax.ShapeDtypeStruct((sm.num_hashes, sm.inputs_per_filter),
                              jnp.uint32, sharding=rep))
        for sm in spec.submodels)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        jax.tree.map(sds, params), jax.tree.map(sds, opt_state), statics,
        jax.ShapeDtypeStruct((1024, spec.total_bits), jnp.bool_,
                             sharding=batch),
        jax.ShapeDtypeStruct((1024,), jnp.int32, sharding=batch),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)).compile()
    assert _fits(compiled)


def test_int8_infer_cell_compiles_for_v5e_2x2(topo, kernels_for_tpu):
    """The int8 ULN-L infer cell (`forward_binary_fused`, backend auto =
    the fused kernel on TPU) with rows over `data` and classes over
    `model`."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    compiled = uleen_cell.lower_uleen_infer_cell(
        mesh, global_batch=BATCH, spec=uleen_cell.ULN_L_SPEC)
    assert "tpu_custom_call" in compiled.as_text()
    assert _fits(compiled)       # per device


def test_class_sharded_packed_cell_compiles_for_v5e_2x2(topo,
                                                        kernels_for_tpu):
    """The class-sharded packed ULN-XL-ensemble (M=32) serve cell, classes
    over a 4-way `model` axis, as `WnnBatcher(mesh=)` serves it."""
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    compiled = uleen_cell.lower_uleen_sharded_infer_cell(
        mesh, global_batch=BATCH)
    assert "tpu_custom_call" in compiled.as_text()
    assert _fits(compiled)       # per device
