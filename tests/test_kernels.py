"""Per-Pallas-kernel shape/dtype sweeps vs the ref.py jnp oracles.

Kernels execute in interpret mode (Python evaluation of the kernel body on
CPU); assert_allclose against the pure-jnp oracle is the correctness
contract for the TPU lowering.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_tiled
from repro.kernels.fused_wnn import fused_wnn
from repro.kernels.h3_hash import h3_hash_tiled
from repro.kernels.thermometer import (thermometer_decompress,
                                       thermometer_encode)


@pytest.mark.parametrize("b,n_f,n,m,e,k", [
    (4, 8, 6, 3, 16, 1),
    (16, 24, 12, 10, 64, 2),
    (9, 17, 20, 5, 128, 3),     # non-multiple shapes exercise padding
    (1, 3, 30, 2, 32, 2),
])
def test_fused_wnn_matches_oracle(b, n_f, n, m, e, k):
    key = jax.random.PRNGKey(b * 1000 + n_f)
    ks = jax.random.split(key, 4)
    tuples = jax.random.bernoulli(ks[0], 0.5, (b, n_f, n)).astype(jnp.int8)
    params = jax.random.randint(ks[1], (k, n), 0, e, dtype=jnp.int32)
    table = jax.random.bernoulli(ks[2], 0.3, (m, n_f, e)).astype(jnp.int8)
    mask = jax.random.bernoulli(ks[3], 0.8, (m, n_f)).astype(jnp.int8)
    bias = jnp.arange(m, dtype=jnp.int32) - 1
    out = fused_wnn(tuples, params, table, mask, bias, interpret=True)
    expect = ref.fused_wnn_ref(tuples, params, table, mask, bias)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("block_b", [32, 128])
def test_fused_wnn_block_shape_invariance(block_b):
    """Output must not depend on the BlockSpec tiling: 40 rows over one
    or two batch tiles, 150 filters over two lane tiles."""
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 4)
    tuples = jax.random.bernoulli(ks[0], 0.5, (40, 150, 8)).astype(jnp.int8)
    params = jax.random.randint(ks[1], (2, 8), 0, 32, dtype=jnp.int32)
    table = jax.random.bernoulli(ks[2], 0.4, (4, 150, 32)).astype(jnp.int8)
    mask = jnp.ones((4, 150), jnp.int8)
    bias = jnp.zeros((4,), jnp.int32)
    out = fused_wnn(tuples, params, table, mask, bias,
                    block_b=block_b, interpret=True)
    expect = ref.fused_wnn_ref(tuples, params, table, mask, bias)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("b,n_f,n,k", [
    (8, 16, 10, 2), (33, 7, 28, 1), (5, 100, 16, 4)])
def test_h3_kernel_matches_oracle(b, n_f, n, k):
    key = jax.random.PRNGKey(b + n_f)
    tuples = jax.random.bernoulli(key, 0.5, (b, n_f, n)).astype(jnp.int8)
    params = jax.random.randint(jax.random.PRNGKey(1), (k, n), 0, 64,
                                dtype=jnp.int32)
    out = h3_hash_tiled(tuples, params, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.h3_hash_ref(tuples, params)))


@pytest.mark.parametrize("b,f,t", [(8, 20, 4), (3, 100, 1), (65, 7, 16)])
def test_thermometer_kernel(b, f, t):
    key = jax.random.PRNGKey(b * 7 + f)
    x = jax.random.normal(key, (b, f))
    thr = jnp.sort(jax.random.normal(jax.random.PRNGKey(2), (f, t)), axis=1)
    out = thermometer_encode(x, thr, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.thermometer_ref(x, thr)))


@pytest.mark.parametrize("b,f,t", [(8, 20, 4), (33, 9, 7)])
def test_decompress_kernel(b, f, t):
    counts = jax.random.randint(jax.random.PRNGKey(0), (b, f), 0,
                                t + 1).astype(jnp.uint8)
    out = thermometer_decompress(counts, t, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.decompress_ref(counts, t)))


@pytest.mark.parametrize("sq,sk,d,causal,window,dtype", [
    (64, 64, 32, True, 0, jnp.float32),
    (64, 64, 32, True, 16, jnp.float32),
    (32, 96, 16, False, 0, jnp.float32),
    (70, 50, 32, True, 0, jnp.float32),     # ragged -> padding paths
    (64, 64, 32, True, 0, jnp.bfloat16),
])
def test_flash_attention_kernel(sq, sk, d, causal, window, dtype):
    key = jax.random.PRNGKey(sq + sk)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (2, sq, d), dtype)
    k = jax.random.normal(ks[1], (2, sk, d), dtype)
    v = jax.random.normal(ks[2], (2, sk, d), dtype)
    out = flash_attention_tiled(q, k, v, causal=causal, window=window,
                                block_q=32, block_k=32, interpret=True)
    expect = ref.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


def test_flash_block_shape_invariance():
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 128, 16))
    k = jax.random.normal(ks[1], (1, 128, 16))
    v = jax.random.normal(ks[2], (1, 128, 16))
    a = flash_attention_tiled(q, k, v, causal=True, block_q=32, block_k=64,
                              interpret=True)
    b = flash_attention_tiled(q, k, v, causal=True, block_q=128, block_k=32,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_ops_wrappers_cpu_fallback():
    """The jit'd public wrappers choose the oracle on CPU and the kernel
    when forced — results must agree."""
    from repro.kernels import ops
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    tuples = jax.random.bernoulli(ks[0], 0.5, (6, 10, 8)).astype(jnp.int8)
    params = jax.random.randint(ks[1], (2, 8), 0, 64, dtype=jnp.int32)
    table = jax.random.bernoulli(ks[2], 0.4, (3, 10, 64)).astype(jnp.int8)
    mask = jnp.ones((3, 10), jnp.int8)
    bias = jnp.zeros((3,), jnp.int32)
    a = ops.wnn_infer(tuples, params, table, mask, bias, use_kernel=False)
    b = ops.wnn_infer(tuples, params, table, mask, bias, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _uleen_geometries():
    from repro.launch import uleen_cell as uc
    for name in ("ULN_S_SPEC", "ULN_L_SPEC", "ULN_XL_SPEC",
                 "ULN_XL_ENSEMBLE_SPEC"):
        spec = getattr(uc, name)
        for i, sm in enumerate(spec.submodels):
            yield pytest.param(spec.num_classes, sm.inputs_per_filter,
                               sm.entries, id=f"{name}.sm{i}")


@pytest.mark.parametrize("b", [1, 100, 256])
@pytest.mark.parametrize("m,n,entries", list(_uleen_geometries()))
def test_vmem_plans_are_tile_aligned(b, m, n, entries):
    """Both kernels' block plans, ULN-S through ULN-XL (W up to 1024):
    filters in whole 128-lane tiles, batch rows in whole int8 sublane
    tiles, class blocks dividing M, inside the VMEM limit. The int8
    kernel keeps all classes per block, so its plan only has to fit
    where its E-wide tables can (E <= 2^9 here)."""
    from repro.kernels import fused_wnn as fw, packed_wnn as pw
    plans = [pw.vmem_plan(b, n, m, entries)]
    if entries <= 512:
        plans.append(fw.vmem_plan(b, n, m, entries))
        assert plans[-1]["block_m"] == m
    for plan in plans:
        assert plan["block_f"] % 128 == 0, plan
        assert plan["block_b"] % 32 == 0, plan
        assert m % plan["block_m"] == 0, plan
        assert plan["fits"] and plan["vmem_bytes"] <= fw.VMEM_LIMIT, plan
