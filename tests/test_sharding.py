"""Sharding rules + launch specs (1-device mesh; no placeholder devices)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import SHAPES, get_config
from repro.dist import sharding as sh
from repro.launch import specs
from repro.launch.mesh import make_host_mesh


def _fake_mesh(shape=(4, 2), axes=("data", "model")):
    """An abstract mesh for rule resolution (no real devices needed —
    resolve() only reads axis names/sizes)."""
    import types
    m = types.SimpleNamespace()
    m.axis_names = axes
    m.devices = np.empty(shape, dtype=object)
    return m


def test_resolver_divisibility_sanitizer():
    mesh = _fake_mesh((4, 16))
    rules = sh.TRAIN_RULES
    # 24 heads over model=16 -> dropped; 32 over 16 -> kept
    assert rules.resolve(("heads",), mesh, shape=(24,)) == P(None)
    assert rules.resolve(("heads",), mesh, shape=(32,)) == P("model")
    # without a shape: no sanitizing
    assert rules.resolve(("heads",), mesh) == P("model")


def test_resolver_multi_axis_batch():
    mesh = _fake_mesh((2, 4, 2), ("pod", "data", "model"))
    spec = sh.TRAIN_RULES.resolve(("batch", "seq"), mesh, shape=(16, 128))
    assert spec == P(("pod", "data"), None)
    # batch=2 only fits pod
    spec = sh.TRAIN_RULES.resolve(("batch", "seq"), mesh, shape=(2, 128))
    assert spec == P("pod", None)


def test_resolver_never_reuses_axis():
    mesh = _fake_mesh((4, 2))
    spec = sh.TRAIN_RULES.resolve(("fsdp", "batch"), mesh, shape=(8, 8))
    # fsdp takes data; batch wants (pod, data) but data is used -> None
    assert spec == P("data", None)


def test_serve_rules_shard_cache_seq():
    mesh = _fake_mesh((4, 4))
    spec = sh.SERVE_RULES.resolve(
        ("batch", "kv_heads", "cache_seq", None), mesh,
        shape=(8, 8, 1024, 128))
    assert spec == P("data", None, "model", None)


def test_param_shardings_all_divisible():
    """Every resolved param sharding must evenly divide its dimension —
    the sanitizer guarantees jit in_shardings validity."""
    from repro.models import transformer
    mesh = _fake_mesh((16, 16))
    sizes = {"data": 16, "model": 16}
    for arch in ("qwen2p5_14b", "whisper_tiny", "deepseek_v2_lite_16b",
                 "recurrentgemma_2b", "mamba2_2p7b"):
        cfg = get_config(arch)
        shapes = jax.tree.leaves(specs.param_specs(cfg))
        logical = jax.tree.leaves(transformer.param_logical(cfg),
                                  is_leaf=lambda x: isinstance(x, tuple))
        assert len(shapes) == len(logical)
        for leaf, log in zip(shapes, logical):
            spec = sh.TRAIN_RULES.resolve(log, mesh, shape=leaf.shape)
            for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * 8):
                if ax is None:
                    continue
                axes = (ax,) if isinstance(ax, str) else ax
                total = 1
                for a in axes:
                    total *= sizes[a]
                assert dim % total == 0, (arch, leaf.shape, spec)


def test_input_specs_match_shapes():
    cfg = get_config("llama3p2_3b")
    s = specs.input_specs(cfg, SHAPES["train_4k"])
    assert s["tokens"].shape == (256, 4096)
    assert s["labels"].shape == (256, 4096)
    s = specs.input_specs(cfg, SHAPES["decode_32k"])
    assert s["token"].shape == (128, 1)
    cfg_w = get_config("whisper_tiny")
    s = specs.input_specs(cfg_w, SHAPES["prefill_32k"])
    assert s["frames"].shape == (32, 1500, 384)


def test_microbatch_heuristic():
    cfg = get_config("llama3p2_3b")
    mesh = _fake_mesh((16, 16))
    assert specs.microbatches_for(cfg, SHAPES["train_4k"], mesh) == 16
    mesh3 = _fake_mesh((2, 16, 16), ("pod", "data", "model"))
    assert specs.microbatches_for(cfg, SHAPES["train_4k"], mesh3) == 8
    assert specs.microbatches_for(cfg, SHAPES["decode_32k"], mesh) == 1


def test_logical_constraint_noop_outside_mesh():
    x = jnp.ones((4, 4))
    y = sh.logical_constraint(x, ("batch", None))
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_host_mesh_constraint_runs():
    mesh = make_host_mesh()
    with sh.use_mesh(mesh, sh.TRAIN_RULES):
        y = jax.jit(lambda x: sh.logical_constraint(x * 2, ("batch", "ffn")))(
            jnp.ones((4, 8)))
    np.testing.assert_array_equal(np.asarray(y), 2 * np.ones((4, 8)))


@pytest.mark.parametrize("shape,axes", [((1,), ("data",)),
                                        ((2, 4), ("pod", "data")),
                                        ((2, 4), ("data", "model"))])
def test_make_mesh_axes_are_auto(shape, axes):
    """Every mesh the repo builds is steered by GSPMD
    (`with_sharding_constraint`, jit shardings), which Explicit axes —
    `jax.make_mesh`'s default — reject."""
    from jax.sharding import AxisType
    from repro.launch.mesh import make_mesh
    mesh = make_mesh(shape, axes)
    assert mesh.axis_types == (AxisType.Auto,) * len(axes)
