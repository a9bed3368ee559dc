"""Distribution tests: sharding rules, compression, EP, HLO analysis.

These run on 8 fabricated host devices (set before jax import via the
conftest-free module-level guard) — small enough for CPU, structured the
same as the 256/512-chip production meshes.
"""

import os

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ShapeConfig, get_config
from repro.distributed.compression import (compressed_psum,
                                           dequantize_blockwise,
                                           psum_bytes_saved,
                                           quantize_blockwise)
from repro.distributed.sharding import (batch_pspecs, cache_pspecs,
                                        optimizer_pspecs, params_pspecs,
                                        to_named)
from repro.launch.hlo_analysis import collective_stats
from repro.launch.mesh import make_mesh, make_submesh
from repro.models import build_model

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 fabricated devices")


def small_mesh():
    return make_mesh((2, 4), ("data", "model"))


# --------------------------------------------------------------------- #
# sharding rules
# --------------------------------------------------------------------- #
def test_param_specs_divisibility():
    """Every spec must divide its dimension on the mesh (for all archs)."""
    mesh = small_mesh()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for arch in ("llama3-8b", "deepseek-v2-236b", "recurrentgemma-9b",
                 "mamba2-130m", "seamless-m4t-medium", "gemma3-1b"):
        cfg = get_config(arch)
        model = build_model(cfg)
        p_shape = model.param_specs()
        specs = params_pspecs(cfg, p_shape, mesh)
        flat_l = jax.tree_util.tree_leaves(p_shape)
        flat_s = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        assert len(flat_l) == len(flat_s)
        for leaf, spec in zip(flat_l, flat_s):
            for dim, ax in zip(leaf.shape, tuple(spec)):
                if ax is None:
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                n = 1
                for a in axes:
                    n *= sizes[a]
                assert dim % n == 0, (arch, leaf.shape, spec)


def test_tensor_parallel_shards_big_matrices():
    """d_ff / attention heads actually shard over the model axis."""
    mesh = small_mesh()
    cfg = get_config("llama3-8b")
    model = build_model(cfg)
    specs = params_pspecs(cfg, model.param_specs(), mesh)
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    by_name = {jax.tree_util.keystr(k): v for k, v in flat}
    wq = next(v for k, v in by_name.items() if "wq" in k)
    assert "model" in jax.tree_util.tree_leaves(tuple(wq))
    up = next(v for k, v in by_name.items()
              if k.endswith("['up']") and "moe" not in k)
    assert "model" in jax.tree_util.tree_leaves(tuple(up))


def test_optimizer_zero_sharding_adds_data_axis():
    mesh = small_mesh()
    cfg = get_config("llama3-8b")
    model = build_model(cfg)
    p_shape = model.param_specs()
    p_spec = params_pspecs(cfg, p_shape, mesh)
    o_spec = optimizer_pspecs(p_spec, p_shape, mesh, zero=True)
    n_data = sum("data" in jax.tree_util.tree_leaves(tuple(s))
                 for s in jax.tree_util.tree_leaves(
                     o_spec, is_leaf=lambda x: isinstance(x, P)))
    n_data_params = sum("data" in jax.tree_util.tree_leaves(tuple(s))
                        for s in jax.tree_util.tree_leaves(
                            p_spec, is_leaf=lambda x: isinstance(x, P)))
    assert n_data > n_data_params     # moments got extra data sharding


def test_batch_specs_divisible_fallback():
    mesh = small_mesh()
    spec = batch_pspecs(jax.ShapeDtypeStruct((1, 7), jnp.int32), mesh)
    assert tuple(spec) == (None, None)   # B=1 cannot shard over data=2
    spec = batch_pspecs(jax.ShapeDtypeStruct((8, 7), jnp.int32), mesh)
    assert spec[0] in ("data", ("data",))


def test_sharded_train_step_executes():
    """Real execution on 8 devices: one sharded train step, loss finite."""
    from repro.data import batches_for_model
    from repro.training import AdamWConfig, TrainConfig, init_adamw, make_train_step

    mesh = small_mesh()
    cfg = get_config("llama3-8b").reduced(
        n_repeats=2, d_model=64, n_heads=4, d_ff=128, vocab_size=512)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tcfg = TrainConfig(adamw=AdamWConfig(warmup_steps=1))
    opt = init_adamw(tcfg.adamw, params)
    shape = ShapeConfig("t", seq_len=32, global_batch=8, kind="train")
    batch = next(batches_for_model(cfg, shape))

    p_spec = params_pspecs(cfg, jax.eval_shape(lambda: params), mesh)
    with mesh:
        step = jax.jit(make_train_step(cfg, tcfg),
                       in_shardings=(to_named(mesh, p_spec), None, None))
        params2, opt2, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_sharded_decode_step_executes():
    """Real execution: decode with the seq-sharded cache layout."""
    mesh = small_mesh()
    cfg = get_config("llama3-8b").reduced(
        n_repeats=2, d_model=64, n_heads=4, d_ff=128, vocab_size=512)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache = model.init_cache(8, 64)
    c_spec = cache_pspecs(cfg, jax.eval_shape(lambda: cache), mesh)
    tokens = jnp.zeros((8, 1), jnp.int32)
    with mesh:
        step = jax.jit(
            lambda p, c, t, pos: model.decode_step(p, c, t, pos),
            in_shardings=(None, to_named(mesh, c_spec), None, None),
            out_shardings=(None, to_named(mesh, c_spec)))
        logits, cache2 = step(params, cache, tokens, jnp.int32(3))
    assert np.isfinite(np.asarray(logits, np.float32)).all()


# --------------------------------------------------------------------- #
# gradient compression
# --------------------------------------------------------------------- #
def test_quantize_roundtrip_error_bounded():
    x = jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 3.0
    q, s, pad = quantize_blockwise(x)
    back = dequantize_blockwise(q, s, pad, x.shape)
    err = np.abs(np.asarray(back - x))
    scale = np.abs(np.asarray(x)).max()
    assert err.max() <= scale / 127 + 1e-6


def test_compressed_psum_close_to_exact():
    mesh = make_mesh((8,), ("pod",))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 512))

    def f(xs):
        return compressed_psum(xs, "pod")

    got = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("pod"),
                                out_specs=P("pod")))(x)
    want = jnp.broadcast_to(x.sum(0, keepdims=True), x.shape)
    rms_rel = float(jnp.sqrt(jnp.mean((got - want) ** 2))
                    / jnp.sqrt(jnp.mean(want ** 2)))
    assert rms_rel < 0.02


def test_compression_saves_bytes():
    tree = {"w": jnp.zeros((1 << 20,))}
    full, comp = psum_bytes_saved(tree)
    assert comp < full / 3.5


# --------------------------------------------------------------------- #
# expert parallel path vs dense-dispatch oracle
# --------------------------------------------------------------------- #
def test_moe_ep_matches_dense_dispatch():
    from repro.distributed.expert_parallel import apply_moe_ep
    from repro.models.moe import apply_moe, init_moe

    mesh = make_mesh((8,), ("model",))
    cfg = get_config("deepseek-v2-236b").reduced(
        n_repeats=1, d_model=32, n_heads=4, d_ff=64)
    # 8 experts over 8 shards; uncapped-ish capacity for exactness
    import dataclasses
    cfg = cfg.with_overrides(moe=dataclasses.replace(
        cfg.moe, n_experts=8, top_k=2, capacity_factor=8.0))
    params = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    want = apply_moe(params, x, cfg)
    with mesh:
        got = jax.jit(lambda p, xx: apply_moe_ep(p, xx, cfg, mesh=mesh))(
            params, x)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-4, rtol=2e-3)


# --------------------------------------------------------------------- #
# HLO collective parsing
# --------------------------------------------------------------------- #
def test_collective_stats_parser():
    hlo = """
  %all-reduce = f32[128,256]{1,0} all-reduce(%x), replica_groups={}
  %ag = (bf16[64]{0}, bf16[32]{0}) all-gather(%a, %b), dim=0
  %rs = f32[16,16]{1,0} reduce-scatter(%y), dimensions={0}
  %cp-start = bf16[8]{0} collective-permute-start(%z)
  %cp-done = bf16[8]{0} collective-permute-done(%cp-start)
  %fusion = f32[4]{0} fusion(%w), calls=%comp
"""
    stats = collective_stats(hlo)
    assert stats.count_by_op["all-reduce"] == 1
    assert stats.bytes_by_op["all-reduce"] == 128 * 256 * 4
    assert stats.bytes_by_op["all-gather"] == (64 + 32) * 2
    assert stats.bytes_by_op["reduce-scatter"] == 16 * 16 * 4
    assert stats.count_by_op["collective-permute"] == 1  # start+done once
    assert stats.total_count == 4


def test_collective_stats_on_real_program():
    mesh = small_mesh()
    from jax.sharding import NamedSharding

    def f(w, x):
        return (x @ w).sum()

    w = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    with mesh:
        comp = jax.jit(
            f, in_shardings=(NamedSharding(mesh, P(None, "model")),
                             NamedSharding(mesh, P("data", None))),
            out_shardings=NamedSharding(mesh, P())).lower(w, x).compile()
    stats = collective_stats(comp.as_text())
    assert stats.count_by_op.get("all-reduce", 0) >= 1


def test_submesh_shapes():
    m = make_submesh(8)
    assert m.devices.size == 8 and m.shape["model"] == 8
    m = make_submesh(8, model_parallel=4)
    assert m.shape["data"] == 2 and m.shape["model"] == 4
    with pytest.raises(ValueError):
        make_submesh(8, model_parallel=3)
