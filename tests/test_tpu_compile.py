"""Ahead-of-time compiles for a described TPU v5e chip (no chip needed).

The TPU compiler is installed with jaxlib, so it can compile for a chip
that is described and not attached.  This catches what interpret-mode
tests cannot: Mosaic's tiling rules for a kernel's blocks, its VMEM
limit, and a program that does not fit the chip's 16 GB of HBM.

Covered, at the sizes ``chip_smoke.py`` serves gemma3-1b (H=4 q heads,
Hkv=1 kv head, head_dim 256, bf16, window 512, max_seq 1024, batch
cells 1 and 2, a 128-token prompt bucket and the 508-token comparison
prompt):

* ``flash_attention`` and ``decode_attention`` with ``interpret=False``,
  plus decode at a Llama-style H=32 / Hkv=8 / D=128;
* one jitted gemma3-1b decode step at published widths and full depth,
  which must fit one chip;
* the minitron-8b-l16 benchmark's decode step (16 layers, 48 q / 8 kv
  heads of dim 128, b=8, max_seq 1024, donated cache), which must write
  its new K/V position into the stacked cache in place: no copy or
  fresh buffer of the whole stack.

The topology is described inside a module-scoped fixture, never at
import time: only one process at a time may load the TPU library.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.models.lm import build_model
from repro.models.serve_lm import gemma3_1b_config

V5E_HBM_BYTES = 16 * 10**9
MAX_SEQ = 1024          # chip_smoke.py / make_lm_engine("gemma3-1b")
WINDOW = 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("B,S,H,Hkv,D,block_kv", [
    (1, MAX_SEQ, 4, 1, 256, 1024),    # gemma3-1b global layer, full cache
    (2, MAX_SEQ, 4, 1, 256, 1024),
    (2, WINDOW, 4, 1, 256, 1024),     # gemma3-1b local layer, ring cache
    (8, 4096, 32, 8, 128, 1024),      # Llama-style GQA
])
def test_decode_attention_compiles_for_v5e(one_chip, no_persistent_cache,
                                           B, S, H, Hkv, D, block_kv):
    bf16 = jnp.bfloat16

    def f(q, k, v, lengths):
        return decode_attention(q, k, v, lengths,
                                block_kv=min(block_kv, S), interpret=False)

    compiled = jax.jit(f).lower(
        _sds((B, 1, H, D), bf16, one_chip),
        _sds((B, Hkv, S, D), bf16, one_chip),
        _sds((B, Hkv, S, D), bf16, one_chip),
        _sds((B,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("B,S,window", [
    (1, 128, WINDOW), (2, 128, WINDOW),   # serving prompt bucket, local
    (2, 128, 0),                          # ... global layer
    (1, 512, WINDOW), (1, 512, 0),        # the 508-token compare prompt
])
def test_flash_attention_compiles_for_v5e(one_chip, no_persistent_cache,
                                          B, S, window):
    bf16 = jnp.bfloat16

    def f(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=min(512, S), block_kv=min(1024, S),
                               interpret=False)

    compiled = jax.jit(f).lower(
        _sds((B, S, 4, 256), bf16, one_chip),
        _sds((B, S, 1, 256), bf16, one_chip),
        _sds((B, S, 1, 256), bf16, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_gemma3_1b_decode_step_fits_one_v5e(one_chip, no_persistent_cache,
                                            monkeypatch, request):
    """The served decode step, at published widths and all 26 layers,
    compiles with the Pallas kernel and fits one chip's HBM."""
    # the described chip is not the default backend; steer the kernel
    # off the CPU interpreter, and drop jit caches traced for the CPU
    # (and, afterwards, those traced here for the chip)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    jax.clear_caches()
    request.addfinalizer(jax.clear_caches)
    cfg = gemma3_1b_config()
    assert (cfg.n_layers, cfg.d_model, cfg.dtype) == (26, 1152, "bfloat16")
    model = build_model(cfg)
    B = 2

    def place(tree):
        return jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                            tree)

    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: model.init_cache(B, MAX_SEQ)))
    step = jax.jit(model.decode_step, donate_argnums=(1,))
    compiled = step.lower(params, cache,
                          _sds((B, 1), jnp.int32, one_chip),
                          _sds((), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, total


# `  [ROOT] %name = dtype[dims]{layout} opcode(operands), ...`
_HLO_ARRAY = re.compile(
    r"^\s*(?:ROOT\s+)?%(\S+)\s*=\s*(\w+)\[([\d,]*)\]\S*\s+([\w-]+)\((.*)$")


def _hlo_arrays(text):
    """name → (dtype, dims, opcode, operand names) of every array-valued
    instruction in a compiled module's text."""
    out = {}
    for line in text.splitlines():
        m = _HLO_ARRAY.match(line)
        if m:
            name, dtype, dims, opcode, rest = m.groups()
            dims = tuple(int(d) for d in dims.split(",") if d)
            out[name] = (dtype, dims, opcode, re.findall(r"%([\w.-]+)", rest))
    return out


def test_decode_step_writes_kv_cache_in_place_on_v5e(
        one_chip, no_persistent_cache, monkeypatch, request):
    """The scanned decode step writes its one new K/V position into the
    stacked cache in place: the only operation that yields a whole stack
    is that one-position dynamic-update-slice, and no temporary holds a
    stack."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    jax.clear_caches()
    request.addfinalizer(jax.clear_caches)
    # bench/configs/minitron-8b-l16.json's program overrides
    cfg = get_config("minitron-8b").with_overrides(
        n_heads=48, n_repeats=16, use_pallas_kernels=True)
    assert cfg.scan_layers
    model = build_model(cfg)
    B = 8
    stack = (cfg.n_repeats, B, cfg.n_kv_heads, MAX_SEQ, cfg.resolved_head_dim)

    def place(tree):
        return jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                            tree)

    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: model.init_cache(B, MAX_SEQ)))
    assert cache["pattern"][0]["k"].shape == stack
    step = jax.jit(model.decode_step, donate_argnums=(1,))
    compiled = step.lower(params, cache,
                          _sds((B, 1), jnp.int32, one_chip),
                          _sds((), jnp.int32, one_chip)).compile()
    arrays = _hlo_arrays(compiled.as_text())

    writes = 0
    for name, (dtype, dims, opcode, operands) in arrays.items():
        if (dtype, dims) != ("bf16", stack):
            continue
        assert opcode in ("parameter", "get-tuple-element",
                          "dynamic-update-slice"), (name, opcode)
        if opcode == "dynamic-update-slice":
            update = arrays[operands[1]][1]
            # one position of every layer: (R, B, Hkv, 1, Dh)
            assert update == stack[:3] + (1, stack[4]), (name, update)
            writes += 1
    assert writes == 2, writes     # K and V

    kv_pair = 2 * 2 * math.prod(stack)      # bf16 K and V
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < kv_pair, (temp, kv_pair)
