"""Per-architecture smoke + correctness tests (deliverable f).

Every assigned architecture instantiates a REDUCED config of the same
family and runs: one forward pass (shape + finiteness), one train step
(loss finite, params update), and the KV-cache equivalence invariant
(prefill + decode_step == full forward position-by-position).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import all_configs, applicable_shapes, get_config
from repro.configs.base import LONG_500K, SHAPES
from repro.models import build_model
from repro.models.lm import apply_head, param_count
from repro.training import AdamWConfig, TrainConfig, init_adamw, make_train_step
from repro.training.train_loop import shift_labels

ARCHS = sorted(all_configs())


def make_batch(cfg, B, S, key=0, with_labels=False):
    tok = jax.random.randint(jax.random.PRNGKey(key), (B, S), 0,
                             cfg.vocab_size)
    batch = {"tokens": tok}
    text_start = 0
    if cfg.frontend and cfg.frontend.kind == "vision":
        P = cfg.frontend.n_prefix_tokens
        batch["tokens"] = tok[:, : S - P]
        batch["vision_embeds"] = jax.random.normal(
            jax.random.PRNGKey(key + 1), (B, P, cfg.d_model), jnp.bfloat16)
        text_start = P
    if cfg.is_encdec:
        batch["frames"] = jax.random.normal(
            jax.random.PRNGKey(key + 2), (B, S, cfg.d_model), jnp.bfloat16)
    if with_labels:
        batch["labels"] = shift_labels(tok)
    return batch, text_start


@pytest.fixture(scope="module")
def reduced_models():
    cache = {}

    def get(name, **kw):
        key = (name, tuple(sorted(kw.items())))
        if key not in cache:
            cfg = all_configs()[name].reduced(**kw)
            model = build_model(cfg)
            params = model.init(jax.random.PRNGKey(0))
            cache[key] = (cfg, model, params)
        return cache[key]

    return get


# --------------------------------------------------------------------- #
# smoke: forward
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch, reduced_models):
    cfg, model, params = reduced_models(arch)
    B, S = 2, 32
    batch, _ = make_batch(cfg, B, S)
    h = model.forward(params, batch)
    assert h.shape == (B, S, cfg.d_model)
    assert bool(jnp.all(jnp.isfinite(h.astype(jnp.float32))))
    logits = model.logits(params, h)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert logits.dtype == jnp.float32


# --------------------------------------------------------------------- #
# smoke: one train step on CPU, no NaNs, params move
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("arch", ARCHS)
def test_train_step(arch, reduced_models):
    cfg, model, params = reduced_models(arch)
    tcfg = TrainConfig(adamw=AdamWConfig(learning_rate=1e-3, warmup_steps=1,
                                         decay_steps=10))
    step = jax.jit(make_train_step(cfg, tcfg))
    opt = init_adamw(tcfg.adamw, params)
    batch, _ = make_batch(cfg, 2, 32, with_labels=True)
    new_params, new_opt, metrics = step(params, opt, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and loss > 0
    assert int(new_opt.step) == 1
    # at least one leaf changed
    moved = any(
        not np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(new_params)))
    assert moved


# --------------------------------------------------------------------- #
# KV-cache equivalence: prefill + decode == forward
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_matches_forward(arch, reduced_models):
    cfg, model, params = reduced_models(arch, dtype="float32")
    B, S, n_dec = 2, 24, 4
    batch, text_start = make_batch(cfg, B, S)
    full_logits = apply_head(params, model.forward(params, batch), cfg)

    n_pre = S - n_dec
    pre = dict(batch)
    pre["tokens"] = batch["tokens"][:, : n_pre - text_start]
    logits_last, cache = model.prefill(params, pre, max_len=S)
    scale = float(jnp.max(jnp.abs(full_logits))) + 1e-6
    errs = [float(jnp.max(jnp.abs(logits_last[:, 0]
                                  - full_logits[:, n_pre - 1])))]
    for i in range(n_pre, S):
        tok = batch["tokens"][:, i - text_start: i - text_start + 1]
        logits, cache = model.decode_step(params, cache, tok, jnp.int32(i))
        errs.append(float(jnp.max(jnp.abs(logits[:, 0] - full_logits[:, i]))))
    assert max(errs) / scale < 2e-4, errs


# --------------------------------------------------------------------- #
# windowed caches: gemma3 ring buffer stays faithful past the window
# --------------------------------------------------------------------- #
def test_ring_buffer_decode_beyond_window():
    cfg = get_config("gemma3-1b").reduced(dtype="float32")
    assert cfg.sliding_window and cfg.sliding_window < 80
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 1, 96   # > window
    batch, _ = make_batch(cfg, B, S)
    full_logits = apply_head(params, model.forward(params, batch), cfg)
    n_pre = S - 8
    logits_last, cache = model.prefill(
        params, {"tokens": batch["tokens"][:, :n_pre]}, max_len=S)
    scale = float(jnp.max(jnp.abs(full_logits))) + 1e-6
    errs = [float(jnp.max(jnp.abs(logits_last[:, 0]
                                  - full_logits[:, n_pre - 1])))]
    for i in range(n_pre, S):
        logits, cache = model.decode_step(
            params, cache, batch["tokens"][:, i:i + 1], jnp.int32(i))
        errs.append(float(jnp.max(jnp.abs(logits[:, 0] - full_logits[:, i]))))
    assert max(errs) / scale < 2e-4


# --------------------------------------------------------------------- #
# scan_layers must not change the math
# --------------------------------------------------------------------- #
def _scanned_and_unrolled(arch, **overrides):
    """(cfg, model, params) unrolled, and the same params restacked for
    the scanned layout."""
    cfg_u = all_configs()[arch].reduced(n_repeats=3, dtype="float32",
                                        **overrides)
    cfg_s = cfg_u.with_overrides(scan_layers=True)
    model_u, model_s = build_model(cfg_u), build_model(cfg_s)
    params_u = model_u.init(jax.random.PRNGKey(0))
    params_s = dict(params_u)
    params_s["pattern"] = _restack(params_u["pattern"], len(cfg_u.pattern))
    return (cfg_u, model_u, params_u), (cfg_s, model_s, params_s)


def _restack(per_repeat, n_positions):
    """[repeat][position] pytrees → [position] pytrees with leaves (R, ...)."""
    return [None if per_repeat[0][j] is None else jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *[r[j] for r in per_repeat])
            for j in range(n_positions)]


@pytest.mark.parametrize("arch", ["llama3-8b", "gemma3-1b", "deepseek-v2-236b",
                                  "recurrentgemma-9b", "seamless-m4t-medium"])
def test_scan_equals_unrolled(arch):
    (cfg_u, model_u, params_u), (_, model_s, params_s) = \
        _scanned_and_unrolled(arch)
    batch, _ = make_batch(cfg_u, 2, 16)
    hu = model_u.forward(params_u, batch)
    hs = model_s.forward(params_s, batch)
    np.testing.assert_allclose(np.asarray(hu, np.float32),
                               np.asarray(hs, np.float32),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("arch", ["llama3-8b", "gemma3-1b", "deepseek-v2-236b",
                                  "recurrentgemma-9b", "seamless-m4t-medium"])
def test_scan_decode_equals_unrolled(arch):
    """The scanned decode step reads each layer's slice of the stacked
    cache and writes every layer's new position in place after the scan;
    the unrolled step is the reference."""
    # a window of 8 makes the ring caches wrap inside the decode steps
    ring = {"sliding_window": 8} if all_configs()[arch].sliding_window else {}
    (cfg, model_u, params_u), (_, model_s, params_s) = \
        _scanned_and_unrolled(arch, **ring)
    B, S, n_dec = 2, 16, 5
    batch, text_start = make_batch(cfg, B, S)
    n_pre = S - n_dec
    pre = dict(batch)
    pre["tokens"] = batch["tokens"][:, : n_pre - text_start]
    _, cache_u = model_u.prefill(params_u, pre, max_len=S)
    _, cache_s = model_s.prefill(params_s, pre, max_len=S)
    step_s = jax.jit(model_s.decode_step, donate_argnums=(1,))

    def check(a, b):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=1e-4, rtol=1e-4)

    for i in range(n_pre, S):
        tok = batch["tokens"][:, i - text_start: i - text_start + 1]
        lu, cache_u = model_u.decode_step(params_u, cache_u, tok,
                                          jnp.int32(i))
        ls, cache_s = step_s(params_s, cache_s, tok, jnp.int32(i))
        check(lu, ls)
        want = dict(cache_u,
                    pattern=_restack(cache_u["pattern"], len(cfg.pattern)))
        assert (jax.tree_util.tree_structure(want)
                == jax.tree_util.tree_structure(cache_s))
        jax.tree_util.tree_map(check, want, cache_s)


# --------------------------------------------------------------------- #
# config registry / shape applicability (assignment bookkeeping)
# --------------------------------------------------------------------- #
def test_all_ten_archs_registered():
    from repro.configs.archs import ARCH_IDS
    assert len(ARCH_IDS) == 10
    for a in ARCH_IDS:
        assert a in all_configs()


def test_published_dimensions():
    """Exact dims from the assignment table."""
    expect = {
        "llama3-8b": (32, 4096, 32, 8, 14336, 128256),
        "gemma3-1b": (26, 1152, 4, 1, 6912, 262144),
        "minitron-8b": (32, 4096, 32, 8, 16384, 256000),
        "stablelm-12b": (40, 5120, 32, 8, 13824, 100352),
        "deepseek-v2-236b": (60, 5120, 128, 128, 1536, 102400),
        "deepseek-v3-671b": (61, 7168, 128, 128, 2048, 129280),
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "internvl2-1b": (24, 896, 14, 2, 4864, 151655),
        "mamba2-130m": (24, 768, 24, 1, 0, 50280),
    }
    for arch, (L, d, H, Hkv, ff, V) in expect.items():
        cfg = get_config(arch)
        assert cfg.n_layers == L, arch
        assert cfg.d_model == d, arch
        assert cfg.n_heads == H, arch
        assert cfg.n_kv_heads == Hkv, arch
        assert cfg.d_ff == ff, arch
        assert cfg.vocab_size == V, arch
    # seamless: 12 enc + 12 dec
    sm = get_config("seamless-m4t-medium")
    assert sm.n_layers == 24 and sm.n_repeats == 12
    assert sm.d_model == 1024 and sm.vocab_size == 256206


def test_long_context_applicability():
    """long_500k only for sub-quadratic archs (DESIGN.md §4)."""
    eligible = {a for a, c in all_configs().items()
                if any(s.name == "long_500k" for s in applicable_shapes(c))}
    assert eligible == {"mamba2-130m", "recurrentgemma-9b", "gemma3-1b"}


def test_moe_active_params_below_total():
    from repro.models.lm import active_param_count
    cfg = get_config("deepseek-v2-236b").reduced()
    model = build_model(cfg)
    p = model.param_specs()
    assert active_param_count(cfg, p) < param_count(p)


def test_param_counts_match_published_scale():
    """Full configs hit the advertised parameter counts (±15%)."""
    import math
    expected = {"llama3-8b": 8.0e9, "deepseek-v2-236b": 236e9,
                "deepseek-v3-671b": 671e9, "mamba2-130m": 130e6,
                "stablelm-12b": 12.1e9, "recurrentgemma-9b": 9e9}
    for arch, n in expected.items():
        model = build_model(get_config(arch))
        got = param_count(model.param_specs())
        assert abs(got - n) / n < 0.15, (arch, got, n)
