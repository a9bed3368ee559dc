#!/usr/bin/env python3
"""Chip smoke test: serve the published-width gemma3-1b on one TPU chip.

Drives the real LM serving path once, end to end, in one process:

1. checks that JAX's first device is a TPU, and exits non-zero if not;
2. serves a few seconds of the ``steady-poisson`` scenario through
   ``run_lm_scenario`` (the ``bench_serving --execution real
   --real-model gemma3-1b`` path) under both policies, and checks that
   every prompt was answered and every decode chain completed;
3. checks that the engine's jitted prefill and decode programs contain
   ``tpu_custom_call``: the Pallas kernels compiled for the chip, with
   no fallback to interpret mode or to the jnp attention path;
4. compares logits: prefill then decode steps through the engine,
   against the model's own non-Pallas forward in float32 on the same
   weights.  The prompt ends just inside the 512-token sliding window
   and decode crosses it, so the local layers' ring caches wrap.

Lines before the last are informational.  The last line of standard
output is one JSON object: ``{"ok": true, "device": {"platform": "tpu",
"kind": ..., "count": ...}}``.  Any failed phase exits non-zero.

Compiled programs persist in ``$JAX_COMPILATION_CACHE_DIR`` if set,
otherwise in ``<repo>/.jax_cache``.

Usage::

    python chip_smoke.py
"""

from __future__ import annotations

import json
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent
MODEL = "gemma3-1b"
SEED = 0
SCENARIO = "steady-poisson"
UNITS = 2               # one unit per phase pool under packrat's split
MAX_BATCH = 2           # pow2 batch cells b ∈ {1, 2} per phase
DURATION_S = 4.0
DECODE_STEPS = 4
PROMPT_LEN = 508        # prefill ends 4 tokens inside the 512 window...
COMPARE_STEPS = 8       # ...and decode writes positions 508..515
# bf16 weights, activations and KV cache (unit roundoff 2^-9) through 26
# layers, against a float32 forward on the same weights: the engine's
# logits must sit within 5% of the reference's largest logit.
LOGIT_REL_TOL = 0.05


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def check_tpu():
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU found: JAX's first device is {devices[0].platform!r} "
             f"({devices[0].device_kind}); this smoke test never falls "
             "back to the CPU")
    return devices


def serve(model: str, *, units: int, max_batch: int, duration: float,
          decode_steps: int) -> dict:
    """Phase 2: both policies through run_lm_scenario; checks that every
    prompt was answered and every decode chain ran to its end."""
    from repro.launch.bench_serving import run_lm_scenario
    from repro.serving.scenarios import get_scenario
    t0 = time.perf_counter()
    result = run_lm_scenario(
        get_scenario(SCENARIO), real_model=model, units=units,
        duration=duration, seed=SEED, initial_batch=max_batch,
        max_batch=max_batch, decode_steps=decode_steps, slo_factor=4.0,
        reconfigure_timeout=1.0, dispatches=("continuous",))
    print(f"serve: {SCENARIO} {duration:g}s, {units} units, max batch "
          f"{max_batch}, {decode_steps} decode steps per prompt, "
          f"{time.perf_counter() - t0:.1f}s wall incl. compiles")
    prompts = result["offered_prompts"]
    if prompts < 1:
        fail("the scenario offered no prompts")
    for key in result["policies"]:
        rep = result[key]
        phases = rep.get("phases", {})
        answered = phases.get("prefill", {}).get("completed", 0)
        steps = phases.get("decode", {}).get("completed", 0)
        ttft = rep.get("ttft_ms", {}).get("p50")
        tpot = rep.get("tpot_ms", {}).get("p50")
        print(f"serve[{key}]: {answered}/{prompts} prompts answered, "
              f"{steps}/{prompts * decode_steps} decode steps, "
              f"shed {rep['shed']}; TTFT p50 {ttft} ms, TPOT p50 {tpot} ms "
              "(informational, not a measurement claim)")
        if answered != prompts:
            fail(f"{key}: {answered} of {prompts} prompts answered")
        if steps != prompts * decode_steps:
            fail(f"{key}: {steps} of {prompts * decode_steps} decode "
                 "steps completed")
        if rep["shed"] or rep["incomplete"]:
            fail(f"{key}: shed {rep['shed']}, incomplete "
                 f"{rep['incomplete']}")
    return result


def check_kernels_compiled(engine, *, batch: int, seq: int) -> None:
    """Phase 3: the jitted prefill and decode programs call the Pallas
    kernels as TPU custom calls."""
    import jax
    import jax.numpy as jnp
    tokens = jnp.zeros((batch, seq), jnp.int32)
    prefill_hlo = engine.jit_prefill.lower(engine.params, tokens).as_text()
    _, cache = jax.eval_shape(engine.jit_prefill, engine.params, tokens)
    decode_hlo = engine.jit_decode.lower(
        engine.params, cache, tokens[:, :1], jnp.int32(seq)).as_text()
    for name, hlo in (("prefill", prefill_hlo), ("decode", decode_hlo)):
        n = hlo.count("tpu_custom_call")
        print(f"kernels: {name} program holds {n} tpu_custom_call")
        if n == 0:
            fail(f"the {name} program has no tpu_custom_call: its "
                 "attention kernel did not compile for the TPU")


def compare_logits(engine, *, prompt_len: int, steps: int,
                   rel_tol: float) -> float:
    """Phase 4: prefill + decode steps vs the non-Pallas float32 forward.
    Returns the largest absolute logit error."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.lm import apply_head, build_model

    cfg = engine.cfg
    ref_cfg = cfg.with_overrides(use_pallas_kernels=False, dtype="float32")
    ref_model = build_model(ref_cfg)
    ref_params = jax.tree.map(lambda x: x.astype(jnp.float32), engine.params)
    tokens = jax.random.randint(jax.random.PRNGKey(SEED + 7),
                                (1, prompt_len + steps), 0, cfg.vocab_size,
                                jnp.int32)

    @jax.jit
    def reference(params, toks):
        hidden = ref_model.forward(params, {"tokens": toks})
        return apply_head(params, hidden, ref_cfg)

    with jax.default_matmul_precision("highest"):
        ref = np.asarray(reference(ref_params, tokens))[0]   # (S, V)
    del ref_params
    logits, cache = engine.prefill(tokens[:, :prompt_len])
    got = [np.asarray(logits, np.float32)[0, 0]]
    for i in range(prompt_len, prompt_len + steps):
        logits, cache = engine.decode_step(cache, tokens[:, i:i + 1], i)
        got.append(np.asarray(logits, np.float32)[0, 0])
    want = ref[prompt_len - 1:]
    got = np.stack(got)
    if not np.isfinite(got).all():
        fail("the engine produced non-finite logits")
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    tol = rel_tol * scale
    agree = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    print(f"logits: prefill {prompt_len} tokens + {steps} decode steps "
          f"(positions {prompt_len}..{prompt_len + steps - 1}, window "
          f"{cfg.sliding_window}) vs the float32 non-Pallas forward at "
          f"matmul precision 'highest': max |Δlogit| {err:.6g}, tolerance "
          f"{tol:.6g} = {rel_tol:g} × max |logit| {scale:.6g} "
          f"({cfg.dtype} weights, activations and KV cache through "
          f"{cfg.n_layers} layers); argmax agreement {agree:.3f}")
    if not err <= tol:
        fail(f"max |Δlogit| {err:.6g} exceeds the tolerance {tol:.6g}")
    return err


def main() -> int:
    devices = check_tpu()
    sys.path.insert(0, str(REPO / "src"))
    try:
        from repro.launch.compile_cache import configure_compile_cache
    except ModuleNotFoundError as e:
        fail(f"the repro package is not next to chip_smoke.py ({e})")
    cache_dir = configure_compile_cache()
    from repro.models.serve_lm import make_lm_engine

    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache_dir}")
    serve(MODEL, units=UNITS, max_batch=MAX_BATCH, duration=DURATION_S,
          decode_steps=DECODE_STEPS)

    t0 = time.perf_counter()
    engine = make_lm_engine(MODEL, seed=SEED)
    cfg = engine.cfg
    print(f"model: {cfg.name} d_model {cfg.d_model}, {cfg.n_layers} layers, "
          f"{cfg.n_heads} q / {cfg.n_kv_heads} kv heads of dim "
          f"{cfg.resolved_head_dim}, vocab {cfg.vocab_size}, {cfg.dtype}, "
          f"max_seq {engine.max_seq}; built in "
          f"{time.perf_counter() - t0:.1f}s")
    check_kernels_compiled(engine, batch=MAX_BATCH,
                           seq=engine.default_seq_bucket)
    compare_logits(engine, prompt_len=PROMPT_LEN, steps=COMPARE_STEPS,
                   rel_tol=LOGIT_REL_TOL)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
