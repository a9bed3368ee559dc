"""Multi-pod dry-run: lower + compile every (architecture × shape × mesh).

For each cell this driver performs:

1. the **validation compile** — the full-depth model (lax.scan over
   pattern repeats) lowered with ShapeDtypeStruct stand-ins (params,
   optimizer state, inputs, caches — nothing allocated) and compiled for
   the production mesh; ``memory_analysis()`` proves per-device
   residency, and the optimized HLO carries the collective schedule;
2. the **cost differencing pass** — two *unrolled* compiles at
   ``n_repeats = r0`` and ``r0 + 1``; the difference is the exact
   per-pattern cost (HLO cost analysis counts a scanned body once, so
   full-depth FLOPs must be reconstructed this way — see
   launch/hlo_analysis.py) and ``total = base + n_repeats × pattern``;
3. roofline terms + MODEL_FLOPS ratios, appended to a JSON results file
   consumed by EXPERIMENTS.md §Dry-run/§Roofline and by the Packrat
   analytic profiler.

Usage:
    python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
    python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time
import traceback
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs import SHAPES, ShapeConfig, all_configs, applicable_shapes, get_config
from ..configs.base import ModelConfig
from ..core.roofline import TPU_V5E, RooflineTerms
from ..distributed.sharding import (batch_pspecs, cache_pspecs,
                                    optimizer_pspecs, params_pspecs,
                                    to_named)
from ..models import build_model
from ..models.lm import param_count
from ..training.optimizer import AdamWConfig, init_adamw
from ..training.train_loop import TrainConfig, make_train_step
from .compile_cache import configure_compile_cache
from .hlo_analysis import ProgramCost, program_cost, roofline_from_cost
from .mesh import make_production_mesh

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"


def setup_host() -> None:
    """512 placeholder host devices for the production meshes, and the
    persistent compilation cache, so repeated lowers (differencing
    reruns, hillclimb iterations) hit disk instead of recompiling.  Must
    run before JAX initializes a backend."""
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 5.0)


# --------------------------------------------------------------------- #
# step builders
# --------------------------------------------------------------------- #
def _train_cfg(cfg: ModelConfig) -> TrainConfig:
    return TrainConfig(adamw=AdamWConfig(state_dtype=cfg.train_state_dtype))


def _specs_for(cfg: ModelConfig, shape: ShapeConfig, mesh):
    model = build_model(cfg)
    p_shape = model.param_specs()
    p_spec = params_pspecs(cfg, p_shape, mesh)
    in_specs = model.input_specs(shape)
    in_spec = batch_pspecs(in_specs, mesh)
    return model, p_shape, p_spec, in_specs, in_spec


def lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh):
    """Lower the cell's step on `mesh`; returns (lowered, n_chips)."""
    model, p_shape, p_spec, in_specs, in_spec = _specs_for(cfg, shape, mesh)
    n_chips = mesh.devices.size

    if shape.kind == "train":
        tcfg = _train_cfg(cfg)
        step = make_train_step(cfg, tcfg)
        opt_shape = jax.eval_shape(
            lambda p: init_adamw(tcfg.adamw, p), p_shape)
        opt_spec = type(opt_shape)(
            step=jax.sharding.PartitionSpec(),
            mu=optimizer_pspecs(p_spec, p_shape, mesh),
            nu=optimizer_pspecs(p_spec, p_shape, mesh),
            master=(optimizer_pspecs(p_spec, p_shape, mesh)
                    if opt_shape.master is not None else None))
        metrics_spec = {"grad_norm": jax.sharding.PartitionSpec(),
                        "lr": jax.sharding.PartitionSpec(),
                        "loss": jax.sharding.PartitionSpec()}
        with jax.sharding.set_mesh(mesh):
            jitted = jax.jit(
                step,
                in_shardings=(to_named(mesh, p_spec),
                              to_named(mesh, opt_spec),
                              to_named(mesh, in_spec)),
                out_shardings=(to_named(mesh, p_spec),
                               to_named(mesh, opt_spec),
                               to_named(mesh, metrics_spec)),
                donate_argnums=(0, 1))
            lowered = jitted.lower(p_shape, opt_shape, in_specs)
        return lowered, n_chips

    if shape.kind == "prefill":
        def prefill_step(params, batch):
            return model.prefill(params, batch)

        cache_shape = jax.eval_shape(
            lambda p, b: model.prefill(p, b), p_shape, in_specs)[1]
        c_spec = cache_pspecs(cfg, cache_shape, mesh)
        logits_spec = batch_pspecs(
            jax.ShapeDtypeStruct((shape.global_batch, 1, cfg.vocab_size),
                                 jnp.float32), mesh)
        with jax.sharding.set_mesh(mesh):
            jitted = jax.jit(
                prefill_step,
                in_shardings=(to_named(mesh, p_spec), to_named(mesh, in_spec)),
                out_shardings=(to_named(mesh, logits_spec),
                               to_named(mesh, c_spec)))
            lowered = jitted.lower(p_shape, in_specs)
        return lowered, n_chips

    # decode: serve_step(params, cache, tokens, pos)
    cache_shape = model.cache_specs(shape)
    c_spec = cache_pspecs(cfg, cache_shape, mesh)
    tok_spec = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    pos_spec = jax.ShapeDtypeStruct((), jnp.int32)
    logits_spec = batch_pspecs(
        jax.ShapeDtypeStruct((shape.global_batch, 1, cfg.vocab_size),
                             jnp.float32), mesh)

    def serve_step(params, cache, tokens, pos):
        return model.decode_step(params, cache, tokens, pos)

    with jax.sharding.set_mesh(mesh):
        jitted = jax.jit(
            serve_step,
            in_shardings=(to_named(mesh, p_spec), to_named(mesh, c_spec),
                          to_named(mesh, batch_pspecs(tok_spec, mesh)),
                          to_named(mesh, jax.sharding.PartitionSpec())),
            out_shardings=(to_named(mesh, logits_spec),
                           to_named(mesh, c_spec)),
            donate_argnums=(1,))
        lowered = jitted.lower(p_shape, cache_shape, tok_spec, pos_spec)
    return lowered, n_chips


# --------------------------------------------------------------------- #
# algorithmic FLOPs (assignment definition)
# --------------------------------------------------------------------- #
def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """6·N·D train / 2·N·D inference, N = active matmul params."""
    model = build_model(cfg)
    p_shape = model.param_specs()
    total = param_count(p_shape)
    embed = cfg.vocab_size * cfg.d_model
    n = total - (0 if cfg.tie_embeddings else embed)
    if cfg.moe is not None:
        moe = cfg.moe
        n_moe_layers = sum(1 for k in cfg.layers if k == "mla_moe")
        per_expert = 3 * cfg.d_model * moe.expert_ff
        n -= n_moe_layers * (moe.n_experts - moe.top_k) * per_expert
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch      # decode: one token per sequence


# --------------------------------------------------------------------- #
# per-cell analysis
# --------------------------------------------------------------------- #
def _reduced_depth(cfg: ModelConfig, r: int) -> ModelConfig:
    return cfg.with_overrides(n_repeats=r, scan_layers=False)


def analyze_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
                 skip_validation: bool = False, validation_only: bool = False,
                 cfg_override: Optional[ModelConfig] = None,
                 tag: str = "") -> Dict:
    shape = SHAPES[shape_name]
    if cfg_override is not None:
        # hillclimb path: caller controls every knob (incl. tile sizes)
        cfg = cfg_override
    else:
        # remat only matters for the backward pass; keeping it off for
        # inference shapes substantially cuts SPMD compile time.  Larger
        # attention tiles reduce the unrolled q-loop count (same math).
        cfg = get_config(arch).with_overrides(
            remat=(shape.kind == "train"),
            attn_block_q=2048,
            attn_block_kv=4096)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    rec: Dict = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": n_chips, "tag": tag,
    }
    t0 = time.perf_counter()

    # ---- 1. validation compile (full depth, scanned) ----------------- #
    if not skip_validation:
        lowered, _ = lower_cell(cfg.with_overrides(scan_layers=True),
                                shape, mesh)
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes_per_device": int(ma.argument_size_in_bytes),
            "temp_bytes_per_device": int(ma.temp_size_in_bytes),
            "output_bytes_per_device": int(ma.output_size_in_bytes),
            "alias_bytes_per_device": int(ma.alias_size_in_bytes),
            "peak_bytes_per_device": int(ma.argument_size_in_bytes
                                         + ma.temp_size_in_bytes
                                         + ma.output_size_in_bytes
                                         - ma.alias_size_in_bytes),
        }
        rec["fits_hbm"] = rec["memory"]["peak_bytes_per_device"] \
            <= TPU_V5E.hbm_capacity
        rec["validation_cost_analysis"] = {
            k: v for k, v in (compiled.cost_analysis() or {}).items()
            if k in ("flops", "bytes accessed")}
        del compiled, lowered

    if validation_only:
        rec["elapsed_s"] = time.perf_counter() - t0
        return rec

    # ---- 2. differencing pass (unrolled r0 / r0+1) -------------------- #
    r0 = 1
    costs = {}
    for r in (r0, r0 + 1):
        lowered, _ = lower_cell(_reduced_depth(cfg, r), shape, mesh)
        compiled = lowered.compile()
        costs[r] = program_cost(compiled)
        del compiled, lowered
    pattern_cost = costs[r0 + 1] - costs[r0]
    base_cost = costs[r0].scaled_add(pattern_cost, -r0)
    total_cost = base_cost.scaled_add(pattern_cost, cfg.n_repeats)
    total_cost.argument_bytes = costs[r0].argument_bytes
    total_cost.temp_bytes = costs[r0].temp_bytes

    terms = roofline_from_cost(total_cost, n_chips)
    mf = model_flops(cfg, shape)
    rec["roofline"] = {
        "hlo_flops_total": terms.flops,
        "hlo_bytes_total": terms.hbm_bytes,
        "collective_bytes_per_chip": terms.collective_bytes,
        "compute_s": terms.compute_s,
        "memory_s": terms.memory_s,
        "collective_s": terms.collective_s,
        "latency_s": terms.latency,
        "dominant": terms.dominant,
        "model_flops": mf,
        "model_flops_ratio": mf / terms.flops if terms.flops else 0.0,
        "roofline_fraction": terms.roofline_fraction(mf),
        "collectives_by_op_per_layer": dict(
            pattern_cost.collectives.bytes_by_op),
    }
    rec["elapsed_s"] = time.perf_counter() - t0
    return rec


def all_cells():
    for arch, cfg in all_configs().items():
        for shape in applicable_shapes(cfg):
            yield arch, shape.name


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", help="architecture id (see configs.archs)")
    ap.add_argument("--shape", help="shape name", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true", help="run every cell")
    ap.add_argument("--skip-validation", action="store_true",
                    help="skip the full-depth compile (differencing only)")
    ap.add_argument("--validation-only", action="store_true",
                    help="full-depth compile proof only (no differencing)")
    ap.add_argument("--skip-existing", action="store_true",
                    help="skip cells whose result JSON already exists OK")
    ap.add_argument("--out", default=str(RESULTS_DIR))
    args = ap.parse_args(argv)
    setup_host()

    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    if args.all:
        cells = list(all_cells())
    else:
        if not args.arch:
            ap.error("--arch or --all required")
        cfg = get_config(args.arch)
        shapes = ([args.shape] if args.shape else
                  [s.name for s in applicable_shapes(cfg)])
        cells = [(args.arch, s) for s in shapes]

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    failures = 0
    for arch, shape in cells:
        for multi in meshes:
            name = f"{arch}__{shape}__{'multi' if multi else 'single'}"
            out_file = outdir / f"{name}.json"
            if args.skip_existing and out_file.exists() \
                    and "error" not in json.loads(out_file.read_text()):
                print(f"[skip] {name}")
                continue
            try:
                rec = analyze_cell(arch, shape, multi_pod=multi,
                                   skip_validation=args.skip_validation,
                                   validation_only=args.validation_only)
                out_file.write_text(json.dumps(rec, indent=2))
                r = rec.get("roofline", {})
                mem = rec.get("memory", {})
                if r:
                    print(f"[ok] {name}: dominant={r['dominant']} "
                          f"L={r['latency_s']*1e3:.2f}ms "
                          f"mfu={r['roofline_fraction']*100:.1f}% "
                          f"peak/dev={mem.get('peak_bytes_per_device', 0)/2**30:.2f}GiB "
                          f"({rec['elapsed_s']:.0f}s)")
                else:
                    print(f"[ok] {name}: compiled; "
                          f"peak/dev={mem.get('peak_bytes_per_device', 0)/2**30:.2f}GiB "
                          f"({rec['elapsed_s']:.0f}s)")
            except Exception as e:  # noqa: BLE001 — record and continue
                failures += 1
                out_file.write_text(json.dumps(
                    {"arch": arch, "shape": shape, "multi_pod": multi,
                     "error": "".join(traceback.format_exception(e))[-4000:]},
                    indent=2))
                print(f"[FAIL] {name}: {type(e).__name__}: {e}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
