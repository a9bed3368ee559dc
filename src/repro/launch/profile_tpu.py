"""Packrat's profiler on TPU: compile-time L[t,b] tables from sub-meshes.

The paper measures ⟨1,t,b⟩ wall-clock latencies; the TPU analogue lowers
``serve_step`` for one *thin instance* on a t-chip sub-mesh at batch b
and derives L(t,b) = max(roofline terms) + dispatch overhead from the
compiled artifact (core.roofline).  The resulting table feeds the same
2-D knapsack optimizer — this is the full Packrat pipeline, profiling
through reconfiguration, on the TPU target (DESIGN.md §2).

Like the paper (§3.2), profiling is restricted to powers of two to keep
the table small; sub-mesh thread counts t are powers of two because TPU
instance slices must tile the pod.
"""

import argparse
import json
import os
import pathlib
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..configs import SHAPES, ShapeConfig, get_config
from ..configs.base import ModelConfig
from ..core.profiler import AnalyticProfiler
from ..core.roofline import TPU_V5E, RooflineTerms
from ..distributed.sharding import (batch_pspecs, cache_pspecs, params_pspecs,
                                    to_named)
from ..models import build_model
from .hlo_analysis import program_cost, roofline_from_cost
from .mesh import make_submesh

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "results"


def _lower_decode(cfg: ModelConfig, mesh, batch: int, seq_len: int):
    model = build_model(cfg)
    shape = ShapeConfig("profile", seq_len=seq_len, global_batch=batch,
                        kind="decode")
    p_shape = model.param_specs()
    p_spec = params_pspecs(cfg, p_shape, mesh)
    cache_shape = model.cache_specs(shape)
    c_spec = cache_pspecs(cfg, cache_shape, mesh)
    tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    logits_spec = batch_pspecs(
        jax.ShapeDtypeStruct((batch, 1, cfg.vocab_size), jnp.float32), mesh)

    def serve_step(params, cache, tokens, p):
        return model.decode_step(params, cache, tokens, p)

    with jax.sharding.set_mesh(mesh):
        return jax.jit(
            serve_step,
            in_shardings=(to_named(mesh, p_spec), to_named(mesh, c_spec),
                          to_named(mesh, batch_pspecs(tok, mesh)),
                          to_named(mesh, jax.sharding.PartitionSpec())),
            out_shardings=(to_named(mesh, logits_spec),
                           to_named(mesh, c_spec)),
            donate_argnums=(1,)).lower(p_shape, cache_shape, tok, pos)


def decode_terms(cfg: ModelConfig, n_chips: int, batch: int, seq_len: int,
                 *, model_parallel: Optional[int] = None) -> RooflineTerms:
    """Roofline terms of one thin instance: serve_step on a t-chip sub-mesh.

    Uses r=1/r=2 differencing (hlo_analysis) to reconstruct full depth.
    """
    mesh = make_submesh(n_chips, model_parallel=model_parallel)
    costs = {}
    for r in (1, 2):
        rcfg = cfg.with_overrides(n_repeats=r, scan_layers=False)
        compiled = _lower_decode(rcfg, mesh, batch, seq_len).compile()
        costs[r] = program_cost(compiled)
        del compiled
    pattern = costs[2] - costs[1]
    total = costs[1].scaled_add(pattern, cfg.n_repeats - 1)
    return roofline_from_cost(total, n_chips)


class TPUPackratProfiler(AnalyticProfiler):
    """AnalyticProfiler whose terms_fn compiles thin-instance sub-meshes."""

    def __init__(self, arch: str, *, seq_len: int = 8192,
                 cache_file: Optional[str] = None, overlap: bool = True):
        self.cfg = get_config(arch)
        self.seq_len = seq_len
        self.cache_file = (pathlib.Path(cache_file) if cache_file else
                           RESULTS_DIR / "profiles" /
                           f"{arch}_s{seq_len}.json")
        self._disk: Dict[str, dict] = {}
        if self.cache_file.exists():
            self._disk = json.loads(self.cache_file.read_text())
        super().__init__(self._terms, overlap=overlap)

    def _terms(self, t: int, b: int) -> RooflineTerms:
        key = f"{t},{b}"
        if key in self._disk:
            d = self._disk[key]
            return RooflineTerms(flops=d["flops"], hbm_bytes=d["hbm_bytes"],
                                 collective_bytes=d["collective_bytes"],
                                 chips=t, hw=TPU_V5E)
        terms = decode_terms(self.cfg, t, b, self.seq_len)
        self._disk[key] = {"flops": terms.flops, "hbm_bytes": terms.hbm_bytes,
                           "collective_bytes": terms.collective_bytes}
        self.cache_file.parent.mkdir(parents=True, exist_ok=True)
        self.cache_file.write_text(json.dumps(self._disk, indent=1))
        return terms


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--chips", type=int, nargs="+",
                    default=[8, 16, 32, 64, 128, 256])
    ap.add_argument("--batches", type=int, nargs="+",
                    default=[1, 4, 16, 64])
    args = ap.parse_args(argv)
    # placeholder host devices for the sub-meshes; before any backend
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=512")
    prof = TPUPackratProfiler(args.arch, seq_len=args.seq)
    print("t,b,compute_s,memory_s,collective_s,L_s")
    for t in args.chips:
        for b in args.batches:
            terms = prof.terms(t, b)
            print(f"{t},{b},{terms.compute_s:.6f},{terms.memory_s:.6f},"
                  f"{terms.collective_s:.6f},{terms.latency:.6f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
