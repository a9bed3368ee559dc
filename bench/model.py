"""The program's configuration for a configuration file, and the peaks.

A configuration file names a configuration the program registers and
the overrides that bring it to the published sizes (``program``).  The
result is checked key by key against the file's published keys, which
the reference reads, so the program and the reference cannot be handed
two different models.
"""

from __future__ import annotations

import json
import pathlib
from typing import Dict

from bench.reference import dims_of

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def program_config(cfgfile: Dict):
    from repro.configs.base import ATTN, get_config
    prog = cfgfile["program"]
    cfg = get_config(prog["registered"]).with_overrides(
        **prog.get("overrides", {}))
    dm = dims_of(cfgfile)
    want = {
        "d_model": dm.d, "n_heads": dm.heads, "n_kv_heads": dm.kv_heads,
        "head_dim": dm.head_dim, "d_ff": dm.ff, "vocab_size": dm.vocab,
        "n_layers": dm.layers, "rope_theta": dm.rope_theta,
        "rope_pct": dm.rope_pct, "norm": dm.norm, "norm_eps": dm.eps,
        "gated": dm.gated, "qkv_bias": dm.qkv_bias,
        "tie_embeddings": dm.tied, "dtype": dm.dtype,
        "pattern": (ATTN,), "extras": (False, False, False, 0.0, (), ()),
    }
    have = {
        "d_model": cfg.d_model, "n_heads": cfg.n_heads,
        "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.resolved_head_dim,
        "d_ff": cfg.d_ff, "vocab_size": cfg.vocab_size,
        "n_layers": cfg.n_layers, "rope_theta": float(cfg.rope_theta),
        "rope_pct": float(cfg.rope_pct), "norm": cfg.norm,
        "norm_eps": float(cfg.norm_eps), "gated": cfg.act == "silu",
        "qkv_bias": cfg.qkv_bias, "tie_embeddings": cfg.tie_embeddings,
        "dtype": cfg.dtype, "pattern": tuple(cfg.pattern),
        "extras": (cfg.qk_norm, cfg.post_norms, cfg.scale_embedding,
                   float(cfg.logit_softcap), cfg.prefix, cfg.suffix),
    }
    if cfg.act not in ("silu", "relu2") or cfg.moe is not None:
        raise ValueError(f"{cfg.name}: no reference for act {cfg.act!r}"
                         f" or experts")
    bad = {k: (have[k], want[k]) for k in want if have[k] != want[k]}
    if bad:
        raise ValueError(f"program config {cfg.name} differs from the "
                         f"published keys: {bad}")
    if not cfg.use_pallas_kernels:
        raise ValueError("the served path runs the Pallas kernels")
    return cfg


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's peaks by ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    p = table[device_kind]
    return {"flops": float(p["flops_bf16"]),
            "bytes_per_s": float(p["hbm_bytes_per_s"])}


__all__ = ["PEAKS", "peaks", "program_config"]
