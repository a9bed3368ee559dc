"""Sharding rules: pytree path → PartitionSpec for every architecture.

Axes: ``pod`` (across pods), ``data`` (within-pod data parallel),
``model`` (tensor parallel).  Batch dims shard over ("pod", "data");
weights shard over "model" following Megatron conventions (column-
parallel up-projections, row-parallel down-projections, head-sharded
attention).  MoE experts shard over "model" on E and over "data" on ff
(the pjit baseline; the shard_map expert-parallel path lives in
expert_parallel.py).  A dimension is only sharded when divisible — e.g.
llama3's 8 KV heads stay replicated on a 16-way model axis while its 32
Q heads shard, and mamba2-130m's tiny mixers replicate entirely.

ZeRO-style optimizer-state sharding: moments/master weights additionally
shard their largest replicated dimension over "data" (``zero=True``),
which is what lets the 236B/671B optimizer states fit (EXPERIMENTS.md
§Dry-run).
"""

from __future__ import annotations

import re
from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig

PyTree = Any

BATCH_AXES = ("pod", "data")   # multi-pod; single-pod meshes lack "pod"
MODEL_AXIS = "model"
DATA_AXIS = "data"


def _axes_in(mesh: Mesh, *names: str) -> Tuple[str, ...]:
    return tuple(n for n in names if n in mesh.axis_names)


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return _axes_in(mesh, "pod", "data")


def _axis_size(mesh: Mesh, name: str) -> int:
    if name not in mesh.axis_names:
        return 1
    return mesh.shape[name]


def _maybe(mesh: Mesh, dim_size: int, axis: str) -> Optional[str]:
    """Shard `dim_size` over `axis` only if divisible (else replicate)."""
    n = _axis_size(mesh, axis)
    return axis if n > 1 and dim_size % n == 0 else None


# --------------------------------------------------------------------- #
# parameter rules
# --------------------------------------------------------------------- #
def param_pspec(path: str, leaf, cfg: ModelConfig, mesh: Mesh) -> P:
    """PartitionSpec for one parameter leaf, keyed on its path string."""
    shape = leaf.shape
    m = lambda d: _maybe(mesh, d, MODEL_AXIS)      # noqa: E731
    dta = lambda d: _maybe(mesh, d, DATA_AXIS)     # noqa: E731

    # ---- embeddings / head ---------------------------------------- #
    if re.search(r"\['embed'\]$", path):
        return P(m(shape[0]), None)                 # (V, d): vocab-sharded
    if re.search(r"\['head'\]$", path):
        return P(None, m(shape[1]))                 # (d, V)

    # ---- norms / small vectors ------------------------------------ #
    if leaf.ndim <= 1:
        return P(*([None] * leaf.ndim))

    # ---- MoE ------------------------------------------------------- #
    if "['moe']" in path:
        if re.search(r"\['router'\]$", path):
            return P(None, m(shape[1]))             # (d, E)
        if "['shared']" in path:
            if re.search(r"\['down'\]$", path):
                return P(m(shape[0]), None)         # (sff, d)
            return P(None, m(shape[1]))             # (d, sff)
        if cfg.moe_ep:
            # expert-parallel layout: E over the largest ("data","model")
            # suffix that divides (matches expert_parallel._ep_axes)
            import math as _math
            sizes = dict(mesh.shape)
            cands = [a for a in ("data", "model") if a in mesh.axis_names]
            ep = None
            for axes in ([tuple(cands)] if len(cands) == 2 else []) + \
                    [(a,) for a in reversed(cands)]:
                n = _math.prod(sizes[a] for a in axes)
                if n > 1 and shape[0] % n == 0:
                    ep = axes if len(axes) > 1 else axes[0]
                    break
            if ep is not None and re.search(r"\['(gate|up|down)'\]$", path):
                return P(ep, None, None)
        if re.search(r"\['(gate|up)'\]$", path):
            return P(m(shape[0]), None, dta(shape[2]))   # (E, d, ff)
        if re.search(r"\['down'\]$", path):
            return P(m(shape[0]), dta(shape[1]), None)   # (E, ff, d)

    # ---- MLA -------------------------------------------------------- #
    if re.search(r"\['wq_b'\]$", path) or re.search(r"\['wk_b'\]$", path) \
            or re.search(r"\['wv_b'\]$", path):
        return P(None, m(shape[1]), None)           # (rank, H, dh)
    if re.search(r"\['(wq_a|wkv_a)'\]$", path):
        return P(None, None)

    # ---- attention --------------------------------------------------- #
    if re.search(r"\['wq'\]$", path):
        return P(None, m(shape[1]), None)           # (d, H, dh)
    if re.search(r"\['(wk|wv)'\]$", path):
        return P(None, m(shape[1]), None)           # (d, Hkv, dh) if divisible
    if re.search(r"\['wo'\]$", path):
        return P(m(shape[0]), None, None)           # (H, dh, d) row-parallel
    if re.search(r"\['b(q|k|v)'\]$", path):
        return P(m(shape[0]), None)

    # ---- dense MLP --------------------------------------------------- #
    if re.search(r"\['(gate|up)'\]$", path):
        return P(None, m(shape[1]))                 # (d, ff) column
    if re.search(r"\['down'\]$", path):
        return P(m(shape[0]), None)                 # (ff, d) row

    # ---- SSM (mamba2) ------------------------------------------------ #
    if re.search(r"\['(in_proj|out_proj)'\]$", path) and cfg.ssm is not None:
        return P(None, None)                        # tiny model: replicate
    if re.search(r"\['conv_w'\]$", path) and cfg.ssm is not None:
        return P(None, None)

    # ---- RG-LRU ------------------------------------------------------ #
    if re.search(r"\['(gate_proj|rec_proj)'\]$", path):
        return P(None, m(shape[1]))                 # (d, w) column
    if re.search(r"\['(w_a|w_x)'\]$", path):
        return P(None, m(shape[1]))                 # (w, w) output-sharded
    if re.search(r"\['out_proj'\]$", path):
        return P(m(shape[0]), None)                 # (w, d) row
    if re.search(r"\['conv_w'\]$", path):
        return P(None, m(shape[1]))                 # (K, w)

    return P(*([None] * leaf.ndim))


def _with_stack_dim(spec: P, leaf, path: str, cfg: ModelConfig) -> P:
    """Pattern-stacked leaves carry a leading (n_repeats,) dim."""
    if "['pattern']" in path and cfg.scan_layers and leaf.ndim == len(spec) + 1:
        return P(None, *spec)
    return spec


def params_pspecs(cfg: ModelConfig, params_shape: PyTree, mesh: Mesh) -> PyTree:
    """PartitionSpec pytree matching `params_shape` (ShapeDtypeStructs)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shape)
    specs = []
    for path, leaf in flat:
        ps = jax.tree_util.keystr(path)
        stacked = "['pattern']" in ps and cfg.scan_layers and leaf.ndim >= 1
        inner = (jax.ShapeDtypeStruct(leaf.shape[1:], leaf.dtype)
                 if stacked else leaf)
        base = param_pspec(ps, inner, cfg, mesh)
        # pad/trim to the (unstacked) leaf rank
        if len(base) < inner.ndim:
            base = P(*(tuple(base) + (None,) * (inner.ndim - len(base))))
        elif len(base) > inner.ndim:
            base = P(*tuple(base)[:inner.ndim])
        if stacked:
            base = P(None, *base)
        specs.append(base)
    return jax.tree_util.tree_unflatten(treedef, specs)


def optimizer_pspecs(param_specs: PyTree, params_shape: PyTree, mesh: Mesh,
                     *, zero: bool = True) -> PyTree:
    """Moment/master shardings = param shardings (+ ZeRO over "data")."""
    if not zero or "data" not in (mesh.axis_names or ()):
        return param_specs

    def zero_spec(spec: P, leaf):
        dims = list(spec) + [None] * (leaf.ndim - len(spec))
        if DATA_AXIS in dims:
            return P(*dims)
        n = _axis_size(mesh, DATA_AXIS)
        # shard the largest replicated dim that divides the data axis
        order = sorted(range(leaf.ndim), key=lambda i: -leaf.shape[i])
        for i in order:
            if dims[i] is None and leaf.shape[i] % n == 0 \
                    and leaf.shape[i] >= n:
                dims[i] = DATA_AXIS
                break
        return P(*dims)

    return jax.tree_util.tree_map(zero_spec, param_specs, params_shape)


# --------------------------------------------------------------------- #
# activations / inputs / caches
# --------------------------------------------------------------------- #
def _divisible_batch_axes(mesh: Mesh, batch: int) -> Tuple[str, ...]:
    """Largest prefix of ("pod","data") whose product divides the batch
    (long_500k has global_batch=1: the data axes idle, which the roofline
    table reports honestly)."""
    axes = []
    prod = 1
    for a in batch_axes(mesh):
        n = _axis_size(mesh, a)
        if batch % (prod * n) == 0:
            axes.append(a)
            prod *= n
    return tuple(axes)


def batch_pspecs(batch_specs: PyTree, mesh: Mesh) -> PyTree:
    """Inputs shard their leading batch dim over ("pod","data")."""

    def spec(leaf):
        axes = _divisible_batch_axes(mesh, leaf.shape[0])
        lead = axes if axes else None
        return P(lead, *([None] * (leaf.ndim - 1)))

    return jax.tree_util.tree_map(spec, batch_specs)


def cache_pspecs(cfg: ModelConfig, cache_shape: PyTree, mesh: Mesh) -> PyTree:
    """Decode-cache shardings.

    Full-length ATTN KV caches — head-major self-attention k/v
    (B, Hkv, S, D) and cross-attention memory (B, M, Hkv, D) — shard
    batch over ("pod","data") and *sequence* over "model": the
    flash-decode layout (DESIGN.md §5) that sidesteps kv_heads <
    model_axis.  Ring buffers, MLA latent caches and recurrent states
    shard batch only (they are small; the latent/recurrent state is
    shared across heads).
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shape)
    specs = []
    window = cfg.sliding_window or 0
    for path, leaf in flat:
        ps = jax.tree_util.keystr(path)
        stacked = "['pattern']" in ps and cfg.scan_layers
        dims = leaf.shape[1:] if stacked else leaf.shape
        lead = (None,) if stacked else ()
        axes0 = _divisible_batch_axes(mesh, dims[0]) if dims else ()
        axes = axes0 if axes0 else None
        if re.search(r"\['(k|v|cross_k|cross_v)'\]$", ps) and len(dims) == 4:
            head_major = re.search(r"\['(k|v)'\]$", ps) is not None
            seq = dims[2] if head_major else dims[1]
            seq_axis = _maybe(mesh, seq, MODEL_AXIS)
            if window and seq <= window:
                seq_axis = None                    # ring buffers replicate S
            spec = (P(*lead, axes, None, seq_axis, None) if head_major
                    else P(*lead, axes, seq_axis, None, None))
        elif re.search(r"\['(c_kv|k_rope)'\]$", ps) and len(dims) == 3:
            spec = P(*lead, axes, _maybe(mesh, dims[1], MODEL_AXIS), None)
        elif len(dims) >= 1:
            spec = P(*lead, axes, *([None] * (len(dims) - 1)))
        else:
            spec = P()
        specs.append(spec)
    return jax.tree_util.tree_unflatten(treedef, specs)


def to_named(mesh: Mesh, spec_tree: PyTree) -> PyTree:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
