"""Expert parallelism via shard_map + all_to_all (the optimized MoE path).

The pjit baseline (models.moe.apply_moe, experts sharded over "model" on
E and "data" on ff) lets XLA infer collectives, which costs activation
all-gathers over the data axis per MoE layer (observed in the dry-run —
EXPERIMENTS.md §Perf).  This module implements DeepSeek-style EP
instead: tokens are routed locally on each shard, exchanged with one
all_to_all to the shards owning their experts, processed, and returned
with a second all_to_all — collective bytes per layer drop from
O(tokens·d·shards) to O(2·tokens·k·d·capacity_factor).

Experts shard over the largest suffix of ("data", "model") that divides
n_experts (deepseek-v3: 256 experts over data×model = 256 shards, one
expert per chip — the deployment DeepSeek describe).  Tokens enter with
their natural layout (batch over ("pod","data"), sequence over "model"
when seq_sharding is on) and the all_to_all permutes them pod-locally.
Enable with ``ModelConfig.moe_ep=True`` (used by the MoE hillclimb cell).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..models.common import _ACTS
from ..models.moe import router_probs


def _ep_axes(mesh, n_experts: int) -> Tuple[str, ...]:
    names = mesh.axis_names
    sizes = dict(mesh.shape)
    cands = [a for a in ("data", "model") if a in names]
    for axes in ([tuple(cands)] if len(cands) == 2 else []) + \
            [(a,) for a in reversed(cands)]:
        n = math.prod(sizes[a] for a in axes)
        if n > 1 and n_experts % n == 0:
            return axes
    return ()


def apply_moe_ep(params, x, cfg: ModelConfig, *, mesh=None):
    """Drop-in for models.moe.apply_moe with explicit EP collectives.

    x: (B, S, d) with B sharded over ("pod","data") and S over "model"
    (falls back silently to those axes that exist/divide).
    """
    from ..models.moe import apply_moe

    moe = cfg.moe
    assert moe is not None
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not getattr(mesh, "axis_names", ()):
        return apply_moe(params, x, cfg)     # no mesh: dense fallback
    ep = _ep_axes(mesh, moe.n_experts)
    if not ep:
        return apply_moe(params, x, cfg)
    sizes = dict(mesh.shape)
    n_shards = math.prod(sizes[a] for a in ep)
    e_local = moe.n_experts // n_shards

    B, S, d = x.shape
    names = mesh.axis_names
    batch_axes = tuple(a for a in ("pod", "data") if a in names)
    bprod = math.prod(sizes[a] for a in batch_axes) if batch_axes else 1
    if B % bprod:
        batch_axes, bprod = (), 1
    seq_axis = "model" if "model" in names and S % sizes["model"] == 0 \
        else None
    sprod = sizes["model"] if seq_axis else 1
    t_local = (B // bprod) * (S // sprod)
    cap = max(4, int(math.ceil(
        t_local * moe.top_k * moe.capacity_factor / n_shards)))
    act = _ACTS[cfg.act]
    k = moe.top_k

    def shard_fn(xs, router_w, gate_w, up_w, down_w):
        # xs: (B_local, S_local, d) → (t_local, d)
        xt = xs.reshape(-1, d)
        gates, experts = router_probs({"router": router_w}, xt, moe)
        flat_e = experts.reshape(-1)
        flat_g = gates.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(t_local, dtype=jnp.int32), k)
        dest = flat_e // e_local
        onehot = jax.nn.one_hot(dest, n_shards, dtype=jnp.int32)
        pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - onehot,
                                  dest[:, None], axis=1)[:, 0]
        keep = pos < cap
        rows = jnp.where(keep, dest, n_shards)
        cols = jnp.where(keep, pos, cap)
        tok_grid = jnp.full((n_shards + 1, cap + 1), t_local, jnp.int32)
        tok_grid = tok_grid.at[rows, cols].set(flat_t)
        eid_grid = jnp.zeros((n_shards + 1, cap + 1), jnp.int32)
        eid_grid = eid_grid.at[rows, cols].set(flat_e % e_local)
        gate_grid = jnp.zeros((n_shards + 1, cap + 1), jnp.float32)
        gate_grid = gate_grid.at[rows, cols].set(flat_g)
        tok_idx = tok_grid[:n_shards, :cap]
        eids = eid_grid[:n_shards, :cap]
        gvals = gate_grid[:n_shards, :cap]

        xp = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], axis=0)
        send = xp[tok_idx]                                   # (shards, cap, d)
        recv = jax.lax.all_to_all(send, ep, 0, 0, tiled=False)
        recv_eids = jax.lax.all_to_all(eids, ep, 0, 0, tiled=False)
        valid = jax.lax.all_to_all(tok_idx < t_local, ep, 0, 0, tiled=False)

        flat_in = recv.reshape(-1, d)
        flat_eid = recv_eids.reshape(-1)
        if e_local == 1:
            h = act(flat_in @ gate_w[0]) * (flat_in @ up_w[0])
            y = h @ down_w[0]
        else:
            wg = gate_w[flat_eid]
            wu = up_w[flat_eid]
            wd = down_w[flat_eid]
            h = act(jnp.einsum("nd,ndf->nf", flat_in, wg)) \
                * jnp.einsum("nd,ndf->nf", flat_in, wu)
            y = jnp.einsum("nf,nfd->nd", h, wd)
        y = jnp.where(valid.reshape(-1)[:, None], y, 0.0).astype(xt.dtype)
        y = y.reshape(n_shards, cap, d)

        back = jax.lax.all_to_all(y, ep, 0, 0, tiled=False)
        out = jnp.zeros((t_local + 1, d), back.dtype)
        out = out.at[tok_idx.reshape(-1)].add(
            (back * gvals[..., None].astype(back.dtype)).reshape(-1, d))
        return out[:t_local].reshape(xs.shape)

    x_spec = P(batch_axes if batch_axes else None, seq_axis, None)
    w_spec = P(ep if len(ep) > 1 else ep[0], None, None)
    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, w_spec),
        out_specs=x_spec, check_vma=False)
    out = fn(x, params["router"].astype(jnp.float32),
             params["gate"], params["up"], params["down"])

    if moe.n_shared:
        sp = params["shared"]
        xt = x.reshape(B * S, d)
        shared = (act(xt @ sp["gate"]) * (xt @ sp["up"])) @ sp["down"]
        out = out + shared.reshape(B, S, d).astype(out.dtype)
    return out
