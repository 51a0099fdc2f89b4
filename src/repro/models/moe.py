"""Mixture-of-Experts with shard_map expert parallelism.

Design (DESIGN.md §5): activations enter the MoE block replicated over the
'model' mesh axis (batch sharded over 'pod'/'data'); expert weights are
sharded over 'model' (EP) with their d_model dim on 'data' (FSDP).  Inside
``shard_map`` each model-rank:

  1. computes the router redundantly (deterministic across ranks),
  2. selects, for each of its E/16 local experts, the top-C tokens by gate
     weight (fixed capacity C = T*k/E * capacity_factor — sort-free dispatch
     via lax.top_k),
  3. runs the expert MLPs as one batched matmul (E_local, C, d) x
     (E_local, d, f),
  4. scatter-adds weighted expert outputs into a (T, d) buffer and
     merges across ranks with a single psum.

The psum merge is the paper-faithful baseline; §Perf replaces it with a
reduce-scatter + sequence-sharded residual stream for the collective-bound
hillclimb.  Token dropping (beyond capacity) is the standard fixed-capacity
behaviour; dropped tokens fall through on the residual stream.

Without an active mesh (CPU smoke tests) the same math runs single-shard
with all experts local.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.parallel.sharding import active_mesh, constrain, spec_for

from .layers import apply_mlp, dense_init, init_mlp
# NOTE: no fsdp_use() here — the expert FFN runs inside shard_map
# (manual axes), where mesh sharding constraints are disallowed and
# the expert weights are already per-shard slices.


def init_moe(cfg: ArchConfig, key) -> Tuple[Dict, Dict]:
    m = cfg.moe
    D, E, F = cfg.d_model, m.n_experts, m.d_ff_expert
    ks = jax.random.split(key, 6)
    p, a = {}, {}
    p["router"], a["router"] = dense_init(ks[0], (D, E), ("embed", None))
    p["wi"], a["wi"] = dense_init(ks[1], (E, D, F), ("experts", "embed", "mlp"),
                                  in_axis=1)
    p["wo"], a["wo"] = dense_init(ks[2], (E, F, D), ("experts", "mlp", "embed"),
                                  in_axis=1)
    if cfg.mlp == "swiglu":
        p["wg"], a["wg"] = dense_init(ks[3], (E, D, F),
                                      ("experts", "embed", "mlp"), in_axis=1)
    if m.n_shared:
        sh, sha = init_mlp(cfg, ks[4], d_ff=m.n_shared * m.d_ff_expert)
        p["shared"], a["shared"] = sh, sha
    if m.dense_residual:
        dr, dra = init_mlp(cfg, ks[5], d_ff=cfg.d_ff)
        p["dense"], a["dense"] = dr, dra
    return p, a


def _expert_ffn(cfg: ArchConfig, p: Dict, xg: jax.Array) -> jax.Array:
    """Batched expert MLP: xg (E_loc, C, D) -> (E_loc, C, D)."""
    h = jnp.einsum("ecd,edf->ecf", xg, p["wi"].astype(xg.dtype))
    if cfg.mlp == "swiglu":
        g = jnp.einsum("ecd,edf->ecf", xg, p["wg"].astype(xg.dtype))
        h = jax.nn.silu(h) * g
    elif cfg.mlp == "relu2":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(xg.dtype))


def _moe_shard(cfg: ArchConfig, p: Dict, x: jax.Array,
               shard_idx: int, n_shards: int, capacity: int) -> jax.Array:
    """MoE math for one model-rank holding E/n_shards experts.

    x (B, S, D) — the rank's (data-sharded) tokens, full feature dim.
    Returns this rank's contribution (B, S, D) (to be psum-merged).
    """
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    E = m.n_experts
    e_loc = E // n_shards
    xt = x.reshape(T, D)

    gates = jax.nn.softmax(
        jnp.einsum("td,de->te", xt, p["router"].astype(xt.dtype))
        .astype(jnp.float32), axis=-1)                    # (T, E)
    topv, topi = jax.lax.top_k(gates, m.top_k)            # (T, k)
    topv = topv / (topv.sum(-1, keepdims=True) + 1e-9)
    # dense (T, E) weight matrix of the top-k selection
    sel = jnp.zeros((T, E), jnp.float32)
    sel = sel.at[jnp.arange(T)[:, None], topi].set(topv)  # scatter top-k
    w_loc = jax.lax.dynamic_slice_in_dim(sel, shard_idx * e_loc, e_loc, 1)

    # fixed-capacity per-expert token selection (top-C by gate weight)
    wv, idx = jax.lax.top_k(w_loc.T, capacity)            # (e_loc, C)
    valid = wv > 0.0
    xg = xt[idx]                                          # (e_loc, C, D) gather
    yg = _expert_ffn(cfg, p, xg)
    yg = yg * (wv * valid)[..., None].astype(yg.dtype)
    # scatter-add back to token buffer
    yt = jnp.zeros((T, D), yg.dtype)
    yt = yt.at[idx.reshape(-1)].add(yg.reshape(-1, D))
    return yt.reshape(B, S, D)


def moe_block(cfg: ArchConfig, p: Dict, x: jax.Array) -> jax.Array:
    """Full MoE block (router + experts + optional shared/dense paths)."""
    m = cfg.moe
    mesh = active_mesh()
    n_shards = mesh.shape["model"] if (mesh and "model" in mesh.shape.keys()) else 1

    B, S, D = x.shape
    # capacity per expert per rank, from the rank-local token count
    t_local = (B * S) // _data_shards(mesh)
    capacity = max(1, int(math.ceil(t_local * m.top_k / m.n_experts
                                    * m.capacity_factor)))

    if mesh is None or n_shards == 1:
        y = _moe_shard(cfg, p, x, 0, 1, max(1, int(math.ceil(
            B * S * m.top_k / m.n_experts * m.capacity_factor))))
    else:
        batch_spec = spec_for((B, S, D), ("batch", "seq", "act_embed"))
        expert3 = P("model", None, None)
        has_gate = "wg" in p
        operands = [x, p["router"], p["wi"], p["wo"]]
        specs = [batch_spec, P(None, None), expert3, expert3]
        if has_gate:
            operands.append(p["wg"])
            specs.append(expert3)

        def shard_fn(xb, router, wi, wo, *rest):
            pl_ = {"router": router, "wi": wi, "wo": wo}
            if rest:
                pl_["wg"] = rest[0]
            ridx = jax.lax.axis_index("model")
            y = _moe_shard(cfg, pl_, xb, ridx, n_shards, capacity)
            return jax.lax.psum(y, "model")

        y = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=tuple(specs),
            out_specs=batch_spec,
            check_vma=False,
        )(*operands)

    if m.n_shared:
        y = y + apply_mlp(cfg, p["shared"], x)
    if m.dense_residual:
        y = y + apply_mlp(cfg, p["dense"], x)
    return constrain(y, ("batch", "seq", "act_embed"))


def _data_shards(mesh) -> int:
    if mesh is None:
        return 1
    n = 1
    for ax in ("pod", "data"):
        if ax in mesh.shape.keys():
            n *= mesh.shape[ax]
    return n
