"""Logical-axis sharding rules (MaxText-style) for the production mesh.

Models annotate tensors with *logical* axis names; this module maps them to
mesh axes through a rule table with **divisibility fallbacks**: each logical
name carries a preference list of mesh axis specs, and the first candidate
whose total size divides the tensor dimension wins.  This is how one rule
table serves ten architectures — e.g. `heads` shards over 'model' for
nemotron (48 % 16 == 0) but falls back to replicated for arctic (56 heads),
whose attention then runs data-parallel while its weights stay FSDP-sharded
on 'data' (DESIGN.md §5).

Baseline layout (paper-faithful starting point for §Perf):
  batch        -> ('pod', 'data')     pure DP across pods (DCN), DP within
  weight d_model -> 'data'            FSDP/ZeRO-3: params + opt state sharded
  heads/mlp/experts/vocab -> 'model'  tensor/expert parallelism
  activations d_model / seq -> None   replicated (SP is a §Perf hillclimb)
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisSpec = Union[None, str, Tuple[str, ...]]


def vary_like(tree, ref):
    """Mark every leaf of ``tree`` as varying over the same manual mesh
    axes as ``ref``.

    Inside ``jax.shard_map(check_vma=True)`` a value built from constants
    (a ``StreamSummary.zeros`` scan carry, a cond branch that returns
    zeros) is device-invariant, while one derived from a per-device key is
    varying; ``lax.scan`` and ``lax.cond`` require the two to match.
    Outside ``shard_map`` ``ref`` varies over nothing and ``tree`` is
    returned unchanged."""
    axes = tuple(jax.typeof(ref).vma)
    if not axes:
        return tree
    return jax.tree_util.tree_map(
        lambda x: jax.lax.pcast(x, axes, to="varying"), tree)


# Mesh axis name for the Monte-Carlo trial dimension.  Distinct from the
# model stack's ('pod', 'data', 'model') so a trial mesh can never collide
# with an active model mesh's rule table.
TRIAL_AXIS = "trials"


def trial_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """1-D mesh over all *global* devices for trial-axis sharding.

    ``jax.devices()`` enumerates every process's devices (process-major:
    global index = process_index * local_count + local_index) once
    ``repro.parallel.distributed.initialize()`` has joined a multi-host
    grid — a single-process run sees only its own, so the same mesh
    construction covers both.  The Monte-Carlo trial dimension is
    embarrassingly parallel, so the only collective the streaming engine
    needs is the cross-device summary merge (psum/pmax over
    ``TRIAL_AXIS``), which is integer-exact and therefore also the
    cross-host reduction (DESIGN.md §10)."""
    devices = jax.devices() if devices is None else list(devices)
    return Mesh(np.array(devices), (TRIAL_AXIS,))


def _axis_size(mesh: Mesh, spec: AxisSpec) -> int:
    if spec is None:
        return 1
    if isinstance(spec, str):
        return mesh.shape[spec]
    return int(np.prod([mesh.shape[a] for a in spec]))


@dataclass(frozen=True)
class Rules:
    """logical name -> preference list of mesh axis specs."""
    table: Dict[str, Tuple[AxisSpec, ...]]

    def candidates(self, name: Optional[str]) -> Tuple[AxisSpec, ...]:
        if name is None:
            return (None,)
        if name not in self.table:
            raise KeyError(f"unknown logical axis {name!r}")
        return self.table[name]


def default_rules(sequence_parallel: bool = False,
                  expert_all_to_all: bool = False) -> Rules:
    t: Dict[str, Tuple[AxisSpec, ...]] = {
        # activations
        "batch": (("pod", "data"), ("data",), None),
        "seq": (("model",), None) if sequence_parallel else (None,),
        # context-parallel fallback for attention: when the head count does
        # not divide the 'model' axis (e.g. musicgen's 24 heads on a 16-way
        # axis) the q/scores/output seq dim shards on 'model' instead, so
        # attention compute is never replicated across the model axis.
        "seq_sp": (("model",), None),
        "act_embed": (None,),
        "heads": (("model",), None),
        "kv_heads": (("model",), None),
        "head_dim": (None,),
        "mlp_act": (("model",), None),
        "vocab_act": (("model",), None),
        # KV-cache sequence dim: prefer the widest free sharding.  'data' is
        # taken by batch for decode_32k (cache then shards on 'model'); for
        # long_500k (batch=1) the cache spreads over all 256 chips.
        "cache_seq": (("data", "model"), ("model",), ("data",), None),
        # weights
        "embed": (("data",), None),          # FSDP dim (d_model of weights)
        # the FSDP dim *after* the per-layer gather (unsharded); used by
        # fsdp_use() to force the all-gather to happen on the bf16 cast of a
        # weight rather than its f32 master copy (halves AG link bytes).
        "embed_full": (None,),
        # embed-table d_model: sharded on 'model' so the token gather needs
        # no collective (indices are batch-sharded, operand dim-sharded).
        "embed_td": (("model",), None),
        "mlp": (("model",), None),
        "w_heads": (("model",), None),
        "w_kv_heads": (("model",), None),
        "w_vocab": (("model",), None),
        "experts": (("model",),),
        "kv_lora": (None,),
        "ssm_inner": (("model",), None),
        "ssm_state": (None,),
        "conv": (None,),
        "norm": (None,),
    }
    return Rules(t)


# ---------------------------------------------------------------------------
# Active context.
# ---------------------------------------------------------------------------

class _Ctx(threading.local):
    mesh: Optional[Mesh] = None
    rules: Optional[Rules] = None


_CTX = _Ctx()


@contextlib.contextmanager
def sharding_ctx(mesh: Optional[Mesh], rules: Optional[Rules] = None):
    """Activate (mesh, rules) for logical constraints; None mesh = no-op."""
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh = mesh
    _CTX.rules = rules or (default_rules() if mesh is not None else None)
    try:
        if mesh is not None:
            with mesh:
                yield
        else:
            yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def active_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def spec_for(shape: Sequence[int], axes: Sequence[Optional[str]],
             mesh: Optional[Mesh] = None, rules: Optional[Rules] = None) -> P:
    """Resolve logical axes to a PartitionSpec with divisibility fallback."""
    mesh = mesh or _CTX.mesh
    rules = rules or _CTX.rules
    if mesh is None or rules is None:
        return P()
    assert len(shape) == len(axes), (shape, axes)
    mesh_axes = set(mesh.shape.keys())
    used: set = set()
    out = []
    for dim, name in zip(shape, axes):
        chosen: AxisSpec = None
        for cand in rules.candidates(name):
            flat = (cand,) if isinstance(cand, str) else (cand or ())
            if any(a not in mesh_axes for a in flat):
                continue                      # e.g. 'pod' on a single-pod mesh
            if any(a in used for a in flat):
                continue
            if cand is not None and dim % _axis_size(mesh, cand) != 0:
                continue
            chosen = cand
            break
        flat = (chosen,) if isinstance(chosen, str) else (chosen or ())
        used.update(flat)
        out.append(chosen)
    return P(*out)


def constrain(x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
    """Apply a logical sharding constraint (identity without a mesh)."""
    mesh = _CTX.mesh
    if mesh is None:
        return x
    spec = spec_for(x.shape, axes, mesh, _CTX.rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def fsdp_use(w: jax.Array, axes: Sequence[Optional[str]], dtype) -> jax.Array:
    """Cast a weight to its compute dtype and release the FSDP ('embed')
    sharding dim — in that order.

    The per-layer FSDP all-gather then moves the bf16 CAST of the weight
    instead of the f32 master copy: half the link bytes for every weight
    gather, on any backend (EXPERIMENTS.md §Perf, deepseek_7b iteration 2).
    Other dims ('w_heads', 'mlp', ... on 'model') keep their sharding.
    """
    w = w.astype(dtype)
    if _CTX.mesh is None:
        return w
    ax2 = tuple("embed_full" if a == "embed" else a for a in axes)
    return constrain(w, ax2)


def named_sharding(shape: Sequence[int], axes: Sequence[Optional[str]],
                   mesh: Mesh, rules: Optional[Rules] = None) -> NamedSharding:
    return NamedSharding(mesh, spec_for(shape, axes, mesh,
                                        rules or default_rules()))


def tree_shardings(tree, tree_axes, mesh: Mesh,
                   rules: Optional[Rules] = None):
    """Map (pytree of arrays/ShapeDtypeStructs, matching pytree of
    logical-axes tuples) to NamedShardings — used for jit in_shardings of
    params and optimizer state.  The first tree's leaves must be array-like
    (have ``.shape``); the axes tree mirrors its structure with tuple
    leaves."""
    rules = rules or default_rules()
    return jax.tree.map(
        lambda arr, ax: named_sharding(arr.shape, ax, mesh, rules),
        tree, tree_axes)
