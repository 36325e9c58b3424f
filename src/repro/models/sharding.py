"""Logical-axis -> PartitionSpec mapping (the MaxText-style indirection).

Mesh axes: ("data", "model") single-pod, ("pod", "data", "model") multi-pod.
  - batch/token dims shard over ("pod", "data") — pure DP across pods;
  - weights FSDP-shard their d_model dim over "data" only (no cross-pod
    weight all-gathers: the pod axis carries one gradient reduce per step);
  - optimizer state additionally shards over "pod" (ZeRO-1): the update's
    reduce-scatter + the param all-gather together cost one all-reduce;
  - TP dims (heads / d_ff / vocab / experts-or-expert_mlp / lru) over "model".

Every rule is divisibility-guarded: a dim that does not divide its mesh axes
falls back to replication (e.g. n_kv=8 over the 16-way model axis — the
attention layer instead replicates KV per head-group, see attention.py).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def auto_mesh(mesh):
    """``mesh`` with plain auto axes (``jax.make_mesh`` makes explicit
    ones, which put shardings into array types and make the model's
    sharded contractions ambiguous)."""
    if mesh is None:
        return None
    return Mesh(mesh.devices, mesh.axis_names)


def batch_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def tp_width(mesh) -> int:
    return mesh.shape.get("model", 1)


def n_batch_shards(mesh) -> int:
    return math.prod(mesh.shape[a] for a in batch_axes(mesh))


def logical_map(cfg, mesh, *, opt: bool = False) -> dict:
    ba = batch_axes(mesh)
    fsdp = ba if opt else (
        ("data",) if ("data" in mesh.axis_names and cfg.fsdp_params) else ())
    ep = cfg.moe_mode == "ep"
    # Token-routed EP (Perf iteration 4): experts shard over "data" and
    # their d_ff over "model", so expert weights are fully resident
    # (2D-sharded, no per-layer FSDP gathers — those dominated the llama4
    # profile at ~2.3TiB/step); the *tokens* move instead: the dispatch
    # buffer's expert dim is data-sharded, so GSPMD lowers dispatch/combine
    # to all-to-all-class collectives whose bytes scale with tokens, not
    # parameters.  Dispatch groups then shard over "pod" only.
    return {
        "vocab": ("model",),
        "embed": fsdp,
        # embedding/head tables: vocab over model is plenty (the TP slice is
        # ~100MB); FSDP-sharding their d_model dim forced a per-step
        # resharding gather (SPMD "involuntary full rematerialization").
        # The optimizer state still ZeRO-shards them.
        "embed_r": ba if opt else (),
        "heads": ("model",),
        "kv": ("model",),
        "kv_eff": ("model",),
        "head": (),
        "mlp": ("model",),
        "lru": ("model",),
        "experts": ("data",) if ep else (),
        "expert_mlp": ("model",),
        "act_batch": ba,
        "moe_groups": (("pod",) if "pod" in mesh.axis_names else ()) if ep
        else ba,
        "stack": (),
        "none": (),
        "pos": (),
    }


def pspec(axes: tuple, shape: tuple, cfg, mesh, *, opt: bool = False) -> P:
    lmap = logical_map(cfg, mesh, opt=opt)
    parts = []
    used = set()
    for dim, name in zip(shape, axes):
        ax = tuple(a for a in lmap.get(name, ()) if a not in used)
        size = math.prod(mesh.shape[a] for a in ax) if ax else 1
        if ax and size > 1 and dim % size == 0:
            parts.append(ax if len(ax) > 1 else ax[0])
            used.update(ax)          # a mesh axis shards at most one dim
        else:
            parts.append(None)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def shardings_for(axes_tree, abstract_tree, cfg, mesh, *, opt: bool = False):
    """NamedSharding pytree for (axes, ShapeDtypeStruct) pytrees."""
    return jax.tree.map(
        lambda a, s: NamedSharding(
            mesh, pspec(a, s.shape, cfg, mesh, opt=opt)),
        axes_tree, abstract_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, str) for e in x))


def with_shardings(axes_tree, abstract_tree, cfg, mesh, *, opt: bool = False):
    """Attach shardings to ShapeDtypeStructs (dry-run lowering inputs)."""
    sh = shardings_for(axes_tree, abstract_tree, cfg, mesh, opt=opt)
    return jax.tree.map(
        lambda s, ns: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=ns),
        abstract_tree, sh)


def make_constrain(cfg, mesh):
    """constrain(tensor, logical_axes) -> tensor with sharding constraint."""
    if mesh is None or math.prod(mesh.devices.shape) == 1:
        return lambda t, a: t

    def constrain(t, axes):
        spec = pspec(axes, t.shape, cfg, mesh)
        return jax.lax.with_sharding_constraint(t, NamedSharding(mesh, spec))

    return constrain
