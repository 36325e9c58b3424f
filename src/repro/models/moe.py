"""Mixture-of-Experts FFN: grouped capacity dispatch (GShard-style), pure
pjit + sharding constraints.

Dispatch is *per group*: tokens reshape to [G, T_g, D] where G equals the
mesh's batch-shard count, so every sort/cumsum/scatter in the dispatch is
local to a device under GSPMD — no distributed sorts.  The expert compute is
two batched einsums over a [G, E, C, D] dispatch buffer.

Sharding modes (config.moe_mode, per DESIGN.md section 6):
  "ep"  experts sharded over the model axis (llama4: 128 experts / 16 ranks);
        the dispatch buffer is (G x E)-sharded, combine is a scatter-add back
        to the token layout.
  "tp"  d_ff sharded over the model axis (mixtral: 8 experts < 16 ranks);
        experts replicated, the down-projection contraction inserts the usual
        TP all-reduce.

Tokens overflowing an expert's capacity (cap_factor x fair share) are dropped
(standard Switch/GShard behavior); the combine leaves their residual stream
untouched.  The router adds the Switch load-balancing auxiliary loss.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import ParamSpec


def moe_schema(cfg) -> dict:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    pd = cfg.param_dtype
    return {
        "router": ParamSpec((D, E), ("embed_r", "none"), dtype="float32",
                            fan_in_dims=(0,)),
        "w_gate": ParamSpec((E, D, F), ("experts", "embed", "expert_mlp"),
                            dtype=pd, fan_in_dims=(1,)),
        "w_in": ParamSpec((E, D, F), ("experts", "embed", "expert_mlp"),
                          dtype=pd, fan_in_dims=(1,)),
        "w_out": ParamSpec((E, F, D), ("experts", "expert_mlp", "embed"),
                           dtype=pd, fan_in_dims=(1,)),
    }


def capacity(cfg, tokens_per_group: int) -> int:
    fair = tokens_per_group * cfg.top_k / cfg.n_experts
    return max(4, int(fair * cfg.moe_cap_factor + 0.5))


def moe_ffn(p, x, cfg, n_groups: int, constrain=None):
    """x: [B, S, D] -> (out [B, S, D], aux_loss scalar).

    constrain(tensor, logical_axes) applies a sharding constraint (injected
    by models/sharding.py; identity in single-device tests).
    """
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    cst = constrain or (lambda t, a: t)

    T = B * S
    G = n_groups if T % max(n_groups, 1) == 0 else 1
    Tg = T // G
    C = capacity(cfg, Tg)
    xg = x.reshape(G, Tg, D)
    xg = cst(xg, ("moe_groups", "none", "none"))

    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)              # [G, Tg, E]
    gate, eidx = jax.lax.top_k(probs, k)                 # [G, Tg, k]
    if k > 1:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # Switch aux loss: E * sum_e f_e * P_e (f = token fraction, P = mean prob)
    sel1 = jax.nn.one_hot(eidx[..., 0], E, dtype=jnp.float32)
    aux = E * jnp.mean(jnp.mean(sel1, axis=1) * jnp.mean(probs, axis=1))

    # --- dispatch: rank of each (token, slot) within its expert, per group
    fe = eidx.reshape(G, Tg * k)                         # flat expert ids
    order = jnp.argsort(fe, axis=-1)                     # stable
    se = jnp.take_along_axis(fe, order, axis=-1)
    counts = jax.vmap(lambda v: jnp.bincount(v, length=E))(se)   # [G, E]
    offs = jnp.cumsum(counts, axis=-1) - counts          # group starts
    pos = jnp.arange(Tg * k)[None, :] - jnp.take_along_axis(offs, se, axis=-1)
    keep = pos < C
    slot = jnp.where(keep, se * C + pos, E * C)          # E*C = drop slot

    tok = order // k                                     # token of sorted row
    gsel = jnp.take_along_axis(gate.reshape(G, Tg * k), order, axis=-1)

    # slot -> token / gate tables (scatter; dropped rows land on slot E*C)
    def scatter_tables(slot_g, tok_g, gsel_g):
        t = jnp.full((E * C + 1,), Tg, jnp.int32).at[slot_g].set(
            tok_g.astype(jnp.int32), mode="drop")
        g = jnp.zeros((E * C + 1,), jnp.float32).at[slot_g].set(
            gsel_g, mode="drop")
        return t[:-1], g[:-1]

    slot_tok, slot_gate = jax.vmap(scatter_tables)(slot, tok, gsel)
    slot_tok = slot_tok.reshape(G, E, C)
    slot_gate = slot_gate.reshape(G, E, C)

    xpad = jnp.concatenate([xg, jnp.zeros((G, 1, D), xg.dtype)], axis=1)
    xe = jnp.take_along_axis(
        xpad[:, :, None, :],
        slot_tok.reshape(G, E * C)[:, :, None, None], axis=1
    ).reshape(G, E, C, D)
    xe = cst(xe, ("moe_groups", "experts", "none", "none"))

    # --- expert compute (batched einsum; MXU-shaped) ---
    h = jnp.einsum("gecd,edf->gecf", xe, p["w_in"],
                   preferred_element_type=jnp.float32)
    hg = jnp.einsum("gecd,edf->gecf", xe, p["w_gate"],
                    preferred_element_type=jnp.float32)
    h = (jax.nn.silu(hg) * h).astype(xe.dtype)
    h = cst(h, ("moe_groups", "experts", "none", "expert_mlp"))
    ye = jnp.einsum("gecf,efd->gecd", h, p["w_out"],
                    preferred_element_type=jnp.float32)
    ye = ye * slot_gate[..., None]
    ye = cst(ye.astype(x.dtype), ("moe_groups", "experts", "none", "none"))

    # --- combine: scatter-add back to token layout ---
    def combine(slot_tok_g, ye_g):
        out = jnp.zeros((Tg + 1, D), ye_g.dtype)
        return out.at[slot_tok_g.reshape(-1)].add(
            ye_g.reshape(-1, D), mode="drop")[:-1]

    out = jax.vmap(combine)(slot_tok, ye)
    out = cst(out, ("moe_groups", "none", "none"))
    return out.reshape(B, S, D), aux * cfg.aux_loss_coef


# ----------------------------------------------------- token-routed EP path
def moe_ffn_ep(p, x, cfg, mesh, constrain=None):
    """Explicit expert parallelism under shard_map (Perf iteration 5).

    Experts shard over "data" (weights fully resident: E over data x d_ff
    over model), tokens move: each device dispatches its tokens to their
    experts' owner ranks with one ``all_to_all`` over "data", computes the
    resident experts, and routes results back.  Traffic scales with tokens
    (vs. per-layer weight gathers that scale with parameters — the llama4
    profile's dominant term, EXPERIMENTS.md §Perf).

    The "pod" axis stays pure data parallelism (experts replicated across
    pods), and "model" ranks replicate the dispatch and psum the d_ff-sharded
    expert output — the same TP contract as the dense MLP.
    """
    import math as _math

    from jax.sharding import PartitionSpec as P


    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    ax = mesh.axis_names
    ba = tuple(a for a in ("pod", "data") if a in ax)
    n_data = mesh.shape.get("data", 1)
    E_loc = E // n_data
    B_loc = max(B // _math.prod(mesh.shape[a] for a in ba), 1)
    T_loc = B_loc * S
    C = max(4, int(T_loc * k / E * cfg.moe_cap_factor + 0.5))

    def local(x_loc, router, w_gate, w_in, w_out):
        Bl = x_loc.shape[0]
        xt = x_loc.reshape(Bl * S, D)
        T = xt.shape[0]

        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, eidx = jax.lax.top_k(probs, k)
        if k > 1:
            gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
        sel1 = jax.nn.one_hot(eidx[..., 0], E, dtype=jnp.float32)
        aux = E * jnp.mean(jnp.mean(sel1, axis=0) * jnp.mean(probs, axis=0))
        aux = jax.lax.pmean(aux, ba) if ba else aux

        # per-expert capacity dispatch (local tokens -> E global slots)
        fe = eidx.reshape(T * k)
        order = jnp.argsort(fe)
        se = fe[order]
        counts = jnp.bincount(se, length=E)
        offs = jnp.cumsum(counts) - counts
        pos = jnp.arange(T * k) - offs[se]
        keep = pos < C
        slot = jnp.where(keep, se * C + pos, E * C)
        tok = order // k
        gsel = gate.reshape(T * k)[order]

        slot_tok = jnp.full((E * C + 1,), T, jnp.int32).at[slot].set(
            tok.astype(jnp.int32), mode="drop")[:-1]
        slot_gate = jnp.zeros((E * C + 1,), jnp.float32).at[slot].set(
            gsel, mode="drop")[:-1]

        xpad = jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)], axis=0)
        xe = xpad[slot_tok].reshape(E, C, D)

        # ---- route tokens to expert owners over "data" ----
        if n_data > 1:
            xe = xe.reshape(n_data, E_loc * C, D)
            xe = jax.lax.all_to_all(xe, "data", split_axis=0, concat_axis=0,
                                    tiled=True)          # [n_data, Eloc*C, D]
            xe = xe.reshape(n_data, E_loc, C, D).transpose(1, 0, 2, 3) \
                .reshape(E_loc, n_data * C, D)
        else:
            xe = xe.reshape(E_loc, C, D)

        h = jnp.einsum("ecd,edf->ecf", xe, w_in,
                       preferred_element_type=jnp.float32)
        hg = jnp.einsum("ecd,edf->ecf", xe, w_gate,
                        preferred_element_type=jnp.float32)
        h = (jax.nn.silu(hg) * h).astype(xe.dtype)
        ye = jnp.einsum("ecf,efd->ecd", h, w_out,
                        preferred_element_type=jnp.float32).astype(x.dtype)
        if "model" in ax and mesh.shape.get("model", 1) > 1:
            ye = jax.lax.psum(ye, "model")   # d_ff is model-sharded

        # ---- route results back ----
        if n_data > 1:
            ye = ye.reshape(E_loc, n_data, C, D).transpose(1, 0, 2, 3) \
                .reshape(n_data, E_loc * C, D)
            ye = jax.lax.all_to_all(ye, "data", split_axis=0, concat_axis=0,
                                    tiled=True)
            ye = ye.reshape(E * C, D)
        else:
            ye = ye.reshape(E * C, D)

        ye = ye * slot_gate[:, None].astype(ye.dtype)
        out = jnp.zeros((T + 1, D), ye.dtype).at[slot_tok].add(
            ye, mode="drop")[:-1]
        return out.reshape(Bl, S, D), aux

    bspec = P(ba if len(ba) > 1 else (ba[0] if ba else None), None, None)
    mspec = "model" if "model" in ax else None
    out, aux = jax.shard_map(
        local, mesh=mesh,
        in_specs=(bspec, P(), P("data" if "data" in ax else None, None,
                              mspec),
                  P("data" if "data" in ax else None, None, mspec),
                  P("data" if "data" in ax else None, mspec, None)),
        out_specs=(bspec, P()), check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_in"], p["w_out"])
    return out, aux * cfg.aux_loss_coef
