"""Step factories: train / prefill / decode, with shardings — the single
entry point used by the trainer, the server, the dry-run, and the tests.

``build_cell(cfg, shape_name, mesh)`` returns (fn, abstract_args) for one
(architecture x input-shape) grid cell: ``jax.jit(fn).lower(*abstract_args)``
is exactly the multi-pod dry-run. The abstract args carry NamedShardings, so
in_shardings are inferred; out_shardings are constrained where it matters
(params/opt state keep their layout across steps).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES, ModelConfig, ShapeSpec
from repro.models import model as model_mod
from repro.models import sharding as shd
from repro.models.attention import ModelCtx
from repro.optim import AdamW


# -------------------------------------------------------------------- loss
def xent_loss(logits, labels, mask, constrain):
    """Mean next-token cross-entropy over masked positions.

    logits stay vocab-sharded: max/logsumexp reduce over the sharded axis
    (one tiny all-reduce), take_along_axis gathers the label logit — no
    [B, S, V] replication.
    """
    lf = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(lf.max(axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1)) + m[..., 0]
    lab = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    per_tok = (lse - lab) * mask
    return per_tok.sum() / jnp.maximum(mask.sum(), 1.0)


def loss_fn(params, cfg, ctx, batch, constrain):
    tokens = batch["tokens"]                      # [B, S+1]
    inp, labels = tokens[:, :-1], tokens[:, 1:]
    logits, _, aux, n_prefix = model_mod.forward(
        params, cfg, ctx, inp, patches=batch.get("patches"),
        frames=batch.get("frames"), constrain=constrain)
    if n_prefix:
        logits = logits[:, n_prefix:]             # loss only on text tokens
    mask = jnp.ones(labels.shape, jnp.float32)
    loss = xent_loss(logits, labels, mask, constrain) + aux
    return loss


# ------------------------------------------------------------------- train
def build_train_step(cfg: ModelConfig, mesh, optimizer: AdamW):
    mesh = shd.auto_mesh(mesh)
    constrain = shd.make_constrain(cfg, mesh)
    ctx = ModelCtx(tp=shd.tp_width(mesh), n_groups=shd.n_batch_shards(mesh),
                   mode="train", mesh=mesh)
    nm = cfg.n_micro
    gdt = jnp.dtype(cfg.grad_dtype)
    p_axes = model_mod.param_axes(cfg)

    def grad_shard(tree):
        """Pin the grad accumulator to the ZeRO (opt-state) layout: the
        per-microbatch cross-pod gradient reduction then lowers to a
        reduce-scatter into the shard instead of a full all-reduce into a
        replicated buffer (Perf iteration 6)."""
        if mesh is None:
            return tree
        from jax.sharding import NamedSharding
        return jax.tree.map(
            lambda t, a: jax.lax.with_sharding_constraint(
                t, NamedSharding(mesh, shd.pspec(a, t.shape, cfg, mesh,
                                                 opt=True))),
            tree, p_axes,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(e, str) for e in x))

    def train_step(params, opt_state, batch, step):
        if nm > 1:
            mbatch = jax.tree.map(
                lambda x: x.reshape((nm, x.shape[0] // nm) + x.shape[1:]),
                batch)

            def micro(acc, mb):
                mb = jax.tree.map(
                    lambda x: constrain(x, ("none", "act_batch") + ("none",)
                                        * (x.ndim - 2)), mb)
                l, g = jax.value_and_grad(loss_fn)(params, cfg, ctx, mb,
                                                   constrain)
                acc_g, acc_l = acc
                acc_g = grad_shard(jax.tree.map(
                    lambda a, b: a + b.astype(gdt), acc_g, g))
                return (acc_g, acc_l + l), None

            zeros = grad_shard(jax.tree.map(
                lambda p: jnp.zeros(p.shape, gdt), params))
            (grads, loss), _ = jax.lax.scan(
                micro, (zeros, jnp.float32(0.0)), mbatch)
            grads = jax.tree.map(lambda g: g / nm, grads)
            loss = loss / nm
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, cfg, ctx,
                                                      batch, constrain)
        params, opt_state, om = optimizer.update(grads, opt_state, params,
                                                 step)
        return params, opt_state, {"loss": loss, **om}

    return train_step


# ----------------------------------------------------------------- serving
def build_prefill_step(cfg: ModelConfig, mesh, s_cache: int):
    mesh = shd.auto_mesh(mesh)
    constrain = shd.make_constrain(cfg, mesh)
    tp = shd.tp_width(mesh)
    ctx = ModelCtx(tp=tp, n_groups=shd.n_batch_shards(mesh), mode="prefill",
                   mesh=mesh)

    def prefill_step(params, batch):
        tokens = batch["tokens"]
        cache = model_mod.init_cache(cfg, tokens.shape[0], s_cache, tp)
        logits, cache, _, _ = model_mod.forward(
            params, cfg, ctx, tokens, patches=batch.get("patches"),
            frames=batch.get("frames"), cache=cache, constrain=constrain)
        return cache, logits[:, -1]

    return prefill_step


def build_decode_step(cfg: ModelConfig, mesh):
    mesh = shd.auto_mesh(mesh)
    constrain = shd.make_constrain(cfg, mesh)
    tp = shd.tp_width(mesh)
    ng = shd.n_batch_shards(mesh)

    def decode_step(params, cache, tokens, pos):
        ctx = ModelCtx(tp=tp, n_groups=ng, mode="decode", pos=pos,
                       mesh=mesh)
        frames = None
        logits, cache, _, _ = model_mod.forward(
            params, cfg, ctx, tokens, frames=frames, cache=cache,
            constrain=constrain)
        return logits[:, -1], cache

    return decode_step


# ----------------------------------------------------------- abstract args
def abstract_batch(cfg: ModelConfig, shape: ShapeSpec, *, train: bool):
    B, S = shape.global_batch, shape.seq_len
    D = cfg.d_model
    extra = 1 if train else 0
    batch = {}
    s_text = S
    if cfg.n_patches:
        s_text = S - cfg.n_patches
        batch["patches"] = jax.ShapeDtypeStruct((B, cfg.n_patches, D),
                                                jnp.bfloat16)
    if cfg.n_frames:
        batch["frames"] = jax.ShapeDtypeStruct((B, cfg.n_frames, D),
                                               jnp.bfloat16)
    batch["tokens"] = jax.ShapeDtypeStruct((B, s_text + extra), jnp.int32)
    return batch


def batch_axes_tree(batch):
    return {k: ("act_batch",) + ("none",) * (v.ndim - 1)
            for k, v in batch.items()}


def abstract_cache(cfg: ModelConfig, batch: int, s_cache: int, tp: int):
    return jax.eval_shape(
        partial(model_mod.init_cache, cfg, batch, s_cache, tp))


def build_cell(cfg: ModelConfig, shape_name: str, mesh,
               optimizer: AdamW = None):
    """(fn, abstract_args) for one dry-run grid cell."""
    shape = SHAPES[shape_name]
    tp = shd.tp_width(mesh)
    p_abs = model_mod.abstract_params(cfg)
    p_axes = model_mod.param_axes(cfg)
    p_in = shd.with_shardings(p_axes, p_abs, cfg, mesh)

    if shape.kind == "train":
        opt = optimizer or AdamW.from_config(cfg)
        o_abs = jax.eval_shape(opt.init, p_abs)
        o_in = shd.with_shardings(opt.state_axes(p_axes), o_abs, cfg, mesh,
                                  opt=True)
        b_abs = abstract_batch(cfg, shape, train=True)
        b_in = shd.with_shardings(batch_axes_tree(b_abs), b_abs, cfg, mesh)
        step0 = jax.ShapeDtypeStruct((), jnp.int32)
        return build_train_step(cfg, mesh, opt), (p_in, o_in, b_in, step0)

    if shape.kind == "prefill":
        b_abs = abstract_batch(cfg, shape, train=False)
        b_in = shd.with_shardings(batch_axes_tree(b_abs), b_abs, cfg, mesh)
        return build_prefill_step(cfg, mesh, shape.seq_len), (p_in, b_in)

    # decode: one new token against an S-deep cache
    B = shape.global_batch
    s_c = min(shape.seq_len, cfg.window) if cfg.window else shape.seq_len
    c_abs = abstract_cache(cfg, B, s_c, tp)
    c_axes = model_mod.cache_axes(cfg, tp)
    c_in = shd.with_shardings(c_axes, c_abs, cfg, mesh)
    t_in = shd.with_shardings(
        {"t": ("act_batch", "none")},
        {"t": jax.ShapeDtypeStruct((B, 1), jnp.int32)}, cfg, mesh)["t"]
    pos0 = jax.ShapeDtypeStruct((), jnp.int32)
    return build_decode_step(cfg, mesh), (p_in, c_in, t_in, pos0)
