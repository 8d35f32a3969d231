"""Distributed train step: loss -> grads -> AdamW, with microbatch gradient
accumulation, remat, and optional DROP gradient compression across pods.

The step is a pure function jitted with explicit in/out shardings by the
launcher (launch/train.py, launch/dryrun.py). Parallelism falls out of the
sharding specs: XLA inserts FSDP all-gathers around layer use, reduce-scatters
for grads over "data", all-reduce over "pod" — the latter optionally replaced
by the compressed shard_map psum below.
"""

from __future__ import annotations

import functools
from typing import Any

import jax

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.model import loss_fn
from repro.sharding.specs import ShardCtx
from repro.train.optimizer import OptimizerConfig, adamw_update


def make_train_step(
    cfg: ModelConfig,
    opt_cfg: OptimizerConfig,
    ctx: ShardCtx,
    remat: str = "full",
    microbatches: int = 1,
    compress_bases: dict | None = None,
):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state, metrics)."""

    def grads_of(params, batch):
        def loss_only(p, b):
            return loss_fn(p, b, cfg, ctx, remat=remat)

        if microbatches <= 1:
            (loss, metrics), grads = jax.value_and_grad(loss_only, has_aux=True)(
                params, batch
            )
            return loss, metrics, grads

        # gradient accumulation: scan over microbatch splits, fp32 accumulator
        def split(x):
            b = x.shape[0]
            assert b % microbatches == 0, (b, microbatches)
            return x.reshape(microbatches, b // microbatches, *x.shape[1:])

        micro = jax.tree_util.tree_map(split, batch)
        zero = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )

        def body(acc, mb):
            (loss, metrics), g = jax.value_and_grad(loss_only, has_aux=True)(
                params, mb
            )
            acc = jax.tree_util.tree_map(
                lambda a, x: a + x.astype(jnp.float32), acc, g
            )
            return acc, loss

        grads, losses = jax.lax.scan(body, zero, micro)
        grads = jax.tree_util.tree_map(lambda g: g / microbatches, grads)
        loss = jnp.mean(losses)
        return loss, {"loss": loss}, grads

    def train_step(params, opt_state, batch):
        loss, metrics, grads = grads_of(params, batch)
        params, opt_state, opt_metrics = adamw_update(
            params, grads, opt_state, opt_cfg
        )
        metrics = {**metrics, **opt_metrics, "loss": loss}
        return params, opt_state, metrics

    if compress_bases is None or ctx.mesh is None or "pod" not in (
        ctx.mesh.axis_names
    ):
        return train_step

    # ------------------------------------------------------------------
    # DROP-compressed cross-pod gradient reduction.
    #
    # The whole grad computation runs inside a shard_map that binds ONLY the
    # "pod" axis manually ("data"/"model" stay auto-sharded), so gradients
    # reaching this code are per-pod partial means. The pod all-reduce then
    # happens in the DROP-discovered low-rank basis: pmean(G V) V^T, cutting
    # inter-pod bytes to r/c of the dense reduce. Residuals (error feedback)
    # are returned per-pod for the trainer to fold into the next step.
    # NOTE: not supported for MoE families (nested shard_map in the MoE block
    # would re-bind "pod"); launchers enforce this.
    # ------------------------------------------------------------------
    from repro.train import grad_compress as gc

    mesh = ctx.mesh
    n_pods = mesh.devices.shape[list(mesh.axis_names).index("pod")]
    # XLA-CPU platform bug (verified by bisection; EXPERIMENTS.md §Perf A8):
    # with_sharding_constraint on auto axes INSIDE a partial-manual shard_map
    # aborts the SPMD partitioner. Inner model constraints are therefore
    # disabled here (mesh=None ctx); data/model sharding still propagates from
    # the jit-level in_shardings. On TPU builds the constraints can stay on.
    inner_ctx = ShardCtx(mesh=None)
    inner_ctx.onehot_loss = ctx.onehot_loss

    def per_pod(params_, batch_, residual_):
        residual_ = jax.tree_util.tree_map(lambda e: e[0], residual_)

        def loss_only(p, b):
            return loss_fn(p, b, cfg, inner_ctx, remat=remat)

        (loss, metrics), grads = jax.value_and_grad(loss_only, has_aux=True)(
            params_, batch_
        )
        # fold in last step's compression residual (error feedback)
        grads = jax.tree_util.tree_map(
            lambda g, e: g + e.astype(g.dtype), grads, residual_
        )

        def pod_mean(x):  # NB: lax.pmean trips an XLA-CPU AllReducePromotion
            return jax.lax.psum(x, "pod") / n_pods  # bug; psum+div is safe

        def reduce_leaf(path, g):
            v = compress_bases.get(gc._path_key(path))
            if v is None:
                return pod_mean(g), jnp.zeros_like(g)
            gm = g.reshape(-1, g.shape[-1]).astype(jnp.float32)
            low = gm @ v
            approx_local = (low @ v.T).reshape(g.shape).astype(g.dtype)
            reduced = (pod_mean(low) @ v.T).reshape(g.shape)
            return reduced.astype(g.dtype), g - approx_local

        paths = jax.tree_util.tree_leaves_with_path(grads)
        treedef = jax.tree_util.tree_structure(grads)
        pairs = [reduce_leaf(p, g) for p, g in paths]
        grads_red = jax.tree_util.tree_unflatten(treedef, [a for a, _ in pairs])
        new_resid = jax.tree_util.tree_unflatten(
            treedef, [b[None] for _, b in pairs]
        )
        loss = jax.lax.psum(loss, "pod") / n_pods
        metrics = jax.tree_util.tree_map(
            lambda m: jax.lax.psum(m, "pod") / n_pods, metrics
        )
        return loss, metrics, grads_red, new_resid

    def train_step_compressed(params, opt_state, batch, residual):
        loss, metrics, grads, residual = jax.shard_map(
            per_pod,
            mesh=mesh,
            in_specs=(P(), jax.tree_util.tree_map(lambda _: P("pod"), batch), P("pod")),
            out_specs=(P(), P(), P(), P("pod")),
            axis_names={"pod"},
            check_vma=False,
        )(params, batch, residual)
        params, opt_state, opt_metrics = adamw_update(
            params, grads, opt_state, opt_cfg
        )
        return params, opt_state, {**metrics, **opt_metrics, "loss": loss}, residual

    return train_step_compressed


def init_compression_residual(params: Any, n_pods: int) -> Any:
    """Per-pod error-feedback buffers: leading pod dim, sharded over "pod"."""
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros((n_pods, *p.shape), jnp.float32), params
    )
