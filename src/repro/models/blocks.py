"""Decoder blocks: dense / MoE / Mamba2 / cross-attention, sharding-aware.

Each block takes the sequence-parallel residual stream (B, S, d) sharded
(batch->dp, seq->"model"), applies Megatron-SP style gather/scatter around the
TP sublayers via ShardCtx constraints, and returns the residual in the same
layout. With ctx.mesh=None all constraints no-op (smoke tests).
"""

from __future__ import annotations

import functools

import jax

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.attention import attention, init_attention
from repro.models.mlp import gelu_mlp, init_gelu_mlp, init_swiglu, swiglu
from repro.models.moe import init_moe, moe_ffn
from repro.models.ssm import init_mamba2, mamba2_forward
from repro.models.layers import rms_norm
from repro.sharding.specs import ShardCtx


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_dense_block(key, cfg, dtype):
    ka, km = jax.random.split(key)
    return {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "attn": init_attention(
            ka, cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            dtype, qk_norm=cfg.qk_norm,
        ),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "mlp": init_swiglu(km, cfg.d_model, cfg.d_ff, dtype),
    }


def init_moe_block(key, cfg, dtype):
    ka, km = jax.random.split(key)
    return {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "attn": init_attention(
            ka, cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            dtype, qk_norm=cfg.qk_norm,
        ),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "moe": init_moe(km, cfg.d_model, cfg.moe_d_ff, cfg.num_experts, dtype),
    }


def init_mamba_block(key, cfg, dtype):
    return {
        "ln": jnp.ones((cfg.d_model,), dtype),
        "mamba": init_mamba2(key, cfg, dtype),
    }


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _attn_sublayer(x, params, cfg, ctx: ShardCtx, pos_q, pos_k, x_kv=None,
                   causal=True, return_kv=False):
    """Pre-norm attention with sp_q sharding. x is the seq-sharded residual."""
    h = rms_norm(x, params["ln1"], cfg.norm_eps)
    if ctx.tuned:
        h = ctx.residual(h)  # pin cotangent layout at the norm boundary
    h_kv = h if x_kv is None else x_kv
    out = attention(
        h,
        h_kv,
        params["attn"],
        num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim,
        pos_q=pos_q,
        pos_k=pos_k,
        causal=causal,
        window=cfg.sliding_window,
        rope_theta=cfg.rope_theta if causal else 0.0,  # no rope on cross-attn
        mrope_sections=cfg.mrope_sections,
        kv_chunk=cfg.kv_chunk,
        kv_constrain=ctx.kv_gathered if ctx.mesh is not None else None,
        return_kv=return_kv,
    )
    if return_kv:
        y, kv = out
        if ctx.tuned:
            y = ctx.residual(y)  # force reduce-scatter of the wo output
        return ctx.residual(x + y), kv
    if ctx.tuned:
        out = ctx.residual(out)
    return ctx.residual(x + out)


def _mlp_sublayer(x, params, cfg, ctx: ShardCtx, kind="swiglu"):
    h = rms_norm(x, params["ln2"], cfg.norm_eps)
    if ctx.tuned:
        h = ctx.residual(h)
    h = ctx.gathered(h)  # all-gather seq; TP (f-sharded) matmuls follow
    if kind == "swiglu":
        if ctx.tuned:
            # pin the TP intermediate so w_down's input cotangent stays
            # f-sharded (avoids a full (B,S,f) gather in backward)
            g = ctx.ffn_hidden(h @ params["mlp"]["w_gate"])
            u = ctx.ffn_hidden(h @ params["mlp"]["w_up"])
            y = (jax.nn.silu(g) * u) @ params["mlp"]["w_down"]
        else:
            y = swiglu(h, params["mlp"])
    else:
        y = gelu_mlp(h, params["mlp"])
    if ctx.tuned:
        y = ctx.residual(y)  # reduce-scatter the partial w_down output
    return ctx.residual(x + y)


def _moe_sublayer(x, params, cfg, ctx: ShardCtx):
    """MoE FFN: tokens local to each dp shard (shard_map), expert width TP."""
    h = rms_norm(x, params["ln2"], cfg.norm_eps)
    h = ctx.gathered(h)
    b, s, d = h.shape
    kwargs = dict(
        num_experts=cfg.num_experts,
        experts_per_token=cfg.experts_per_token,
        capacity_factor=cfg.capacity_factor,
        token_chunk=16384 if b * s > 16384 else None,
    )

    if ctx.mesh is None:
        y, aux = moe_ffn(h.reshape(b * s, d), params["moe"], **kwargs)
        return ctx.residual(x + y.reshape(b, s, d)), aux

    mesh = ctx.mesh
    dp = ctx.dp
    moe_specs = {
        "router": P(None, None),
        "w_gate": P(None, None, "model"),
        "w_up": P(None, None, "model"),
        "w_down": P(None, "model", None),
    }

    def local_fn(hl, p):
        bl, sl, _ = hl.shape
        y, aux = moe_ffn(hl.reshape(bl * sl, d), p, **kwargs)
        y = jax.lax.psum(y, "model")  # combine TP partial w_down outputs
        if dp:  # (psum/size, not pmean: XLA-CPU AllReducePromotion bug)
            n = 1
            for ax in dp:
                n *= mesh.devices.shape[list(mesh.axis_names).index(ax)]
            aux = jax.lax.psum(aux, dp) / n
        return y.reshape(bl, sl, d), aux

    y, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(dp, None, None), moe_specs),
        out_specs=(P(dp, None, None), P()),
        check_vma=False,
    )(h, params["moe"])
    return ctx.residual(x + y), aux


def _mamba_sublayer(x, params, cfg, ctx: ShardCtx):
    h = rms_norm(x, params["ln"], cfg.norm_eps)
    if ctx.tuned:
        h = ctx.residual(h)
    h = ctx.gathered(h)  # SSD needs the full sequence; heads are TP-sharded
    y, _ = mamba2_forward(
        h, params["mamba"], cfg,
        constrain_heads=ctx.heads_sharded if (ctx.tuned and ctx.mesh) else None,
    )
    if ctx.tuned:
        y = ctx.residual(y)  # reduce-scatter the out_proj partials
    return ctx.residual(x + y)


# --------------------------------------------------------------------------
# block-level entry points (used by transformer.py scan bodies)
# --------------------------------------------------------------------------

def dense_block(x, params, cfg, ctx, pos):
    x = _attn_sublayer(x, params, cfg, ctx, pos, pos)
    x = _mlp_sublayer(x, params, cfg, ctx)
    return x


def moe_block(x, params, cfg, ctx, pos):
    x = _attn_sublayer(x, params, cfg, ctx, pos, pos)
    x, aux = _moe_sublayer(x, params, cfg, ctx)
    return x, aux


def mamba_block(x, params, cfg, ctx):
    return _mamba_sublayer(x, params, cfg, ctx)


def hybrid_attn_block(x, params, cfg, ctx, pos):
    """zamba2 shared transformer block: attention + dense MLP."""
    x = _attn_sublayer(x, params, cfg, ctx, pos, pos)
    x = _mlp_sublayer(x, params, cfg, ctx)
    return x
