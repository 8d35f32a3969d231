"""Single-token decode: distributed flash-decode + per-family serve_step.

flash_decode is the sequence-parallel decode attention (DESIGN.md §5): the KV
cache's time axis is sharded over "model"; every shard computes attention of
the (replicated, single-token) query against its local cache slice and the
partial softmax stats (running max + denominator) are combined with
pmax/psum — the distributed form of the FlashAttention recurrence. This is
what makes 32k-cache x128-batch and 500k-cache decode fit and balance.
"""

from __future__ import annotations

import functools

import jax

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.attention import decode_attention
from repro.models.layers import apply_mrope, apply_rope, rms_norm
from repro.models.mlp import gelu_mlp, swiglu
from repro.models.moe import moe_ffn
from repro.models.ssm import mamba2_decode_step
from repro.sharding.specs import ShardCtx

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# distributed flash-decode
# ---------------------------------------------------------------------------

def decode_layout(ctx: ShardCtx, batch: int) -> tuple[tuple, tuple]:
    """(batch_axes, seq_axes) for decode-cache sharding.

    Batch shards over dp when divisible; the cache sequence axis shards over
    "model" plus any dp axes the batch could not use — so long_500k (batch=1)
    spreads its 500k-slot cache over ALL chips."""
    if ctx.mesh is None:
        return (), ()
    sizes = dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))
    batch_axes, seq_axes = [], []
    rem = batch
    for ax in ctx.dp:
        if rem % sizes[ax] == 0 and rem >= sizes[ax]:
            batch_axes.append(ax)
            rem //= sizes[ax]
        else:
            seq_axes.append(ax)
    seq_axes.append("model")
    return tuple(batch_axes), tuple(seq_axes)


def flash_decode(
    q: jax.Array,  # (B, 1, KV, G, hd)
    k_cache: jax.Array,  # (B, T, KV, hd) — T sharded over seq_axes under mesh
    v_cache: jax.Array,
    valid: jax.Array,  # (B, T) bool
    ctx: ShardCtx,
) -> jax.Array:
    if ctx.mesh is None or "model" not in ctx.mesh.axis_names:
        return decode_attention(q, k_cache, v_cache, length_mask=valid)

    batch_axes, seq_axes = decode_layout(ctx, q.shape[0])
    hd = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))

    def local(ql, kl, vl, validl):
        # ql: (b, 1, KV, G, hd) replicated over seq_axes; kl: (b, T/shards, KV, hd)
        if jax.default_backend() == "tpu":
            # per-shard hot loop as a Pallas kernel (one HBM pass over the
            # cache slice); stats still combined across shards below
            from repro.kernels.flash_decode.ops import flash_decode as fd_kernel

            # kernel returns normalized output; recover (m, l, o) by also
            # computing local stats — cheaper: use the jnp stats path on TPU
            # only for the cross-shard terms. For simplicity the kernel path
            # is used when there is a single seq shard:
            if not seq_axes:
                out = fd_kernel(ql[:, 0], kl, vl, validl)
                return out[:, None].astype(ql.dtype)
        s = jnp.einsum(
            "bqkgh,btkh->bkgqt", ql, kl, preferred_element_type=jnp.float32
        ) * scale
        s = jnp.where(validl[:, None, None, None, :], s, NEG_INF)
        m = jnp.max(s, axis=-1)  # local max (b, KV, G, 1)
        m_g = jax.lax.pmax(m, seq_axes)
        p = jnp.exp(s - m_g[..., None])
        l = jax.lax.psum(jnp.sum(p, axis=-1), seq_axes)
        o = jnp.einsum("bkgqt,btkh->bkgqh", p, vl,
                       preferred_element_type=jnp.float32)
        o = jax.lax.psum(o, seq_axes)
        out = o / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 3, 1, 2, 4).astype(ql.dtype)  # (b,1,KV,G,hd)

    ba = tuple(batch_axes)
    sa = tuple(seq_axes)
    return jax.shard_map(
        local,
        mesh=ctx.mesh,
        in_specs=(
            P(ba, None, None, None, None),
            P(ba, sa, None, None),
            P(ba, sa, None, None),
            P(ba, sa),
        ),
        out_specs=P(ba, None, None, None, None),
        check_vma=False,
    )(q, k_cache, v_cache, valid)


# ---------------------------------------------------------------------------
# per-layer decode sublayers
# ---------------------------------------------------------------------------

def _attn_decode(
    x, layer, cfg: ModelConfig, ctx: ShardCtx, k_c, v_c, pos_c, lengths,
    *, ring: bool, use_rope: bool = True,
):
    """One attention layer's decode. Returns (out, k_c, v_c, pos_c)."""
    b = x.shape[0]
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = h // kv
    t = k_c.shape[1]

    hn = rms_norm(x, layer["ln1"], cfg.norm_eps)
    q = (hn @ layer["attn"]["wq"]).reshape(b, 1, h, hd)
    k = (hn @ layer["attn"]["wk"]).reshape(b, 1, kv, hd)
    v = (hn @ layer["attn"]["wv"]).reshape(b, 1, kv, hd)
    if "q_norm" in layer["attn"]:
        q = rms_norm(q, layer["attn"]["q_norm"], cfg.norm_eps)
        k = rms_norm(k, layer["attn"]["k_norm"], cfg.norm_eps)
    if use_rope:
        pos_new = lengths[:, None]  # (B, 1) absolute position of the new token
        if cfg.mrope_sections:
            p3 = jnp.broadcast_to(pos_new, (3, b, 1))
            q = apply_mrope(q, p3, cfg.mrope_sections, cfg.rope_theta)
            k = apply_mrope(k, p3, cfg.mrope_sections, cfg.rope_theta)
        else:
            q = apply_rope(q, pos_new, cfg.rope_theta)
            k = apply_rope(k, pos_new, cfg.rope_theta)

    # write the new K/V into the cache (ring buffers wrap at T=window)
    slot = lengths % t if ring else jnp.minimum(lengths, t - 1)
    bi = jnp.arange(b)
    k_c = k_c.at[bi, slot].set(k[:, 0])
    v_c = v_c.at[bi, slot].set(v[:, 0])
    pos_c = pos_c.at[bi, slot].set(lengths)

    # valid slots: written and (for SWA) within the window
    filled = jnp.minimum(lengths + 1, t)
    valid = jnp.arange(t)[None, :] < filled[:, None]
    if ring and cfg.sliding_window is not None:
        valid &= pos_c > (lengths[:, None] - cfg.sliding_window)

    out = flash_decode(q.reshape(b, 1, kv, g, hd), k_c, v_c, valid, ctx)
    y = out.reshape(b, 1, h * hd)[:, 0] @ layer["attn"]["wo"]
    return x + y, k_c, v_c, pos_c


def _mlp_decode(x, layer, cfg, kind="swiglu"):
    hn = rms_norm(x, layer["ln2"], cfg.norm_eps)
    y = swiglu(hn, layer["mlp"]) if kind == "swiglu" else gelu_mlp(hn, layer["mlp"])
    return x + y


def _moe_decode(x, layer, cfg, ctx):
    hn = rms_norm(x, layer["ln2"], cfg.norm_eps)
    y, _ = moe_ffn(
        hn,
        layer["moe"],
        num_experts=cfg.num_experts,
        experts_per_token=cfg.experts_per_token,
        capacity_factor=max(cfg.capacity_factor, 2.0),  # tiny N: avoid drops
    )
    return x + y


# ---------------------------------------------------------------------------
# serve_step per family
# ---------------------------------------------------------------------------

def serve_step(
    params: dict,
    token: jax.Array,  # (B, 1) int32
    cache: dict,
    lengths: jax.Array,  # (B,) filled context lengths
    cfg: ModelConfig,
    ctx: ShardCtx,
) -> tuple[jax.Array, dict]:
    """One decode step: next-token logits + updated cache."""
    b = token.shape[0]
    x = jnp.take(params["embed"], token[:, 0], axis=0)  # (B, d)

    if cfg.family in ("dense", "vlm", "moe"):
        x, cache = _decode_attn_stack(params, x, cache, lengths, cfg, ctx)
    elif cfg.family == "ssm":
        x, cache = _decode_ssm_stack(params, x, cache, lengths, cfg, ctx)
    elif cfg.family == "hybrid":
        x, cache = _decode_hybrid(params, x, cache, lengths, cfg, ctx)
    elif cfg.is_encoder_decoder:
        x, cache = _decode_encdec(params, x, cache, lengths, cfg, ctx)
    else:
        raise ValueError(cfg.family)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head  # (B, Vp)
    return logits, cache


def _decode_attn_stack(params, x, cache, lengths, cfg, ctx):
    ring = cfg.sliding_window is not None
    ac = cache["attn"]
    pos_c = ac["pos"]

    def body(carry, layer_in):
        h, pos_c = carry
        layer, k_l, v_l = layer_in
        h, k_l, v_l, pos_c = _attn_decode(
            h, layer, cfg, ctx, k_l, v_l, pos_c, lengths, ring=ring
        )
        if cfg.family == "moe":
            h = _moe_decode(h, layer, cfg, ctx)
        else:
            h = _mlp_decode(h, layer, cfg)
        return (h, pos_c), (k_l, v_l)

    (x, pos_c), (k_new, v_new) = jax.lax.scan(
        body, (x, pos_c), (params["layers"], ac["k"], ac["v"])
    )
    return x, {"attn": {"k": k_new, "v": v_new, "pos": pos_c}}


def _decode_ssm_stack(params, x, cache, lengths, cfg, ctx):
    sc = cache["ssm"]

    def body(h, layer_in):
        layer, ssm_l, conv_l = layer_in
        hn = rms_norm(h, layer["ln"], cfg.norm_eps)
        y, new_state = mamba2_decode_step(
            hn[:, None, :], layer["mamba"], cfg, {"ssm": ssm_l, "conv": conv_l}
        )
        return h + y[:, 0], (new_state["ssm"], new_state["conv"])

    x, (ssm_new, conv_new) = jax.lax.scan(
        body, x, (params["layers"], sc["ssm"], sc["conv"])
    )
    return x, {"ssm": {"ssm": ssm_new, "conv": conv_new}}


def _decode_hybrid(params, x, cache, lengths, cfg, ctx):
    from repro.models.transformer import _hybrid_segments

    sc, ac = cache["ssm"], cache["attn"]
    pos_c = ac["pos"]
    runs = _hybrid_segments(cfg)

    def mamba_body(h, layer_in):
        layer, ssm_l, conv_l = layer_in
        hn = rms_norm(h, layer["ln"], cfg.norm_eps)
        y, ns = mamba2_decode_step(
            hn[:, None, :], layer["mamba"], cfg, {"ssm": ssm_l, "conv": conv_l}
        )
        return h + y[:, 0], (ns["ssm"], ns["conv"])

    ssm_out, conv_out, k_out, v_out = [], [], [], []
    off = 0
    for i, ln in enumerate(runs):
        if ln > 0:
            seg = jax.tree_util.tree_map(
                lambda a, o=off, n=ln: a[o : o + n], params["layers"]
            )
            x, (s_n, c_n) = jax.lax.scan(
                mamba_body, x, (seg, sc["ssm"][off : off + ln], sc["conv"][off : off + ln])
            )
            ssm_out.append(s_n)
            conv_out.append(c_n)
            off += ln
        if i < len(runs) - 1:
            shared = params["shared_attn"]
            x, k_n, v_n, pos_c = _attn_decode(
                x, shared, cfg, ctx, ac["k"][i], ac["v"][i], pos_c, lengths,
                ring=False,
            )
            x = _mlp_decode(x, shared, cfg)
            k_out.append(k_n)
            v_out.append(v_n)

    return x, {
        "ssm": {
            "ssm": jnp.concatenate(ssm_out, axis=0),
            "conv": jnp.concatenate(conv_out, axis=0),
        },
        "attn": {
            "k": jnp.stack(k_out, axis=0),
            "v": jnp.stack(v_out, axis=0),
            "pos": pos_c,
        },
    }


def _decode_encdec(params, x, cache, lengths, cfg, ctx):
    """Whisper decoder step: causal self-attn cache + fixed cross K/V."""
    from repro.models.layers import sinusoidal_positions

    ac, cc = cache["attn"], cache["cross"]
    pos_c = ac["pos"]
    b = x.shape[0]
    pos_table = jnp.asarray(
        sinusoidal_positions(ac["k"].shape[2], cfg.d_model), x.dtype
    )
    x = x + pos_table[jnp.minimum(lengths, pos_table.shape[0] - 1)]

    t_enc = cc["k"].shape[2]  # padded to a shardable multiple; mask the tail
    cross_valid = jnp.broadcast_to(
        jnp.arange(t_enc)[None, :] < cfg.encoder_ctx, (b, t_enc)
    )
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    g = cfg.num_heads // kv

    def body(carry, layer_in):
        h, pos_c = carry
        layer, k_l, v_l, ck_l, cv_l = layer_in
        h, k_l, v_l, pos_c = _attn_decode(
            h, layer, cfg, ctx, k_l, v_l, pos_c, lengths, ring=False,
            use_rope=False,
        )
        # cross attention against the precomputed encoder K/V
        hn = rms_norm(h, layer["ln_cross"], cfg.norm_eps)
        qc = (hn @ layer["cross"]["wq"]).reshape(b, 1, kv, g, hd)
        out = flash_decode(qc, ck_l, cv_l, cross_valid, ctx)
        h = h + out.reshape(b, cfg.num_heads * hd) @ layer["cross"]["wo"]
        h = _mlp_decode(h, layer, cfg, kind="gelu")
        return (h, pos_c), (k_l, v_l)

    (x, pos_c), (k_new, v_new) = jax.lax.scan(
        body, (x, pos_c),
        (params["dec_layers"], ac["k"], ac["v"], cc["k"], cc["v"]),
    )
    return x, {
        "attn": {"k": k_new, "v": v_new, "pos": pos_c},
        "cross": cc,
    }
