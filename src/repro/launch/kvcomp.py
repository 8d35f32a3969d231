"""DROP KV-compressed decode for the dry-run (§Perf cell C).

The cache stores rank-r projections of K/V (bases discovered by DROP on
sampled key/value rows — serve/kv_compress.py); decode attention runs wholly
in r dims: scores = (q V_k)·c_k, out = (p·c_v) V_vᵀ. Cache memory and decode
HBM traffic scale by r/hd with exact algebra given the basis.
"""

from __future__ import annotations

import jax

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.dryrun import params_struct
from repro.models.layers import apply_mrope, apply_rope, rms_norm
from repro.serve.decode import _mlp_decode, _moe_decode, decode_layout
from repro.sharding.specs import ShardCtx, param_specs

NEG_INF = -1e30


def flash_decode_compressed(qc, ck, cv, basis_v, valid, ctx: ShardCtx, hd: int):
    """qc: (B,1,KV,G,r) query already in key-basis; ck/cv: (B,T,KV,r);
    returns (B,1,KV,G,hd) after expanding through basis_v."""
    batch_axes, seq_axes = decode_layout(ctx, qc.shape[0])
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))

    def local(ql, kl, vl, validl, bv):
        s = jnp.einsum("bqkgr,btkr->bkgqt", ql, kl,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(validl[:, None, None, None, :], s, NEG_INF)
        m = jnp.max(s, axis=-1)
        m_g = jax.lax.pmax(m, seq_axes) if seq_axes else m
        p = jnp.exp(s - m_g[..., None])
        l = jnp.sum(p, axis=-1)
        oc = jnp.einsum("bkgqt,btkr->bkgqr", p, vl,
                        preferred_element_type=jnp.float32)
        if seq_axes:
            l = jax.lax.psum(l, seq_axes)
            oc = jax.lax.psum(oc, seq_axes)
        o = jnp.einsum("bkgqr,hr->bkgqh", oc / jnp.maximum(l, 1e-30)[..., None],
                       bv.astype(jnp.float32))
        return o.transpose(0, 3, 1, 2, 4).astype(ql.dtype)

    if ctx.mesh is None:
        return local(qc, ck, cv, valid, basis_v)
    ba, sa = tuple(batch_axes), tuple(seq_axes)
    return jax.shard_map(
        local,
        mesh=ctx.mesh,
        in_specs=(P(ba, None, None, None, None), P(ba, sa, None, None),
                  P(ba, sa, None, None), P(ba, sa), P(None, None)),
        out_specs=P(ba, None, None, None, None),
        check_vma=False,
    )(qc, ck, cv, valid, basis_v)


def serve_step_compressed(params, token, cache, lengths, bases, cfg, ctx):
    """Decode step with rank-r compressed attention caches (dense families)."""
    b = token.shape[0]
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = h // kv
    x = jnp.take(params["embed"], token[:, 0], axis=0)
    ck_all, cv_all = cache["attn"]["ck"], cache["attn"]["cv"]
    t = ck_all.shape[2]
    filled = jnp.minimum(lengths + 1, t)
    valid = jnp.arange(t)[None, :] < filled[:, None]
    slot = jnp.minimum(lengths, t - 1)
    bi = jnp.arange(b)

    def body(hcar, layer_in):
        layer, ck_l, cv_l, bk, bv = layer_in
        hn = rms_norm(hcar, layer["ln1"], cfg.norm_eps)
        q = (hn @ layer["attn"]["wq"]).reshape(b, 1, h, hd)
        k = (hn @ layer["attn"]["wk"]).reshape(b, 1, kv, hd)
        v = (hn @ layer["attn"]["wv"]).reshape(b, 1, kv, hd)
        if "q_norm" in layer["attn"]:
            q = rms_norm(q, layer["attn"]["q_norm"], cfg.norm_eps)
            k = rms_norm(k, layer["attn"]["k_norm"], cfg.norm_eps)
        pos_new = lengths[:, None]
        if cfg.mrope_sections:
            p3 = jnp.broadcast_to(pos_new, (3, b, 1))
            q = apply_mrope(q, p3, cfg.mrope_sections, cfg.rope_theta)
            k = apply_mrope(k, p3, cfg.mrope_sections, cfg.rope_theta)
        elif cfg.rope_theta > 0:
            q = apply_rope(q, pos_new, cfg.rope_theta)
            k = apply_rope(k, pos_new, cfg.rope_theta)
        # compress the new K/V rows into the DROP basis and cache them
        ck_new = jnp.einsum("bqkh,hr->bqkr", k, bk).astype(ck_l.dtype)
        cv_new = jnp.einsum("bqkh,hr->bqkr", v, bv).astype(cv_l.dtype)
        ck_l = ck_l.at[bi, slot].set(ck_new[:, 0])
        cv_l = cv_l.at[bi, slot].set(cv_new[:, 0])
        qc = jnp.einsum(
            "bqkgh,hr->bqkgr", q.reshape(b, 1, kv, g, hd), bk
        ).astype(ck_l.dtype)
        out = flash_decode_compressed(qc, ck_l, cv_l, bv, valid, ctx, hd)
        y = out.reshape(b, 1, h * hd)[:, 0] @ layer["attn"]["wo"]
        hcar = hcar + y.astype(hcar.dtype)
        if cfg.family == "moe":
            hcar = _moe_decode(hcar, layer, cfg, ctx)
        else:
            hcar = _mlp_decode(hcar, layer, cfg)
        return hcar, (ck_l, cv_l)

    x, (ck_new, cv_new) = jax.lax.scan(
        body, x,
        (params["layers"], ck_all, cv_all, bases["k"], bases["v"]),
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return x @ head, {"attn": {"ck": ck_new, "cv": cv_new}}


def compressed_decode_specs(
    cfg: ModelConfig, shape: ShapeConfig, ctx: ShardCtx, rank: int,
    serve_params: bool = False,
):
    """(args, specs, step_fn, donate) for the compressed-decode dry-run."""
    dtype = jnp.dtype(cfg.dtype)
    b, t = shape.global_batch, shape.seq_len
    l, kvh = cfg.num_layers, cfg.num_kv_heads
    ba, sa = decode_layout(ctx, b)

    p_struct = params_struct(cfg)
    cache = {
        "attn": {
            "ck": jax.ShapeDtypeStruct((l, b, t, kvh, rank), dtype),
            "cv": jax.ShapeDtypeStruct((l, b, t, kvh, rank), dtype),
        }
    }
    cache_spec = {
        "attn": {
            "ck": P(None, ba, sa, None, None),
            "cv": P(None, ba, sa, None, None),
        }
    }
    bases = {
        "k": jax.ShapeDtypeStruct((l, cfg.head_dim, rank), jnp.float32),
        "v": jax.ShapeDtypeStruct((l, cfg.head_dim, rank), jnp.float32),
    }
    bases_spec = {"k": P(None, None, None), "v": P(None, None, None)}
    tok = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    lengths = jax.ShapeDtypeStruct((b,), jnp.int32)

    def step(params, token, cache, lens, bases_):
        return serve_step_compressed(params, token, cache, lens, bases_, cfg, ctx)

    args = (p_struct, tok, cache, lengths, bases)
    specs = (
        param_specs(p_struct, serve=serve_params),
        P(ba, None), cache_spec, P(ba), bases_spec,
    )
    return args, specs, step, (2,)
