import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes, print memory/cost analysis, and persist roofline JSONs.

The two lines above MUST precede any other import (jax locks the device count
on first init). Usage:

    PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both]
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3_32b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --list

Outputs: artifacts/dryrun/<arch>__<shape>__<mesh>.json (resumable: existing
files are skipped unless --force).
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (
    ARCH_IDS,
    SHAPES,
    ModelConfig,
    ShapeConfig,
    cell_is_runnable,
    get_config,
)
from repro.launch.mesh import make_production_mesh
from repro.models.model import init_model
from repro.roofline.analysis import build_roofline, save_report, suggestion
from repro.serve.kvcache import cache_specs, cache_struct, plan_cache
from repro.sharding.specs import ShardCtx, param_specs
from repro.train.optimizer import OptimizerConfig
from repro.train.train_step import make_train_step

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "artifacts", "dryrun")


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; never allocate)
# ---------------------------------------------------------------------------

def params_struct(cfg: ModelConfig):
    return jax.eval_shape(lambda k: init_model(cfg, k), jax.random.PRNGKey(0))


def opt_struct(p_struct):
    return {
        "mu": jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), p_struct
        ),
        "nu": jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), p_struct
        ),
        "count": jax.ShapeDtypeStruct((), jnp.int32),
    }


def batch_struct(cfg: ModelConfig, shape: ShapeConfig):
    b, s = shape.global_batch, shape.seq_len
    out = {
        "inputs": jax.ShapeDtypeStruct((b, s), jnp.int32),
        "targets": jax.ShapeDtypeStruct((b, s), jnp.int32),
        "mask": jax.ShapeDtypeStruct((b, s), jnp.float32),
    }
    if cfg.family == "vlm":
        # stub vision frontend: precomputed patch/text embeddings
        out["inputs"] = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)
    if cfg.is_encoder_decoder:
        # stub audio frontend: precomputed frame embeddings
        out["frames"] = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)
    return out


def batch_specs(cfg, shape, ctx: ShardCtx):
    dp = ctx.dp
    out = {
        "inputs": P(dp, None),
        "targets": P(dp, None),
        "mask": P(dp, None),
    }
    if cfg.family == "vlm":
        out["inputs"] = P(dp, None, None)
    if cfg.is_encoder_decoder:
        out["frames"] = P(dp, None, None)
    return out


def input_specs(cfg: ModelConfig, shape: ShapeConfig, ctx: ShardCtx):
    """(args_structs, in_specs, step_fn, donate) for one dry-run cell."""
    mesh = ctx.mesh
    p_struct = params_struct(cfg)
    p_specs = param_specs(p_struct)

    if shape.kind == "train":
        o_struct = opt_struct(p_struct)
        o_specs = param_specs(o_struct)
        b_struct = batch_struct(cfg, shape)
        b_specs = batch_specs(cfg, shape, ctx)
        opt_cfg = OptimizerConfig()
        step = make_train_step(cfg, opt_cfg, ctx, remat="full")
        return (
            (p_struct, o_struct, b_struct),
            (p_specs, o_specs, b_specs),
            step,
            (0, 1),
        )

    if shape.kind == "prefill":
        from repro.models.model import forward

        b_struct = batch_struct(cfg, shape)
        b_specs = batch_specs(cfg, shape, ctx)

        def step(params, batch):
            return forward(params, batch, cfg, ctx, remat="full")

        return ((p_struct, b_struct), (p_specs, b_specs), step, ())

    # decode
    from repro.serve.decode import decode_layout, serve_step

    plan = plan_cache(cfg, shape.global_batch, shape.seq_len)
    c_struct = cache_struct(cfg, plan)
    c_specs = cache_specs(cfg, plan, ctx)
    ba, _ = decode_layout(ctx, shape.global_batch)
    tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    lengths = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)

    def step(params, token, cache, lens):
        return serve_step(params, token, cache, lens, cfg, ctx)

    return (
        (p_struct, tok, c_struct, lengths),
        (p_specs, P(ba, None), c_specs, P(ba)),
        step,
        (2,),
    )


# ---------------------------------------------------------------------------
# run one cell
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             force: bool = False, verbose: bool = True) -> dict:
    mesh_name = "multi" if multi_pod else "single"
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}.json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_is_runnable(cfg, shape)
    record: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "runnable": ok, "skip_reason": why, "status": "skipped",
    }
    if not ok:
        with open(out_path, "w") as f:
            json.dump(record, f, indent=2)
        return record

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        chips = mesh.devices.size
        ctx = ShardCtx(mesh=mesh)
        args, specs, step, donate = input_specs(cfg, shape, ctx)
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        with mesh:
            jitted = jax.jit(
                step, in_shardings=shardings, donate_argnums=donate
            )
            lowered = jitted.lower(*args)
            compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        roof = build_roofline(cfg, shape, mesh_name, chips, compiled)
        record.update(
            status="ok",
            compile_s=time.time() - t0,
            memory_analysis=str(mem),
            cost_flops=float((cost or {}).get("flops", 0.0)),
            roofline=json.loads(json.dumps(roof.__dict__)),
            suggestion=suggestion(roof),
        )
        if verbose:
            print(f"[{arch} x {shape_name} x {mesh_name}] OK "
                  f"({record['compile_s']:.0f}s compile)")
            print("  memory_analysis:", mem)
            print(f"  terms: compute={roof.compute_s:.4f}s "
                  f"memory={roof.memory_s:.4f}s "
                  f"collective={roof.collective_s:.4f}s -> {roof.dominant}")
            print(f"  useful_ratio={roof.useful_ratio:.3f}  "
                  f"suggestion: {suggestion(roof)}")
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        record.update(
            status="error",
            error=f"{type(e).__name__}: {e}",
            traceback=traceback.format_exc()[-4000:],
            compile_s=time.time() - t0,
        )
        if verbose:
            print(f"[{arch} x {shape_name} x {mesh_name}] FAILED: "
                  f"{record['error']}")
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2, default=str)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(OUT_DIR))
    args = ap.parse_args()

    if args.list:
        from repro.configs.base import all_cells

        for arch, shape, ok, why in all_cells():
            print(f"{arch:24s} {shape:12s} {'RUN' if ok else 'SKIP: ' + why}")
        return

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = (
        [(a, s) for a in ARCH_IDS for s in SHAPES]
        if args.all
        else [(args.arch, args.shape)]
    )
    statuses = []
    for arch, shape in cells:
        for mesh_name in meshes:
            rec = run_cell(arch, shape, mesh_name == "multi", args.out,
                           force=args.force)
            statuses.append((arch, shape, mesh_name, rec["status"]))
    n_ok = sum(1 for *_, s in statuses if s == "ok")
    n_skip = sum(1 for *_, s in statuses if s == "skipped")
    n_err = sum(1 for *_, s in statuses if s == "error")
    print(f"\n== dry-run summary: {n_ok} ok / {n_skip} skipped / {n_err} errors ==")
    for arch, shape, mesh_name, s in statuses:
        if s == "error":
            print(f"  FAILED: {arch} x {shape} x {mesh_name}")


if __name__ == "__main__":
    main()
