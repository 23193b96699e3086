import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell we build the real step function (train / prefill / decode),
give every input a ShapeDtypeStruct stand-in (weak-type-correct, shardable,
zero allocation), lower under the production mesh, compile, and record:

* ``memory_analysis()``  — proves the cell fits per-device HBM,
* ``cost_analysis()``    — per-device FLOPs/bytes for §Roofline,
* collective schedule    — parsed from the partitioned HLO.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                    # all cells, both meshes
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --multi-pod-only --out reports/dryrun.json
"""

import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..configs import ARCHS, SHAPES, get_config, runnable_cells
from ..models.registry import model_fns
from ..roofline.analysis import analyze_compiled, model_flops_for
from ..sharding.rules import ShardingCtx
from ..train import steps as steps_lib
from ..train.optimizer import OptConfig
from .compile_cache import use_compile_cache
from .mesh import devices_per_pod, make_production_mesh


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins; no allocation)
# ---------------------------------------------------------------------------
def input_specs(cfg, shape, kind: str) -> Dict[str, Any]:
    B, S = shape.global_batch, shape.seq_len
    specs: Dict[str, Any] = {}
    if kind == "train":
        specs["tokens"] = jax.ShapeDtypeStruct((B, S + 1), jnp.int32)
    elif kind == "prefill":
        specs["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    elif kind == "decode":
        specs["tokens"] = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    if cfg.family == "encdec" and kind in ("train", "prefill"):
        T = cfg.modality_seq or 1024
        specs["frames"] = jax.ShapeDtypeStruct((B, T, cfg.d_model), jnp.bfloat16)
    return specs


def _opt_cfg_for(cfg) -> OptConfig:
    # int8 optimizer states for the >=300B arch so one pod fits (DESIGN.md §3)
    if cfg.param_count() > 3e11:
        return OptConfig(state_dtype="int8")
    return OptConfig(state_dtype="float32")


def _microbatch_for(cfg) -> int:
    # gradient accumulation for the big train cells (activation memory /M)
    n = cfg.param_count()
    if n > 1e11:
        return 8
    if n > 1e10 or cfg.is_moe:   # MoE dispatch buffers scale with tokens
        return 4
    if cfg.padded_vocab >= 150_000 or cfg.family == "encdec":
        return 4                  # giant-vocab logits / enc+dec double stacks
    if n > 3e9:
        return 2
    return 1


# ---------------------------------------------------------------------------
# Cell construction
# ---------------------------------------------------------------------------
def build_cell(
    arch: str,
    shape_name: str,
    mesh,
    *,
    rules_overrides: Optional[Dict] = None,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    donate: bool = True,
    head_dim_fallback: bool = True,
    microbatch: Optional[int] = None,
):
    """Lower + compile one cell. Returns (lowered, compiled, meta dict)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    kind = shape.kind
    fns = model_fns(cfg)
    ctx = ShardingCtx(mesh=mesh, head_dim_fallback=head_dim_fallback)
    if kind == "decode":
        ctx = ctx.with_rules(kv_seq="model")
    if kind == "train":
        # sequence-parallel residual stream: per-layer activations saved for
        # the backward pass shard 16-way over 'model' — without this the
        # >=32-layer archs cannot hold remat residuals in 16 GiB HBM.
        ctx = ctx.with_rules(res_seq="model")
    if rules_overrides:
        ctx = ctx.with_rules(**rules_overrides)

    specs = input_specs(cfg, shape, kind)
    batch_sh = steps_lib.batch_shardings(cfg, ctx, specs)
    rng = jax.random.PRNGKey(0)

    if kind == "train":
        opt_cfg = _opt_cfg_for(cfg)
        state_shapes = jax.eval_shape(
            lambda: steps_lib.init_train_state(rng, cfg, opt_cfg))
        st_sh = steps_lib.state_shardings(cfg, ctx, state_shapes)
        step = steps_lib.make_train_step(cfg, opt_cfg, ctx,
                                         q_chunk=q_chunk, kv_chunk=kv_chunk,
                                         microbatch=microbatch
                                         if microbatch is not None
                                         else _microbatch_for(cfg))
        jitted = jax.jit(
            step, in_shardings=(st_sh, batch_sh),
            out_shardings=(st_sh, None),
            donate_argnums=(0,) if donate else (),
        )
        with mesh:
            lowered = jitted.lower(state_shapes, specs)
    else:
        params_shapes = jax.eval_shape(lambda: fns.init_params(rng, cfg))
        p_sh = steps_lib.params_shardings(cfg, ctx, params_shapes)
        B, S = shape.global_batch, shape.seq_len
        if cfg.family == "encdec":
            enc_len = cfg.modality_seq or 1024
            cache_shapes = jax.eval_shape(
                lambda: fns.init_cache(cfg, B, S, enc_len))
        else:
            cache_shapes = jax.eval_shape(lambda: fns.init_cache(cfg, B, S))
        c_sh = steps_lib.cache_shardings(cfg, ctx, cache_shapes)
        if kind == "prefill":
            step = steps_lib.make_prefill_step(cfg, ctx, q_chunk=q_chunk,
                                               kv_chunk=kv_chunk)
            jitted = jax.jit(
                step, in_shardings=(p_sh, batch_sh, c_sh),
                out_shardings=(None, c_sh),
                donate_argnums=(2,) if donate else (),
            )
            with mesh:
                lowered = jitted.lower(params_shapes, specs, cache_shapes)
        else:
            step = steps_lib.make_decode_step(cfg, ctx)
            jitted = jax.jit(
                step, in_shardings=(p_sh, batch_sh["tokens"], c_sh),
                out_shardings=(None, c_sh),
                donate_argnums=(2,) if donate else (),
            )
            with mesh:
                lowered = jitted.lower(params_shapes, specs["tokens"],
                                       cache_shapes)

    compiled = lowered.compile()
    return lowered, compiled, dict(cfg=cfg, shape=shape, kind=kind)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             rules_overrides: Optional[Dict] = None,
             q_chunk: int = 1024, kv_chunk: int = 1024,
             head_dim_fallback: bool = True,
             microbatch: Optional[int] = None) -> Dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = 512 if multi_pod else 256
    t0 = time.monotonic()
    lowered, compiled, meta = build_cell(
        arch, shape_name, mesh, rules_overrides=rules_overrides,
        q_chunk=q_chunk, kv_chunk=kv_chunk,
        head_dim_fallback=head_dim_fallback, microbatch=microbatch)
    compile_s = time.monotonic() - t0
    rep = analyze_compiled(
        compiled, arch=arch, shape=shape_name, mesh_name=mesh_name,
        chips=chips, devices_per_pod=devices_per_pod(mesh),
        model_flops=model_flops_for(meta["cfg"], meta["shape"], meta["kind"]),
    )
    out = rep.to_dict()
    out["compile_s"] = compile_s
    out["status"] = "ok"
    return out


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------
def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--out", default="reports/dryrun.json")
    ap.add_argument("--append", action="store_true",
                    help="merge into an existing report file")
    ap.add_argument("--q-chunk", type=int, default=512)
    ap.add_argument("--kv-chunk", type=int, default=512)
    args = ap.parse_args()

    use_compile_cache()

    archs = [args.arch] if args.arch else list(ARCHS)
    meshes = []
    if not args.multi_pod_only:
        meshes.append(False)
    if not args.single_pod_only:
        meshes.append(True)

    results = []
    if args.append and os.path.exists(args.out):
        results = json.load(open(args.out))

    def key(r):
        return (r["arch"], r["shape"], r["mesh"])

    done = {key(r) for r in results if r.get("status") == "ok"}

    for arch in archs:
        cfg = get_config(arch)
        cells = runnable_cells(cfg)
        shapes = [args.shape] if args.shape else cells
        for shape_name in shapes:
            if shape_name not in cells:
                print(f"SKIP {arch} x {shape_name}: not runnable "
                      f"(full attention at 500k — see DESIGN.md §4)")
                continue
            for multi_pod in meshes:
                mesh_name = "2x16x16" if multi_pod else "16x16"
                if (arch, shape_name, mesh_name) in done:
                    print(f"cached {arch} x {shape_name} x {mesh_name}")
                    continue
                print(f"=== {arch} x {shape_name} x {mesh_name} ===", flush=True)
                try:
                    r = run_cell(arch, shape_name, multi_pod=multi_pod,
                                 q_chunk=args.q_chunk, kv_chunk=args.kv_chunk)
                    mem = r["memory_per_device"] or {}
                    print(
                        f"  ok in {r['compile_s']:.1f}s | "
                        f"t_comp={r['t_compute']*1e3:.2f}ms "
                        f"t_mem={r['t_memory']*1e3:.2f}ms "
                        f"t_coll={r['t_collective']*1e3:.2f}ms "
                        f"bottleneck={r['bottleneck']} "
                        f"| args/dev={mem.get('argument', 0)/2**30:.2f}GiB "
                        f"temp/dev={mem.get('temp', 0)/2**30:.2f}GiB",
                        flush=True,
                    )
                except Exception as e:
                    r = dict(arch=arch, shape=shape_name, mesh=mesh_name,
                             status="error", error=f"{type(e).__name__}: {e}",
                             traceback=traceback.format_exc()[-2000:])
                    print(f"  ERROR: {type(e).__name__}: {e}", flush=True)
                results = [x for x in results if key(x) != (arch, shape_name, mesh_name)]
                results.append(r)
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1, default=str)

    n_ok = sum(1 for r in results if r.get("status") == "ok")
    print(f"\n{n_ok}/{len(results)} cells compiled OK -> {args.out}")


if __name__ == "__main__":
    main()
