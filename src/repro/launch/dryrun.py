import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this proves the distribution config is coherent end-to-end:
``jax.jit(step, in_shardings, out_shardings).lower(...).compile()`` on the
production meshes (16×16 single pod, 2×16×16 multi-pod), records
``memory_analysis()`` (fits-in-HBM evidence), ``cost_analysis()`` (per-chip
FLOPs/bytes) and the collective schedule parsed from the partitioned HLO —
the inputs to EXPERIMENTS.md §Dry-run and §Roofline.

Usage:
  python -m repro.launch.dryrun --all                 # 40 cells × 2 meshes
  python -m repro.launch.dryrun --arch smollm-135m --shape train_4k --mesh single
"""
import argparse
import dataclasses
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, SHAPES, applicable, get_config
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.parallel.annotate import activation_sharding
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.optim import AdamW
from repro.parallel.sharding import (batch_specs, cache_specs,
                                     make_shardings, param_specs)
from repro.roofline.analysis import model_flops
from repro.roofline.hlo import parse_collectives
from repro.train.steps import make_decode_step, make_train_step



def _sds(tree):
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)


def _astype(tree, dtype):
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(
            l.shape, dtype if l.dtype == jnp.float32 else l.dtype), tree)


def batch_struct(cfg: ModelConfig, kind: str, B: int, S: int):
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
    batch = {"tokens": tok}
    if cfg.enc_dec:
        batch["enc_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.enc_seq, cfg.d_model), jnp.dtype(cfg.dtype))
    elif cfg.frontend:
        batch = {"embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                                jnp.dtype(cfg.dtype)),
                 "labels": tok}
    return batch


# §Perf variants (EXPERIMENTS.md): flags flip the optimizations the
# hillclimb iterations introduce, so baseline and optimized lowerings of
# the SAME cell can be compared.
#   bf16params — bf16 weights (+bf16 Adam moments) for ALL train cells:
#                halves every FSDP all-gather / grad reduction payload
#   int8kv     — int8 KV cache for decode cells: halves cache HBM traffic
PERF_VARIANT = os.environ.get("REPRO_PERF_VARIANT", "baseline")


def input_specs(arch: str, shape_name: str):
    """(cfg, step_fn, example args as ShapeDtypeStructs, arg kinds)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    B, S = shape.global_batch, shape.seq_len
    params = jax.eval_shape(lambda k: M.init(cfg, k), jax.random.PRNGKey(0))

    if shape.kind == "train":
        # ≥100B models: bf16 weights + bf16 Adam moments (ZeRO-3 sharded)
        big = cfg.approx_params() > 100e9 or "bf16params" in PERF_VARIANT
        if big:
            params = _astype(params, jnp.bfloat16)
        optim = AdamW(moment_dtype="bfloat16" if big else "float32")
        opt_state = jax.eval_shape(optim.init, params)
        batch = batch_struct(cfg, "train", B, S)
        step = make_train_step(cfg, optim)
        return cfg, step, (params, opt_state, batch), ("params", "opt", "batch")

    params = _astype(params, jnp.bfloat16)          # serving weights
    if shape.kind == "prefill":
        batch = batch_struct(cfg, "prefill", B, S)
        step = lambda p, b: M.prefill(cfg, p, b, max_len=S)
        return cfg, step, (params, batch), ("params", "batch")

    # decode: one new token against a KV/state cache of length S
    kv_dtype = jnp.int8 if "int8kv" in PERF_VARIANT else None
    cache = jax.eval_shape(lambda: M.init_cache(cfg, B, S, dtype=kv_dtype))
    tokens = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    step = make_decode_step(cfg)
    return cfg, step, (params, tokens, cache), ("params", "tokens", "cache")


def shardings_for(cfg, mesh, args, kinds):
    serve_tp = "tpserve" in PERF_VARIANT and "opt" not in kinds
    fsdp_all = ("hybrid" if "hybridshard" in PERF_VARIANT
                else "fsdp256" in PERF_VARIANT)
    out = []
    for a, k in zip(args, kinds):
        if k == "params":
            out.append(make_shardings(
                mesh, param_specs(cfg, a, mesh, serve_tp_only=serve_tp,
                                  fsdp_all=fsdp_all)))
        elif k == "opt":
            pspec = make_shardings(
                mesh, param_specs(cfg, a.mu, mesh, fsdp_all=fsdp_all))
            out.append(type(a)(step=make_shardings(
                mesh, jax.tree_util.tree_map(lambda _: None, a.step)),
                mu=pspec, nu=pspec))
        elif k in ("batch", "tokens"):
            out.append(make_shardings(
                mesh, batch_specs(cfg, a, mesh, fsdp_all=fsdp_all)))
        elif k == "cache":
            out.append(make_shardings(mesh, cache_specs(cfg, a, mesh)))
    return tuple(out)


def run_cell(arch: str, shape_name: str, mesh_kind: str, outdir: pathlib.Path,
             verbose: bool = True) -> dict:
    cfg_full = get_config(arch)
    ok, why = applicable(cfg_full, shape_name)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind}
    if not ok:
        rec.update(status="skipped", reason=why)
        outdir.mkdir(parents=True, exist_ok=True)
        (outdir / f"{arch}__{shape_name}__{mesh_kind}.json").write_text(
            json.dumps(rec, indent=1))
        print(f"[{arch} × {shape_name} × {mesh_kind}] skipped: {why}")
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.devices.size
    cfg, step, args, kinds = input_specs(arch, shape_name)
    in_sh = shardings_for(cfg, mesh, args, kinds)

    t0 = time.time()
    if "fsdp256" in PERF_VARIANT:
        # pure FSDP: batch over every axis, no TP activation constraints
        act_ctx = activation_sharding(mesh, tuple(mesh.axis_names),
                                      model_axis=None)
    else:
        # hybridshard changes only WEIGHT sharding; activations as baseline
        act_ctx = activation_sharding(mesh, dp_axes(mesh))
    # donate the state buffers (params/opt for train, cache for decode):
    # outputs alias inputs, halving resident memory — the production setup
    donate = tuple(i for i, k in enumerate(kinds) if k in ("opt", "cache")
                   or (k == "params" and "opt" in kinds))
    with mesh, act_ctx:
        jitted = jax.jit(step, in_shardings=in_sh, donate_argnums=donate)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    t1 = time.time()

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    coll = parse_collectives(compiled.as_text(),
                             default_group=mesh.shape["model"])

    shape = SHAPES[shape_name]
    mf = model_flops(cfg, shape.kind, shape.seq_len, shape.global_batch)
    rec.update(
        status="ok",
        chips=int(chips),
        compile_s=round(t1 - t0, 1),
        flops=float(ca.get("flops", 0.0)),              # per chip
        hbm_bytes=float(ca.get("bytes accessed", 0.0)),  # per chip
        wire_bytes_per_chip=float(coll["total"]["wire_bytes"]),
        collectives={k: {kk: float(vv) for kk, vv in v.items()}
                     for k, v in coll.items()},
        model_flops=mf / chips,                          # per chip
        arg_bytes_per_device=int(mem.argument_size_in_bytes),
        temp_bytes_per_device=int(mem.temp_size_in_bytes),
        output_bytes_per_device=int(mem.output_size_in_bytes),
        alias_bytes_per_device=int(mem.alias_size_in_bytes),
    )
    if verbose:
        print(f"[{arch} × {shape_name} × {mesh_kind}] compiled in "
              f"{rec['compile_s']}s; args/dev="
              f"{rec['arg_bytes_per_device']/2**30:.2f}GiB "
              f"temp/dev={rec['temp_bytes_per_device']/2**30:.2f}GiB "
              f"flops/dev={rec['flops']:.3g} "
              f"wire/dev={rec['wire_bytes_per_chip']/2**20:.1f}MiB")
        print("  memory_analysis:", mem)
        print("  collectives:", {k: v["count"] for k, v in
                                 rec["collectives"].items() if k != "total"})
    outdir.mkdir(parents=True, exist_ok=True)
    fname = f"{arch}__{shape_name}__{mesh_kind}.json".replace("/", "_")
    (outdir / fname).write_text(json.dumps(rec, indent=1))
    return rec


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    outdir = pathlib.Path(args.out)
    cells = []
    archs = ARCH_IDS if (args.all or not args.arch) else (args.arch,)
    shapes = tuple(SHAPES) if (args.all or not args.shape) else (args.shape,)
    meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)
    for a in archs:
        for s in shapes:
            for m in meshes:
                cells.append((a, s, m))

    failures = []
    for a, s, m in cells:
        try:
            run_cell(a, s, m, outdir)
        except Exception as e:  # noqa: BLE001 — report all failing cells
            failures.append((a, s, m, repr(e)))
            print(f"[{a} × {s} × {m}] FAILED: {e}")
            traceback.print_exc()
    print(f"\n{len(cells) - len(failures)}/{len(cells)} cells compiled")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
