import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# ruff: noqa: E402  (the lines above MUST precede any jax-touching import)
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces, into artifacts/dryrun/:
  - memory_analysis (per-device bytes: proves it fits 16 GB HBM),
  - cost_analysis FLOPs/bytes (XLA's view; while bodies counted once),
  - trip-count-corrected dot FLOPs / HBM bytes / collective traffic from
    the post-optimization HLO (repro.analysis.hlo),
  - the three roofline terms + dominant bottleneck (§Roofline).

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--cells a,b,...]
"""
import argparse
import dataclasses
import functools
import json
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import optim
from repro.analysis import hlo as hlo_analysis
from repro.compile_cache import enable_compile_cache
from repro.configs.base import SHAPES_BY_NAME, ShapeConfig, shape_applicable
from repro.launch.mesh import make_production_mesh, production_rules
from repro.models.registry import (ARCH_IDS, active_param_count,
                                   build_model, get_config, param_count)
from repro.serve import make_prefill_step, make_serve_step
from repro.sharding import MeshRules, tree_shardings, use_rules
from repro.train import make_jitted_train_step

ARTIFACTS = os.path.join(os.path.dirname(__file__),
                         "..", "..", "..", "artifacts", "dryrun")


def pick_accum(cfg, shape: ShapeConfig, total_dp: int) -> int:
    """Gradient-accumulation depth: keeps per-chip microbatch at a size
    class that fits activations in 16 GB (giants -> 1 seq/chip)."""
    n = param_count(cfg)
    per_dp = max(1, shape.global_batch // total_dp)
    mb = 1 if n > 3e10 else (2 if n > 5e9 else 4)
    return max(1, per_dp // mb)


def batch_shardings(rules: MeshRules, specs: Dict[str, Any]):
    out = {}
    for k, v in specs.items():
        if v.ndim == 0:
            out[k] = NamedSharding(rules.mesh, P())
        else:
            bspec = rules.rules.get("batch")
            n = 1
            if bspec is not None:
                names = (bspec,) if isinstance(bspec, str) else bspec
                for a in names:
                    n *= rules.mesh.shape[a]
            spec = bspec if (n > 1 and v.shape[0] % n == 0) else None
            out[k] = NamedSharding(rules.mesh, P(spec))
    return out


def _opt_axes(model, use_master: bool = True):
    pax = model.param_logical_axes()
    return optim.OptState(step=(), mu=pax, nu=pax,
                          master=pax if use_master else None)


def build_cell(arch: str, shape_name: str, *, multi_pod: bool,
               cross_pod_mode: str = "xla",
               order: str = "grouped", seq_parallel: bool = False,
               fsdp: bool = True, accum_override: int = 0,
               use_master: bool = True):
    """Returns (lowered, meta) for one cell."""
    shape = SHAPES_BY_NAME[shape_name]
    cfg = get_config(arch)
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        return None, {"skipped": reason}
    model = build_model(cfg, remat=True)
    mesh = make_production_mesh(multi_pod=multi_pod)
    long_ctx = shape_name == "long_500k"
    from repro.sharding import make_rules
    rules = make_rules(mesh, long_ctx=long_ctx,
                       seq_shard=(shape.kind == "decode" and not long_ctx),
                       fsdp=fsdp, seq_parallel=seq_parallel)
    n_chips = mesh.size
    total_dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)

    params_shapes = jax.eval_shape(model.init, jax.random.key(0))
    pax = model.param_logical_axes()
    param_sh = tree_shardings(mesh, rules, params_shapes, pax)
    in_specs = model.input_specs(shape)
    batch_sh = batch_shardings(rules, in_specs)

    meta: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind, "n_chips": n_chips,
        "params": param_count(cfg),
        "active_params": active_param_count(cfg),
        "tokens": shape.tokens,
        "knobs": {"seq_parallel": seq_parallel, "fsdp": fsdp,
                  "cross_pod_mode": cross_pod_mode,
                  "accum_override": accum_override,
                  "use_master": use_master},
    }

    if shape.kind == "train":
        accum = accum_override or pick_accum(cfg, shape, total_dp)
        meta["accum"] = accum
        ocfg = optim.AdamWConfig(use_master=use_master)
        opt_shapes = jax.eval_shape(
            functools.partial(optim.init, ocfg), params_shapes)
        # ZeRO-1 when fsdp is off: optimizer states stay data-sharded
        opt_rules = rules if fsdp else make_rules(
            mesh, long_ctx=long_ctx, fsdp=True,
            seq_parallel=seq_parallel)
        opt_sh = tree_shardings(mesh, opt_rules, opt_shapes,
                                _opt_axes(model, use_master))
        jitted = make_jitted_train_step(
            model, ocfg, accum=accum, rules=rules, param_shardings=param_sh,
            opt_shardings=opt_sh, batch_sharding=batch_sh,
            cross_pod_mode=cross_pod_mode)
        with mesh:
            lowered = jitted.lower(params_shapes, opt_shapes, in_specs)
        meta["model_flops"] = 6.0 * active_param_count(cfg) * shape.tokens
    elif shape.kind == "prefill":
        def pre(params, batch):
            with use_rules(rules):
                logits, _ = model.forward_logits(params, batch)
                return logits
        jitted = jax.jit(pre, in_shardings=(param_sh, batch_sh))
        with mesh:
            lowered = jitted.lower(params_shapes, in_specs)
        meta["model_flops"] = 2.0 * active_param_count(cfg) * shape.tokens
    else:                          # decode
        cache_shapes = jax.eval_shape(
            functools.partial(model.init_cache, shape.global_batch,
                              shape.seq_len))
        cache_sh = tree_shardings(mesh, rules, cache_shapes,
                                  model.cache_logical_axes())

        def dec(params, cache, tokens, pos):
            with use_rules(rules):
                return model.decode_step(params, cache, tokens, pos)

        jitted = jax.jit(
            dec, donate_argnums=(1,),
            in_shardings=(param_sh, cache_sh,
                          batch_sh["tokens"], batch_sh["pos"]))
        with mesh:
            lowered = jitted.lower(params_shapes, cache_shapes,
                                   in_specs["tokens"], in_specs["pos"])
        meta["model_flops"] = 2.0 * active_param_count(cfg) * shape.tokens
    return lowered, meta


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             cross_pod_mode: str = "xla", order: str = "grouped",
             out_dir: Optional[str] = None, seq_parallel: bool = False,
             fsdp: bool = True, accum_override: int = 0,
             use_master: bool = True,
             tag: str = "") -> Dict[str, Any]:
    t0 = time.time()
    try:
        lowered, meta = build_cell(arch, shape_name, multi_pod=multi_pod,
                                   cross_pod_mode=cross_pod_mode,
                                   order=order, seq_parallel=seq_parallel,
                                   fsdp=fsdp,
                                   accum_override=accum_override,
                                   use_master=use_master)
        if lowered is None:
            meta.update({"arch": arch, "shape": shape_name,
                         "mesh": "2x16x16" if multi_pod else "16x16",
                         "status": "skipped"})
            return _write(meta, out_dir, tag)
        meta["lower_s"] = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        meta["compile_s"] = time.time() - t1

        mem = compiled.memory_analysis()
        meta["memory"] = {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_estimate_bytes": (mem.argument_size_in_bytes
                                    + mem.temp_size_in_bytes),
            "fits_16gb": (mem.argument_size_in_bytes
                          + mem.temp_size_in_bytes) < 16e9,
        }
        ca = compiled.cost_analysis() or {}
        if isinstance(ca, (list, tuple)):   # older jax: one dict per device
            ca = ca[0] if ca else {}
        meta["cost_analysis"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        }
        cpp = 256 if multi_pod else None
        stats = hlo_analysis.analyze(compiled.as_text(),
                                     chips_per_pod=cpp)
        rf = hlo_analysis.roofline(
            stats, n_chips=meta["n_chips"],
            model_flops_global=meta["model_flops"])
        meta["hlo"] = {
            "dot_flops_per_device": stats.dot_flops,
            "hbm_bytes_per_device": stats.hbm_bytes,
            "collective_bytes_per_device": stats.collective_bytes,
            "collective_operand_bytes": stats.collective_operand_bytes,
            "cross_pod_bytes_per_device": stats.cross_pod_bytes,
            "collective_ops": stats.collective_ops,
        }
        meta["roofline"] = rf.to_dict()
        meta["status"] = "ok"
    except Exception as e:                      # noqa: BLE001
        meta = {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "error", "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-4000:]}
    meta["total_s"] = time.time() - t0
    return _write(meta, out_dir, tag)


def _write(meta: Dict[str, Any], out_dir: Optional[str], tag: str):
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        name = f"{meta['arch']}__{meta['shape']}__{meta['mesh']}"
        if tag:
            name += f"__{tag}"
        with open(os.path.join(out_dir, name + ".json"), "w") as f:
            json.dump(meta, f, indent=1, default=str)
    return meta


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(ARCH_IDS))
    ap.add_argument("--shape", default=None,
                    choices=list(SHAPES_BY_NAME))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    # 'compressed' retired: multi-pod meshes raise NotImplementedError
    # in make_train_step (use hier_bucketed + slow_compress_bits=8)
    ap.add_argument("--cross-pod-mode", default="xla",
                    choices=["xla"])
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true",
                    help="ZeRO-1: replicate params over data, shard only "
                         "optimizer states")
    ap.add_argument("--accum", type=int, default=0)
    ap.add_argument("--no-master", action="store_true",
                    help="AdamW without f32 master weights (bf16 params as master)")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default=os.path.abspath(ARTIFACTS))
    args = ap.parse_args()
    enable_compile_cache()

    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in SHAPES_BY_NAME:
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures = 0
    for multi_pod in meshes:
        for arch, shape in cells:
            meta = run_cell(arch, shape, multi_pod=multi_pod,
                            cross_pod_mode=args.cross_pod_mode,
                            seq_parallel=args.seq_parallel,
                            fsdp=not args.no_fsdp,
                            accum_override=args.accum,
                            use_master=not args.no_master,
                            out_dir=args.out, tag=args.tag)
            status = meta.get("status")
            line = (f"[{meta.get('mesh')}] {arch:24s} {shape:12s} "
                    f"{status:8s}")
            if status == "ok":
                m = meta["memory"]
                r = meta["roofline"]
                line += (f" mem={m['peak_estimate_bytes']/1e9:6.2f}GB"
                         f" fits={m['fits_16gb']}"
                         f" dom={r['dominant']:10s}"
                         f" bound={r['bound_s']*1e3:8.2f}ms"
                         f" compile={meta['compile_s']:5.1f}s")
            elif status == "error":
                failures += 1
                line += " " + meta["error"][:120]
            else:
                line += " " + meta.get("skipped", "")[:80]
            print(line, flush=True)
            if status == "ok":
                print("  memory:", meta["memory"], flush=True)
                print("  cost:", meta["cost_analysis"],
                      "collectives:", meta["hlo"]["collective_ops"],
                      flush=True)
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
