"""Gradient-sync benchmark: collective-op counts, slow-axis bytes, step time.

Compares the cross-pod gradient-sync schedules on a (pod, data) mesh:

- ``flat``             single-level psum over both tiers (stock-NCCL
                       workaround baseline);
- ``hier_per_tensor``  hierarchical schedule per gradient leaf (3
                       collectives + pad per tensor — latency-bound);
- ``hier_bucketed``    the schedule once per flat f32 bucket; without
                       compute/comm overlap the optimal bucket size is
                       "everything", so the headline entry fuses the whole
                       gradient set into one bucket and a sweep over
                       bucket sizes shows the curve;
- ``hier_bucketed_int8``  + int8 slow hop;
- ``hier_bucketed_overlap``  the multi-bucket software pipeline
                       (``overlap=True``): bucket i+1's fast reduce-scatter
                       issues under bucket i's slow hop.

Collective-op counts and slow-axis bytes come from the compiled HLO via
``repro.analysis.hlo`` (the Fig. 11 methodology: ``cross_pod_bytes`` is
ring-model traffic crossing the pod cut, ``cross_pod_operand_bytes`` the
payload handed to those ops).  Every entry also runs the
``slow_collective_chains`` dependency checker: ``independent=True``
proves from the lowered HLO that no bucket's slow collective
data-depends on another's — the pipelinability invariant the overlapped
schedule relies on.  ``jct_model`` prices the serial vs pipelined
schedules analytically (``core.jct_model.hier_sync_makespan`` over the
ICI/DCN tier bandwidths): ``serial - overlapped`` is the slow-tier
latency the pipeline hides.  The XLA CPU pipeline does not merge
manual-mode collectives, so the counts are exactly what the schedule
issues.  Step wall-clock times real train steps per ``cross_pod_mode`` on
the reduced config over 8 fake host devices.

Writes ``BENCH_grad_sync.json`` (CI uploads ``BENCH_*.json`` artifacts)
and emits the usual ``name,us,derived`` CSV rows.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
DEFAULT_OUT = os.path.join(REPO, "BENCH_grad_sync.json")
ARCH = "llama3.2-1b"
MESH_SHAPE = (2, 4)                    # (pod, data) over 8 fake devices
BUCKET_MB_SWEEP = (64, 512)


def _inner(quick: bool, out_path: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro import optim
    from repro import parallel as PX
    from repro.analysis import ir
    from repro.analysis.hlo import (DCN_BW_PER_CHIP, ICI_BW, analyze,
                                    slow_collective_chains)
    from repro.collectives import bucketing as BK
    from repro.core.jct_model import (bucket_sync_times,
                                      exposed_slow_fraction,
                                      hier_sync_makespan)
    from repro.collectives.hierarchical import (flat_all_reduce_mean,
                                                hier_all_reduce_mean)
    from repro.models.registry import build_model, get_config, \
        reduced_config
    from repro.sharding import make_rules
    from repro.train import make_bucket_layout, make_jitted_train_step
    from benchmarks.common import time_fn

    mesh = PX.make_device_mesh(MESH_SHAPE, ("pod", "data"))
    n_pod, n_data = MESH_SHAPE

    # ---------------- HLO accounting over the gradient pytree ------------
    cfg = get_config(ARCH)
    if quick:
        cfg = reduced_config(cfg)
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.key(0))
    grads = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), shapes)
    n_leaves = len(jax.tree.leaves(grads))
    total_bytes = sum(4 * math.prod(l.shape)
                      for l in jax.tree.leaves(grads))

    def per_tensor_sync(compress_bits=0, flat=False):
        def fn(g):
            if flat:
                return jax.tree.map(
                    lambda x: flat_all_reduce_mean(
                        x, axes=("pod", "data")), g)
            return jax.tree.map(
                lambda x: hier_all_reduce_mean(
                    x, fast_axis="data", slow_axis="pod",
                    compress_bits=compress_bits), g)
        return fn, None

    def bucketed_sync(bucket_bytes, compress_bits=0, overlap=False):
        layout = BK.plan_buckets(grads, bucket_bytes=bucket_bytes,
                                 align=n_data)

        def fn(g):
            b = BK.flatten_to_buckets(layout, g)
            s = BK.hier_reduce_bucket_shards(
                b, fast_axis="data", slow_axis="pod",
                compress_bits=compress_bits, overlap=overlap)
            full = BK.all_gather_buckets(s, fast_axis="data")
            return BK.unflatten_from_buckets(layout, full,
                                             dtype=jnp.float32)
        return fn, layout

    fuse_all = total_bytes + 4 * n_data          # one bucket for everything
    pipeline_bytes = -(-total_bytes // 4)        # >= 2 buckets to pipeline
    sync_cases = [
        ("flat", per_tensor_sync(flat=True), None),
        ("hier_per_tensor", per_tensor_sync(), None),
        ("hier_bucketed", bucketed_sync(fuse_all), fuse_all),
        ("hier_bucketed_int8", bucketed_sync(fuse_all, compress_bits=8),
         fuse_all),
        ("hier_bucketed_overlap",
         bucketed_sync(pipeline_bytes, overlap=True), pipeline_bytes),
    ] + [(f"hier_bucketed_{mb}mb", bucketed_sync(mb << 20), mb << 20)
         for mb in (() if quick else BUCKET_MB_SWEEP)]

    specs = jax.tree.map(lambda _: P(), grads)
    sync_hlo = {}
    for name, (fn, layout), bucket_bytes in sync_cases:
        jitted = jax.jit(PX.shard_map(
            fn, mesh=mesh, in_specs=(specs,), out_specs=specs,
            check_vma=False, axis_names={"pod", "data"}))
        # parse once into the shared IR; both checkers accept a Module
        mod = ir.parse(jitted.lower(grads).compile().as_text())
        st = analyze(mod, chips_per_pod=n_data)
        chain = slow_collective_chains(mod, chips_per_pod=n_data)
        sync_hlo[name] = {
            "collective_ops": st.collective_ops,
            "n_collective_ops": int(sum(st.collective_ops.values())),
            "cross_pod_bytes": st.cross_pod_bytes,
            "cross_pod_operand_bytes": st.cross_pod_operand_bytes,
            "slow_operand_frac": st.cross_pod_operand_bytes / total_bytes,
            "n_buckets": layout.n_buckets if layout else None,
            "bucket_bytes": bucket_bytes,
            "slow_chain": chain.to_dict(),
        }

    # ------------- analytic schedule pricing (serial vs pipelined) --------
    ov_layout = BK.plan_buckets(grads, bucket_bytes=pipeline_bytes,
                                align=n_data)
    stage_times = bucket_sync_times(
        ov_layout.bucket_sizes, nf=n_data, ns=n_pod,
        fast_bps=ICI_BW, slow_bps=DCN_BW_PER_CHIP)
    serial_s = hier_sync_makespan(*stage_times, overlap=False)
    overlapped_s = hier_sync_makespan(*stage_times, overlap=True)
    jct = {
        "n_buckets": ov_layout.n_buckets,
        "bucket_numels": list(ov_layout.bucket_sizes),
        "serial_s": serial_s,
        "overlapped_s": overlapped_s,
        "hidden_slow_s": serial_s - overlapped_s,
        "speedup": serial_s / max(overlapped_s, 1e-12),
        "exposed_slow_frac_serial": exposed_slow_fraction(
            *stage_times, overlap=False),
        "exposed_slow_frac_overlap": exposed_slow_fraction(
            *stage_times, overlap=True),
    }

    # ---------------- step wall-clock on the reduced config --------------
    rcfg = reduced_config(get_config(ARCH))
    model = build_model(rcfg, remat=False)
    rules = make_rules(mesh, fsdp=False)
    B, S = 16, 32
    rng = jax.random.key(1)
    batch = {"tokens": jax.random.randint(rng, (B, S), 0,
                                          rcfg.vocab_size),
             "targets": jax.random.randint(rng, (B, S), 0,
                                           rcfg.vocab_size)}
    ocfg = optim.AdamWConfig(peak_lr=1e-3, warmup_steps=5,
                             total_steps=100)
    # 'compressed' is absent: its partial-manual shard_map (auto 'data'
    # inside manual 'pod') trips a fatal XLA check on jax 0.4.37's CPU
    # backend for (pod, data) meshes — same class of crash PR 1 hit with
    # flash-decode, uncatchable from Python
    multibucket = 1 << 20          # several buckets on the reduced config
    step_cases = [("hier", "hier", {}),
                  ("hier_bucketed", "hier_bucketed", {}),
                  ("hier_bucketed_multibucket", "hier_bucketed",
                   {"bucket_bytes": multibucket}),
                  ("hier_bucketed_multibucket_overlap", "hier_bucketed",
                   {"bucket_bytes": multibucket, "overlap": True})]
    if not quick:
        step_cases = ([("xla", "xla", {})] + step_cases +
                      [("hier_bucketed_zero1", "hier_bucketed_zero1", {}),
                       ("hier_bucketed_zero1_overlap",
                        "hier_bucketed_zero1",
                        {"bucket_bytes": multibucket, "overlap": True})])
    step_us = {}
    iters = 2 if quick else 5
    for label, mode, kw in step_cases:
        params = model.init(jax.random.key(0))
        if mode == "hier_bucketed_zero1":
            layout = make_bucket_layout(
                params, mesh,
                bucket_bytes=kw.get("bucket_bytes",
                                    BK.DEFAULT_BUCKET_BYTES))
            state = optim.init_bucketed(ocfg, params, layout)
        else:
            state = optim.init(ocfg, params)
        step = make_jitted_train_step(model, ocfg, accum=1, rules=rules,
                                      cross_pod_mode=mode, **kw)
        box = [params, state]

        def run():
            p, s, m = step(box[0], box[1], batch)
            box[0], box[1] = p, s
            jax.block_until_ready(m["loss"])

        with mesh:
            step_us[label] = time_fn(run, warmup=1, iters=iters)

    # ---------------- acceptance summary ---------------------------------
    op_reduction = (sync_hlo["hier_per_tensor"]["n_collective_ops"]
                    / max(sync_hlo["hier_bucketed"]["n_collective_ops"], 1))
    slow_frac = sync_hlo["hier_bucketed"]["slow_operand_frac"]
    slow_bound = 1.0 / n_data + 0.05
    ov = sync_hlo["hier_bucketed_overlap"]
    overlap_ok = bool(ov["n_buckets"] >= 2
                      and ov["slow_chain"]["independent"]
                      and jct["overlapped_s"] < jct["serial_s"])
    out = {
        "arch": ARCH,
        "quick": quick,
        "mesh": {"pod": n_pod, "data": n_data},
        "n_grad_leaves": n_leaves,
        "total_grad_bytes": total_bytes,
        "sync_hlo": sync_hlo,
        "jct_model": jct,
        "step_wallclock_us": step_us,
        "acceptance": {
            "op_reduction_bucketed_vs_per_tensor": op_reduction,
            "op_reduction_target": 10.0,
            "slow_operand_frac_bucketed": slow_frac,
            "slow_frac_bound": slow_bound,
            "overlap_n_buckets": ov["n_buckets"],
            "overlap_slow_collectives_independent": (
                ov["slow_chain"]["independent"]),
            "overlap_hidden_slow_s": jct["hidden_slow_s"],
            "overlap_pipelinable": overlap_ok,
            "pass": bool(op_reduction >= 10.0 and slow_frac <= slow_bound
                         and overlap_ok),
        },
    }
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"WROTE {out_path}")


def main(quick: bool = False, out_path: str = DEFAULT_OUT) -> None:
    """Run the measurement in a fake-device subprocess, emit CSV rows."""
    from benchmarks.common import emit

    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{MESH_SHAPE[0] * MESH_SHAPE[1]}")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(REPO, "src"), REPO] +
        env.get("PYTHONPATH", "").split(os.pathsep))
    cmd = [sys.executable, "-m", "benchmarks.grad_sync_bench", "--inner",
           "--out", out_path] + (["--quick"] if quick else [])
    res = subprocess.run(cmd, capture_output=True, text=True,
                         timeout=3000, env=env, cwd=REPO)
    if res.returncode != 0:
        raise RuntimeError(
            f"grad_sync inner failed:\n{res.stderr[-4000:]}")
    with open(out_path) as f:
        data = json.load(f)
    for name, row in data["sync_hlo"].items():
        emit(f"grad_sync_{name}", 0.0,
             f"n_collectives={row['n_collective_ops']};"
             f"slow_operand_frac={row['slow_operand_frac']:.4f};"
             f"slow_chain_depth={row['slow_chain']['max_depth']}")
    for mode, us in data["step_wallclock_us"].items():
        emit(f"grad_sync_step_{mode}", us, "reduced-config train step")
    jct = data["jct_model"]
    emit("grad_sync_overlap_model", jct["overlapped_s"] * 1e6,
         f"serial_us={jct['serial_s']*1e6:.1f};"
         f"speedup={jct['speedup']:.2f}x;"
         f"exposed_slow_frac={jct['exposed_slow_frac_overlap']:.3f}")
    acc = data["acceptance"]
    emit("grad_sync_acceptance", 0.0,
         f"op_reduction={acc['op_reduction_bucketed_vs_per_tensor']:.1f}x;"
         f"slow_frac={acc['slow_operand_frac_bucketed']:.4f}"
         f"<=bound={acc['slow_frac_bound']:.4f};"
         f"overlap_pipelinable={acc['overlap_pipelinable']};"
         f"pass={acc['pass']}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--inner", action="store_true")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=DEFAULT_OUT)
    args = ap.parse_args()
    if args.inner:
        _inner(args.quick, args.out)
    else:
        main(args.quick, args.out)
