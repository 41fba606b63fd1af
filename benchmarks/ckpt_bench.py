"""Checkpoint benchmark: per-rank bytes + save/restore wallclock.

Two measurements:

1. **Byte accounting** (exact, full ``llama3.2-1b``): per-rank bytes a
   sharded ZeRO-1 checkpoint writes vs. the gathered-full legacy
   baseline.  The flat f32 state (masters + both moments) shards 1/F
   over the fast axis, so per-rank sharded bytes for the optimizer state
   are expected at ~1/F of the gathered write — the restart-at-scale
   win: checkpoint time stops growing with model size per rank.

2. **Wallclock** (reduced config, 8 fake host devices, subprocess): real
   ``save_sharded`` / ``restore_sharded`` round trips for a sharded
   zero1 state on a (2, 4) pod x data mesh, including a reshard-restore
   onto the (4, 2) re-factorization (the elastic repack path), against
   the legacy gathered save/restore.

Writes ``BENCH_ckpt.json`` (CI uploads ``BENCH_*.json``) and emits the
usual ``name,us,derived`` CSV rows.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
DEFAULT_OUT = os.path.join(REPO, "BENCH_ckpt.json")
ARCH = "llama3.2-1b"
MESH_SHAPE = (2, 4)                    # (pod, data) over 8 fake devices
RESHARD_SHAPE = (4, 2)                 # elastic repack target


def _accounting() -> dict:
    """Exact per-rank byte math for the full arch (no training)."""
    import jax

    from repro.collectives import bucketing as BK
    from repro.collectives.deterministic import det_align
    from repro.models.registry import build_model, get_config

    n_pod, n_data = MESH_SHAPE
    cfg = get_config(ARCH)
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.key(0))
    param_bytes = sum(l.dtype.itemsize * math.prod(l.shape)
                      for l in jax.tree.leaves(shapes))
    layout = BK.plan_buckets(shapes, align=det_align(n_data))
    flat_elems = layout.n_padded_elements()
    opt_f32 = 3 * 4 * flat_elems           # masters + mu + nu, f32
    return {
        "arch": ARCH,
        "mesh": {"pod": n_pod, "data": n_data},
        "n_buckets": layout.n_buckets,
        "param_bytes": param_bytes,
        "opt_state_bytes_full": opt_f32,
        # legacy gathered format: the saving host writes everything
        "legacy_rank_bytes": param_bytes + opt_f32,
        # sharded: every rank writes its 1/F opt shards; rank 0 also
        # writes the replicated leaves (params + step) + manifest
        "sharded_rank_bytes": opt_f32 // n_data,
        "sharded_rank0_bytes": param_bytes + opt_f32 // n_data,
        "opt_shard_frac": (opt_f32 // n_data) / opt_f32,
        "expected_frac": 1.0 / n_data,
    }


def _inner(out_path: str, quick: bool) -> None:
    import time

    import jax

    from repro import ckpt
    from repro import checkpoint as legacy
    from repro import optim
    from repro import parallel as PX
    from repro.models.registry import build_model, get_config, \
        reduced_config
    from repro.train import init_sharded_zero1, make_bucket_layout
    import shutil
    import tempfile

    acct = _accounting()

    rcfg = reduced_config(get_config(ARCH))
    model = build_model(rcfg, remat=False)
    mesh = PX.make_device_mesh(MESH_SHAPE, ("pod", "data"))
    params = model.init(jax.random.key(0))
    layout = make_bucket_layout(params, mesh, deterministic=True)
    state, opt_sh = init_sharded_zero1(optim.AdamWConfig(), params,
                                       layout, mesh)

    def timed(fn, iters=1 if quick else 3):
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    base = tempfile.mkdtemp()
    sdir = ckpt.step_dir(base, 1)
    ldir = ckpt.step_dir(base, 2)

    def save_shard():
        ckpt.save_sharded(sdir, 1, (params, state), layout=layout,
                          mesh=mesh)

    def save_legacy():
        legacy.save(ldir, 2, (params, state))

    wall = {"save_sharded_s": timed(save_shard),
            "save_legacy_s": timed(save_legacy)}

    def restore_same():
        ckpt.restore_sharded(sdir, (params, state),
                             shardings=(None, opt_sh))

    wall["restore_sharded_s"] = timed(restore_same)

    mesh2 = PX.make_device_mesh(RESHARD_SHAPE, ("pod", "data"))
    params2 = model.init(jax.random.key(0))
    layout2 = make_bucket_layout(params2, mesh2, deterministic=True)
    assert layout2.bucket_sizes == layout.bucket_sizes
    state2, opt_sh2 = init_sharded_zero1(optim.AdamWConfig(), params2,
                                         layout2, mesh2)

    def restore_reshard():
        ckpt.restore_sharded(sdir, (params2, state2),
                             shardings=(None, opt_sh2))

    wall["restore_resharded_s"] = timed(restore_reshard)

    def restore_legacy():
        legacy.restore(ldir, (params, state))

    wall["restore_legacy_s"] = timed(restore_legacy)

    # verify the reshard actually recovered the state before reporting
    import numpy as np
    _, (rp, rs) = ckpt.restore_sharded(sdir, (params2, state2),
                                       shardings=(None, opt_sh2))
    for a, b in zip(jax.tree.leaves((params, state)),
                    jax.tree.leaves((rp, rs))):
        assert np.array_equal(np.asarray(a), np.asarray(b))

    # measured (not analytic) shard fraction: walk the manifest the save
    # actually wrote — if save_sharded ever regressed into writing full
    # gathered buckets, this is the number that catches it
    man = ckpt.read_manifest(sdir)
    measured_frac = 0.0
    n_sharded = 0
    for e in man.leaves.values():
        if e.kind != "sharded":
            continue
        n_sharded += 1
        total = int(np.prod(e.shape))
        for s in e.shards:
            vol = 1
            for a, b in s.index:
                vol *= b - a
            measured_frac = max(measured_frac, vol / total)
    assert n_sharded > 0
    shutil.rmtree(base, ignore_errors=True)

    frac = acct["opt_shard_frac"]
    n_data = MESH_SHAPE[1]
    out = {
        "quick": quick,
        "accounting": acct,
        "wallclock": {**wall,
                      "reduced_arch": ARCH,
                      "reshard": {"from": list(MESH_SHAPE),
                                  "to": list(RESHARD_SHAPE)}},
        "acceptance": {
            "opt_shard_frac": frac,
            "measured_max_shard_frac": measured_frac,
            "n_sharded_leaves": n_sharded,
            "bound": 1.0 / n_data + 1e-9,
            "pass": bool(frac <= 1.0 / n_data + 1e-9
                         and measured_frac <= 1.0 / n_data + 1e-9),
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
    cmd = [sys.executable, "-m", "benchmarks.ckpt_bench", "--inner",
           "--out", out_path] + (["--quick"] if quick else [])
    res = subprocess.run(cmd, capture_output=True, text=True,
                         timeout=1800, env=env, cwd=REPO)
    if res.returncode != 0:
        raise RuntimeError(f"ckpt bench inner failed:\n"
                           f"{res.stderr[-4000:]}")
    with open(out_path) as f:
        data = json.load(f)
    acct = data["accounting"]
    emit("ckpt_bytes_per_rank", 0.0,
         f"sharded={acct['sharded_rank_bytes']};"
         f"legacy={acct['legacy_rank_bytes']};"
         f"opt_frac={acct['opt_shard_frac']:.4f}"
         f"~1/F={acct['expected_frac']:.4f}")
    for k, v in data["wallclock"].items():
        if k.endswith("_s"):
            emit(f"ckpt_{k[:-2]}", v * 1e6, "reduced-config zero1 state")
    acc = data["acceptance"]
    emit("ckpt_acceptance", 0.0,
         f"opt_shard_frac={acc['opt_shard_frac']:.4f};"
         f"measured_max_shard_frac={acc['measured_max_shard_frac']:.4f}"
         f"<=bound={acc['bound']:.4f};pass={acc['pass']}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--inner", action="store_true")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=DEFAULT_OUT)
    args = ap.parse_args()
    if args.inner:
        _inner(args.out, args.quick)
    else:
        main(args.quick, args.out)
