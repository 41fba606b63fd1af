"""Fig. 11: collective bandwidth, SHM vs NET, 2/4/6/8 MIG instances —
plus the TPU-adapted equivalent: hierarchical vs flat all-reduce measured
in lowered-HLO collective bytes (run in a fake-device subprocess)."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

from benchmarks.common import emit, time_fn
from repro.core.jct_model import WORKLOADS
from repro.collectives.transport import gpu_collective, \
    hierarchical_vs_flat_bytes


def run_gpu_model() -> dict:
    out = {}
    for n in (2, 4, 6, 8):
        per_gpu = (n // 2, n - n // 2) if n > 1 else (1,)
        for op in ("all_reduce", "all_gather"):
            shm = gpu_collective(op, 128e6, transport="SHM",
                                 leaves_per_gpu=(n,) if n <= 7
                                 else (4, 4))
            net = gpu_collective(op, 128e6, transport="NET",
                                 leaves_per_gpu=per_gpu,
                                 concurrent_net_jobs=1)
            out[f"{op}_{n}"] = (shm.bus_bandwidth_gbps,
                                net.bus_bandwidth_gbps)
    return out


def run_tpu_hlo() -> str:
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro import parallel as PX
        from repro.collectives.hierarchical import make_hier_all_reduce
        from repro.analysis.hlo import analyze
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = PX.make_device_mesh((2, 4), ("pod", "data"))
        x = jax.ShapeDtypeStruct((8, 1 << 20), jnp.float32)
        rows = []
        for name, kw in (("flat", dict(flat=True)), ("hier", dict()),
                         ("hier_int8", dict(compress_bits=8))):
            fn = make_hier_all_reduce(mesh, fast_axis="data",
                                      slow_axis="pod", **kw)
            txt = fn.lower(x).compile().as_text()
            st = analyze(txt, chips_per_pod=4)
            rows.append(f"{name}_crosspod={st.cross_pod_bytes/1e6:.1f}MB")
        print("|".join(rows))
        """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src)
    res = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=560,
                         env=env)
    if res.returncode != 0:
        return f"hlo_measure_failed({res.stderr.strip()[-120:]})"
    return res.stdout.strip().splitlines()[-1]


def main() -> None:
    us = time_fn(run_gpu_model, warmup=0, iters=3)
    out = run_gpu_model()
    for key, (shm, net) in out.items():
        emit(f"fig11_{key}", us,
             f"shm_busbw={shm:.2f}GBps;net_busbw={net:.2f}GBps")
    hb = hierarchical_vs_flat_bytes(1e9, fast=16, slow=2)
    emit("fig11_tpu_analytic", us,
         f"slow_bytes_reduction={hb['reduction']:.1f}x")
    emit("fig11_tpu_hlo", 0.0, run_tpu_hlo())


if __name__ == "__main__":
    main()
