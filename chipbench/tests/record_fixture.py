"""Record the small device trace that ``test_trace.py`` reads.

    python -m chipbench.tests.record_fixture OUT_DIR

Run on the chip, in one process that holds every chip it should trace.
Each step is a jitted bf16 matmul chain followed by an all-reduce over
the chips (a plain sum on one chip), with a host sleep of ``GAP_S``
between steps, and a host marker (``chipbench.mark <i>``) at the start
of each step, the way ``chipbench.trace`` finds step boundaries in a
run.  Writes ``OUT_DIR/fixture.xplane.pb`` and ``OUT_DIR/fixture.json``
(what the recording knows: steps, gap, devices), and prints a summary of
the planes and lines.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile
import time

STEPS = 4
GAP_S = 0.05
N = 2048


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    devs = jax.devices()
    mesh = jax.sharding.Mesh(devs, ("d",))

    @jax.jit
    def step(x, w):
        for _ in range(4):
            x = jnp.tanh(x @ w)
        return jax.lax.with_sharding_constraint(
            jnp.sum(x, axis=0, keepdims=True), NamedSharding(mesh, P()))

    x = jax.device_put(jnp.ones((N * len(devs), N), jnp.bfloat16) * 0.01,
                       NamedSharding(mesh, P("d")))
    w = jax.device_put(jnp.eye(N, dtype=jnp.bfloat16),
                       NamedSharding(mesh, P()))
    jax.block_until_ready(step(x, w))
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        for i in range(STEPS + 1):
            with jax.profiler.TraceAnnotation(f"chipbench.mark {i}"):
                pass
            if i == STEPS:
                break
            jax.block_until_ready(step(x, w))
            time.sleep(GAP_S)
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True)
        os.makedirs(out_dir, exist_ok=True)
        shutil.copy(path, os.path.join(out_dir, "fixture.xplane.pb"))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    with open(os.path.join(out_dir, "fixture.json"), "w") as f:
        json.dump({"steps": STEPS, "gap_s": GAP_S, "n_devices": len(devs),
                   "device_kind": devs[0].device_kind}, f)

    from jax.profiler import ProfileData
    pd = ProfileData.from_file(os.path.join(out_dir, "fixture.xplane.pb"))
    for plane in pd.planes:
        lines = list(plane.lines)
        print("PLANE", plane.name, len(lines))
        for line in lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for e in evs[:12]:
                print("    ", repr(e.name)[:90], e.start_ns, e.duration_ns)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
