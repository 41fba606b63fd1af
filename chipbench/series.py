"""Run cells of the benchmark several times, one process after another.

    python3 -m chipbench.series OUT_DIR SECONDS WORKLOAD:SEED[:TRACE] ...

Each run is ``python3 -m chipbench.run`` in a child process of its own
(this process never touches JAX, so each child has the chips to
itself).  Each run's standard output and error go to
``OUT_DIR/<i>.<workload>.<seed>.<trace>.{out,err}``; each result line is
appended to ``OUT_DIR/results.jsonl`` with the run's workload, seed,
trace flag, exit code and wall seconds.  Used to measure the spread of a
cell (two sets of runs with the same seeds) and to read limits.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time


def main(argv) -> int:
    out_dir, seconds, specs = argv[0], argv[1], argv[2:]
    os.makedirs(out_dir, exist_ok=True)
    worst = 0
    for i, spec in enumerate(specs):
        parts = spec.split(":")
        workload, seed = parts[0], parts[1]
        trace = parts[2] if len(parts) > 2 else "0"
        stem = os.path.join(out_dir, f"{i:02d}.{workload}.{seed}.{trace}")
        t0 = time.perf_counter()
        with open(stem + ".out", "w") as fo, open(stem + ".err", "w") as fe:
            rc = subprocess.call(
                [sys.executable, "-m", "chipbench.run", "--workload",
                 workload, "--seed", seed, "--seconds", seconds,
                 "--trace", trace], stdout=fo, stderr=fe)
        wall = time.perf_counter() - t0
        worst = max(worst, rc)
        lines = open(stem + ".out").read().strip().splitlines()
        try:
            result = json.loads(lines[-1]) if lines else None
        except json.JSONDecodeError:
            result = None
        rec = {"workload": workload, "seed": int(seed), "trace": int(trace),
               "rc": rc, "wall_s": wall, "result": result}
        with open(os.path.join(out_dir, "results.jsonl"), "a") as f:
            f.write(json.dumps(rec) + "\n")
        summary = "no result" if result is None else json.dumps(
            {"correct": result.get("correct"),
             "metrics": {k: v["value"] for k, v in
                         result.get("metrics", {}).items()},
             "checks": {k: v["value"] for k, v in
                        result.get("checks", {}).items()}})
        print(f"{workload} seed={seed} trace={trace} rc={rc} "
              f"wall={wall:.1f}s {summary}", flush=True)
    return worst


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
