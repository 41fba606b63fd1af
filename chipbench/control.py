"""Readings of the control and of the planted faults of a training cell,
from the float32 reference put in the program's place.

    python3 -m chipbench.control WORKLOAD OUT_DIR SEED [SEED ...]

For each seed, at the cell's own batch and sequence length, on the first
chip: the reference's three steps, then each stand-in below, each
compared with the reference by ``harness.compare`` and judged by the
cell's limits as a run is (``correct``):

* ``fp8``: the control, every matmul of the reference computed from
  fp8 operands (per-tensor scales; e4m3 forward, the incoming gradient
  e5m2 in the backward pass), one precision step below
  the bfloat16 the configurations state;
* ``half_batch``: the reference on the first half of each batch's rows.

A step that returns its state unchanged reads 1 for ``grad_gap`` and
``update_gap`` by their definition and needs no run.  Writes
``OUT_DIR/control.jsonl`` and prints one line per seed and stand-in.
Needs a TPU; the benchmark's own runs never run this.
"""
from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Dict, Iterator


def stand_in_readings(cell) -> Iterator[Dict[str, Any]]:
    """The reference and each stand-in for one cell and seed."""
    from chipbench import harness as H
    from chipbench.drivers.train import copy_batch
    names = H.leaf_names(H.make_weights(cell)())
    batches = [copy_batch(cell, s) for s in range(3)]
    t0 = time.perf_counter()
    ref = H.reference_readings(cell, batches)
    ref_s = time.perf_counter() - t0
    stand_ins = {"fp8": dict(matmul="fp8"),
                 "half_batch": dict(rows=slice(0, cell.mix["global_batch"]
                                               // 2))}
    for what, kw in stand_ins.items():
        nums = H.compare(H.reference_readings(cell, batches, **kw), ref,
                         names)
        checks = H.checks_line(nums, cell.limits)
        yield {"workload": cell.name, "seed": cell.seed, "stand_in": what,
               "correct": H.all_within(checks), "reference_s": ref_s,
               "loss_gaps_by_step": nums["loss_gaps_by_step"],
               "worst_grad_leaf": nums["worst_grad_leaf"],
               "worst_update_leaf": nums["worst_update_leaf"],
               "ref_losses": ref.losses, "leaf_norms": nums["leaf_norms"],
               "checks": checks}


def main(argv) -> int:
    workload, out_dir, seeds = argv[0], argv[1], [int(s) for s in argv[2:]]
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src"))
    from chipbench import harness as H
    from chipbench.run import check_devices
    from repro.compile_cache import enable_compile_cache
    os.makedirs(out_dir, exist_ok=True)
    check_devices(1)
    enable_compile_cache()
    for seed in seeds:
        cell = H.load_cell(workload, seed=seed, seconds=0.0, trace=False,
                           t_start=time.perf_counter())
        for rec in stand_in_readings(cell):
            with open(os.path.join(out_dir, "control.jsonl"), "a") as f:
                f.write(json.dumps(rec) + "\n")
            print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
