#!/usr/bin/env python
"""Lower the canonical train-step matrix and lint every program.

Runs the ``cross_pod_mode x overlap x det x zero1`` matrix (every valid
combination — overlap and deterministic_reduce are bucketed-only and
mutually exclusive) on a (pod=2, data=2) mesh over 4 forced host CPU
devices with the reduced llama3.2-1b, then runs every
``repro.analysis.lint`` rule over both HLO dialects of each cell
against the declared budgets in ``src/repro/analysis/budgets.json``.

Usage::

    python scripts/lint_hlo.py                      # full matrix, exit 1 on findings
    python scripts/lint_hlo.py --cells xla zero1_det
    python scripts/lint_hlo.py --update-budgets     # regenerate budgets.json
    python scripts/lint_hlo.py --json /tmp/lint.json

The script re-executes itself with a pinned
``--xla_force_host_platform_device_count=4`` CPU backend so the mesh
shape (and therefore the budgets) is identical no matter the ambient
XLA_FLAGS (CI also runs tier-1 under an 8-device flag).
"""
import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

N_DEVICES = 4
MESH_SHAPE = (2, 2)                    # (pod, data)
CHIPS_PER_POD = 2
BUCKET_BYTES = 1 << 20
ARCH = "llama3.2-1b"

# every valid cell of the matrix; overlap/det apply to bucketed modes
# only and are mutually exclusive (make_train_step validates both)
CELLS = {
    "xla": dict(cross_pod_mode="xla"),
    "hier": dict(cross_pod_mode="hier"),
    "hier_bucketed": dict(cross_pod_mode="hier_bucketed"),
    "hier_bucketed_overlap": dict(cross_pod_mode="hier_bucketed",
                                  overlap=True),
    "hier_bucketed_det": dict(cross_pod_mode="hier_bucketed",
                              deterministic_reduce=True),
    "zero1": dict(cross_pod_mode="hier_bucketed_zero1"),
    "zero1_overlap": dict(cross_pod_mode="hier_bucketed_zero1",
                          overlap=True),
    "zero1_det": dict(cross_pod_mode="hier_bucketed_zero1",
                      deterministic_reduce=True),
}


def _reexec(argv):
    env = dict(os.environ)
    env["_LINT_HLO_INNER"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={N_DEVICES}")
    env["PYTHONPATH"] = SRC + (
        ":" + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    return subprocess.call([sys.executable, os.path.abspath(__file__)]
                           + argv, env=env)


def _split_budget(count, n_buckets):
    """Heuristic (fixed, per_bucket) split of a measured count.

    Per-bucket collectives dominate in the bucketed modes, so the
    integer quotient is attributed per bucket and the remainder (loss /
    grad-norm reductions) is fixed.  budgets.json is versioned — edit
    the split by hand when the heuristic misattributes."""
    if n_buckets > 1 and count >= n_buckets:
        per = count // n_buckets
        return count - per * n_buckets, per
    return count, 0


def run_matrix(args):
    import jax  # noqa: E402  (after the re-exec pinned the backend)
    from repro import optim, train
    from repro import parallel as PX
    from repro.analysis import hlo, ir
    from repro.analysis.lint import (LintContext, budget_for,
                                     load_budgets, run_rules)
    from repro.models.registry import build_model, get_config, \
        reduced_config
    from repro.sharding import make_rules

    assert jax.device_count() == N_DEVICES, jax.devices()
    mesh = PX.make_device_mesh(MESH_SHAPE, ("pod", "data"))
    # fsdp=False for every cell: the manual sync modes require
    # replicated params, and keeping the xla cell on the same rules
    # makes the budgets comparable across the matrix
    rules = make_rules(mesh, fsdp=False)
    cfg = reduced_config(get_config(ARCH))
    model = build_model(cfg, remat=False)
    ocfg = optim.AdamWConfig(peak_lr=1e-3, warmup_steps=5,
                             total_steps=100)
    budgets = None if args.update_budgets else load_budgets()

    cells = args.cells or list(CELLS)
    unknown = sorted(set(cells) - set(CELLS))
    if unknown:
        sys.exit(f"unknown cells {unknown}; known: {sorted(CELLS)}")

    report = {}
    measured = {}
    n_findings = 0
    for name in cells:
        kw = CELLS[name]
        h = train.train_step_hlo(model, ocfg, rules=rules,
                                 bucket_bytes=BUCKET_BYTES, **kw)
        optimized = ir.parse(h.compiled_text)
        lowered = ir.parse(h.lowered_text)
        config = {
            "cell": name,
            "cross_pod_mode": kw["cross_pod_mode"],
            "overlap": bool(kw.get("overlap")),
            "deterministic_reduce": bool(kw.get("deterministic_reduce")),
            "slow_compress_bits": int(kw.get("slow_compress_bits", 0)),
            "chips_per_pod": CHIPS_PER_POD,
            "n_buckets": h.n_buckets,
            "grad_bytes": h.grad_bytes,
        }
        if args.update_budgets:
            stats = hlo.analyze(optimized, chips_per_pod=CHIPS_PER_POD)
            fixed, per_bucket = {}, {}
            for k, c in sorted(stats.collective_ops.items()):
                f, p = _split_budget(c, h.n_buckets)
                if f:
                    fixed[k] = f
                if p:
                    per_bucket[k] = p
            cell = {"fixed": fixed, "per_bucket": per_bucket,
                    "max_operand_bytes_factor": round(
                        stats.collective_operand_bytes
                        / h.grad_bytes * 1.25, 2)}
            measured[name] = cell
            findings = []
        else:
            ctx = LintContext(optimized=optimized, lowered=lowered,
                              config=config,
                              budget=budget_for(budgets, name))
            findings = run_rules(ctx, only=args.only or None)
        report[name] = {"config": config,
                        "findings": [f.to_dict() for f in findings]}
        status = "clean" if not findings else f"{len(findings)} finding(s)"
        print(f"[lint-hlo] {name:24s} n_buckets={h.n_buckets} {status}")
        for f in findings:
            print("  " + f.format().replace("\n", "\n  "))
        n_findings += len(findings)

    if args.update_budgets:
        from repro.analysis.lint.core import BUDGETS_PATH
        out = {
            "version": 1,
            "comment": ("per-step collective budgets for the lint "
                        "matrix; regenerate with "
                        "scripts/lint_hlo.py --update-budgets"),
            "arch": ARCH + " (reduced)",
            "mesh": list(MESH_SHAPE),
            "bucket_bytes": BUCKET_BYTES,
            "cells": measured,
        }
        with open(BUDGETS_PATH, "w") as f:
            json.dump(out, f, indent=2, sort_keys=False)
            f.write("\n")
        print(f"[lint-hlo] wrote {BUDGETS_PATH}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
    if n_findings:
        print(f"[lint-hlo] FAIL: {n_findings} finding(s)")
        return 1
    print(f"[lint-hlo] OK: {len(cells)} cell(s) clean")
    return 0


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--cells", nargs="*", default=None,
                    help="subset of matrix cells (default: all)")
    ap.add_argument("--only", nargs="*", default=None,
                    help="subset of lint rules to run")
    ap.add_argument("--update-budgets", action="store_true",
                    help="rewrite analysis/budgets.json from measured "
                         "collective counts (with 25%% bytes headroom)")
    ap.add_argument("--json", default=None,
                    help="write the full report to this path")
    args = ap.parse_args()
    if os.environ.get("_LINT_HLO_INNER") != "1":
        sys.exit(_reexec(sys.argv[1:]))
    sys.exit(run_matrix(args))


if __name__ == "__main__":
    main()
