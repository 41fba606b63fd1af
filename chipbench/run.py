"""The chip benchmark.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine, in one
process, and prints one JSON line last on stdout:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number that decided ``correct`` beside its limit, which also end
standard error.

Exits non-zero with no result line when JAX finds no TPU, fewer chips
than the cell asks for, or a device kind missing from ``peaks.json``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import json
import math
import os
import sys
import traceback
from typing import Any, Dict, List

from chipbench import harness as H


class NoChip(SystemExit):
    pass


def check_devices(chips: int) -> Dict[str, Any]:
    """The peaks of this machine's chips; refuses anything but enough
    TPUs of a kind ``peaks.json`` lists."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return peak_row(devs[0].device_kind)


def peak_row(kind: str) -> Dict[str, Any]:
    """``peaks.json``'s row for a device kind; an unknown kind is an
    error, never a default."""
    peaks = H.read_json(H.HERE, "peaks.json")["devices"]
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in peaks.json")
    return peaks[kind]


def end_to_end(workload: str) -> List[Dict[str, Any]]:
    """The end-to-end metrics of BENCHMARK.json this cell reports."""
    return [m for m in H.read_json(H.ROOT, "BENCHMARK.json")["end_to_end"]
            if workload in m.get("workloads", [workload])]


def metric_readers(workload: str, reported: List[str]):
    """The cell's per-layer metrics of BENCHMARK.json, each with its
    reader ``metrics/<name>.py``: those that list the cell, and those
    without a list that move an end-to-end metric the cell reports."""
    bench = H.read_json(H.ROOT, "BENCHMARK.json")
    out = []
    for m in bench["per_layer"]:
        if m.get("workloads") is not None and workload not in m["workloads"]:
            continue
        if m.get("workloads") is None and m["moves"] not in reported:
            continue
        path = os.path.join(H.HERE, "metrics", m["name"] + ".py")
        out.append((m, H.load_file_module(path, "chipbench_metric_"
                                          + m["name"].replace(".", "_"))))
    return out


def run_cell(cell: H.Cell, peak: Dict[str, Any]) -> Dict[str, Any]:
    """Drive the cell and build the result line."""
    import importlib
    driver = importlib.import_module(f"chipbench.drivers.{cell.mix['driver']}")
    out = driver.run(cell)
    checks = H.checks_line(out["numbers"], cell.limits)
    metrics: Dict[str, Any] = {}
    e2e = end_to_end(cell.name)
    if not cell.trace:
        for m in e2e:
            metrics[m["name"]] = {"value": out["end_to_end"][m["name"]],
                                  "unit": m["unit"]}
    else:
        inputs = dict(out["layer_inputs"], peak=peak)
        for m, mod in metric_readers(cell.name, [x["name"] for x in e2e]):
            v = mod.read(inputs)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result: Dict[str, Any] = {
        "correct": H.all_within(checks) and out["failed"] == 0,
        "attempted": out["attempted"], "failed": out["failed"],
        "metrics": metrics, "device": out["device"]}
    if cell.trace:
        from chipbench import trace as T
        reds = out["layer_inputs"]["reductions"]
        merged = T.merge(reds)
        result["device"]["busy_s"] = merged.busy_mean_ns * 1e-9
        result["device"]["window_s"] = merged.window_ns * 1e-9
        result["breakdown"] = T.breakdown(merged)
    result["detail"] = {k: v for k, v in out["numbers"].items()
                        if k not in checks}
    result["detail"].update(out.get("extra", {}))
    result["checks"] = checks
    return result


def finite(x):
    """The result with every non-finite number written as 1e308, so the
    line stays JSON that any reader parses."""
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return 1e308
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(H.ROOT, "src"))
    cell = H.load_cell(args.workload, seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace), t_start=T_START)
    try:
        peak = check_devices(cell.chips)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    import jax
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program of the run, small ones too, is read from the cache
    # after a checkout's first run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        result = run_cell(cell, peak)
    except Exception:
        traceback.print_exc()
        result = {"correct": False, "attempted": 0, "failed": 1,
                  "metrics": {}, "device": H.device_info(cell.chips),
                  "checks": {}}
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(finite(result)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
