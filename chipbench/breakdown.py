"""One cell run as ``chipbench.run`` runs it, inside the program's
``repro.tracing.recording()``, then one more JSON line: where the
window's time went by the program's own spans and scopes
(``chipbench.scopes``).

    python3 -m chipbench.breakdown --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The first line is ``chipbench.run``'s result line.  The second holds
``split``: with ``--trace 1`` the share of the traced steps under each
device scope (``<scope>_pct``, ``recompute_pct``, ``unscoped_pct``,
``idle_pct``), the longest ops of each (``top_ops``) and the device's
idle time by host span (``idle_by_span_s``); in every run the host
numbers of the window's untraced steps (``data_wait_ms``,
``host_ms_per_step``, ``window_compiles``, ``setup_compile_s``,
``setup_init_s``).  With
``--trace 0`` the result line's ``tokens_per_s`` is the throughput with
the recorder on and the profiler off.  It exits 1 where the split lacks
the host numbers, or with ``--trace 1`` every device scope's share.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Callable, Dict, List, Tuple

from chipbench import harness as H
from chipbench import run as R
from chipbench import scopes as S
from chipbench import trace as T


def split_run(run: Callable[[], Any]) -> Tuple[Any, Dict[str, Any]]:
    """``run()`` (one cell through the train driver) with the program's
    recorder on and the driver's traces read before it deletes them;
    returns what ``run`` returns and the split."""
    from repro import tracing

    kept: List[H.Marks] = []
    splits: List[S.DeviceSplit] = []
    idle: Dict[str, float] = {}
    marks_cls, reduce_traces = H.Marks, H.reduce_traces

    class Marks(marks_cls):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            kept.append(self)

    def reduce_and_split(marks):
        # the driver deletes the traces once this returns
        for t in marks.traces:
            tr = T.load(t["dir"])
            window = T.mark_window(tr, t["first"], t["last"])
            splits.append(S.device_split(tr, window, S.tf_ops(t["dir"])))
            for k, v in S.idle_by_span(tr, window).items():
                idle[k] = idle.get(k, 0.0) + v * 1e-9
        return reduce_traces(marks)

    H.Marks, H.reduce_traces = Marks, reduce_and_split
    try:
        with tracing.recording() as rec:
            out = run()
    finally:
        H.Marks, H.reduce_traces = marks_cls, reduce_traces
    split: Dict[str, Any] = {}
    if kept:
        t = kept[-1].times             # the window's run: steps 0..n+1
        traced = [(t[a], t[b]) for a, b in kept[-1].trace_spans]
        split.update(S.host_split(rec, (t[1], t[-1]), traced))
    if splits:
        merged = S.merge(splits)
        split.update(merged.shares())
        split["idle_by_span_s"] = idle
        split["top_ops"] = merged.top()
    return out, split


def missing(split: Dict[str, Any], traced: bool) -> List[str]:
    """What the split lacks: the host numbers in every run, and with a
    trace at least one device scope's share.  Either goes missing
    without an error when the driver stops calling ``harness.Marks`` or
    ``harness.reduce_traces``, which ``split_run`` wraps."""
    out = [] if "steps" in split else ["host split"]
    if traced and not any(f"{k}_pct" in split for k in S.SCOPES):
        out.append("device scope shares")
    return out


def main(argv=None) -> int:
    sys.path.insert(0, os.path.join(H.ROOT, "src"))
    argv = sys.argv[1:] if argv is None else argv
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--trace", type=int, default=0)
    traced = bool(ap.parse_known_args(argv)[0].trace)
    rc, split = split_run(lambda: R.main(argv))
    print(json.dumps({"split": split}))
    lacks = missing(split, traced)
    if rc == 0 and lacks:
        print(f"chipbench.breakdown: the run left no {' and no '.join(lacks)}",
              file=sys.stderr)
        return 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
