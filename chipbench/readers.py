"""Arithmetic the per-layer metric readers share.

Each reader in ``metrics/`` gets the run's ``inputs``: ``reductions``
(one ``trace.Reduction`` per traced span), ``flops_per_step`` (model
FLOPs of one step of the global batch), ``traced_steps`` (steps in each
span), ``chips`` and ``peak`` (this device kind's row of
``peaks.json``).  A reader returns None where there is nothing to read.
"""
from __future__ import annotations

from typing import Any, Dict, Optional


def mfu_pct(inputs: Dict[str, Any]) -> Optional[float]:
    """Model FLOPs of the traced steps over what the chips could do in
    the traced windows at their bf16 peak."""
    reds = inputs["reductions"]
    window_s = sum(r.window_ns for r in reds) * 1e-9
    if not reds or window_s <= 0:
        return None
    flops = inputs["flops_per_step"] * inputs["traced_steps"] * len(reds)
    return 100.0 * flops / (window_s * inputs["chips"]
                            * inputs["peak"]["bf16_flops_per_s"])


def idle_pct(inputs: Dict[str, Any]) -> Optional[float]:
    """Share of the traced windows in which no op ran, mean over chips."""
    reds = inputs["reductions"]
    window = sum(r.window_ns for r in reds)
    if not reds or window <= 0:
        return None
    busy = sum(r.busy_mean_ns for r in reds)
    return 100.0 * (1.0 - busy / window)
