"""jit'd public wrapper for the SSD chunked-scan kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.mamba_scan.kernel import ssd_pallas


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, A, B, C, *, chunk: int = 256, interpret: bool = False):
    """x: (Bt,S,H,P); dt: (Bt,S,H); A: (H,); B/C: (Bt,S,G,N).

    Returns (y (Bt,S,H,P), final_state (Bt,H,P,N) f32).
    """
    heads_major = lambda t: t.transpose(0, 2, 1, 3)
    y, st = ssd_pallas(heads_major(x), dt.transpose(0, 2, 1)[..., None],
                       A.astype(jnp.float32), heads_major(B),
                       heads_major(C), chunk=chunk, interpret=interpret)
    return heads_major(y), st
