"""jit'd public wrapper for fused RMSNorm."""
from __future__ import annotations

import functools

import jax

from repro.kernels.rmsnorm.kernel import rmsnorm_pallas


@functools.partial(jax.jit, static_argnames=("eps", "block_rows",
                                             "interpret"))
def rmsnorm(x, w, *, eps: float = 1e-5, block_rows: int = 256,
            interpret: bool = False):
    """x: (..., D); w: (D,)."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    out = rmsnorm_pallas(x2, w, eps=eps, block_rows=block_rows,
                         interpret=interpret)
    return out.reshape(shape)
