"""jit'd public wrapper for the chunked mLSTM kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.mlstm.kernel import mlstm_pallas


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm(q, k, v, i_raw, f_raw, *, chunk: int = 256,
          interpret: bool = False):
    """q,k,v: (B,S,H,D); gates: (B,S,H).

    Returns (h (B,S,H,D), (C (B,H,D,D), n (B,H,D), m (B,H)) f32).
    """
    B, S, H, D = q.shape
    heads_major = lambda x: x.transpose(0, 2, 1, 3)
    gate = lambda g: g.transpose(0, 2, 1)[..., None]
    h, (C, n, m) = mlstm_pallas(
        heads_major(q), heads_major(k), heads_major(v), gate(i_raw),
        gate(f_raw), chunk=chunk, interpret=interpret)
    return heads_major(h), (C, n.reshape(B, H, D), m.reshape(B, H))
