"""jit'd public wrapper for the flash-attention kernel."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_bhsd


def _pick_block(s: int, target: int) -> int:
    if s % target == 0:
        return target
    b = math.gcd(s, target)
    while s % b:
        b -= 1
    return max(b, 1)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "softcap", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, softcap: float = 0.0,
                    interpret: bool = False):
    """q: (B, S, H, D); k/v: (B, S, Kv, Dv).  Returns (B, S, H, Dv)."""
    B, S, H, D = q.shape
    Kv = k.shape[2]
    Dv = v.shape[-1]
    G = H // Kv
    bq = _pick_block(S, block_q)
    bk = _pick_block(S, block_k)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Kv, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Kv, S, Dv)
    o = flash_attention_bhsd(qf, kf, vf, causal=causal, group=G,
                             block_q=bq, block_k=bk, softcap=softcap,
                             interpret=interpret)
    return o.reshape(B, H, S, Dv).transpose(0, 2, 1, 3)
