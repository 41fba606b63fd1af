"""jit'd public wrapper for the flash-attention kernels, differentiable
through a ``jax.custom_vjp`` whose backward is the Pallas dK/dV and dQ
kernels.  The residuals are q, k, v, o and the per-query log-sum-exp:
no (block_q, block_k) tile is saved."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_bwd, flash_fwd

# (block_q, block_k) of the forward and of both backward kernels: the
# fastest of {256, 512, 1024}^2 for each, forward and backward alike, on a
# TPU v5e at B 4, S 4096, H = Kv 32, D 64, bf16
# (``scripts/flash_block_sweep.py``; PERF.md).  Larger blocks exceed the
# 16 MiB of VMEM a kernel may use by default.
BLOCKS = (1024, 1024)


def _pick_block(s: int, target: int) -> int:
    if s % target == 0:
        return target
    b = math.gcd(s, target)
    while s % b:
        b -= 1
    return max(b, 1)


def _heads_major(x):
    """(B, S, N, D) -> (B*N, S, D)."""
    B, S, N, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * N, S, D)


def _forward(q, k, v, causal, softcap, scale, blocks, interpret):
    """o (B, S, H, Dv) and the log-sum-exp of each query's scaled scores,
    (B*H, 1, S) f32."""
    B, S, H, _ = q.shape
    Kv, Dv = k.shape[2], v.shape[-1]
    vt = v.transpose(0, 2, 3, 1).reshape(B * Kv, Dv, S)
    ot, lse = flash_fwd(_heads_major(q), _heads_major(k), vt,
                        causal=causal, group=H // Kv, block_q=blocks[0],
                        block_k=blocks[1], softcap=softcap, scale=scale,
                        interpret=interpret)
    return ot.reshape(B, H, Dv, S).transpose(0, 3, 1, 2), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, softcap, scale, blocks, interpret):
    return _forward(q, k, v, causal, softcap, scale, blocks, interpret)[0]


def _flash_fwd(q, k, v, causal, softcap, scale, blocks, interpret):
    o, lse = _forward(q, k, v, causal, softcap, scale, blocks, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, softcap, scale, blocks, interpret, res, do):
    q, k, v, o, lse = res
    B, S, H, D = q.shape
    Kv = k.shape[2]
    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    di = di.transpose(0, 2, 1).reshape(B * H, 1, S)
    dqt, dk, dv = flash_bwd(
        _heads_major(q), _heads_major(k), _heads_major(v), _heads_major(do),
        lse, di, causal=causal, group=H // Kv, block_q=blocks[0],
        block_k=blocks[1], softcap=softcap, scale=scale,
        interpret=interpret)
    dq = dqt.reshape(B, H, D, S).transpose(0, 3, 1, 2)
    dk = dk.reshape(B, Kv, S, -1).transpose(0, 2, 1, 3)
    dv = dv.reshape(B, Kv, S, -1).transpose(0, 2, 1, 3)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "softcap", "scale",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q=None,
                    block_k=None, softcap: float = 0.0, scale=None,
                    interpret: bool = False):
    """q, k: (B, S, H | Kv, D); v: (B, S, Kv, Dv).  Returns (B, S, H, Dv).

    ``scale`` multiplies the scores q k^T (default ``1/sqrt(D)``).
    ``block_q`` / ``block_k`` override the tiling of every kernel; by
    default ``BLOCKS``, cut to divisors of S.
    """
    S = q.shape[1]
    blocks = (_pick_block(S, block_q or BLOCKS[0]),
              _pick_block(S, block_k or BLOCKS[1]))
    return _flash(q, k, v, causal, softcap, scale, blocks, interpret)

