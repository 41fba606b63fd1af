"""Pieces the float32 references share: matmuls at full float32 precision,
the norms, and the language-model loss computed in blocks of rows.

Every matmul of a reference goes through an ``mm(subscripts, a, b)``
callable.  ``mm`` is float32 at ``Precision.HIGHEST`` (on a TPU a float32
matmul otherwise runs in bfloat16 passes).  ``mm_fp8`` is the
benchmark's control, the reference computed one precision step below the
bfloat16 the configurations state: both operands rounded to float8 e4m3
with a per-tensor scale, and in the backward pass the incoming gradient
rounded to float8 e5m2, so that all three matmuls of each product take
fp8 operands.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0          # largest finite float8_e4m3fn
FP8_E5M2_MAX = 57344.0   # largest finite float8_e5m2


def mm(subscripts: str, a, b):
    return jnp.einsum(subscripts, a.astype(jnp.float32),
                      b.astype(jnp.float32), precision=HIGHEST)


def _round(x, dtype, largest: float):
    """x rounded to ``dtype`` under a per-tensor scale, in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / largest
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def fp8_round(x):
    """x rounded to e4m3 under a per-tensor scale; gradients pass
    straight through."""
    x = x.astype(jnp.float32)
    q = _round(x, jnp.float8_e4m3fn, FP8_MAX)
    return x + jax.lax.stop_gradient(q - x)


@jax.custom_vjp
def grad_to_fp8(x):
    """The identity, whose cotangent is rounded to e5m2 (per-tensor
    scale), the format of fp8 training's backward pass."""
    return x


grad_to_fp8.defvjp(lambda x: (x, None),
                   lambda _, g: (_round(g.astype(jnp.float32),
                                        jnp.float8_e5m2, FP8_E5M2_MAX),))


def mm_fp8(subscripts: str, a, b):
    """Every operand of the matmul and of its two backward matmuls in
    fp8: e4m3 operands forward, the incoming gradient in e5m2."""
    return grad_to_fp8(jnp.einsum(subscripts, fp8_round(a), fp8_round(b),
                                  precision=HIGHEST))


MATMULS = {"float32": mm, "fp8": mm_fp8}


def layernorm(x, p, eps: float):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["w"] + p["b"]


def rmsnorm(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * w


def lm_loss(x, head, targets, z_loss: float, *, mm: Callable = mm,
            block: int = 512):
    """Mean over tokens of ``logsumexp - gold + z_loss * logsumexp**2``.

    x: (B, S, D) final hidden states; head: (D, V).  Computed ``block``
    positions at a time (all rows of the batch together, so a batch split
    over chips stays split), each block recomputed in the backward pass:
    the float32 logits of the whole batch never exist at once.
    """
    B, S, D = x.shape
    block = min(block, S)
    nb = S // block

    @jax.checkpoint
    def part(args):
        xb, tb = args                                   # (B, blk, D), (B, blk)
        logits = mm("bsd,dv->bsv", xb, head)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tb[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - gold + z_loss * jnp.square(lse))

    xs = x.reshape(B, nb, block, D).transpose(1, 0, 2, 3)
    ts = targets.reshape(B, nb, block).transpose(1, 0, 2)
    return jnp.sum(jax.lax.map(part, (xs, ts))) / targets.size
