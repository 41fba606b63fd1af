"""Grouped matrix multiplication for the expert layer: the rows of
``lhs`` sorted by expert, each expert's rows multiplied by its weights.

On a TPU this is the Pallas kernels of
``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` forward, and behind
megablox's ``custom_vjp`` ``gmm`` against the transposed weights for the
rows' gradient and ``tgmm`` for the weights' gradient.  A kernel visits
only the row tiles of the experts' groups, so its work follows the rows
actually routed, not the buffer's length.  In a profile their ops are
named after megablox's jitted ``gmm`` and ``tgmm`` (``gmm.<n>``,
``tgmm.<n>``).  Elsewhere (the CPU) the same product is
``lax.ragged_dot``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox


def _tile(d: int, cap: int = 512) -> int:
    """A tile of a dimension of ``d``: the whole of it up to 1536, else
    the largest of ``cap``, 256, 128 that divides it."""
    if d <= 1536:
        return d
    return next((t for t in (cap, 256, 128) if d % t == 0), d)


def _tiling(m: int, k: int, n: int):
    """(tm, tk, tn) of one product (m, k) x (k, n), forward or backward:
    at the expert widths (2048, 1408) each kernel's blocks, double
    buffered, and its f32 accumulator take under 10 MiB of VMEM."""
    tm = next((t for t in (512, 256, 128) if m % t == 0), m)
    return tm, _tile(k), _tile(n)


@functools.partial(jax.jit, static_argnames=("interpret",))
def expert_gmm(lhs, rhs, group_sizes, *, interpret: bool = False):
    """lhs: (m, k), its rows grouped by expert in expert order; rhs:
    (g, k, n), the g experts' weights; group_sizes: (g + 1,) int32, the
    rows of each expert and last the rows that belong to none, whose
    output rows are zero.  Returns (m, n) in lhs's dtype, accumulated in
    f32."""
    return megablox.gmm(lhs, rhs, group_sizes, lhs.dtype, _tiling, None,
                        None, False, interpret)


def grouped_matmul(lhs, rhs, group_sizes):
    """``expert_gmm`` on a TPU, ``lax.ragged_dot`` elsewhere."""
    if jax.default_backend() == "tpu":
        return expert_gmm(lhs, rhs, group_sizes)
    out = jax.lax.ragged_dot(lhs, rhs, group_sizes[:-1],
                             preferred_element_type=jnp.float32)
    return out.astype(lhs.dtype)
