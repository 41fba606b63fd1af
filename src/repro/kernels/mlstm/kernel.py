"""Chunked mLSTM (xLSTM matrix-memory cell) Pallas kernel.

Grid: (B, H, num_chunks), chunk axis sequential carrying (C, n, m) in VMEM
scratch.  Math identical to ``repro.models.xlstm.mlstm_chunked`` (see the
stabilized derivation there): per chunk one (Q,Q) score matmul + one (Q,Q)x
(Q,Dv) value matmul + rank-Q state update — same MXU shape regime as flash
attention, with exponential gate stabilization handled in f32 scratch.

Layout is head-major ((B,H,S,D) and (B,H,S,1) gates, transposed by
``ops.mlstm``) so every block's last two dims are (chunk, D) or (chunk, 1),
which the TPU tiling accepts.  Mosaic has no cumsum/cummax, so the in-chunk
prefix sum and prefix max are causal-masked reductions over a (Q,Q) tile.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_BIG = -1e30


def _mlstm_kernel(q_ref, k_ref, v_ref, i_ref, f_ref, h_ref,
                  co_ref, no_ref, mo_ref, c_ref, n_ref, m_ref, *,
                  chunk: int, head_dim: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_BIG)

    D = head_dim
    scale = 1.0 / math.sqrt(D)
    q = q_ref[0, 0].astype(jnp.float32) * scale       # (Q, D)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    ig = i_ref[0, 0].astype(jnp.float32)              # (Q, 1)
    fg = f_ref[0, 0].astype(jnp.float32)

    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = ti >= si

    def along_s(col):                                  # [t, s] = col[s]
        return jnp.broadcast_to(col, (chunk, chunk)).T

    lf = jax.nn.log_sigmoid(fg)
    b = jnp.sum(jnp.where(causal, along_s(lf), 0.0), axis=1,
                keepdims=True)                         # inclusive cumsum
    a = ig - b                                         # (Q, 1)
    a_s = along_s(a)
    m0 = m_ref[...]                                    # (1, 1)
    rm = jnp.maximum(jnp.max(jnp.where(causal, a_s, NEG_BIG), axis=1,
                             keepdims=True), m0)       # (Q, 1) cummax
    m_t = b + rm

    qk = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q,Q)
    w = jnp.where(causal, jnp.exp(a_s - rm), 0.0)
    scores = qk * w

    C0 = c_ref[...]                                    # (Dk, Dv)
    n0 = n_ref[...]                                    # (1, Dk)
    inter_scale = jnp.exp(m0 - rm)                     # (Q, 1)
    inter = jax.lax.dot_general(q, C0, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    num = (jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
           + inter * inter_scale)
    den = (jnp.sum(scores, axis=1, keepdims=True)
           + jnp.sum(q * n0, axis=1, keepdims=True) * inter_scale)
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_t))
    h_ref[0, 0] = h.astype(h_ref.dtype)

    R = rm[chunk - 1:, :]                              # (1, 1)
    kd = k * jnp.exp(a - R)                            # (Q, D)
    c_ref[...] = (C0 * jnp.exp(m0 - R)
                  + jax.lax.dot_general(kd, v, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32))
    n_ref[...] = n0 * jnp.exp(m0 - R) + jnp.sum(kd, axis=0, keepdims=True)
    m_ref[...] = b[chunk - 1:, :] + R

    @pl.when(ci == nc - 1)
    def _emit():
        co_ref[0, 0] = c_ref[...]
        no_ref[0, 0] = n_ref[...]
        mo_ref[0, 0] = m_ref[...]


def mlstm_pallas(q, k, v, i_raw, f_raw, *, chunk: int,
                 interpret: bool = False):
    """q,k,v: (B,H,S,D); i_raw,f_raw: (B,H,S,1).

    Returns (h (B,H,S,D), (C (B,H,D,D), n (B,H,1,D), m (B,H,1,1)) f32).
    """
    B, H, S, D = q.shape
    assert S % chunk == 0
    nc = S // chunk
    kernel = functools.partial(_mlstm_kernel, chunk=chunk, head_dim=D)
    seq = pl.BlockSpec((1, 1, chunk, D), lambda b, hh, c: (b, hh, c, 0))
    gate = pl.BlockSpec((1, 1, chunk, 1), lambda b, hh, c: (b, hh, c, 0))
    h, C, n, m = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[seq, seq, seq, gate, gate],
        out_specs=[
            seq,
            pl.BlockSpec((1, 1, D, D), lambda b, hh, c: (b, hh, 0, 0)),
            pl.BlockSpec((1, 1, 1, D), lambda b, hh, c: (b, hh, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda b, hh, c: (b, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, D, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((D, D), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ) if not interpret else None,
    )(q, k, v, i_raw, f_raw)
    return h, (C, n, m)
