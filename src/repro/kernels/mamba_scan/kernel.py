"""Mamba2 / SSD chunked-scan Pallas kernel.

Grid: (B, H, num_chunks).  The chunk axis is sequential ("arbitrary") and
carries the (P, N) SSM state in VMEM scratch — the TPU-native layout of the
paper's chunked algorithm: intra-chunk work is a pair of MXU matmuls
((Q,N)x(N,Q) and (Q,Q)x(Q,P)), the inter-chunk recurrence is a rank-N state
update.  B/C tensors are grouped (G groups); the head->group mapping lives in
the BlockSpec index maps so grouped heads re-read the same HBM block.

Layout is head-major ((B,H,S,P), (B,H,S,1) dt, (B,G,S,N) B/C, transposed by
``ops.ssd``) so every block's last two dims are (chunk, width), which the
TPU tiling accepts; the per-head decay ``A`` is read as a scalar from SMEM.
Mosaic has no cumsum, so the in-chunk prefix sum is a causal-masked
reduction over a (Q,Q) tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref,
                state_ref, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)               # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)             # (Q, 1)
    A = a_ref[pl.program_id(1)]                       # f32 scalar (SMEM)
    Bm = b_ref[0, 0].astype(jnp.float32)              # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)              # (Q, N)

    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = ti >= si

    def along_s(col):                                  # [t, s] = col[s]
        return jnp.broadcast_to(col, (chunk, chunk)).T

    la = dt * A                                       # (Q, 1) log-decay
    b_end = jnp.sum(jnp.where(causal, along_s(la), 0.0), axis=1,
                    keepdims=True)                    # inclusive cumsum
    xd = x * dt

    # intra-chunk decay matrix L[t,s] = exp(b_t - b_s) for t >= s
    Lmat = jnp.where(causal, jnp.exp(b_end - along_s(b_end)), 0.0)

    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q,Q)
    y_diag = jax.lax.dot_general(CB * Lmat, xd, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    # inter-chunk: y_off[t] = exp(b_t) * C_t . state_prev^T
    state_prev = state_ref[...]                       # (P, N)
    y_off = jax.lax.dot_general(Cm, state_prev, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_off = y_off * jnp.exp(b_end)

    y_ref[0, 0] = (y_diag + y_off).astype(y_ref.dtype)

    # state update: S' = exp(total) S + sum_s exp(total - b_s) x_s B_s^T
    total = b_end[chunk - 1:, :]                      # (1, 1)
    decay = jnp.exp(total - b_end)                    # (Q, 1)
    chunk_state = jax.lax.dot_general(
        xd * decay, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # (P, N)
    state_ref[...] = state_prev * jnp.exp(total) + chunk_state

    @pl.when(ci == nc - 1)
    def _emit_state():
        st_ref[0, 0] = state_ref[...]


def ssd_pallas(x, dt, A, B, C, *, chunk: int, interpret: bool = False):
    """x: (Bt,H,S,P); dt: (Bt,H,S,1); A: (H,) f32; B/C: (Bt,G,S,N).

    Returns (y (Bt,H,S,P), final_state (Bt,H,P,N) f32).
    """
    Bt, H, S, P = x.shape
    G, N = B.shape[1], B.shape[3]
    rep = H // G
    assert S % chunk == 0
    nc = S // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, st = pl.pallas_call(
        kernel,
        grid=(Bt, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, c, rep=rep: (b, h // rep, c, 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, c, rep=rep: (b, h // rep, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bt, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((Bt, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ) if not interpret else None,
    )(x, dt, A, B, C)
    return y, st
