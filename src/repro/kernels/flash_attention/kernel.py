"""Blocked causal GQA flash attention — Pallas TPU kernels, forward and
backward.

Every kernel works on the *transposed* score tile ``s^T = k q^T`` of shape
(block_k, block_q): the query positions run along the 128 lanes, so the
per-query softmax statistics (running max, sum, log-sum-exp, ``rowsum(dO o)``)
are lane-dense (1, block_q) rows, their reductions run over sublanes, and
no kernel transposes a tile.  Outputs that are indexed by query position
(``o`` and ``dq``) are produced transposed, (D, S), and transposed back by
``ops``.

Forward, grid (B*H, nq, nk), kv innermost and sequential ("arbitrary"):
online softmax over the kv blocks in VMEM scratch; writes ``o^T`` and the
per-query log-sum-exp.  Backward: ``dK, dV`` with grid (B*Kv, nk, G*nq),
the G query heads of a group and their q blocks innermost, so dK and dV
sum over the group in VMEM; ``dQ`` with grid (B*H, nq, nk).  Both
recompute P from the log-sum-exp, and form dS = P * (dP - rowsum(dO o)).

GQA lives in the BlockSpec index maps (query head b reads kv head
b // group).  Causal skipping: a fully masked (q, kv) block pair runs no
MXU work (``pl.when``), and its index map repeats the neighbouring live
block, so the pipeline fetches nothing for it.

Matmuls take the operands' own dtype with f32 accumulation; P and dS are
rounded to that dtype for their products, softmax statistics and
accumulators stay f32.  Block shapes come from ``ops.flash_attention``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))     # a @ b.T
_NN = (((1,), (0,)), ((), ()))     # a @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _live(qi, ki, *, causal, block_q, block_k):
    """Whether the (q block, kv block) pair has an unmasked entry."""
    return (not causal) or (ki * block_k <= (qi + 1) * block_q - 1)


def _last_live_k(qi, ki, *, causal, block_q, block_k):
    """kv block to fetch at step ki of q block qi: past the diagonal the
    last live one again, which the pipeline then does not re-fetch."""
    if not causal:
        return ki
    return jnp.minimum(ki, ((qi + 1) * block_q - 1) // block_k)


def _first_live_q(qi, ki, *, causal, block_q, block_k):
    """q block to fetch at step qi of kv block ki: before the diagonal the
    first live one."""
    if not causal:
        return qi
    return jnp.maximum(qi, (ki * block_k) // block_q)


def _scores_t(q, k, qi, ki, *, causal, scale, softcap, block_q, block_k):
    """Scaled, capped, masked transposed scores (block_k, block_q) f32,
    and tanh(s / softcap) for the backward (None without a cap)."""
    s = _dot(k, q, _NT) * scale
    t = None
    if softcap > 0.0:
        t = jnp.tanh(s / softcap)
        s = t * softcap
    if causal:
        shape = (block_k, block_q)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    return s, t


def _compiler_params(interpret):
    """The kv (forward, dQ) or q (dK/dV) axis carries the accumulators."""
    return None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


# ---------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, vt_ref, ot_ref, lse_ref, m_ref, l_ref,
                acc_ref, *, causal, scale, softcap, block_q, block_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    blocks = dict(causal=causal, block_q=block_q, block_k=block_k)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_live(qi, ki, **blocks))
    def _compute():
        s, _ = _scores_t(q_ref[0], k_ref[0], qi, ki, scale=scale,
                         softcap=softcap, **blocks)        # (bk, bq)
        vt = vt_ref[0]                                      # (Dv, bk)
        m_prev = m_ref[...]                                 # (1, bq)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=0, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * corr + _dot(
            vt, p.astype(vt.dtype), _NN)                    # (Dv, bq)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        l = l_ref[...]
        ot_ref[0] = (acc_ref[...] / l).astype(ot_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l)


def flash_fwd(q, k, vt, *, causal: bool, group: int, block_q: int,
              block_k: int, softcap: float = 0.0, scale=None,
              interpret: bool = False):
    """q: (BH, S, D); k: (BKv, S, D); vt: (BKv, Dv, S); group = H // Kv;
    ``scale`` multiplies q k^T (default ``1/sqrt(D)``).

    Returns o^T (BH, Dv, S) in q's dtype and the log-sum-exp of each
    query's scaled scores, (BH, 1, S) f32.
    """
    BH, S, D = q.shape
    Dv = vt.shape[1]
    blocks = dict(causal=causal, block_q=block_q, block_k=block_k)
    kernel = functools.partial(
        _fwd_kernel, scale=1.0 / math.sqrt(D) if scale is None else scale,
        softcap=softcap, **blocks)

    def kv_map(b, qi, ki):
        return b // group, _last_live_k(qi, ki, **blocks), 0

    def vt_map(b, qi, ki):
        return b // group, 0, _last_live_k(qi, ki, **blocks)

    return pl.pallas_call(
        kernel,
        grid=(BH, S // block_q, S // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, Dv, block_k), vt_map),
        ],
        out_specs=[
            pl.BlockSpec((1, Dv, block_q), lambda b, qi, ki: (b, 0, qi)),
            pl.BlockSpec((1, 1, block_q), lambda b, qi, ki: (b, 0, qi)),
        ],
        out_shape=[jax.ShapeDtypeStruct((BH, Dv, S), q.dtype),
                   jax.ShapeDtypeStruct((BH, 1, S), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((1, block_q), jnp.float32),
            pltpu.VMEM((1, block_q), jnp.float32),
            pltpu.VMEM((Dv, block_q), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(q, k, vt)


# --------------------------------------------------------------- backward

def _probs_and_dscores(q, k, v, do, lse, di, qi, ki, *, scale, softcap,
                       **blocks):
    """P^T and dS^T (block_k, block_q) f32, dS^T already scaled by
    ``scale`` (the derivative of the scores with respect to q k^T)."""
    s, t = _scores_t(q, k, qi, ki, scale=scale, softcap=softcap, **blocks)
    p = jnp.exp(s - lse)
    ds = p * (_dot(v, do, _NT) - di)
    if t is not None:
        ds = ds * (1.0 - t * t)
    return p, ds * scale


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, nq, scale, softcap, **blocks):
    ki = pl.program_id(1)
    t = pl.program_id(2)
    qi = t % nq

    @pl.when(t == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_live(qi, ki, **blocks))
    def _compute():
        q, do = q_ref[0], do_ref[0]
        p, ds = _probs_and_dscores(q, k_ref[0], v_ref[0], do, lse_ref[0],
                                   di_ref[0], qi, ki, scale=scale,
                                   softcap=softcap, **blocks)
        dv_acc[...] += _dot(p.astype(do.dtype), do, _NN)    # (bk, Dv)
        dk_acc[...] += _dot(ds.astype(q.dtype), q, _NN)     # (bk, D)

    @pl.when(t == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, kt_ref, do_ref, lse_ref, di_ref,
               dqt_ref, dq_acc, *, scale, softcap, **blocks):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(_live(qi, ki, **blocks))
    def _compute():
        kt = kt_ref[0]                                      # (D, bk)
        _, ds = _probs_and_dscores(q_ref[0], k_ref[0], v_ref[0], do_ref[0],
                                   lse_ref[0], di_ref[0], qi, ki,
                                   scale=scale, softcap=softcap, **blocks)
        dq_acc[...] += _dot(kt, ds.astype(kt.dtype), _NN)   # (D, bq)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        dqt_ref[0] = dq_acc[...].astype(dqt_ref.dtype)


def flash_bwd(q, k, v, do, lse, di, *, causal: bool, group: int,
              block_q: int, block_k: int, softcap: float = 0.0, scale=None,
              interpret: bool = False):
    """q: (BH, S, D); k: (BKv, S, D); v: (BKv, S, Dv); do: (BH, S, Dv);
    lse, di = rowsum(dO o): (BH, 1, S) f32; ``scale`` as the forward's.

    Returns dq^T (BH, D, S), dk (BKv, S, D), dv (BKv, S, Dv).
    """
    BH, S, D = q.shape
    BKv, _, Dv = v.shape
    nq, nk = S // block_q, S // block_k
    blocks = dict(causal=causal, block_q=block_q, block_k=block_k)
    scale = 1.0 / math.sqrt(D) if scale is None else scale

    # dK, dV: kv block outer; (query head of the group, q block) inner
    def q_map(b, ki, t):
        return (b * group + t // nq,
                _first_live_q(t % nq, ki, **blocks), 0)

    def row_map(b, ki, t):
        return (b * group + t // nq, 0,
                _first_live_q(t % nq, ki, **blocks))

    def kv_map(b, ki, t):
        return b, ki, 0

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, nq=nq, scale=scale, softcap=softcap,
                          **blocks),
        grid=(BKv, nk, group * nq),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_map),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, block_k, Dv), kv_map),
            pl.BlockSpec((1, block_q, Dv), q_map),
            pl.BlockSpec((1, 1, block_q), row_map),
            pl.BlockSpec((1, 1, block_q), row_map),
        ],
        out_specs=[pl.BlockSpec((1, block_k, D), kv_map),
                   pl.BlockSpec((1, block_k, Dv), kv_map)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, Dv), jnp.float32)],
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(q, k, v, do, lse, di)

    # dQ: q block outer, kv blocks inner
    def kv_in_map(b, qi, ki):
        return b // group, _last_live_k(qi, ki, **blocks), 0

    def kt_map(b, qi, ki):
        return b // group, 0, _last_live_k(qi, ki, **blocks)

    def qrow_map(b, qi, ki):
        return b, 0, qi

    dqt = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, softcap=softcap,
                          **blocks),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, D), kv_in_map),
            pl.BlockSpec((1, block_k, Dv), kv_in_map),
            pl.BlockSpec((1, D, block_k), kt_map),
            pl.BlockSpec((1, block_q, Dv), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, 1, block_q), qrow_map),
            pl.BlockSpec((1, 1, block_q), qrow_map),
        ],
        out_specs=pl.BlockSpec((1, D, block_q), qrow_map),
        out_shape=jax.ShapeDtypeStruct((BH, D, S), q.dtype),
        scratch_shapes=[pltpu.VMEM((D, block_q), jnp.float32)],
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(q, k, v, k.transpose(0, 2, 1), do, lse, di)
    return dqt, dk, dv
