"""Blocked causal GQA flash attention — Pallas TPU kernel.

Grid: (B*H, num_q_blocks, num_kv_blocks); the kv axis is the innermost,
sequential ("arbitrary") dimension carrying the online-softmax state in VMEM
scratch.  GQA is handled in the BlockSpec index maps (kv blocks are fetched
per kv-head; query heads of the same group re-read them from HBM — no
repeated-KV materialization).  Causal skipping: fully-masked kv blocks are
skipped with ``pl.when`` (no MXU work issued).

Block shapes are MXU-aligned by ``ops.flash_attention`` (multiples of 128 on
the sequence axes whenever the sequence allows it).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 causal: bool, scale: float, softcap: float,
                 block_q: int, block_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal block skip: kv block strictly above the diagonal band
    q_end = (qi + 1) * block_q - 1
    k_start = ki * block_k
    live = (not causal) or (k_start <= q_end)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)               # (bq, D)
        k = k_ref[0].astype(jnp.float32)               # (bk, D)
        v = v_ref[0].astype(jnp.float32)               # (bk, Dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)

        m_prev = m_ref[...]                            # (bq, 1)
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool, group: int,
                         block_q: int, block_k: int, softcap: float = 0.0,
                         interpret: bool = False):
    """q: (BH, S, D); k/v: (BKv, S, D|Dv); group = H // Kv."""
    BH, S, D = q.shape
    Dv = v.shape[-1]
    nq = S // block_q
    nk = S // block_k
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _attn_kernel, causal=causal, scale=scale, softcap=softcap,
        block_q=block_q, block_k=block_k)

    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda b, qi, ki: (b // group, ki, 0)),
            pl.BlockSpec((1, block_k, Dv),
                         lambda b, qi, ki: (b // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dv),
                               lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ) if not interpret else None,
    )(q, k, v)
