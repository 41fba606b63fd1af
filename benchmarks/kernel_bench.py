"""Kernel microbenchmarks (interpret mode on CPU: correctness-path timing;
the derived column reports kernel-vs-jnp-ref output agreement).

One table-driven loop; the warmup call's output is reused for the error
column instead of recomputing each jitted kernel a second time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_fn
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mamba_scan.ops import ssd
from repro.kernels.mamba_scan.ref import ssd_ref
from repro.kernels.mlstm.ops import mlstm
from repro.kernels.mlstm.ref import mlstm_ref
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref


def _cases():
    ks = jax.random.split(jax.random.key(0), 5)
    B = 1

    S, H, Kv, D = 256, 4, 2, 64
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, Kv, D))
    v = jax.random.normal(ks[2], (B, S, Kv, D))

    T, Hh, P, G, N = 256, 2, 32, 1, 16
    x = jax.random.normal(ks[0], (B, T, Hh, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, Hh)))
    A = -jnp.exp(jax.random.normal(ks[2], (Hh,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, T, G, N))
    Cm = jax.random.normal(ks[4], (B, T, G, N))

    Dm = 32
    qm = jax.random.normal(ks[0], (B, T, Hh, Dm))
    km = jax.random.normal(ks[1], (B, T, Hh, Dm))
    vm = jax.random.normal(ks[2], (B, T, Hh, Dm))
    ir = jax.random.normal(ks[3], (B, T, Hh)) * 2
    fr = jax.random.normal(ks[4], (B, T, Hh)) * 2 + 3

    xr = jax.random.normal(ks[0], (512, 768), jnp.bfloat16)
    wr = jnp.ones((768,), jnp.float32)

    # (name, jitted fn, args, ref fn, pick-primary-output)
    first = lambda o: o[0]
    ident = lambda o: o
    return [
        ("kernel_flash_attention",
         jax.jit(lambda q, k, v: flash_attention(
             q, k, v, causal=True, block_q=64, block_k=64,
             interpret=True)),
         (q, k, v),
         lambda q, k, v: attention_ref(q, k, v, causal=True), ident),
        ("kernel_mamba_scan",
         jax.jit(lambda *a: ssd(*a, chunk=64, interpret=True)),
         (x, dt, A, Bm, Cm), ssd_ref, first),
        ("kernel_mlstm",
         jax.jit(lambda *a: mlstm(*a, chunk=64, interpret=True)),
         (qm, km, vm, ir, fr), mlstm_ref, first),
        ("kernel_rmsnorm",
         jax.jit(lambda x, w: rmsnorm(x, w, interpret=True)),
         (xr, wr), rmsnorm_ref, ident),
    ]


def main() -> None:
    for name, fn, args, ref_fn, pick in _cases():
        out = jax.block_until_ready(fn(*args))     # compile + warmup
        us = time_fn(lambda: jax.block_until_ready(fn(*args)), warmup=0)
        ref = pick(ref_fn(*args))
        err = float(jnp.max(jnp.abs(
            (pick(out) - ref).astype(jnp.float32))))
        emit(name, us, f"max_err_vs_ref={err:.2e}")


if __name__ == "__main__":
    main()
