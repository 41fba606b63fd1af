#!/usr/bin/env python
"""Time the flash-attention kernels on a TPU at each block size.

    python scripts/flash_block_sweep.py                # stablelm-1.6b shape
    python scripts/flash_block_sweep.py --shape 1,256,4,2,64 --blocks 64,128 \
        --interpret                                    # CPU rehearsal

At B,S,H,Kv,D (bf16, causal) it times, as the median of ``--reps`` calls
each ended by ``block_until_ready``, the forward and the gradient of
``flash_attention`` at every (block_q, block_k) of ``--blocks``, and of
``layers.blocked_attention``, the XLA path the kernel replaces; the
backward's time is the gradient's less the forward's.  It also prints
how far the kernel's output and gradients lie from the blocked path's at
the fastest blocks.  One JSON line per reading on stdout.
"""
import argparse
import itertools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels.flash_attention import flash_attention  # noqa: E402
from repro.models import layers as L  # noqa: E402


def _ms(fn, args, reps):
    jax.block_until_ready(fn(*args))          # compile and warm up
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def _grad(attn, ct):
    return jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        attn(q, k, v).astype(jnp.float32) * ct), (0, 1, 2)))


def _rel_err(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shape", default="4,4096,32,32,64")
    ap.add_argument("--blocks", default="256,512,1024")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--interpret", action="store_true")
    a = ap.parse_args(argv)
    B, S, H, Kv, D = map(int, a.shape.split(","))
    sizes = [int(b) for b in a.blocks.split(",")]
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, Kv, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, Kv, D), jnp.bfloat16)
    ct = jax.random.normal(ks[3], (B, S, H, D), jnp.float32)
    args = (q, k, v)

    def kernel(bq, bk):
        return lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=bq, block_k=bk,
            interpret=a.interpret)

    def emit(**row):
        print(json.dumps(row), flush=True)

    blocked = lambda q, k, v: L.blocked_attention(q, k, v, causal=True)
    xla_fwd = _ms(jax.jit(blocked), args, a.reps)
    xla_grad = _ms(_grad(blocked, ct), args, a.reps)
    emit(path="blocked_xla", fwd_ms=xla_fwd, bwd_ms=xla_grad - xla_fwd,
         grad_ms=xla_grad)

    grad = {}
    for bq, bk in itertools.product(sizes, sizes):
        f = _ms(jax.jit(kernel(bq, bk)), args, a.reps)
        grad[bq, bk] = _ms(_grad(kernel(bq, bk), ct), args, a.reps)
        emit(path="flash", block_q=bq, block_k=bk, fwd_ms=f,
             bwd_ms=grad[bq, bk] - f, grad_ms=grad[bq, bk])

    best = min(grad, key=grad.get)
    ref_o = jax.jit(blocked)(*args)
    ref_g = _grad(blocked, ct)(*args)
    attn = kernel(*best)
    emit(path="flash_vs_blocked", blocks=best,
         out=_rel_err(jax.jit(attn)(*args), ref_o),
         **{f"d{n}": _rel_err(g, r) for n, g, r in
            zip("qkv", _grad(attn, ct)(*args), ref_g)})
    emit(device=jax.devices()[0].device_kind)


if __name__ == "__main__":
    main()
