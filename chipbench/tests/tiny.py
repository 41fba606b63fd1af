"""Tiny configurations and mixes of the benchmark's cells for CPU tests:
the cell's own files with the sizes cut, every other setting kept."""
from __future__ import annotations

import time

from chipbench import harness as H

XLSTM = dict(arch_id="xlstm-tiny", family="ssm", n_layers=4, d_model=64,
             n_heads=4, n_kv_heads=4, d_ff=0, vocab_size=128,
             use_rope=False, norm="layernorm", norm_eps=1e-5,
             slstm_every=4, tie_embeddings=True, sub_quadratic=True)
DENSE = dict(arch_id="dense-tiny", family="dense", n_layers=2, d_model=64,
             n_heads=4, n_kv_heads=4, d_ff=96, vocab_size=128,
             rope_theta=10000.0, rope_fraction=0.25, norm="layernorm",
             norm_eps=1e-5, qkv_bias=True, tie_embeddings=False, act="silu")
ARCH = {"xlstm": XLSTM, "transformer": DENSE}


def cell(workload: str, *, seed: int = 3_000_000_123,
         seconds: float = 0.5, seq_len: int = 64) -> H.Cell:
    """The workload's cell with a tiny model, short sequences and a
    batch of 4."""
    c = H.load_cell(workload, seed=seed, seconds=seconds, trace=False,
                    t_start=time.perf_counter())
    c.config = dict(c.config, arch=ARCH[c.config["family"]])
    c.mix = dict(c.mix, seq_len=seq_len, global_batch=4)
    return c
