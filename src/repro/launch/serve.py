"""Serving launcher: ``python -m repro.launch.serve --arch <id>``.

Runs the batched continuous-batching server on synthetic requests.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.models.registry import ARCH_IDS, build_model, get_config, \
    reduced_config
from repro.serve import BatchedServer, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=ARCH_IDS)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--full-config", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if not args.full_config:
        cfg = reduced_config(cfg)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.key(0))
    server = BatchedServer(model, params, max_batch=args.max_batch,
                           max_seq=args.max_seq)
    rng = np.random.default_rng(0)
    for rid in range(args.requests):
        prompt = rng.integers(1, cfg.vocab_size,
                              size=int(rng.integers(2, 10))
                              ).astype(np.int32)
        server.submit(Request(rid, prompt, max_new=args.max_new))
    server.run_until_drained()
    for req in sorted(server.completed, key=lambda r: r.rid):
        print(f"request {req.rid}: {len(req.out)} tokens -> {req.out}")


if __name__ == "__main__":
    main()
