"""The benchmark's copy of the program's synthetic corpus, and the check
that the batches a run trains on are the copy's.

Zipf(``zipf_a``)-distributed token ids folded into [1, vocab), documents
ended by the end-of-sequence id 0 at geometric intervals of mean
``mean_doc_len``; row r of step k's batch is drawn from
``numpy.random.default_rng((seed, k, shard, 0xC0FFEE))``.  Keeping the
generator here means that a change to the program's data path shows as a
failed check, not as a silent change of the traffic.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def batch(*, vocab_size: int, seq_len: int, global_batch: int,
          zipf_a: float, mean_doc_len: int, eos_id: int, seed: int,
          step: int, shard: int = 0, n_shards: int = 1
          ) -> Dict[str, np.ndarray]:
    rows = global_batch // n_shards
    rng = np.random.default_rng((seed, step, shard, 0xC0FFEE))
    n = rows * (seq_len + 1)
    toks = rng.zipf(zipf_a, size=n).astype(np.int64)
    toks = toks % (vocab_size - 1) + 1
    ends = np.cumsum(rng.geometric(1.0 / mean_doc_len, size=n // 16))
    toks[ends[ends < n]] = eos_id
    toks = toks.reshape(rows, seq_len + 1)
    return {"tokens": toks[:, :-1].astype(np.int32),
            "targets": toks[:, 1:].astype(np.int32)}


def check_same(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray],
               what: str) -> None:
    """Raise unless two batches are equal, key for key and bit for bit."""
    if set(got) != set(want):
        raise ValueError(f"{what}: batch keys {sorted(got)} != "
                         f"{sorted(want)}")
    for k in want:
        g, w = np.asarray(got[k]), np.asarray(want[k])
        if g.shape != w.shape or g.dtype != w.dtype \
                or not np.array_equal(g, w):
            raise ValueError(f"{what}: {k!r} differs from the benchmark's "
                             f"corpus ({g.shape} {g.dtype} vs {w.shape} "
                             f"{w.dtype})")
