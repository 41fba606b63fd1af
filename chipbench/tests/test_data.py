"""The benchmark's copy of the corpus against the program's generator."""
import numpy as np
import pytest

from chipbench import data as D


@pytest.mark.parametrize("seed", [0, 1234, 2_147_483_701, 3_000_000_123])
def test_copy_equals_program_corpus(seed):
    from repro.data import DataConfig, SyntheticCorpus
    cfg = DataConfig(vocab_size=50304, seq_len=256, global_batch=4,
                     zipf_a=1.2, mean_doc_len=256, eos_id=0, seed=seed)
    corpus = SyntheticCorpus(cfg)
    for step in (0, 1, 7):
        D.check_same(corpus.batch(step), D.batch(
            vocab_size=50304, seq_len=256, global_batch=4, zipf_a=1.2,
            mean_doc_len=256, eos_id=0, seed=seed, step=step), "corpus")


def test_check_same_refuses_a_changed_token():
    b = D.batch(vocab_size=128, seq_len=16, global_batch=2, zipf_a=1.2,
                mean_doc_len=8, eos_id=0, seed=5, step=0)
    c = {k: v.copy() for k, v in b.items()}
    c["targets"][1, 3] += 1
    with pytest.raises(ValueError):
        D.check_same(c, b, "changed")
    with pytest.raises(ValueError):
        D.check_same({"tokens": b["tokens"]}, b, "missing")
    assert np.all(b["tokens"][:, 1:] == b["targets"][:, :-1])
