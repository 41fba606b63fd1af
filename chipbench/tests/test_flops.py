"""The model-FLOP functions against counts made by hand at a tiny size."""
from chipbench.models import transformer, xlstm


def test_xlstm_hand_count():
    cfg = dict(d_model=8, n_heads=2, n_layers=4, slstm_every=4,
               vocab_size=32)
    # one super-block: three mLSTM blocks (Di = 16, two heads of 8) and
    # one sLSTM block (two heads of 4); the tied head
    m_proj = (2 * 8 * 16      # w_up
              + 2 * 8 * 16    # w_z
              + 3 * 2 * 16 * 16   # wq, wk, wv
              + 2 * 16 * 4    # w_if: input and forget gate per head
              + 2 * 16 * 8    # w_down
              + 2 * 16 * 4)   # width-4 depthwise convolution
    assert m_proj == 2560
    m_cell = 2 * (2 * 8 * 8      # C += i * k v^T
                  + 2 * 8 * 8    # C^T q
                  + 2 * 8        # n += i * k
                  + 2 * 8)       # n . q
    assert m_cell == 576
    s_blk = 2 * 8 * 32 + 2 * (2 * 4 * 16) + 2 * 8 * 8
    assert s_blk == 896
    head = 2 * 8 * 32
    per_token = 3 * (m_proj + m_cell) + s_blk + head
    assert per_token == 10816
    assert xlstm.flops_per_token(cfg, 4) == per_token
    assert xlstm.flops_per_step(cfg, 2, 4) == 3 * 2 * 4 * 10816


def test_transformer_hand_count():
    cfg = dict(d_model=8, n_heads=2, n_kv_heads=2, d_ff=12, vocab_size=32,
               n_layers=2)
    S = 4
    proj = 4 * 2 * 8 * 8                 # q, k, v, o
    mlp = 3 * 2 * 8 * 12                 # gate, up, down
    # causal pairs of a 4-token sequence: 1 + 2 + 3 + 4 = 10, each
    # costing q.k and p*v in both heads of 4
    pairs = sum(range(1, S + 1))
    attn_per_token = pairs * 2 * (2 * 4 + 2 * 4) / S
    assert attn_per_token == 80
    per_token = 2 * (proj + mlp + attn_per_token) + 2 * 8 * 32
    assert per_token == 2848
    assert transformer.flops_per_token(cfg, S) == per_token
    assert transformer.flops_per_step(cfg, 2, S) == 3 * 2 * S * 2848


def test_cells_step_flops():
    """The per-step model FLOPs the two one-chip cells' MFU divides by."""
    import json
    import os
    here = os.path.join(os.path.dirname(__file__), "..", "configs")
    x = json.load(open(os.path.join(here, "xlstm-125m.json")))["arch"]
    s = json.load(open(os.path.join(here, "stablelm-1.6b.json")))["arch"]
    assert abs(xlstm.flops_per_step(x, 8, 2048) / 1.52973e13 - 1) < 1e-3
    assert abs(transformer.flops_per_step(s, 4, 4096) / 5.20566e13 - 1) < 1e-3
