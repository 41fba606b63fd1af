"""DeepSeek-V2-Lite's cell on the CPU at a tiny size: the float32
reference against the program's model (loss and every gradient leaf),
the cell driven end to end, sound and with each planted fault, and the
kernel metrics' arithmetic."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness as H
from chipbench import run as R
from chipbench.models import deepseek_v2
from chipbench.tests.test_cells import _plant
from chipbench.trace import Reduction

WORKLOAD = "dsv2lite.train.s4096b4"

# 1 dense + 3 expert layers; 8 routed experts, 4 held (experts 2-5)
TINY = dict(
    arch_id="dsv2-tiny", family="moe", n_layers=4, d_model=64, n_heads=4,
    n_kv_heads=4, d_ff=0, vocab_size=128, head_dim=16, rope_theta=10000.0,
    norm="rmsnorm", norm_eps=1e-6, tie_embeddings=False, act="silu",
    rope_scaling=dict(factor=40.0, original_max_position_embeddings=16,
                      beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                      mscale_all_dim=0.707),
    moe=dict(n_routed=8, n_shared=2, top_k=2, d_ff=32, n_held=4,
             first_held=2, router_z_coef=0.0, aux_coef=0.001, seq_aux=True,
             first_dense_layers=1, dense_d_ff=96),
    mla=dict(kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=16,
             v_head_dim=16))


def _program(arch):
    from repro.configs.base import ArchConfig
    from repro.models.registry import build_model
    return build_model(ArchConfig(**arch), remat=True)


def test_reference_matches_program():
    cfg = dict(TINY, z_loss=1e-4)
    model = _program(TINY)
    w = deepseek_v2.init(jax.random.key(3), cfg)
    want = jax.eval_shape(model.init, jax.random.key(0))
    assert jax.tree.structure(want) == jax.tree.structure(w)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(want)] == \
        [(a.shape, a.dtype) for a in jax.tree.leaves(w)]
    rng = np.random.default_rng(0)
    tok = rng.integers(1, TINY["vocab_size"], (2, 64)).astype(np.int32)
    tgt = rng.integers(1, TINY["vocab_size"], (2, 64)).astype(np.int32)
    w32 = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    with jax.default_matmul_precision("highest"):
        (lp, metrics), gp = jax.value_and_grad(
            lambda p: model.loss(p, {"tokens": tok, "targets": tgt}),
            has_aux=True)(w32)
        lr, gr = jax.value_and_grad(
            lambda p: deepseek_v2.loss(p, tok, tgt, cfg))(w32)
    assert abs(float(lp) - float(lr)) <= 1e-5 * abs(float(lr))
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-4 * float(
            jnp.linalg.norm(b)) + 1e-12
    # the balance loss is in both, and each held pair is counted
    assert float(metrics["aux"]) > 0
    assert 0 < int(metrics["counters"]["moe_held_pairs"]) <= 3 * 2 * 64 * 2


def _tiny_cell(**kw):
    c = H.load_cell(WORKLOAD, seed=3_000_000_161, seconds=0.5, trace=False,
                    t_start=time.perf_counter())
    c.config = dict(c.config, arch=TINY)
    c.mix = dict(c.mix, seq_len=64, global_batch=4)
    return c


@pytest.mark.parametrize("fault", [None, "frozen_state", "half_batch"])
def test_one_chip_cell(fault, monkeypatch):
    _plant(monkeypatch, fault)
    res = R.run_cell(_tiny_cell(), peak={})
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(H.read_json(
        H.HERE, "limits", WORKLOAD + ".json"))
    assert res["correct"] is (fault is None), res["checks"]
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    assert res["attempted"] >= 2 and res["failed"] == 0


def test_flops_follow_the_published_widths():
    """At the cell's own config: 12,288 held pairs a step at the uniform
    expectation, 620.8M model FLOPs a token forward, 30.5 TFLOP a
    step."""
    cfg = H.read_json(H.HERE, "configs", "deepseek-v2-lite.json")["arch"]
    assert deepseek_v2.routed_pairs(cfg, 16384) == 12288
    per_token = deepseek_v2.flops_per_token(cfg, 4096)
    assert 620e6 < per_token < 622e6
    assert 30.4e12 < deepseek_v2.flops_per_step(cfg, 4, 4096) < 30.6e12


def test_expert_gmm_work_counts_every_kernel_run():
    """4 expert layers x 3 products x (2 forward with the remat + 2
    backward) runs of 2 P K N FLOPs; at the uniform expectation, 3.4
    TFLOP a step."""
    cfg = H.read_json(H.HERE, "configs", "deepseek-v2-lite.json")["arch"]
    pairs = 4 * deepseek_v2.routed_pairs(cfg, 16384)
    work = deepseek_v2.expert_gmm_work(cfg, pairs, remat=True)
    assert work["flops"] == 4 * 3 * 4 * 2 * 12288 * 2048 * 1408
    assert deepseek_v2.expert_gmm_work(cfg, pairs, remat=False)[
        "flops"] == work["flops"] * 3 / 4
    # no pair routed here: the kernels still read the held weights
    idle = deepseek_v2.expert_gmm_work(cfg, 0, remat=True)
    assert idle["flops"] == 0
    # 3 products x 4 runs, each reading 4 layers x 8 experts of weights
    assert idle["bytes"] == 3 * 4 * (4 * 8 * 2048 * 1408) * 2


def _reduction(op_ns):
    return Reduction(window_ns=1e9, busy_ns=[1e9],
                     exposed_collective_ns=[0.0], op_ns=op_ns, gaps=[])


def test_kernel_metric_reads_the_kernel_ops():
    pct = H.load_file_module(f"{H.HERE}/metrics/expert_gmm_pct.py", "m1")
    inputs = {"reductions": [_reduction({"gmm.3": 5e7, "tgmm.1": 5e7,
                                         "fusion.2": 8e8})],
              "chips": 1, "traced_steps": 3}
    assert pct.read(inputs) == pytest.approx(10.0)
    none = dict(inputs, reductions=[_reduction({"fusion.2": 1e9})])
    assert pct.read(none) is None

