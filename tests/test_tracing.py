"""Host spans, compile spans and device scopes (``repro.tracing``)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim, tracing
from repro.data import DataConfig
from repro.models.registry import build_model, get_config, reduced_config
from repro.train import (Trainer, TrainerConfig, init_train_state,
                         make_jitted_train_step)

STEP_CHILDREN = ["train.data_wait", "train.put_batch", "train.dispatch",
                 "train.device_wait", "train.readback"]


def test_spans_nest_and_record_their_parent():
    with tracing.recording() as rec:
        with tracing.span("outer") as outer:
            with tracing.span("inner") as inner:
                pass
            with tracing.step_span("step", 7) as step:
                pass
    assert [s.name for s in rec.spans] == ["inner", "step", "outer"]
    assert inner.parent is outer and step.parent is outer
    assert outer.parent is None
    assert outer.start_ns <= inner.start_ns <= inner.end_ns \
        <= step.start_ns <= step.end_ns <= outer.end_ns
    assert rec.children(outer) == [inner, step]
    assert rec.totals["outer"].count == 1
    assert rec.totals["outer"].sum == outer.end_ns - outer.start_ns


def test_nothing_is_stored_outside_recording():
    with tracing.span("before") as before:
        pass
    assert before.end_ns >= before.start_ns > 0   # timed all the same
    with tracing.recording() as rec:
        with tracing.recording() as inner:
            with tracing.span("both"):
                pass
        with tracing.span("outer only"):
            pass
    with tracing.span("after"):
        pass
    assert [s.name for s in rec.spans] == ["both", "outer only"]
    assert [s.name for s in inner.spans] == ["both"]
    assert set(rec.totals) == {"both", "outer only"}
    assert tracing._ACTIVE == []


def test_recorder_is_bounded(monkeypatch):
    monkeypatch.setattr(tracing, "MAX_SPANS", 4)
    with tracing.recording() as rec:
        for _ in range(10):
            with tracing.span("s"):
                pass
    assert len(rec.spans) == 4
    assert rec.totals["s"].count == 10


def test_compile_counter_counts_a_fresh_jit_once():
    x = np.arange(8, dtype=np.float32)
    f = jax.jit(lambda a: a * 3.0 + 1.0)
    with tracing.recording() as rec:
        with tracing.span("first") as first:
            f(x).block_until_ready()
    with tracing.recording() as again:
        f(x).block_until_ready()
    assert rec.totals[tracing.COMPILE].count == 1
    (c,) = rec.named(tracing.COMPILE)
    assert c.parent is first
    assert first.start_ns <= c.start_ns <= c.end_ns <= first.end_ns
    assert tracing.COMPILE not in again.totals


def test_trainer_step_spans_match_history(tmp_path):
    cfg = reduced_config(get_config("llama3.2-1b"))
    model = build_model(cfg, remat=False)
    tr = Trainer(model, optim.AdamWConfig(),
                 TrainerConfig(n_steps=3, ckpt_every=10 ** 9,
                               ckpt_dir=str(tmp_path), log_every=1),
                 DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                            global_batch=2))
    with tracing.recording() as rec:
        tr.run(resume=False)
    steps = rec.named("train.step")
    assert len(steps) == 3
    assert len(rec.named("train.init_state")) == 1
    assert rec.named("train.restore") == [] and rec.named("train.ckpt") == []
    for s, h in zip(steps, tr.history):
        kids = rec.children(s)
        assert [k.name for k in kids] == STEP_CHILDREN
        assert all(s.start_ns <= k.start_ns <= k.end_ns <= s.end_ns
                   for k in kids)
        wait, done = kids[0], kids[3]
        assert h["sec_per_step"] == (done.end_ns - wait.start_ns) * 1e-9
    # the first step compiles inside its dispatch, the others do not
    compiles = rec.named(tracing.COMPILE)
    assert compiles
    assert {c.parent.parent for c in compiles
            if c.parent is not None and c.parent.name == "train.dispatch"
            } == {steps[0]}


@pytest.mark.parametrize("arch,scopes", [
    ("llama3.2-1b", {"attention", "mlp", "head", "optimizer"}),
    ("xlstm-125m", {"mlstm", "slstm", "head", "optimizer"}),
])
def test_train_step_hlo_carries_the_scopes(arch, scopes):
    cfg = reduced_config(get_config(arch))
    model = build_model(cfg, remat=True)
    ocfg = optim.AdamWConfig()
    params, opt_state, _, _ = init_train_state(model, ocfg)
    step = make_jitted_train_step(model, ocfg, accum=1, rules=None)
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
             "targets": jnp.zeros((2, 16), jnp.int32)}
    text = step.lower(params, opt_state, batch).as_text("hlo",
                                                        debug_info=True)
    parts = {p for name in re.findall(r'op_name="([^"]*)"', text)
             for p in re.sub(r"(jvp|transpose)\(|\)", "",
                             name).split("/")}
    assert scopes | {"rematted_computation"} <= parts


def _dsv2_tiny():
    import dataclasses
    cfg = reduced_config(get_config("deepseek-v2-lite-16b"))
    # 4 of the 8 experts held, from expert 2
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, n_held=4, first_held=2))


def test_moe_ops_carry_their_scopes():
    cfg = _dsv2_tiny()
    model = build_model(cfg, remat=True)
    ocfg = optim.AdamWConfig()
    params, opt_state, _, _ = init_train_state(model, ocfg)
    step = make_jitted_train_step(model, ocfg, accum=1, rules=None)
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
             "targets": jnp.zeros((2, 16), jnp.int32)}
    text = step.lower(params, opt_state, batch).as_text("hlo",
                                                        debug_info=True)
    stacks = {re.sub(r"(jvp|transpose)\(|\)", "", name)
              for name in re.findall(r'op_name="([^"]*)"', text)}
    for scope in ("moe_router", "moe_dispatch", "moe_experts",
                  "moe_combine", "moe_shared"):
        assert any(f"/mlp/{scope}/" in s for s in stacks), scope


def test_moe_counters_reach_history(tmp_path, monkeypatch):
    """``moe_held_pairs`` and ``moe_max_expert_pairs`` of each step, in
    ``Trainer.history``, against a plain count over every layer's top-k
    of the same weights and batch."""
    from repro.data import SyntheticCorpus
    from repro.models import ffn as F

    cfg = _dsv2_tiny()
    model = build_model(cfg, remat=False)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=2)
    tr = Trainer(model, optim.AdamWConfig(),
                 TrainerConfig(n_steps=1, ckpt_every=10 ** 9,
                               ckpt_dir=str(tmp_path), log_every=1), dcfg)
    tr.run(seed=5, resume=False)
    (h,) = tr.history

    picks = []
    route = F._route

    def recording(x, router_w, m):
        top_p, top_e, aux = route(x, router_w, m)
        jax.debug.callback(lambda e: picks.append(np.asarray(e)), top_e)
        return top_p, top_e, aux

    monkeypatch.setattr(F, "_route", recording)
    batch = SyntheticCorpus(dcfg).batch(0)
    jax.block_until_ready(model.loss(model.init(jax.random.key(5)), batch))
    n_moe = cfg.n_layers - cfg.moe.first_dense_layers
    assert len(picks) == n_moe
    loads = [np.bincount(e.ravel(), minlength=8)[2:6] for e in picks]
    assert h["moe_held_pairs"] == sum(int(x.sum()) for x in loads)
    assert h["moe_max_expert_pairs"] == max(int(x.max()) for x in loads)
    assert 0 < h["moe_held_pairs"] < n_moe * 2 * 16 * cfg.moe.top_k
