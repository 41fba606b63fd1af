"""One job training on one chip through the program's ``Trainer.run``.

The Trainer is built as ``repro.launch.train.build_trainer`` builds it,
from the cell's config and mix.  One Trainer object serves the whole
run, and every call goes through ``Trainer.run``, which starts from the
seed's weights each time:

1. set-up: ``run`` for 1 step (compiles; the optimizer's first moment
   then holds the first gradient) and for 3 steps (the losses and the
   masters the reference is compared with; the step time that sizes the
   window).  The first steps' batches are checked against the
   benchmark's copy of the corpus;
2. the window: ``run`` for 1 + n + 1 steps; the window is steps 1..n,
   from the start of step 1 to the start of step n + 1 on the host
   clock, with all host work between steps in it.  Step boundaries come
   from the Trainer's per-step ``failure_hook``, which always returns
   False.  With ``--trace 1`` the profiler records ``trace_steps`` steps
   from the middle of the window;
3. after the window, with the program's state freed: the float32
   reference follows the same three steps.
"""
from __future__ import annotations

import math
import shutil
import tempfile
from typing import Any, Dict

from chipbench import data as D
from chipbench import harness as H


def _data_cfg(cell: H.Cell):
    from repro.data import DataConfig
    mx = cell.mix
    return DataConfig(vocab_size=cell.config["arch"]["vocab_size"],
                      seq_len=mx["seq_len"], global_batch=mx["global_batch"],
                      zipf_a=mx["zipf_a"], mean_doc_len=mx["mean_doc_len"],
                      eos_id=0, seed=cell.seed)


def copy_batch(cell: H.Cell, step: int):
    mx = cell.mix
    return D.batch(vocab_size=cell.config["arch"]["vocab_size"],
                   seq_len=mx["seq_len"], global_batch=mx["global_batch"],
                   zipf_a=mx["zipf_a"], mean_doc_len=mx["mean_doc_len"],
                   eos_id=0, seed=cell.seed, step=step)


class _BatchCheck:
    """Wraps the step during set-up: each batch the step receives must
    be the benchmark corpus's batch of that step."""

    def __init__(self, cell, step_fn):
        self.cell, self.step_fn, self.step = cell, step_fn, 0

    def __call__(self, params, opt_state, batch):
        import jax
        D.check_same(jax.device_get(batch), copy_batch(self.cell, self.step),
                     f"step {self.step}")
        self.step += 1
        return self.step_fn(params, opt_state, batch)


def build_trainer(cell: H.Cell, ckpt_dir: str, hook):
    from repro import optim
    from repro.train import Trainer, TrainerConfig
    mx = cell.mix
    return Trainer(
        H.build_model(cell),
        optim.AdamWConfig(**mx["optimizer"]),
        TrainerConfig(n_steps=1, ckpt_every=mx["ckpt_every"],
                      ckpt_dir=ckpt_dir, log_every=1,
                      cross_pod_mode=mx["cross_pod_mode"]),
        _data_cfg(cell), failure_hook=hook)


def run(cell: H.Cell) -> Dict[str, Any]:
    mx, fam = cell.mix, cell.family
    tokens = mx["seq_len"] * mx["global_batch"]
    state = {"marks": None}

    def hook(step: int) -> bool:
        if state["marks"] is not None:
            state["marks"].mark()
        return False

    ckpt_dir = tempfile.mkdtemp(prefix="chipbench-ckpt-")
    try:
        tr = build_trainer(cell, ckpt_dir, hook)
        b1 = mx["optimizer"]["b1"]
        program_step = tr.step_fn
        tr.step_fn = _BatchCheck(cell, program_step)

        # set-up 1: one step; Adam's first moment is (1 - b1) * gradient
        tr.tcfg.n_steps = 1
        out = tr.run(seed=cell.seed, resume=False)
        names = H.leaf_names(out["params"])
        g1 = H.leaf_norms(out["opt_state"].mu, scale=1.0 / (1.0 - b1))
        H.free((out["params"], out["opt_state"]))
        del out

        # set-up 2: three steps; their losses and masters
        tr.step_fn.step = 0
        tr.tcfg.n_steps = 3
        marks = state["marks"] = H.Marks()
        h0 = len(tr.history)
        out = tr.run(seed=cell.seed, resume=False)
        losses = [h["loss"] for h in tr.history[h0:h0 + 3]]
        upd = H.host_deltas(out["opt_state"].master, H.make_weights(cell)())
        H.free((out["params"], out["opt_state"]))
        del out
        step_s = float(marks.intervals()[-1])
        prog = H.Readings(losses, g1, upd)

        # the window: the program's own step, no wrapper
        tr.step_fn = program_step
        n = H.window_steps(cell.seconds, step_s)
        trace_dir = None
        if cell.trace:
            trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            first = 1 + max(0, n // 2 - mx["trace_steps"] // 2)
            marks = H.Marks(trace_dir, [(first, first + mx["trace_steps"])])
        else:
            marks = H.Marks()
        state["marks"] = marks
        tr.tcfg.n_steps = n + 2
        h0 = len(tr.history)
        out = tr.run(seed=cell.seed, resume=False)
        wl = [h["loss"] for h in tr.history[h0:]]
        H.free((out["params"], out["opt_state"]))
        del out, tr
        t = marks.times
        window_s = t[n + 1] - t[1]
        setup_s = t[1] - cell.t_start
        dev = H.device_info(cell.chips)
        reductions = H.reduce_traces(marks) if cell.trace else []
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    # the reference, once the window has closed and the state is freed
    ref = H.reference_readings(cell, [copy_batch(cell, s)
                                      for s in range(3)])
    numbers = H.compare(prog, ref, names)
    failed = sum(1 for x in wl if not math.isfinite(x))
    return {
        "attempted": n, "failed": failed,
        "numbers": numbers,
        "end_to_end": {"tokens_per_s": n * tokens / window_s,
                       "setup_s": setup_s},
        "device": dev,
        "layer_inputs": {
            "reductions": reductions,
            "flops_per_step": fam.flops_per_step(
                cell.ref_cfg, mx["global_batch"], mx["seq_len"]),
            "traced_steps": mx["trace_steps"],
            "chips": cell.chips,
        },
        "extra": {"losses": prog.losses, "ref_losses": ref.losses,
                  "window_steps": n, "window_s": window_s,
                  "last_loss": wl[-1] if wl else None},
    }
