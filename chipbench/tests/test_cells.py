"""Each cell driven end to end on the CPU at a tiny size, past the
harness's look for a chip: a sound run comes out correct, and a run with
the timed path broken underneath comes out not correct, once for each
fault the cell can have.  The cells train on one chip, so there is no
exchange between chips to leave out, and produce no tokens or answers
to alter."""
import os
import subprocess
import sys

import pytest

from chipbench import harness as H
from chipbench import run as R
from chipbench.drivers import train as T
from chipbench.tests import tiny

ROOT = H.ROOT
ONE_CHIP = ["xlstm125m.train.s2048b8", "stablelm16b.train.s4096b4"]


class _Frozen:
    """A step that returns its state unchanged."""

    def __init__(self, step_fn):
        self.step_fn = step_fn

    def __call__(self, params, opt_state, batch):
        import jax
        import jax.numpy as jnp
        copy = jax.tree.map(jnp.copy, (params, opt_state))
        _, _, metrics = self.step_fn(*copy, batch)
        return params, opt_state, metrics


class _HalfBatch:
    """The model with its loss taken over the first half of the rows."""

    def __init__(self, model):
        self._model = model

    def loss(self, params, batch):
        n = batch["tokens"].shape[0]
        return self._model.loss(params, {k: v[:n // 2]
                                         for k, v in batch.items()})

    def __getattr__(self, name):
        return getattr(self._model, name)


def _plant(monkeypatch, fault):
    """Break the program's timed path underneath the harness."""
    if fault == "frozen_state":
        build = T.build_trainer

        def frozen(cell, ckpt_dir, hook):
            tr = build(cell, ckpt_dir, hook)
            tr.step_fn = _Frozen(tr.step_fn)
            return tr
        monkeypatch.setattr(T, "build_trainer", frozen)
    elif fault == "half_batch":
        model = H.build_model
        monkeypatch.setattr(H, "build_model",
                            lambda cell: _HalfBatch(model(cell)))


@pytest.mark.parametrize("workload", ONE_CHIP)
@pytest.mark.parametrize("fault", [None, "frozen_state", "half_batch"])
def test_one_chip_cell(workload, fault, monkeypatch):
    _plant(monkeypatch, fault)
    res = R.run_cell(tiny.cell(workload), peak={})
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(H.read_json(
        H.HERE, "limits", workload + ".json"))
    assert res["correct"] is (fault is None), res["checks"]
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    assert res["attempted"] >= 2 and res["failed"] == 0


def test_no_tpu_exits_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", ONE_CHIP[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_unknown_device_kind_is_an_error():
    assert R.peak_row("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(R.NoChip):
        R.peak_row("TPU v99")


def test_every_name_finds_its_files():
    bench = H.read_json(ROOT, "BENCHMARK.json")
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        cfg = H.read_json(ROOT, c["file"])
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert os.path.exists(os.path.join(
            H.HERE, "models", cfg["family"] + ".py"))
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        cell = H.load_cell(w["name"], seed=0, seconds=1, trace=False,
                           t_start=0.0)
        assert os.path.exists(os.path.join(
            H.HERE, "drivers", cell.mix["driver"] + ".py"))
        reported = {m["name"] for m in R.end_to_end(w["name"])}
        assert "setup_s" in reported and len(reported) >= 2
        assert R.metric_readers(w["name"], sorted(reported))
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert m["moves"] in {x["name"] for x in R.end_to_end(w)}
