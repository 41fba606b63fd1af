"""``chip_smoke.py``'s phases on the CPU at reduced widths.

The script itself refuses to run without a TPU; its phases do not check
the platform, so the same code paths run here on a reduced xlstm-125m.
"""
import sys

import pytest

from tests.conftest import REPO, run_multidevice

sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402


def test_train_phase_reduced(monkeypatch):
    """Train, checkpoint, resume bitwise, and agree with the f32
    reference.  The reduced widths learn slower than the published ones,
    so a larger learning rate moves the weights far enough from constant
    logits for the reference check to be meaningful."""
    monkeypatch.setattr(chip_smoke, "LR", 3e-2)
    chip_smoke.train_phase(full_config=False, seq=64)


def test_reference_check_refuses_uninformative_weights(tmp_path):
    """At the initial weights constant logits score too close to the
    reference for the tolerance to catch a broken forward."""
    import jax

    from repro.launch import train as launch_train

    args = launch_train.parse_args(chip_smoke.train_argv(
        str(tmp_path), steps=1, resume=False, full_config=False, seq=64))
    trainer = launch_train.build_trainer(args)
    params = trainer.model.init(jax.random.key(0))
    with pytest.raises(chip_smoke.SmokeFailure, match="constant logits"):
        chip_smoke.reference_check(trainer.model, trainer.data_cfg, params)


def test_handoff_phase_reduced_multidevice():
    """(2,2) -> (4,1) handoff on four CPU devices: bitwise-equal losses,
    verified restore, each device holding its own shard and rows."""
    out = run_multidevice(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import chip_smoke
        chip_smoke.handoff_phase(full_config=False, seq=64)
        print("HANDOFF_PHASE_OK")
        """, n_devices=4)
    assert "losses bitwise equal" in out
    assert "HANDOFF_PHASE_OK" in out


def test_main_refuses_cpu(monkeypatch, capsys):
    """No TPU: exit non-zero, name the platform, print no result line."""
    import jax

    assert jax.devices()[0].platform == "cpu"
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    monkeypatch.setattr(sys, "path", list(sys.path))
    rc = chip_smoke.main()
    out, err = capsys.readouterr()
    assert rc != 0
    assert "'cpu'" in err
    assert '"ok"' not in out
