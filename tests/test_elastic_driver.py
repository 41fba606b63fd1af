"""End-to-end elastic preemption/repack: simulator reconfig events drive
the real sharded save -> reshard-restore -> continue cycle.

The multidevice tests are the PR-5 acceptance: a simulated trace's
reconfiguration events, mapped onto training steps by
``schedule_from_sim``, replay through ``ElasticDriver`` and the
continued loss curve is bitwise-identical to the uninterrupted run
((2,2) -> (4,1) and (2,2) -> (1,4), ``deterministic_reduce``).
"""
import pytest

from repro import optim
from repro.core.jct_model import ReconfigCostModel
from repro.core.simulator import simulate
from repro.core.traces import TraceCategory, generate_trace
from repro.data import DataConfig
from repro.elastic_driver import (ElasticDriver, ReconfigEvent,
                                  factorizations, schedule_from_sim)
from tests.conftest import run_multidevice


def _sim_with_drains():
    jobs = generate_trace(TraceCategory("philly", "balanced", "mixed"),
                          seed=7, double=False, max_size=4)
    r = simulate(jobs, "DM")
    assert r.n_drains > 0           # the golden trace reconfigures
    return r


def test_factorizations():
    assert factorizations(4) == [(1, 4), (2, 2), (4, 1)]
    assert factorizations(1) == [(1, 1)]
    assert all(p * d == 6 for p, d in factorizations(6))
    with pytest.raises(ValueError):
        factorizations(0)


def test_reconfig_event_validation():
    with pytest.raises(ValueError, match="step"):
        ReconfigEvent(step=0, mesh_shape=(2, 2))
    with pytest.raises(ValueError, match="mesh shape"):
        ReconfigEvent(step=1, mesh_shape=(2, 0))


def test_schedule_from_sim_maps_events_onto_steps():
    r = _sim_with_drains()
    n_steps = 20
    sched = schedule_from_sim(r, n_devices=4, n_steps=n_steps,
                              initial_shape=(2, 2))
    assert sched                               # drains became events
    steps = [e.step for e in sched]
    assert steps == sorted(set(steps))         # increasing, deduped
    assert all(1 <= s <= n_steps - 1 for s in steps)
    shapes = [(2, 2)] + [e.mesh_shape for e in sched]
    for prev, cur in zip(shapes, shapes[1:]):
        assert cur != prev                     # every event re-factors
        assert cur in factorizations(4)
    # sim times carried through, in order
    assert [e.sim_time for e in sched] == \
        sorted(e.sim_time for e in sched)
    # deterministic: same sim result -> same schedule
    assert schedule_from_sim(r, n_devices=4, n_steps=n_steps,
                             initial_shape=(2, 2)) == sched


def test_schedule_from_sim_degenerate_cases():
    r = _sim_with_drains()
    assert schedule_from_sim(r, n_devices=4, n_steps=1) == []
    # a single-factorization device count has nowhere to repack to
    assert schedule_from_sim(r, n_devices=1, n_steps=20) == []
    # FM never reconfigures -> empty schedule
    jobs = generate_trace(TraceCategory("philly", "balanced", "mixed"),
                          seed=7, double=False, max_size=4)
    fm = simulate(jobs, "FM")
    assert schedule_from_sim(fm, n_devices=4, n_steps=20) == []
    # max_events truncates
    one = schedule_from_sim(r, n_devices=4, n_steps=20, max_events=1)
    assert len(one) == 1


def test_run_refuses_stale_newer_checkpoint(tmp_path):
    """A leftover committed checkpoint past the first event would win
    the handoff's latest_step lookup — the driver must refuse, before
    compiling anything (so ``model`` is never touched here)."""
    stale = tmp_path / "step_00000099"
    stale.mkdir()
    # committed_steps verifies the manifest's step matches the dir name
    (stale / "manifest.json").write_text('{"step": 99}')
    drv = ElasticDriver(object(), optim.AdamWConfig(),
                        DataConfig(vocab_size=16, seq_len=4,
                                   global_batch=2),
                        base_dir=str(tmp_path))
    with pytest.raises(RuntimeError, match="stale"):
        drv.run(8, [ReconfigEvent(step=2, mesh_shape=(2, 2))])


def test_driver_rejects_bad_mode_and_duplicate_steps(tmp_path):
    dcfg = DataConfig(vocab_size=16, seq_len=4, global_batch=2)
    with pytest.raises(ValueError, match="mode"):
        ElasticDriver(object(), optim.AdamWConfig(), dcfg,
                      base_dir=str(tmp_path), mode="teleport")
    drv = ElasticDriver(object(), optim.AdamWConfig(), dcfg,
                        base_dir=str(tmp_path))
    with pytest.raises(ValueError, match="duplicate"):
        drv.run(8, [ReconfigEvent(step=2, mesh_shape=(2, 2)),
                    ReconfigEvent(step=2, mesh_shape=(4, 1))])
    with pytest.raises(ValueError, match="past the run"):
        drv.run(8, [ReconfigEvent(step=8, mesh_shape=(2, 2))])
    with pytest.raises(ValueError, match="factorization"):
        drv.run(8, [ReconfigEvent(step=2, mesh_shape=(3, 1))],
                initial_shape=(2, 2))


def test_simulate_rejects_conflicting_reconfig_args():
    """A 'drain'-labeled replay with a handoff cost model would report a
    handoff-vs-handoff delta of ~0 — refuse instead of mislabeling."""
    jobs = generate_trace(TraceCategory("philly", "small", "train"),
                          seed=0, double=False, max_size=4)
    cm = ReconfigCostModel(mode="handoff")
    with pytest.raises(ValueError, match="conflicts"):
        simulate(jobs, "DM", reconfig_mode="drain", reconfig_cost=cm)
    # a cost model alone governs the charging (no mode arg needed)
    r = simulate(jobs, "DM", reconfig_cost=cm)
    assert r.n_drains == 0


def test_elastic_driver_smoke_multidevice():
    """One save -> reshard-restore -> continue cycle, bitwise (the CI
    elastic-e2e step runs exactly this in both device-matrix legs)."""
    out = run_multidevice("""
        import tempfile
        from repro import optim, tracing
        from repro.data import DataConfig
        from repro.elastic_driver import ElasticDriver, ReconfigEvent
        from repro.models.registry import get_config, build_model, \\
            reduced_config

        cfg = reduced_config(get_config('llama3.2-1b'))
        model = build_model(cfg, remat=False)
        ocfg = optim.AdamWConfig(peak_lr=1e-3, warmup_steps=2,
                                 total_steps=4)
        dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                          global_batch=8)
        ref = ElasticDriver(model, ocfg, dcfg,
                            base_dir=tempfile.mkdtemp()).run(
            4, (), initial_shape=(2, 2))
        with tracing.recording() as rec:
            out = ElasticDriver(model, ocfg, dcfg,
                                base_dir=tempfile.mkdtemp()).run(
                4, [ReconfigEvent(step=2, mesh_shape=(4, 1))],
                initial_shape=(2, 2))
        assert out.losses == ref.losses, (out.losses, ref.losses)
        assert out.mesh_shapes[:2] == [(2, 2)] * 2
        assert out.mesh_shapes[2:] == [(4, 1)] * 2
        (m,) = out.measurements
        assert m.verified
        assert m.save_s > 0 and m.restore_s > 0
        assert m.save_bytes > 0 and m.state_bytes > 0
        # each phase is a span, and each duration is read from its span
        (save,) = rec.named('handoff.save')
        (setup,) = rec.named('handoff.setup')
        (restore,) = rec.named('handoff.restore')
        assert len(rec.named('handoff.verify')) == 1
        assert (m.save_s, m.setup_s, m.restore_s) == (
            save.seconds, setup.seconds, restore.seconds)
        steps = rec.named('train.step')
        assert len(steps) == 4
        assert save.end_ns <= restore.start_ns <= steps[2].start_ns
        # the first step on (4,1) compiles its own program
        assert m.first_step_compiles >= 1 and m.first_step_compile_s > 0
        print('ELASTIC_SMOKE_OK')
        """, n_devices=8)
    assert "ELASTIC_SMOKE_OK" in out


def test_preemption_replay_bitwise_multidevice():
    """The PR-5 acceptance: a *simulated trace's* reconfiguration event
    replays through the real driver; the continued loss curve is
    bitwise-identical to the uninterrupted run for (2,2) -> (4,1) and
    (2,2) -> (1,4)."""
    r = _sim_with_drains()
    sched = schedule_from_sim(r, n_devices=4, n_steps=8,
                              initial_shape=(2, 2), max_events=1)
    assert sched, "the simulated trace must provide a reconfig event"
    event_step = sched[0].step
    out = run_multidevice(f"""
        import tempfile
        from repro import optim
        from repro.data import DataConfig
        from repro.elastic_driver import ElasticDriver, ReconfigEvent
        from repro.models.registry import get_config, build_model, \\
            reduced_config

        cfg = reduced_config(get_config('llama3.2-1b'))
        model = build_model(cfg, remat=False)
        ocfg = optim.AdamWConfig(peak_lr=1e-3, warmup_steps=2,
                                 total_steps=8)
        dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                          global_batch=8)

        def drive(schedule):
            drv = ElasticDriver(model, ocfg, dcfg,
                                base_dir=tempfile.mkdtemp(),
                                bucket_bytes=64 << 10)
            return drv.run(8, schedule, initial_shape=(2, 2))

        ref = drive(())
        for target in ((4, 1), (1, 4)):
            out = drive([ReconfigEvent(step={event_step},
                                       mesh_shape=target)])
            assert out.losses == ref.losses, (target, out.losses,
                                              ref.losses)
            (m,) = out.measurements
            assert m.verified and m.to_shape == target
            print('REPLAY_%dx%d_OK' % target)
        print('PREEMPTION_REPLAY_BITWISE_OK')
        """, n_devices=8)
    assert "REPLAY_4x1_OK" in out
    assert "REPLAY_1x4_OK" in out
    assert "PREEMPTION_REPLAY_BITWISE_OK" in out
