"""Overlapped (software-pipelined) bucket sync: parity + pipelinability.

The overlapped schedule must be bitwise-identical to the serial one (it
reorders collective *issue*, never per-bucket arithmetic), silently
no-op in the degenerate cases, keep its slow collectives data-independent
in the lowered HLO (the pipelinability invariant), and — with the int8
slow hop — error feedback must pull the loss curve back toward the
uncompressed one.
"""
import jax
import jax.numpy as jnp
import pytest

from repro import optim
from repro.train import make_train_step
from tests.conftest import run_multidevice


def test_overlap_rejected_outside_bucketed_modes():
    with pytest.raises(ValueError, match="overlap"):
        make_train_step(object(), optim.AdamWConfig(),
                        cross_pod_mode="xla", overlap=True)
    with pytest.raises(ValueError, match="overlap"):
        make_train_step(object(), optim.AdamWConfig(),
                        cross_pod_mode="hier", slow_error_feedback=True,
                        slow_compress_bits=8)


def test_error_feedback_requires_int8():
    with pytest.raises(ValueError, match="slow_compress_bits=8"):
        make_train_step(object(), optim.AdamWConfig(),
                        cross_pod_mode="hier_bucketed",
                        slow_error_feedback=True)


def test_overlap_bitwise_parity_10_steps_multidevice():
    """Acceptance: overlap=True vs overlap=False is bitwise-identical in
    loss and params over 10 steps on a (2,2) pod x data mesh, for both
    hier_bucketed and hier_bucketed_zero1, on a multi-bucket layout —
    with and without the int8+error-feedback slow hop (which exercises
    the pipelined-with-residuals schedule and the zero1 EFState specs).
    """
    out = run_multidevice("""
        from repro import parallel as PX
        import jax, jax.numpy as jnp, numpy as np
        from repro import optim
        from repro.data import DataConfig, SyntheticCorpus
        from repro.models.registry import get_config, build_model, \\
            reduced_config
        from repro.sharding import make_rules
        from repro.train import (EFState, init_slow_residuals,
                                 make_jitted_train_step,
                                 make_bucket_layout)

        cfg = reduced_config(get_config('llama3.2-1b'))
        model = build_model(cfg, remat=False)
        mesh = PX.make_device_mesh((2, 2), ('pod', 'data'))
        rules = make_rules(mesh, fsdp=False)
        corpus = SyntheticCorpus(DataConfig(vocab_size=cfg.vocab_size,
                                            seq_len=16, global_batch=8))
        ocfg = optim.AdamWConfig(peak_lr=1e-3, warmup_steps=2,
                                 total_steps=20)
        bb = 64 << 10          # small buckets -> a real multi-bucket pipe
        layout = make_bucket_layout(model.init(jax.random.key(0)), mesh,
                                    bucket_bytes=bb)
        assert layout.n_buckets >= 2, layout.n_buckets

        results = {}
        for mode in ('hier_bucketed', 'hier_bucketed_zero1'):
            for ef in (False, True):
                for overlap in (False, True):
                    p = model.init(jax.random.key(0))
                    st = (optim.init_bucketed(ocfg, p, layout)
                          if mode == 'hier_bucketed_zero1'
                          else optim.init(ocfg, p))
                    if ef:
                        st = EFState(st, init_slow_residuals(
                            p, mesh, bucket_bytes=bb))
                    step = make_jitted_train_step(
                        model, ocfg, accum=1, rules=rules,
                        cross_pod_mode=mode, bucket_bytes=bb,
                        slow_compress_bits=8 if ef else 0,
                        slow_error_feedback=ef, overlap=overlap)
                    losses = []
                    with mesh:
                        for i in range(10):
                            b = {k: jnp.asarray(v)
                                 for k, v in corpus.batch(i).items()}
                            p, st, m = step(p, st, b)
                            losses.append(float(m['loss']))
                    results[(mode, ef, overlap)] = (losses, p, st)

        for mode in ('hier_bucketed', 'hier_bucketed_zero1'):
            for ef in (False, True):
                serial, p_s, st_s = results[(mode, ef, False)]
                piped, p_o, st_o = results[(mode, ef, True)]
                assert serial == piped, (mode, ef, serial, piped)
                assert serial[0] != serial[-1]   # it actually trained
                for a, b in zip(jax.tree.leaves(p_s),
                                jax.tree.leaves(p_o)):
                    assert np.array_equal(np.asarray(a),
                                          np.asarray(b)), (mode, ef)
                if ef:
                    # carried residuals are live and themselves bitwise
                    # identical across the two schedules
                    assert any(float(jnp.sum(jnp.abs(r))) > 0
                               for r in st_s.residuals)
                    for a, b in zip(st_s.residuals, st_o.residuals):
                        assert np.array_equal(np.asarray(a),
                                              np.asarray(b)), mode
        print("OVERLAP_PARITY_OK")
        """, n_devices=4)
    assert "OVERLAP_PARITY_OK" in out


def test_overlap_degenerate_noop_multidevice():
    """Single-bucket layouts and size-1 meshes must take the serial path
    under overlap=True — same losses, and (size-1) no collectives at
    all."""
    out = run_multidevice("""
        import jax, jax.numpy as jnp
        from repro import optim, parallel as PX
        from repro.models.registry import get_config, build_model, \\
            reduced_config
        from repro.sharding import make_rules
        from repro.train import make_jitted_train_step, make_bucket_layout

        cfg = reduced_config(get_config('llama3.2-1b'))
        model = build_model(cfg, remat=False)
        rng = jax.random.key(1)
        batch = {'tokens': jax.random.randint(rng, (4, 32), 0,
                                              cfg.vocab_size),
                 'targets': jax.random.randint(rng, (4, 32), 0,
                                               cfg.vocab_size)}
        ocfg = optim.AdamWConfig(peak_lr=1e-3, warmup_steps=1,
                                 total_steps=10)

        # (2,2) mesh, one giant bucket: pipeline degenerates to serial
        mesh = PX.make_device_mesh((2, 2), ('pod', 'data'))
        rules = make_rules(mesh, fsdp=False)
        losses = {}
        for overlap in (False, True):
            p = model.init(jax.random.key(0))
            st = optim.init(ocfg, p)
            step = make_jitted_train_step(
                model, ocfg, accum=1, rules=rules,
                cross_pod_mode='hier_bucketed',
                bucket_bytes=1 << 30, overlap=overlap)
            with mesh:
                for _ in range(2):
                    p, st, m = step(p, st, batch)
            losses[overlap] = float(m['loss'])
        assert losses[False] == losses[True], losses

        # (1,1) mesh: overlap=True must run the local (collective-free)
        # path without touching axis names
        mesh1 = PX.make_device_mesh((1, 1), ('pod', 'data'),
                                    devices=jax.devices()[:1])
        rules1 = make_rules(mesh1, fsdp=False)
        for mode in ('hier_bucketed', 'hier_bucketed_zero1'):
            p = model.init(jax.random.key(0))
            st = (optim.init_bucketed(
                      ocfg, p, make_bucket_layout(p, mesh1))
                  if mode == 'hier_bucketed_zero1'
                  else optim.init(ocfg, p))
            step = make_jitted_train_step(
                model, ocfg, accum=1, rules=rules1,
                cross_pod_mode=mode, overlap=True)
            with mesh1:
                p, st, m = step(p, st, batch)
            assert jnp.isfinite(m['loss'])
        print("OVERLAP_DEGENERATE_OK")
        """, n_devices=4)
    assert "OVERLAP_DEGENERATE_OK" in out


def test_overlap_hlo_slow_collectives_independent_multidevice():
    """Pipelinability, proven from lowered HLO: the overlapped schedule
    emits one slow collective per bucket and none of them data-depends
    on another (``analysis.hlo.slow_collective_chains``)."""
    out = run_multidevice("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro import parallel as PX
        from repro.analysis.hlo import slow_collective_chains
        from repro.collectives import bucketing as BK

        mesh = PX.make_device_mesh((2, 2), ('pod', 'data'))
        grads = {f't{i}': jax.ShapeDtypeStruct((256,), jnp.float32)
                 for i in range(6)}
        layout = BK.plan_buckets(grads, bucket_bytes=2048, align=2)
        assert layout.n_buckets >= 2

        def fn(g):
            b = BK.flatten_to_buckets(layout, g)
            s = BK.hier_reduce_bucket_shards(
                b, fast_axis='data', slow_axis='pod', overlap=True)
            full = BK.all_gather_buckets(s, fast_axis='data')
            return BK.unflatten_from_buckets(layout, full,
                                             dtype=jnp.float32)

        specs = jax.tree.map(lambda _: P(), grads)
        # compiled as the bucketed train step is compiled
        txt = jax.jit(PX.shard_map(
            fn, mesh=mesh, in_specs=(specs,), out_specs=specs,
            check_vma=False, axis_names={'pod', 'data'},
        ), compiler_options=BK.NO_COMBINE_COMPILER_OPTIONS,
        ).lower(grads).compile().as_text()
        chain = slow_collective_chains(txt, chips_per_pod=2)
        assert chain.n_slow == layout.n_buckets, chain
        assert chain.independent, chain.dependent_pairs
        print("OVERLAP_HLO_OK")
        """, n_devices=4)
    assert "OVERLAP_HLO_OK" in out


def test_int8_error_feedback_converges_closer_multidevice():
    """int8 + error feedback tracks the uncompressed loss curve strictly
    closer than int8 alone (summed |deviation| over 15 steps)."""
    out = run_multidevice("""
        from repro import parallel as PX
        import jax, jax.numpy as jnp, numpy as np
        from repro import optim
        from repro.data import DataConfig, SyntheticCorpus
        from repro.models.registry import get_config, build_model, \\
            reduced_config
        from repro.sharding import make_rules
        from repro.train import (EFState, init_slow_residuals,
                                 make_jitted_train_step)

        cfg = reduced_config(get_config('llama3.2-1b'))
        model = build_model(cfg, remat=False)
        mesh = PX.make_device_mesh((2, 2), ('pod', 'data'))
        rules = make_rules(mesh, fsdp=False)
        corpus = SyntheticCorpus(DataConfig(vocab_size=cfg.vocab_size,
                                            seq_len=16, global_batch=8))
        ocfg = optim.AdamWConfig(peak_lr=3e-3, warmup_steps=2,
                                 total_steps=20)
        bb = 64 << 10

        def run(bits, ef):
            p = model.init(jax.random.key(0))
            st = optim.init(ocfg, p)
            if ef:
                st = EFState(st, init_slow_residuals(p, mesh,
                                                     bucket_bytes=bb))
            step = make_jitted_train_step(
                model, ocfg, accum=1, rules=rules,
                cross_pod_mode='hier_bucketed', bucket_bytes=bb,
                slow_compress_bits=bits, slow_error_feedback=ef)
            losses = []
            with mesh:
                for i in range(15):
                    b = {k: jnp.asarray(v)
                         for k, v in corpus.batch(i).items()}
                    p, st, m = step(p, st, b)
                    losses.append(float(m['loss']))
            if ef:
                # residuals are live state: quantization error is
                # actually being carried
                assert any(float(jnp.sum(jnp.abs(r))) > 0
                           for r in st.residuals)
            return np.asarray(losses)

        ref = run(0, False)
        q = run(8, False)
        qef = run(8, True)
        dev_q = float(np.abs(q - ref).sum())
        dev_qef = float(np.abs(qef - ref).sum())
        print('dev int8', dev_q, 'dev int8+EF', dev_qef)
        assert dev_q > 0.0                      # int8 does perturb
        assert dev_qef < dev_q, (dev_qef, dev_q)
        print("EF_CONVERGENCE_OK")
        """, n_devices=4)
    assert "EF_CONVERGENCE_OK" in out
