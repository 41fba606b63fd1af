"""Reshard-on-restore acceptance: bitwise elastic continuation.

Save a ``hier_bucketed_zero1`` + ``deterministic_reduce`` training run's
sharded checkpoint at step 10 on a (2, 2) pod x data mesh, restore onto
(4, 1) and (1, 4) re-factorizations, continue to step 20 — losses and
final params must be bitwise-identical to the uninterrupted 20-step run,
with and without the int8 error-feedback slow hop.  Along the way the
test asserts the sharded-memory guarantee: saved shard files and
restored per-device shards are always 1/F-sized, never a full gathered
bucket.
"""
import jax
import jax.numpy as jnp
import pytest

from repro import optim
from repro.train import make_train_step
from tests.conftest import run_multidevice


def test_deterministic_reduce_rejected_outside_bucketed_modes():
    with pytest.raises(ValueError, match="deterministic_reduce"):
        make_train_step(object(), optim.AdamWConfig(),
                        cross_pod_mode="hier", deterministic_reduce=True)
    with pytest.raises(ValueError, match="overlap"):
        make_train_step(object(), optim.AdamWConfig(),
                        cross_pod_mode="hier_bucketed",
                        deterministic_reduce=True, overlap=True)


def test_reshard_continuation_bitwise_multidevice():
    """The PR-4 acceptance criterion, end to end."""
    out = run_multidevice("""
        from repro import parallel as PX
        import os, tempfile
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import ckpt, optim
        from repro.data import DataConfig, SyntheticCorpus
        from repro.models.registry import get_config, build_model, \\
            reduced_config
        from repro.sharding import make_rules
        from repro.train import (EFState, init_sharded_zero1,
                                 init_slow_residuals,
                                 make_jitted_train_step,
                                 make_bucket_layout)

        cfg = reduced_config(get_config('llama3.2-1b'))
        model = build_model(cfg, remat=False)
        corpus = SyntheticCorpus(DataConfig(vocab_size=cfg.vocab_size,
                                            seq_len=16, global_batch=8))
        ocfg = optim.AdamWConfig(peak_lr=1e-3, warmup_steps=2,
                                 total_steps=20)
        bb = 64 << 10                 # multi-bucket layout

        def batches(lo, hi):
            for i in range(lo, hi):
                yield {k: jnp.asarray(v)
                       for k, v in corpus.batch(i).items()}

        def setup(shape, ef):
            mesh = PX.make_device_mesh(shape, ('pod', 'data'))
            rules = make_rules(mesh, fsdp=False)
            p = model.init(jax.random.key(0))
            layout = make_bucket_layout(p, mesh, bucket_bytes=bb,
                                        deterministic=True)
            st, opt_sh = init_sharded_zero1(ocfg, p, layout, mesh)
            if ef:
                rshard = NamedSharding(mesh, P(('pod', 'data')))
                res = tuple(jax.device_put(r, rshard)
                            for r in init_slow_residuals(
                                p, mesh, bucket_bytes=bb,
                                deterministic=True))
                st = EFState(st, res)
                opt_sh = EFState(opt_sh, (rshard,) * layout.n_buckets)
            step = make_jitted_train_step(
                model, ocfg, accum=1, rules=rules,
                cross_pod_mode='hier_bucketed_zero1', bucket_bytes=bb,
                slow_compress_bits=8 if ef else 0,
                slow_error_feedback=ef, deterministic_reduce=True)
            return mesh, layout, p, st, opt_sh, step

        def train(mesh, step, p, st, lo, hi):
            losses = []
            with mesh:
                for b in batches(lo, hi):
                    p, st, m = step(p, st, b)
                    losses.append(float(m['loss']))
            return losses, p, st

        for ef in (False, True):
            tag = 'ef' if ef else 'noef'
            # uninterrupted 20-step reference on (2, 2)
            mesh, layout, p, st, opt_sh, step = setup((2, 2), ef)
            ref_losses, ref_p, _ = train(mesh, step, p, st, 0, 20)

            # interrupted leg: 10 steps on (2, 2), sharded save
            mesh, layout, p, st, opt_sh, step = setup((2, 2), ef)
            first, p, st = train(mesh, step, p, st, 0, 10)
            assert first == ref_losses[:10], (tag, 'prefix')
            d = tempfile.mkdtemp()
            sdir = ckpt.step_dir(d, 10)
            ckpt.save_sharded(sdir, 10, (p, st), layout=layout,
                              mesh=mesh)
            # no rank ever wrote a full gathered bucket: every shard
            # file of the flat zero1 state spans exactly C/F elements
            man = ckpt.read_manifest(sdir)
            n_sharded = 0
            for key, e in man.leaves.items():
                if e.kind != 'sharded' or len(e.shape) != 1:
                    continue
                n_sharded += 1
                # EF residuals ("[1][1][i]" under EFState) shard over
                # (pod, data) = 4 ways; flat opt buckets over data = 2
                F = 4 if (ef and key.startswith('[1][1]')) else 2
                for s in e.shards:
                    ext = s.index[0][1] - s.index[0][0]
                    assert ext == e.shape[0] // F, (key, s.index,
                                                    e.shape)
            assert n_sharded >= 3 * layout.n_buckets, n_sharded

            # restore onto both re-factorizations and continue
            for shape in ((4, 1), (1, 4)):
                mesh2, layout2, p2, st2, opt_sh2, step2 = setup(shape,
                                                               ef)
                assert layout2.bucket_sizes == layout.bucket_sizes
                rstep, (p2, st2) = ckpt.restore_sharded(
                    sdir, (p2, st2), shardings=(None, opt_sh2),
                    layout=layout2)
                assert rstep == 10
                # each restored device shard is 1/F' of the bucket —
                # restore never materialized a gathered bucket either
                opt2 = st2.opt if ef else st2
                F2 = mesh2.shape['data']
                for x in opt2.master:
                    for sh in x.addressable_shards:
                        (a, b), = [(sl.indices(x.shape[0])[0],
                                    sl.indices(x.shape[0])[1])
                                   for sl in sh.index]
                        assert b - a == x.shape[0] // F2, (shape,
                                                          sh.index)
                cont, p2, _ = train(mesh2, step2, p2, st2, 10, 20)
                assert cont == ref_losses[10:], (tag, shape, cont,
                                                 ref_losses[10:])
                for a, b in zip(jax.tree.leaves(ref_p),
                                jax.tree.leaves(p2)):
                    assert np.array_equal(np.asarray(a),
                                          np.asarray(b)), (tag, shape)
            print(f'CONTINUATION_{tag.upper()}_OK')
        print('RESHARD_BITWISE_OK')
        """, n_devices=4)
    assert "CONTINUATION_NOEF_OK" in out
    assert "CONTINUATION_EF_OK" in out
    assert "RESHARD_BITWISE_OK" in out
