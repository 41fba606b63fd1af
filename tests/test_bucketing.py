"""Bucketed flat-buffer gradient collectives + shard-resident optimizer.

Layout/round-trip tests run single-device; schedule-equivalence tests run
on 1/2/4-device fake meshes in subprocesses (tests/conftest.py); the
ZeRO-1 bitwise-parity test drives 20 real train steps on a (pod, data)
mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.collectives import bucketing as BK
from tests.conftest import run_multidevice


def _mixed_tree():
    return {
        "emb": jnp.arange(7 * 5, dtype=jnp.bfloat16).reshape(7, 5),
        "blocks": {
            "w": jnp.linspace(-2, 2, 4 * 3 * 2,
                              dtype=jnp.float32).reshape(4, 3, 2),
            "b": jnp.ones((11,), jnp.float16),
        },
        "scalar": jnp.asarray(3.25, jnp.float32),
        "head": jnp.full((2, 9), -1.5, jnp.bfloat16),
    }


# ------------------------------------------------------------------ layout

def test_roundtrip_exact_mixed_shapes_dtypes():
    tree = _mixed_tree()
    for bucket_bytes, align in ((4, 1), (64, 3), (1 << 20, 4), (128, 7)):
        layout = BK.plan_buckets(tree, bucket_bytes=bucket_bytes,
                                 align=align)
        buckets = BK.flatten_to_buckets(layout, tree)
        assert all(b.dtype == jnp.float32 for b in buckets)
        assert all(b.shape[0] % align == 0 for b in buckets)
        back = BK.unflatten_from_buckets(layout, buckets)
        assert jax.tree.structure(back) == jax.tree.structure(tree)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            assert a.dtype == b.dtype and a.shape == b.shape
            # bf16/f16 -> f32 -> back is exact: round-trip is bitwise
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_layout_deterministic_and_first_fit():
    tree = _mixed_tree()
    l1 = BK.plan_buckets(tree, bucket_bytes=64, align=2)
    l2 = BK.plan_buckets(jax.eval_shape(lambda: tree), bucket_bytes=64,
                         align=2)
    # same layout from concrete arrays and from avals
    assert l1.slots == l2.slots and l1.bucket_sizes == l2.bucket_sizes
    # slots follow flatten order with in-bucket contiguity
    for prev, cur in zip(l1.slots, l1.slots[1:]):
        assert (cur.bucket, cur.offset) > (prev.bucket, prev.offset) or \
            cur.bucket > prev.bucket


def test_single_giant_tensor_gets_own_bucket():
    tree = {"small": jnp.ones((3,)), "giant": jnp.ones((1000,)),
            "tail": jnp.ones((2,))}
    layout = BK.plan_buckets(tree, bucket_bytes=64, align=4)  # cap=16 elems
    slots = {s.size: s for s in layout.slots}
    # dict leaves flatten alphabetically: giant | (small, tail)
    assert slots[1000].offset == 0          # giant opens its own bucket
    assert layout.bucket_sizes[slots[1000].bucket] == 1000
    assert layout.n_buckets == 2
    assert slots[3].bucket == slots[2].bucket != slots[1000].bucket
    assert layout.n_elements() == 1005
    assert layout.n_padded_elements() >= 1005


def test_bucket_count_vs_bytes_edge_cases():
    many = {f"t{i}": jnp.ones((5,)) for i in range(7)}   # 35 elems
    # capacity 2 elems: every leaf alone
    assert BK.plan_buckets(many, bucket_bytes=8).n_buckets == 7
    # huge capacity: all in one
    one = BK.plan_buckets(many, bucket_bytes=1 << 30, align=8)
    assert one.n_buckets == 1
    assert one.bucket_sizes[0] == 40        # 35 padded to align=8
    # 5-elem leaves into 10-elem buckets: 7 leaves -> 4 buckets (2,2,2,1)
    paired = BK.plan_buckets(many, bucket_bytes=40)
    assert paired.n_buckets == 4
    # empty-ish tree still yields one (padded) bucket
    assert BK.plan_buckets({"x": jnp.zeros(())},
                           bucket_bytes=1024).n_buckets == 1


def test_unflatten_dtype_override():
    tree = {"w": jnp.ones((4, 2), jnp.bfloat16)}
    layout = BK.plan_buckets(tree)
    buckets = BK.flatten_to_buckets(layout, tree)
    g = BK.unflatten_from_buckets(layout, buckets, dtype=jnp.float32)
    assert g["w"].dtype == jnp.float32


# --------------------------------------------------- schedule equivalence

def test_bucketed_schedule_matches_flat_multidevice():
    """Bucketed hier reduce-scatter/psum/all-gather == plain mean, on
    1-, 2- and 4-device meshes (with and without a pod axis)."""
    out = run_multidevice("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro import parallel as PX
        from repro.collectives import bucketing as BK

        tree = {"a": jnp.arange(24.0).reshape(2, 3, 4),
                "b": {"c": jnp.linspace(-1, 1, 7)},
                "d": jnp.ones((5, 5), jnp.bfloat16)}

        for shape, names in (((1,), ("data",)), ((2,), ("data",)),
                             ((2, 2), ("pod", "data")),
                             ((4,), ("data",)),
                             ((2,), ("pod",))):
            n = 1
            for s in shape:
                n *= s
            mesh = PX.make_device_mesh(shape, names,
                                       devices=jax.devices()[:n])
            fast = "data" if "data" in names else None
            slow = "pod" if "pod" in names else None
            nf = mesh.shape[fast] if fast else 1
            layout = BK.plan_buckets(tree, bucket_bytes=128, align=nf)

            def rank(t):
                t = jax.tree.map(lambda x: x[0], t)   # strip stack dim
                b = BK.flatten_to_buckets(layout, t)
                s = BK.hier_reduce_bucket_shards(
                    b, fast_axis=fast, slow_axis=slow)
                gn = BK.shard_global_norm(s, fast)
                full = BK.all_gather_buckets(s, fast_axis=fast)
                return BK.unflatten_from_buckets(
                    layout, full, dtype=jnp.float32), gn

            # rank i contributes tree * (i+1): mean = tree * (n+1)/2
            def scaled(t, i):
                return jax.tree.map(
                    lambda x: x.astype(jnp.float32) * (i + 1.0), t)
            stacked = jax.tree.map(
                lambda x: jnp.stack([np.asarray(
                    x.astype(jnp.float32)) * (i + 1.0)
                    for i in range(n)]), tree)

            got, gn = jax.jit(PX.shard_map(
                rank, mesh=mesh,
                in_specs=(jax.tree.map(lambda _: P(names), stacked),),
                out_specs=(jax.tree.map(lambda _: P(), tree), P()),
                check_vma=False, axis_names=set(names)))(stacked)

            want = jax.tree.map(
                lambda x: np.asarray(x, np.float32) * (n + 1) / 2.0, tree)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                np.testing.assert_allclose(np.asarray(a), b,
                                           rtol=1e-6, atol=1e-6)
            # the shard-computed norm is the global norm of the mean tree
            ref = np.sqrt(sum(float(np.sum(np.square(b)))
                              for b in jax.tree.leaves(want)))
            np.testing.assert_allclose(float(gn), ref, rtol=1e-5)
        print("BUCKET_SCHED_OK")
        """, n_devices=4)
    assert "BUCKET_SCHED_OK" in out


def test_train_modes_equivalent_multidevice():
    """hier / hier_bucketed / hier_bucketed_zero1 match the xla step on a
    (pod, data) mesh, and the bucketed pair is bitwise-identical."""
    out = run_multidevice("""
        from repro import parallel as PX
        import jax, jax.numpy as jnp, numpy as np
        from repro import optim
        from repro.models.registry import get_config, build_model, \\
            reduced_config
        from repro.sharding import make_rules
        from repro.train import make_jitted_train_step, make_bucket_layout

        cfg = reduced_config(get_config('llama3.2-1b'))
        model = build_model(cfg, remat=False)
        mesh = PX.make_device_mesh((2, 2), ('pod', 'data'))
        rules = make_rules(mesh, fsdp=False)
        rng = jax.random.key(1)
        batch = {'tokens': jax.random.randint(rng, (8, 32), 0,
                                              cfg.vocab_size),
                 'targets': jax.random.randint(rng, (8, 32), 0,
                                               cfg.vocab_size)}
        ocfg = optim.AdamWConfig(peak_lr=1e-3, warmup_steps=2,
                                 total_steps=30)
        results = {}
        for mode in ('xla', 'hier', 'hier_bucketed',
                     'hier_bucketed_zero1'):
            p = model.init(jax.random.key(0))
            if mode == 'hier_bucketed_zero1':
                layout = make_bucket_layout(p, mesh)
                st = optim.init_bucketed(ocfg, p, layout)
            else:
                st = optim.init(ocfg, p)
            step = make_jitted_train_step(model, ocfg, accum=2,
                                          rules=rules,
                                          cross_pod_mode=mode)
            losses = []
            with mesh:
                for i in range(4):
                    p, st, m = step(p, st, batch)
                    losses.append(float(m['loss']))
            results[mode] = (losses, p)

        ref = results['xla'][0]
        for mode in ('hier', 'hier_bucketed', 'hier_bucketed_zero1'):
            np.testing.assert_allclose(results[mode][0], ref,
                                       rtol=1e-4, atol=1e-5)
        assert results['hier_bucketed'][0] == \\
            results['hier_bucketed_zero1'][0]
        for a, b in zip(jax.tree.leaves(results['hier_bucketed'][1]),
                        jax.tree.leaves(
                            results['hier_bucketed_zero1'][1])):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        print("MODES_OK")
        """, n_devices=4)
    assert "MODES_OK" in out


def test_zero1_bitwise_parity_20_steps_multidevice():
    """Acceptance: hier_bucketed_zero1 preserves bitwise-identical loss
    curves vs hier_bucketed over a 20-step run on a (pod, data) mesh,
    with the optimizer state sharded over the fast axis."""
    out = run_multidevice("""
        from repro import parallel as PX
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import optim
        from repro.data import DataConfig, SyntheticCorpus
        from repro.models.registry import get_config, build_model, \\
            reduced_config
        from repro.sharding import make_rules
        from repro.train import make_jitted_train_step, make_bucket_layout

        cfg = reduced_config(get_config('llama3.2-1b'))
        model = build_model(cfg, remat=False)
        mesh = PX.make_device_mesh((2, 2), ('pod', 'data'))
        rules = make_rules(mesh, fsdp=False)
        corpus = SyntheticCorpus(DataConfig(vocab_size=cfg.vocab_size,
                                            seq_len=16, global_batch=8))
        ocfg = optim.AdamWConfig(peak_lr=1e-3, warmup_steps=3,
                                 total_steps=40)
        curves = {}
        for mode in ('hier_bucketed', 'hier_bucketed_zero1'):
            p = model.init(jax.random.key(0))
            if mode == 'hier_bucketed_zero1':
                layout = make_bucket_layout(p, mesh)
                st = optim.init_bucketed(ocfg, p, layout)
                shard = NamedSharding(mesh, P('data'))
                st = optim.BucketedOptState(
                    step=st.step,
                    mu=tuple(jax.device_put(b, shard) for b in st.mu),
                    nu=tuple(jax.device_put(b, shard) for b in st.nu),
                    master=tuple(jax.device_put(b, shard)
                                 for b in st.master))
            else:
                st = optim.init(ocfg, p)
            step = make_jitted_train_step(model, ocfg, accum=1,
                                          rules=rules,
                                          cross_pod_mode=mode)
            losses = []
            with mesh:
                for i in range(20):
                    b = {k: jnp.asarray(v)
                         for k, v in corpus.batch(i).items()}
                    p, st, m = step(p, st, b)
                    losses.append(float(m['loss']))
            curves[mode] = losses
        assert curves['hier_bucketed'] == curves['hier_bucketed_zero1'], (
            curves)
        assert curves['hier_bucketed'][0] != curves['hier_bucketed'][-1]
        print("ZERO1_BITWISE_OK")
        """, n_devices=4)
    assert "ZERO1_BITWISE_OK" in out


# ------------------------------------------------------ flat optim pieces

def test_apply_flat_matches_apply_elementwise():
    """apply_flat on flat buckets == apply on the tree, bit for bit."""
    params = {"w": jnp.linspace(-1, 1, 12, dtype=jnp.bfloat16
                                ).reshape(3, 4),
              "b": jnp.zeros((5,), jnp.float32)}
    grads32 = {"w": jnp.linspace(0.1, 0.5, 12).reshape(3, 4),
               "b": jnp.full((5,), -0.2)}
    cfg = optim.AdamWConfig(peak_lr=1e-2, warmup_steps=1, total_steps=10)
    layout = BK.plan_buckets(params, bucket_bytes=40)   # multiple buckets
    tree_state = optim.init(cfg, params)
    flat_state = optim.init_bucketed(cfg, params, layout)
    gnorm = optim.global_norm(grads32)

    for _ in range(3):
        params, tree_state, m1 = optim.apply(cfg, params, grads32,
                                             tree_state, gnorm=gnorm)
        gb = BK.flatten_to_buckets(layout, grads32)
        flat_state, m2 = optim.apply_flat(cfg, gb, flat_state,
                                          gnorm=gnorm)
        rebuilt = BK.unflatten_from_buckets(layout, flat_state.master)
        for a, b in zip(jax.tree.leaves(params),
                        jax.tree.leaves(rebuilt)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert float(m1["lr"]) == float(m2["lr"])


def test_init_bucketed_requires_masters():
    params = {"w": jnp.ones((4,), jnp.bfloat16)}
    layout = BK.plan_buckets(params)
    with pytest.raises(AssertionError):
        optim.init_bucketed(optim.AdamWConfig(use_master=False), params,
                            layout)


def test_bucketed_modes_on_size1_mesh():
    """A (1,1) (pod, data) mesh must degenerate to the local path — the
    axis names must never reach a collective outside shard_map."""
    from repro.models.registry import build_model, get_config, \
        reduced_config
    from repro.sharding import make_rules
    from repro.train import make_bucket_layout, make_jitted_train_step
    from repro import parallel as PX

    cfg = reduced_config(get_config("llama3.2-1b"))
    model = build_model(cfg, remat=False)
    mesh = PX.make_device_mesh((1, 1), ("pod", "data"),
                               devices=jax.devices()[:1])
    rules = make_rules(mesh, fsdp=False)
    rng = jax.random.key(1)
    batch = {"tokens": jax.random.randint(rng, (4, 32), 0,
                                          cfg.vocab_size),
             "targets": jax.random.randint(rng, (4, 32), 0,
                                           cfg.vocab_size)}
    ocfg = optim.AdamWConfig()
    losses = []
    for mode in ("hier", "hier_bucketed", "hier_bucketed_zero1"):
        p = model.init(jax.random.key(0))
        st = (optim.init_bucketed(ocfg, p, make_bucket_layout(p, mesh))
              if mode == "hier_bucketed_zero1" else optim.init(ocfg, p))
        step = make_jitted_train_step(model, ocfg, accum=1, rules=rules,
                                      cross_pod_mode=mode)
        with mesh:
            p, st, m = step(p, st, batch)
        losses.append(float(m["loss"]))
    assert losses[0] == losses[1] == losses[2]


def test_unknown_mode_rejected():
    from repro.train import make_train_step
    with pytest.raises(ValueError, match="cross_pod_mode"):
        make_train_step(object(), optim.AdamWConfig(),
                        cross_pod_mode="nope")
