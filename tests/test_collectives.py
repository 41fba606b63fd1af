"""Hierarchical collectives + compression (multi-device via subprocess)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:      # no-network env: deterministic example-based shim
    from tests._hypothesis_stub import given, settings, st

from repro.collectives.compression import (apply_error_feedback,
                                           dequantize_int8, quantize_int8)
from repro.collectives.transport import (gpu_collective,
                                         hierarchical_vs_flat_bytes,
                                         tpu_collective_time)
from tests.conftest import run_multidevice


def test_int8_quantization_roundtrip():
    x = jnp.linspace(-3.0, 3.0, 128)
    q, s = quantize_int8(x)
    err = jnp.max(jnp.abs(dequantize_int8(q, s) - x))
    assert float(err) <= float(s) * 0.51


@settings(max_examples=20, deadline=None)
@given(scale=st.floats(1e-3, 1e3))
def test_quantization_error_bounded_property(scale):
    x = jax.random.normal(jax.random.key(0), (256,)) * scale
    q, s = quantize_int8(x)
    err = jnp.max(jnp.abs(dequantize_int8(q, s) - x))
    assert float(err) <= float(s) * 0.51


def test_error_feedback_reduces_bias():
    """Residual carrying: the average of compressed grads converges to the
    true mean over steps."""
    rng = np.random.default_rng(0)
    g_true = jnp.asarray(rng.normal(size=(64,)) * 1e-4)
    resid = None
    acc = jnp.zeros_like(g_true)
    n = 50
    for _ in range(n):
        gq, resid = apply_error_feedback(g_true, resid)
        acc = acc + gq
    np.testing.assert_allclose(np.asarray(acc / n), np.asarray(g_true),
                               rtol=0.05, atol=1e-7)


def test_hier_vs_flat_slow_boundary_bytes():
    out = hierarchical_vs_flat_bytes(1e9, fast=16, slow=2)
    assert out["reduction"] == pytest.approx(16.0)


def test_gpu_collective_model_shm_beats_net_under_contention():
    shm = gpu_collective("all_reduce", 200e6, transport="SHM",
                         leaves_per_gpu=(2, 2))
    net = gpu_collective("all_reduce", 200e6, transport="NET",
                         leaves_per_gpu=(2, 2), concurrent_net_jobs=4)
    assert shm.time_s < net.time_s


def test_tpu_collective_two_tier():
    ici = tpu_collective_time("all_reduce", 1e8, n_chips=16, axis="ici")
    dcn = tpu_collective_time("all_reduce", 1e8, n_chips=2, axis="dcn")
    assert dcn > ici


def test_hierarchical_allreduce_correct_multidevice():
    out = run_multidevice("""
        from repro import parallel as PX
        import jax, jax.numpy as jnp, numpy as np
        from repro.collectives.hierarchical import make_hier_all_reduce
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = PX.make_device_mesh((2, 4), ("pod", "data"))
        x = jnp.arange(8 * 33, dtype=jnp.float32).reshape(8, 33)
        xs = jax.device_put(x, NamedSharding(mesh, P(("pod", "data"))))
        want = np.broadcast_to(np.asarray(x).reshape(8, 33).mean(0), (33,))
        for kw in (dict(), dict(flat=True), dict(compress_bits=16)):
            fn = make_hier_all_reduce(mesh, fast_axis="data",
                                      slow_axis="pod", **kw)
            got = np.asarray(fn(xs))
            # every shard now holds the mean of its pod... full mean:
            assert got.shape == (8, 33)
            np.testing.assert_allclose(got, np.tile(want, (8, 1)),
                                       rtol=2e-2, atol=2e-2)
        # int8 path: looser tolerance
        fn8 = make_hier_all_reduce(mesh, fast_axis="data",
                                   slow_axis="pod", compress_bits=8)
        got = np.asarray(fn8(xs))
        np.testing.assert_allclose(got, np.tile(want, (8, 1)),
                                   rtol=0.05, atol=1.5)
        print("HIER_OK")
        """)
    assert "HIER_OK" in out


def test_moe_sharded_matches_single_device():
    """EP shard_map MoE == single-shard MoE on identical inputs: the
    dropless expert layer, each of the 4 model shards holding 2 of the 8
    experts (``first = shard * 2``)."""
    out = run_multidevice("""
        from repro import parallel as PX
        import jax, jax.numpy as jnp, numpy as np
        from repro.models.registry import get_config, reduced_config
        from repro.models import ffn as F
        from repro.sharding import make_rules, use_rules
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = reduced_config(get_config("qwen2-moe-a2.7b"))
        mesh = PX.make_device_mesh((2, 4), ("data", "model"))
        rules = make_rules(mesh)
        key = jax.random.key(0)
        p = F.moe_init(key, cfg)
        x = jax.random.normal(jax.random.key(1), (4, 16, cfg.d_model),
                              jnp.float32).astype(jnp.bfloat16)

        ref, st_ref = F.moe_apply(x, p, cfg)           # no rules: 1 shard

        with mesh:
            with use_rules(rules):
                xs = jax.device_put(x, NamedSharding(
                    mesh, P("data", None, None)))
                out, st = jax.jit(
                    lambda x, p: F.moe_apply(x, p, cfg))(xs, p)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=3e-2, atol=3e-2)
        np.testing.assert_allclose(float(st["aux"]), float(st_ref["aux"]),
                                   rtol=1e-2, atol=1e-4)
        # dropless: every one of the 4 x 16 tokens' top-k pairs is held
        assert int(st["moe_held_pairs"]) == int(st_ref["moe_held_pairs"]) \
            == 4 * 16 * cfg.moe.top_k
        assert int(st["moe_max_expert_pairs"]) == \
            int(st_ref["moe_max_expert_pairs"])
        print("MOE_OK")
        """)
    assert "MOE_OK" in out
