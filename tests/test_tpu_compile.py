"""Ahead-of-time compiles for a described TPU v5e: the Pallas kernels at
real widths, and the deterministic gradient reduce on a (2, 2) mesh.

Nothing runs: the TPU compiler lowers each kernel at real widths for a
chip that is described, not attached, and refuses what the chip would
refuse (block shapes off the (8, 128) tiling, unsupported primitives,
too much VMEM).  The topology is described only inside the module
fixture below, so importing this file never loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.mamba_scan.ops import ssd
from repro.kernels.mlstm.ops import mlstm
from repro.kernels.rmsnorm.ops import rmsnorm


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.mark.parametrize("H,Kv,D", [
    (32, 8, 64),       # llama3.2-1b attention widths
    (32, 2, 128),      # glm4-9b attention widths
])
def test_flash_attention_compiles_for_v5e(one_chip, H, Kv, D):
    S = 2048
    txt = _compile_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        [((1, S, H, D), BF16), ((1, S, Kv, D), BF16),
         ((1, S, Kv, D), BF16)], one_chip)
    assert "tpu_custom_call" in txt


def test_gqa_apply_grad_compiles_to_flash_kernels_for_v5e(one_chip,
                                                         monkeypatch):
    """stablelm-1.6b attention at its published width and context (B 4,
    S 4096, H = Kv 32, D 64, bf16): on a TPU ``gqa_apply`` and its
    gradient run the flash kernels (forward, dK/dV, dQ) in place of the
    blocked XLA loop."""
    from repro.models import attention as A
    from repro.models.registry import get_config

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("stablelm-1.6b")
    B, S = 4, 4096
    params = jax.eval_shape(lambda key: A.gqa_init(key, cfg),
                            jax.random.key(0))
    shapes = [((B, S, cfg.d_model), BF16)] + [
        (w.shape, w.dtype) for w in jax.tree.leaves(params)]
    treedef = jax.tree.structure(params)

    def grads(x, *leaves):
        def loss(x, p):
            out = A.gqa_apply(x, p, cfg, positions=jnp.arange(S))
            return jnp.sum(out.astype(F32))
        return jax.grad(loss, (0, 1))(
            x, jax.tree.unflatten(treedef, leaves))

    txt = _compile_text(grads, shapes, one_chip)
    assert txt.count('custom_call_target="tpu_custom_call"') >= 3
    assert "while" not in txt


def test_flash_attention_mla_widths_compile_for_v5e(one_chip):
    """deepseek-v2-lite's expanded MLA (B 4, S 4096, H = Kv 16, q and k
    192 dims, v 128) with YaRN's scale: forward and both backward
    kernels at ``ops.BLOCKS`` fit the chip's VMEM."""
    B, S, H = 4, 4096, 16

    def grads(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, scale=0.1147).astype(F32)),
            (0, 1, 2))(q, k, v)

    txt = _compile_text(grads, [((B, S, H, 192), BF16),
                                ((B, S, H, 192), BF16),
                                ((B, S, H, 128), BF16)], one_chip)
    assert txt.count('custom_call_target="tpu_custom_call"') >= 3


def test_mla_apply_grad_compiles_to_flash_kernels_for_v5e(one_chip,
                                                         monkeypatch):
    """deepseek-v2-lite attention at its published widths and its 4096
    context: on a TPU ``mla_apply`` and its gradient run the flash
    kernels, with no blocked-attention loop."""
    from repro.models import attention as A
    from repro.models.registry import get_config

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("deepseek-v2-lite-16b")
    B, S = 4, 4096
    params = jax.eval_shape(lambda key: A.mla_init(key, cfg),
                            jax.random.key(0))
    shapes = [((B, S, cfg.d_model), BF16)] + [
        (w.shape, w.dtype) for w in jax.tree.leaves(params)]
    treedef = jax.tree.structure(params)

    def grads(x, *leaves):
        def loss(x, p):
            out = A.mla_apply(x, p, cfg, positions=jnp.arange(S))
            return jnp.sum(out.astype(F32))
        return jax.grad(loss, (0, 1))(
            x, jax.tree.unflatten(treedef, leaves))

    txt = _compile_text(grads, shapes, one_chip)
    assert txt.count('custom_call_target="tpu_custom_call"') >= 3
    assert "while" not in txt


@pytest.mark.parametrize("K,N", [(2048, 1408), (1408, 2048)])
def test_expert_gmm_compiles_for_v5e(one_chip, K, N):
    """The grouped matmul at deepseek-v2-lite's expert widths (gate/up
    2048 -> 1408, down 1408 -> 2048) over the cell's dropless buffer of
    16,384 tokens x top-6 rows and 8 held experts: forward, and the
    rows' and weights' gradients."""
    from repro.kernels.grouped_matmul import expert_gmm

    def grads(lhs, rhs, sizes):
        return jax.grad(lambda a, b: jnp.sum(jnp.square(
            expert_gmm(a, b, sizes).astype(F32))), (0, 1))(lhs, rhs)

    txt = _compile_text(grads, [((16384 * 6, K), BF16), ((8, K, N), BF16),
                                ((9,), jnp.int32)], one_chip)
    assert txt.count('custom_call_target="tpu_custom_call"') >= 3
    # the kernels' ops carry the names a profile reader looks for
    assert "%gmm." in txt and "%tgmm." in txt


def test_mlstm_compiles_for_v5e(one_chip):
    # xlstm-125m: d_model 768 -> 2x up-projection 1536 over 4 heads
    B, S, H, D = 1, 2048, 4, 384
    txt = _compile_text(
        lambda q, k, v, i, f: mlstm(q, k, v, i, f, chunk=256),
        [((B, S, H, D), BF16)] * 3 + [((B, S, H), F32)] * 2, one_chip)
    assert "tpu_custom_call" in txt


def test_ssd_compiles_for_v5e(one_chip):
    # zamba2-1.2b: d_inner 4096 over head_dim 64, d_state 64, one group
    B, S, H, P, G, N = 1, 2048, 64, 64, 1, 64
    txt = _compile_text(
        lambda x, dt, A, Bm, Cm: ssd(x, dt, A, Bm, Cm, chunk=256),
        [((B, S, H, P), BF16), ((B, S, H), F32), ((H,), F32),
         ((B, S, G, N), BF16), ((B, S, G, N), BF16)], one_chip)
    assert "tpu_custom_call" in txt


def test_rmsnorm_compiles_for_v5e(one_chip):
    txt = _compile_text(lambda x, w: rmsnorm(x, w),
                        [((4096, 2048), BF16), ((2048,), F32)], one_chip)
    assert "tpu_custom_call" in txt


def test_det_reduce_compiles_small_for_v5e(topo):
    """The deterministic bucket reduce on a (2, 2) mesh: its program must
    not grow with the bucket size (gathering an (R, C) stack and cutting
    rows out of it compiled to ~50 MB of code per 16 MiB of buckets)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import parallel as PX
    from repro.collectives.deterministic import det_reduce_bucket_full

    mesh = PX.make_device_mesh((2, 2), ("pod", "data"),
                               devices=topo.devices[:4])
    specs = (P(), P())

    def body(buckets):
        return det_reduce_bucket_full(buckets, sync_axes=("pod", "data"))[0]

    fn = PX.shard_map(body, mesh=mesh, in_specs=(specs,), out_specs=specs,
                      check_vma=False, axis_names={"pod", "data"})
    rep = NamedSharding(mesh, P())
    buckets = tuple(jax.ShapeDtypeStruct((2 << 20,), F32, sharding=rep)
                    for _ in specs)
    compiled = jax.jit(fn).lower(buckets).compile()
    assert compiled.memory_analysis().generated_code_size_in_bytes < 4 << 20
    assert "all-gather" in compiled.as_text()
