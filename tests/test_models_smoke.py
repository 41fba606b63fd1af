"""Per-arch smoke tests (deliverable f): reduced same-family config, one
forward/train step on CPU, output shapes + no NaNs."""
import glob
import json
import math
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ArchConfig, MLAConfig, MoEConfig, YaRNConfig
from repro.models.registry import (ARCH_IDS, build_model, get_config,
                                   reduced_config)


def _batch(cfg, rng, B=2, S=32):
    batch = {
        "tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
        "targets": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
    }
    if cfg.frontend == "patch":
        batch["media"] = jnp.ones((B, cfg.n_media_tokens, cfg.d_model),
                                  jnp.bfloat16)
    if cfg.frontend == "audio":
        batch["frames"] = jnp.ones((B, cfg.enc_seq_len, cfg.d_model),
                                   jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    cfg = reduced_config(get_config(arch))
    model = build_model(cfg, remat=False)
    rng = jax.random.key(0)
    params = model.init(rng)
    batch = _batch(cfg, rng)

    logits, aux = jax.jit(model.forward_logits)(params, batch)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))

    # one SGD-flavoured train step: loss + grads finite, params update
    def loss_fn(p):
        return model.loss(p, batch)[0]
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    assert bool(jnp.isfinite(loss))
    gnorm = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree.leaves(grads))
    assert bool(jnp.isfinite(gnorm)) and float(gnorm) > 0.0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_axes_trees_match(arch):
    cfg = reduced_config(get_config(arch))
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0))
    axes = model.param_logical_axes()
    td_p = jax.tree.structure(params)
    td_a = jax.tree.structure(axes, is_leaf=lambda v: isinstance(v, tuple))
    assert td_p == td_a, f"{arch}: param/axes tree mismatch"
    # every axes tuple is no longer than the param rank
    flat_p = jax.tree.leaves(params)
    flat_a = jax.tree.leaves(axes,
                             is_leaf=lambda v: isinstance(v, tuple))
    for p, a in zip(flat_p, flat_a):
        assert len(a) == len(p.shape), f"{arch}: {a} vs {p.shape}"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_decode_step(arch):
    cfg = reduced_config(get_config(arch))
    model = build_model(cfg, remat=False)
    rng = jax.random.key(1)
    params = model.init(rng)
    B, S = 2, 16
    cache = model.init_cache(B, S)
    tokens = jax.random.randint(rng, (B, 1), 0, cfg.vocab_size)
    logits, cache2 = jax.jit(model.decode_step)(
        params, cache, tokens, jnp.int32(0))
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    assert jax.tree.structure(cache) == jax.tree.structure(cache2)


def test_full_configs_match_assignment():
    """Exact published dims for every assigned arch."""
    expect = {
        "whisper-tiny": (4, 384, 6, 6, 1536, 51865),
        "llama-3.2-vision-90b": (100, 8192, 64, 8, 28672, 128256),
        "command-r-plus-104b": (64, 12288, 96, 8, 33792, 256000),
        "glm4-9b": (40, 4096, 32, 2, 13696, 151552),
        "stablelm-1.6b": (24, 2048, 32, 32, 5632, 100352),
        "llama3.2-1b": (16, 2048, 32, 8, 8192, 128256),
        "qwen2-moe-a2.7b": (24, 2048, 16, 16, 0, 151936),
        "deepseek-v2-lite-16b": (27, 2048, 16, 16, 0, 102400),
        "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32000),
        "xlstm-125m": (12, 768, 4, 4, 0, 50304),
    }
    for arch, (L, d, h, kv, ff, v) in expect.items():
        cfg = get_config(arch)
        assert cfg.n_layers == L, arch
        assert cfg.d_model == d, arch
        assert cfg.n_heads == h and cfg.n_kv_heads == kv, arch
        assert cfg.d_ff == ff, arch
        assert cfg.vocab_size == v, arch
    q = get_config("qwen2-moe-a2.7b").moe
    assert (q.n_routed, q.n_shared, q.top_k, q.d_ff) == (60, 4, 4, 1408)
    dv = get_config("deepseek-v2-lite-16b")
    assert dv.mla.kv_lora_rank == 512
    assert dv.moe.top_k == 6
    z = get_config("zamba2-1.2b")
    assert z.ssm.d_state == 64 and z.sub_quadratic
    assert get_config("xlstm-125m").sub_quadratic


BENCH_CONFIGS = sorted(glob.glob(os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "chipbench", "configs", "*.json")))


@pytest.mark.parametrize("path", BENCH_CONFIGS,
                         ids=[os.path.basename(p) for p in BENCH_CONFIGS])
def test_benchmark_configs_build_through_archconfig(path):
    """Each benchmark config's ``arch`` builds the program's model the
    way the benchmark's harness does, ``ArchConfig(**arch)``: nested
    ``moe``, ``mla`` and ``rope_scaling`` groups become their dataclasses
    and the config stays hashable."""
    with open(path) as f:
        arch = json.load(f)["arch"]
    cfg = ArchConfig(**arch)
    hash(cfg)
    for name, cls in (("moe", MoEConfig), ("mla", MLAConfig),
                      ("rope_scaling", YaRNConfig)):
        assert (name in arch) == isinstance(getattr(cfg, name), cls)
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(shapes)) > 1e8
