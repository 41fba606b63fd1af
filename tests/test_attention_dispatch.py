"""Which attention core ``gqa_apply`` runs: the Pallas flash kernel on a
single TPU device for self-attention at S a multiple of 128, the XLA
paths everywhere else (CPU included, where its output is unchanged)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.kernels.flash_attention import flash_attention
from repro.models import attention as A
from repro.models import layers as L
from repro.models.registry import get_config, reduced_config
from repro.sharding import MeshRules, use_rules


def _qk(Sq, Sk, H=32, D=64):
    return (jax.ShapeDtypeStruct((4, Sq, H, D), jnp.bfloat16),
            jax.ShapeDtypeStruct((4, Sk, H, D), jnp.bfloat16))


@pytest.mark.parametrize("backend,Sq,Sk,softcap,devices,want", [
    ("tpu", 4096, 4096, 0.0, None, True),
    ("tpu", 4096, 4096, 0.0, 1, True),
    ("cpu", 4096, 4096, 0.0, None, False),
    ("tpu", 4096, 1500, 0.0, None, False),     # cross-attention
    ("tpu", 1500, 1500, 0.0, None, False),     # whisper's encoder
    ("tpu", 4096, 4096, 30.0, None, False),    # logit softcap
    ("tpu", 4096, 4096, 0.0, 2, False),        # operands split
])
def test_use_flash_kernel(monkeypatch, backend, Sq, Sk, softcap, devices,
                          want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    mesh = None if devices is None else AbstractMesh((devices,), ("data",))
    q, k = _qk(Sq, Sk)
    with use_rules(MeshRules(rules={"batch": "data"}, mesh=mesh)):
        assert A.use_flash_kernel(q, k, softcap) is want


def _stablelm_inputs(S=1152):
    cfg = reduced_config(get_config("stablelm-1.6b"))
    ks = jax.random.split(jax.random.key(0), 2)
    p = A.gqa_init(ks[0], cfg)
    p = {n: w + 0.02 if n.startswith("b") else w for n, w in p.items()}
    x = jax.random.normal(ks[1], (1, S, cfg.d_model), jnp.bfloat16)
    return cfg, p, x, jnp.arange(S)


def _blocked_gqa(x, p, cfg, positions):
    """``gqa_apply``'s blocked XLA path, spelled out."""
    q, k, v = A._qkv(x, p, cfg)
    rd = A._rope_dims(cfg)
    cos, sin = L.rope_angles(positions, rd, cfg.rope_theta)
    q = L.apply_rope(q, cos, sin, rd)
    k = L.apply_rope(k, cos, sin, rd)
    o = L.blocked_attention(q, k, v, causal=True, block_q=512,
                            block_k=1024)
    return jnp.einsum("bsh,hd->bsd", o.reshape(x.shape[0], x.shape[1], -1),
                      p["wo"])


def test_gqa_apply_on_cpu_is_the_blocked_path():
    """Off the TPU, S 1152 (> 1024^2 scores) keeps the blocked XLA path,
    bit for bit."""
    cfg, p, x, pos = _stablelm_inputs()
    got = jax.jit(lambda x, p: A.gqa_apply(x, p, cfg, positions=pos))(x, p)
    want = jax.jit(lambda x, p: _blocked_gqa(x, p, cfg, pos))(x, p)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_gqa_apply_routes_to_the_kernel_on_tpu(monkeypatch):
    """Where the condition holds, ``gqa_apply`` runs the kernel (here in
    interpret mode), and agrees with the blocked path in value and
    gradient within bf16 rounding."""
    cfg, p, x, pos = _stablelm_inputs(S=1280)
    calls = []

    def kernel(*args, **kw):
        calls.append(kw)
        return flash_attention(*args, block_q=256, block_k=256,
                               interpret=True, **kw)

    def loss(fn, x, p):
        return jnp.sum(fn(x, p).astype(jnp.float32) ** 2)

    want = jax.value_and_grad(functools.partial(
        loss, lambda x, p: _blocked_gqa(x, p, cfg, pos)))(x, p)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(A, "flash_attention", kernel)
    got = jax.value_and_grad(functools.partial(
        loss, lambda x, p: A.gqa_apply(x, p, cfg, positions=pos)))(x, p)
    assert calls == [{"causal": True}]
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-2)
    g = np.asarray(got[1], np.float32)
    w = np.asarray(want[1], np.float32)
    assert np.max(np.abs(g - w)) < 3e-2 * np.max(np.abs(w))
