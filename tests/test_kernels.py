"""Per-kernel shape/dtype sweeps + allclose vs pure-jnp oracles
(interpret=True executes kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:      # no-network env: deterministic example-based shim
    from tests._hypothesis_stub import given, settings, st

from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mamba_scan.ops import ssd
from repro.kernels.mamba_scan.ref import ssd_ref
from repro.kernels.mlstm.ops import mlstm
from repro.kernels.mlstm.ref import mlstm_ref
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("S,H,Kv,D", [
    (128, 4, 4, 64),      # MHA
    (256, 4, 2, 64),      # GQA 2:1
    (128, 8, 2, 128),     # GQA 4:1, MXU-width head
    (192, 2, 1, 32),      # non-pow2 seq, MQA
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(S, H, Kv, D, causal, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    B = 2
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Kv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Kv, D), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_attention_softcap():
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 32))
    k = jax.random.normal(ks[1], (1, 128, 2, 32))
    v = jax.random.normal(ks[2], (1, 128, 2, 32))
    out = flash_attention(q, k, v, causal=True, softcap=20.0,
                          block_q=64, block_k=64, interpret=True)
    ref = attention_ref(q, k, v, causal=True, softcap=20.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=6, deadline=None)
@given(bq=st.sampled_from([32, 64, 128]), bk=st.sampled_from([32, 64]))
def test_flash_attention_block_invariance(bq, bk):
    """Property: output is independent of the BlockSpec tiling."""
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 32))
    k = jax.random.normal(ks[1], (1, 128, 2, 32))
    v = jax.random.normal(ks[2], (1, 128, 2, 32))
    a = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                        interpret=True)
    b = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=3e-5, atol=3e-5)


def _attention_grads(fn, q, k, v, ct):
    """d<fn(q, k, v), ct>/d(q, k, v)."""
    return jax.grad(lambda q, k, v: jnp.sum(
        fn(q, k, v).astype(jnp.float32) * ct), (0, 1, 2))(q, k, v)


def _assert_grads_close(got, want, tol):
    for name, g, w in zip("qkv", got, want):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        err = np.max(np.abs(g - w)) / np.max(np.abs(w))
        assert err < tol, f"d{name}: max error {err:.3g} of the largest"


@pytest.mark.parametrize("S,H,Kv,D", [
    (128, 4, 4, 64),      # MHA
    (256, 4, 2, 64),      # GQA 2:1
    (128, 8, 2, 128),     # GQA 4:1, MXU-width head
    (192, 2, 1, 32),      # non-pow2 seq, MQA
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_grad_sweep(S, H, Kv, D, causal, dtype):
    """dq, dk, dv of the kernel's custom VJP against autodiff through the
    oracle, in float32 on the same (rounded) inputs.  block_q != block_k,
    so the backward's causal block skip meets blocks cut by the diagonal
    and blocks wholly above it."""
    ks = jax.random.split(jax.random.key(7), 4)
    q = jax.random.normal(ks[0], (1, S, H, D), dtype)
    k = jax.random.normal(ks[1], (1, S, Kv, D), dtype)
    v = jax.random.normal(ks[2], (1, S, Kv, D), dtype)
    ct = jax.random.normal(ks[3], (1, S, H, D))
    got = _attention_grads(
        lambda q, k, v: flash_attention(q, k, v, causal=causal, block_q=64,
                                        block_k=32, interpret=True),
        q, k, v, ct)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    want = _attention_grads(
        lambda q, k, v: attention_ref(q, k, v, causal=causal), *f32, ct)
    _assert_grads_close(got, want, 2e-5 if dtype == jnp.float32 else 3e-2)


def test_flash_attention_softcap_grad():
    """The backward carries the cap's tanh derivative."""
    ks = jax.random.split(jax.random.key(8), 4)
    q, k, v, ct = (jax.random.normal(kk, (1, 128, 2, 32)) for kk in ks)
    got = _attention_grads(
        lambda q, k, v: flash_attention(q, k, v, causal=True, softcap=5.0,
                                        block_q=64, block_k=32,
                                        interpret=True), q, k, v, ct)
    want = _attention_grads(
        lambda q, k, v: attention_ref(q, k, v, causal=True, softcap=5.0),
        q, k, v, ct)
    _assert_grads_close(got, want, 2e-5)


def test_flash_attention_lse_residual():
    """The forward's log-sum-exp residual is logsumexp of the scaled,
    causally masked reference scores."""
    ks = jax.random.split(jax.random.key(9), 3)
    B, S, H, Kv, D = 1, 256, 4, 2, 64
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, Kv, D))
    v = jax.random.normal(ks[2], (B, S, Kv, D))
    _, lse = jax.jit(flash_ops._forward, static_argnums=(3, 4, 5, 6, 7))(
        q, k, v, True, 0.0, None, (64, 128), True)
    kg = jnp.repeat(k, H // Kv, axis=2)
    s = jnp.einsum("bqhd,bshd->bhqs", q, kg,
                   precision=jax.lax.Precision.HIGHEST) / np.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    want = jax.scipy.special.logsumexp(s, axis=-1)
    np.testing.assert_allclose(np.asarray(lse).reshape(B, H, S),
                               np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("T,H,P,G,N,chunk", [
    (128, 4, 32, 1, 16, 32),
    (128, 4, 32, 2, 16, 64),
    (64, 2, 64, 2, 32, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_kernel_sweep(T, H, P, G, N, chunk, dtype):
    ks = jax.random.split(jax.random.key(3), 5)
    B = 2
    x = jax.random.normal(ks[0], (B, T, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H))).astype(
        jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, T, G, N), dtype)
    Cm = jax.random.normal(ks[4], (B, T, G, N), dtype)
    y, s = ssd(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    yr, sr = ssd_ref(x, dt, A, Bm, Cm)
    tol = dict(rtol=4e-2, atol=4e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("T,H,D,chunk", [
    (128, 2, 32, 32),
    (64, 4, 16, 16),
    (96, 2, 64, 32),
])
def test_mlstm_kernel_sweep(T, H, D, chunk):
    ks = jax.random.split(jax.random.key(4), 5)
    B = 2
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, H, D))
    v = jax.random.normal(ks[2], (B, T, H, D))
    i_raw = jax.random.normal(ks[3], (B, T, H)) * 2
    f_raw = jax.random.normal(ks[4], (B, T, H)) * 2 + 3
    h, (C, n, m) = mlstm(q, k, v, i_raw, f_raw, chunk=chunk,
                         interpret=True)
    hr, (Cr, nr, mr) = mlstm_ref(q, k, v, i_raw, f_raw)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               rtol=5e-3, atol=5e-3)
    np.testing.assert_allclose(np.asarray(C), np.asarray(Cr),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(m), np.asarray(mr),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("R,D", [(64, 128), (256, 512), (100, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(R, D, dtype):
    ks = jax.random.split(jax.random.key(5), 2)
    x = jax.random.normal(ks[0], (R, D), dtype)
    w = jax.random.normal(ks[1], (D,), jnp.float32)
    out = rmsnorm(x, w, interpret=True)
    ref = rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


@settings(max_examples=10, deadline=None)
@given(logf=st.floats(-5.0, 5.0), logi=st.floats(-5.0, 5.0))
def test_mlstm_gate_stability_property(logf, logi):
    """Property: extreme gate magnitudes never produce NaN/Inf (the
    max-stabilizer contract)."""
    B, T, H, D = 1, 32, 1, 8
    ks = jax.random.split(jax.random.key(6), 3)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, H, D))
    v = jax.random.normal(ks[2], (B, T, H, D))
    i_raw = jnp.full((B, T, H), logi)
    f_raw = jnp.full((B, T, H), logf)
    h, _ = mlstm(q, k, v, i_raw, f_raw, chunk=16, interpret=True)
    assert bool(jnp.all(jnp.isfinite(h)))
