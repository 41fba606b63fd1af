"""End-to-end behaviour tests for the paper's system: schedule -> execute ->
communicate, plus SSM/mLSTM math properties (hypothesis)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:      # no-network env: deterministic example-based shim
    from tests._hypothesis_stub import given, settings, st

from repro.core.executor import JobExecutor
from repro.core.job import Job
from repro.core.leaves import Cluster
from repro.core.modes import FlexMIG
from repro.core.registry import DuplicateGpuError, TopologyMismatchError
from repro.models import ssm as S
from repro.models import xlstm as X


def test_end_to_end_schedule_launch_communicate():
    """Fig. 4/5 wiring: FM places a size-4 job across both GPUs; the
    executor builds the pod env; the MIG-aware communicator forms with SHM
    transports; the stock path fails."""
    cluster = Cluster(n_hosts=1, gpus_per_host=2)
    fm = FlexMIG()
    fm.setup(cluster)
    job = Job("job-1", "bert-base", "train", 4, 32, 1200.0)
    placement = fm.try_place(job, cluster)
    assert placement is not None
    assert len({i.gpu_id for i in placement.instances}) == 2  # round-robin

    ex = JobExecutor()
    launched = ex.launch(job, placement, mig_aware=True)
    assert launched.pod.n_workers == 4
    assert set(launched.transports.values()) == {"SHM"}
    uuids = launched.pod.env["NVIDIA_VISIBLE_DEVICES"].split(",")
    assert len(set(uuids)) == 4

    with pytest.raises((DuplicateGpuError, TopologyMismatchError)):
        ex.launch(job, placement, mig_aware=False)   # stock NCCL fails


def test_one_to_many_spans_gpus_c3_lifted():
    """C3 (no cross-GPU aggregation) is exactly what one-to-many lifts."""
    cluster = Cluster(n_hosts=1, gpus_per_host=2)
    fm = FlexMIG()
    fm.setup(cluster)
    job = Job("big", "resnet101", "train", 8, 256, 2000.0)
    placement = fm.try_place(job, cluster)
    assert placement is not None
    assert sorted(placement.leaves_per_gpu()) == [4, 4]


@settings(max_examples=8, deadline=None)
@given(T=st.sampled_from([32, 64, 96]),
       chunk=st.sampled_from([8, 16, 32]),
       seed=st.integers(0, 100))
def test_ssd_chunk_invariance_property(T, chunk, seed):
    """Property: SSD output is independent of chunk size (the kernel's
    core contract)."""
    B, H, P, G, N = 1, 2, 8, 1, 4
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, T, G, N))
    Cm = jax.random.normal(ks[4], (B, T, G, N))
    y1, s1 = S.ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk)
    y2, s2 = S.ssd_sequential_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-3, atol=1e-4)


@settings(max_examples=8, deadline=None)
@given(T=st.sampled_from([32, 64]), chunk=st.sampled_from([8, 16, 32]),
       seed=st.integers(0, 100))
def test_mlstm_chunk_invariance_property(T, chunk, seed):
    B, H, D = 1, 2, 8
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, H, D))
    v = jax.random.normal(ks[2], (B, T, H, D))
    ir = jax.random.normal(ks[3], (B, T, H)) * 2
    fr = jax.random.normal(ks[4], (B, T, H)) * 2 + 2
    h1, _ = X.mlstm_chunked(q, k, v, ir, fr, chunk=chunk)
    h2, _ = X.mlstm_sequential_ref(q, k, v, ir, fr)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("f_raw", [3.0, -10.0, -40.0])
def test_mlstm_gradient_finite_under_closing_forget_gates(f_raw):
    """Forget gates far below zero make the gate exponent above the
    causal diagonal overflow float32; the output and the gradient must
    stay finite all the same."""
    B, T, H, D = 1, 32, 1, 4
    ks = jax.random.split(jax.random.key(3), 3)
    q, k, v = (jax.random.normal(x, (B, T, H, D)) for x in ks)
    ir = jnp.zeros((B, T, H))

    def loss(fr):
        return X.mlstm_chunked(q, k, v, ir, fr, chunk=16)[0].sum()

    fr = jnp.full((B, T, H), f_raw)
    h, _ = X.mlstm_chunked(q, k, v, ir, fr, chunk=16)
    g = jax.grad(loss)(fr)
    assert bool(jnp.all(jnp.isfinite(h))) and bool(jnp.all(jnp.isfinite(g)))


def test_decode_state_matches_chunked_ssm():
    """Mamba decode recurrence continues exactly where prefill stopped."""
    B, T, H, P, G, N = 1, 32, 2, 8, 1, 4
    ks = jax.random.split(jax.random.key(9), 5)
    x = jax.random.normal(ks[0], (B, T + 1, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T + 1, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, T + 1, G, N))
    Cm = jax.random.normal(ks[4], (B, T + 1, G, N))
    y_all, _ = S.ssd_sequential_ref(x, dt, A, Bm, Cm)
    y_pre, state = S.ssd_chunked(x[:, :T], dt[:, :T], A, Bm[:, :T],
                                 Cm[:, :T], chunk=8)
    y_t, _ = S.ssd_step(state, x[:, T], dt[:, T], A, Bm[:, T], Cm[:, T])
    np.testing.assert_allclose(np.asarray(y_t), np.asarray(y_all[:, T]),
                               rtol=1e-3, atol=1e-4)
