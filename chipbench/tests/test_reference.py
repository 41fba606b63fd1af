"""The float32 references against the program's models at a tiny size,
with every matmul at full float32 precision, and the reference's own two
forms of the mLSTM cell against each other."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.models import common as C
from chipbench.models import transformer, xlstm
from chipbench.tests import tiny


def _program(arch):
    from repro.configs.base import ArchConfig
    from repro.models.registry import build_model
    return build_model(ArchConfig(**arch), remat=True)


@pytest.mark.parametrize("fam", [xlstm, transformer],
                         ids=["xlstm", "transformer"])
def test_reference_matches_program(fam):
    arch = tiny.ARCH[fam.__name__.rsplit(".", 1)[1]]
    cfg = dict(arch, z_loss=1e-4)
    model = _program(arch)
    w = fam.init(jax.random.key(3), cfg)
    want = jax.eval_shape(model.init, jax.random.key(0))
    assert jax.tree.structure(want) == jax.tree.structure(w)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(want)] == \
        [(a.shape, a.dtype) for a in jax.tree.leaves(w)]
    rng = np.random.default_rng(0)
    tok = rng.integers(1, arch["vocab_size"], (2, 64)).astype(np.int32)
    tgt = rng.integers(1, arch["vocab_size"], (2, 64)).astype(np.int32)
    batch = {"tokens": tok, "targets": tgt}
    w32 = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(
            lambda p: model.loss(p, batch)[0])(w32)
    lr, gr = jax.value_and_grad(lambda p: fam.loss(p, tok, tgt, cfg))(w32)
    assert abs(float(lp) - float(lr)) <= 1e-5 * abs(float(lr))
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-4 * float(
            jnp.linalg.norm(b)) + 1e-12


def test_mlstm_parallel_equals_recurrent():
    k = jax.random.split(jax.random.key(1), 5)
    q, kk, v = (jax.random.normal(k[i], (2, 64, 2, 16)) for i in range(3))
    ig, fg = (jax.random.normal(k[3 + i], (2, 64, 2)) * 2 for i in range(2))
    a = xlstm.mlstm_parallel(q, kk, v, ig, fg, block=16)
    b = xlstm.mlstm_recurrent(q, kk, v, ig, fg)
    assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * float(
        jnp.max(jnp.abs(b)))


def test_fp8_matmul_is_coarser_than_float32():
    a = jax.random.normal(jax.random.key(0), (64, 64))
    b = jax.random.normal(jax.random.key(1), (64, 64))
    exact = C.mm("ij,jk->ik", a, b)
    err = float(jnp.max(jnp.abs(C.mm_fp8("ij,jk->ik", a, b) - exact)))
    assert 1e-3 * float(jnp.max(jnp.abs(exact))) < err
    # straight-through: the gradient of the rounding is the identity
    g = jax.grad(lambda x: jnp.sum(C.fp8_round(x)))(a)
    assert bool(jnp.all(g == 1.0))
