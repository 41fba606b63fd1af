"""Sharding rules, HLO analysis parser, serve batcher."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import hlo as H
from repro.models.registry import ARCH_IDS, build_model, get_config, \
    reduced_config
from repro.serve import BatchedServer, Request
from repro.sharding import MeshRules, single_device_rules, use_rules
from tests.conftest import run_multidevice


def test_type_bytes():
    assert H.type_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert H.type_bytes("bf16[2,3]") == 12
    assert H.type_bytes("(s32[], f32[8])") == 4 + 32
    assert H.type_bytes("pred[]") == 1


def test_hlo_analysis_counts_while_trip():
    """dot inside a scanned body must be multiplied by the trip count."""
    def f(w, x):
        def body(h, _):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, None, length=7)
        return h
    lowered = jax.jit(f).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32),
        jax.ShapeDtypeStruct((8, 64), jnp.float32))
    stats = H.analyze(lowered.compile().as_text())
    want = 7 * 2 * 8 * 64 * 64
    assert stats.dot_flops == pytest.approx(want, rel=0.01)


def test_hlo_analysis_collectives_multidevice():
    out = run_multidevice("""
        from repro import parallel as PX
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.analysis import hlo as H
        mesh = PX.make_device_mesh((8,), ("d",))
        def f(x):
            return jax.lax.with_sharding_constraint(
                x.sum(axis=0, keepdims=True), NamedSharding(mesh, P()))
        j = jax.jit(f, in_shardings=NamedSharding(mesh, P("d")),
                    out_shardings=NamedSharding(mesh, P()))
        txt = j.lower(jax.ShapeDtypeStruct((8, 1024), jnp.float32)
                      ).compile().as_text()
        stats = H.analyze(txt)
        assert stats.collective_bytes > 0, txt[:2000]
        assert any("all-reduce" in k or "all-gather" in k
                   for k in stats.collective_ops), stats.collective_ops
        print("HLO_COLL_OK")
        """)
    assert "HLO_COLL_OK" in out


_SYNTH_HLO_HEADER = """
HloModule synth

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}
"""


def test_slow_chain_independent_collectives():
    """Two cross-pod all-reduces on disjoint data: depth 1, pipelinable."""
    txt = _SYNTH_HLO_HEADER + """
ENTRY %main (p0: f32[8], p1: f32[8]) -> (f32[8], f32[8]) {
  %p0 = f32[8] parameter(0)
  %p1 = f32[8] parameter(1)
  %ar0 = f32[8] all-reduce(%p0), replica_groups={{0,2},{1,3}}, to_apply=%add
  %ar1 = f32[8] all-reduce(%p1), replica_groups={{0,2},{1,3}}, to_apply=%add
  ROOT %t = (f32[8], f32[8]) tuple(%ar0, %ar1)
}
"""
    ch = H.slow_collective_chains(txt, chips_per_pod=2)
    assert ch.n_slow == 2
    assert ch.max_depth == 1 and ch.independent
    assert ch.dependent_pairs == []


def test_slow_chain_detects_data_dependence():
    """A slow collective fed (transitively) by another slow collective's
    result is a depth-2 chain — not pipelinable."""
    txt = _SYNTH_HLO_HEADER + """
ENTRY %main (p0: f32[8], p1: f32[8]) -> f32[8] {
  %p0 = f32[8] parameter(0)
  %p1 = f32[8] parameter(1)
  %ar0 = f32[8] all-reduce(%p0), replica_groups={{0,2},{1,3}}, to_apply=%add
  %mix = f32[8] add(%ar0, %p1)
  ROOT %ar1 = f32[8] all-reduce(%mix), replica_groups={{0,2},{1,3}}, to_apply=%add
}
"""
    ch = H.slow_collective_chains(txt, chips_per_pod=2)
    assert ch.n_slow == 2
    assert ch.max_depth == 2 and not ch.independent
    assert any(a.endswith("ar0") and b.endswith("ar1")
               for a, b in ch.dependent_pairs), ch.dependent_pairs


def test_slow_chain_ignores_fast_collectives_and_done_halves():
    """Intra-pod collectives are not slow nodes, and the -done half of an
    async pair passes its cone through without counting twice — a slow
    hop chained only through *fast* collectives stays depth 1."""
    txt = _SYNTH_HLO_HEADER + """
ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8] parameter(0)
  %rs = f32[4] reduce-scatter(%p0), replica_groups={{0,1},{2,3}}, dimensions={0}, to_apply=%add
  %ars = f32[4] all-reduce-start(%rs), replica_groups={{0,2},{1,3}}, to_apply=%add
  %ard = f32[4] all-reduce-done(%ars)
  ROOT %ag = f32[8] all-gather(%ard), replica_groups={{0,1},{2,3}}, dimensions={0}
}
"""
    ch = H.slow_collective_chains(txt, chips_per_pod=2)
    assert ch.n_slow == 1
    assert ch.max_depth == 1 and ch.independent


def test_slow_chain_follows_called_computations():
    """Slow collectives inside a called computation chain with ones that
    consume the call's result."""
    txt = _SYNTH_HLO_HEADER + """
%inner (q0: f32[8]) -> f32[8] {
  %q0 = f32[8] parameter(0)
  ROOT %arin = f32[8] all-reduce(%q0), replica_groups={{0,2},{1,3}}, to_apply=%add
}

ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8] parameter(0)
  %c = f32[8] call(%p0), to_apply=%inner
  ROOT %ar1 = f32[8] all-reduce(%c), replica_groups={{0,2},{1,3}}, to_apply=%add
}
"""
    ch = H.slow_collective_chains(txt, chips_per_pod=2)
    assert ch.n_slow == 2
    assert ch.max_depth == 2 and not ch.independent


def test_slow_chain_dependence_entering_called_computation():
    """A slow collective feeding a call whose body holds another slow
    collective is a depth-2 chain: the `parameter(i)` op inside the
    callee must inherit the call operand's cone, not reset it."""
    txt = _SYNTH_HLO_HEADER + """
%inner (q0: f32[8]) -> f32[8] {
  %q0 = f32[8] parameter(0)
  ROOT %arin = f32[8] all-reduce(%q0), replica_groups={{0,2},{1,3}}, to_apply=%add
}

ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8] parameter(0)
  %ar0 = f32[8] all-reduce(%p0), replica_groups={{0,2},{1,3}}, to_apply=%add
  ROOT %c = f32[8] call(%ar0), to_apply=%inner
}
"""
    ch = H.slow_collective_chains(txt, chips_per_pod=2)
    assert ch.n_slow == 2
    assert ch.max_depth == 2 and not ch.independent
    assert any(a.endswith("ar0") and b.endswith("arin")
               for a, b in ch.dependent_pairs), ch.dependent_pairs


def test_slow_chain_respects_root_marker_not_print_order():
    """The callee's result cone comes from its ROOT op even when the
    printed op order puts another (slow-free) op last."""
    txt = _SYNTH_HLO_HEADER + """
%inner (q0: f32[8]) -> f32[8] {
  %q0 = f32[8] parameter(0)
  ROOT %arin = f32[8] all-reduce(%q0), replica_groups={{0,2},{1,3}}, to_apply=%add
  %dead = f32[8] negate(%q0)
}

ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8] parameter(0)
  %c = f32[8] call(%p0), to_apply=%inner
  ROOT %ar1 = f32[8] all-reduce(%c), replica_groups={{0,2},{1,3}}, to_apply=%add
}
"""
    ch = H.slow_collective_chains(txt, chips_per_pod=2)
    assert ch.n_slow == 2
    assert ch.max_depth == 2 and not ch.independent


def test_slow_chain_while_body_counted_once():
    """A slow collective inside a while body registers once — the
    cone-propagation second pass must not double n_slow."""
    txt = _SYNTH_HLO_HEADER + """
%cond (cv: f32[8]) -> pred[] {
  %cv = f32[8] parameter(0)
  ROOT %lt = pred[] constant(0)
}

%body (bv: f32[8]) -> f32[8] {
  %bv = f32[8] parameter(0)
  ROOT %arb = f32[8] all-reduce(%bv), replica_groups={{0,2},{1,3}}, to_apply=%add
}

ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8] parameter(0)
  ROOT %w = f32[8] while(%p0), condition=%cond, body=%body
}
"""
    ch = H.slow_collective_chains(txt, chips_per_pod=2)
    assert ch.n_slow == 1, ch


def test_rules_divisibility_dropping():
    """Non-dividing dims silently stay replicated (whisper's 6 heads on a
    16-way axis)."""
    out = run_multidevice("""
        from repro import parallel as PX
        import jax, jax.numpy as jnp
        from repro.sharding import make_rules, use_rules, shard
        mesh = PX.make_device_mesh((2, 4), ("data", "model"))
        rules = make_rules(mesh)
        with mesh:
            with use_rules(rules):
                def f(x):
                    return shard(x, "batch", None, "heads", None)
                x = jnp.ones((4, 8, 6, 16))    # 6 heads !% 4
                y = jax.jit(f)(x)
                assert y.shape == x.shape
                x2 = jnp.ones((4, 8, 8, 16))   # 8 heads % 4 == 0
                y2 = jax.jit(f)(x2)
        print("RULES_OK")
        """, n_devices=8)
    assert "RULES_OK" in out


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_tree_shardings_cover_params(arch):
    """tree_shardings produces a NamedSharding for every param leaf on the
    production mesh shape (checked abstractly via rules=None here; the
    full-mesh check runs inside the dry-run)."""
    cfg = reduced_config(get_config(arch))
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0))
    axes = model.param_logical_axes()
    n_p = len(jax.tree.leaves(params))
    n_a = len(jax.tree.leaves(
        axes, is_leaf=lambda v: isinstance(v, tuple)))
    assert n_p == n_a


def test_single_device_rules_noop():
    with use_rules(single_device_rules()):
        x = jnp.ones((4, 4))
        from repro.sharding import shard
        y = shard(x, "batch", "heads")
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_batched_server_continuous_batching():
    cfg = reduced_config(get_config("llama3.2-1b"))
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.key(0))
    srv = BatchedServer(model, params, max_batch=2, max_seq=32)
    for i in range(3):                        # 3 requests, 2 slots
        srv.submit(Request(i, np.array([5 + i, 6, 7], np.int32),
                           max_new=4))
    srv.run_until_drained()
    assert len(srv.completed) == 3
    assert all(len(r.out) == 4 for r in srv.completed)
