"""HLO lint rules: one seeded-bug positive + one clean negative per rule,
plus golden parse tests against real lowered train-step modules.

The positives reconstruct bugs this repo actually shipped: the PR 4
``init_bucketed`` donation alias (a donated buffer escaping unaliased)
and the PR 4 missing-``optimization_barrier`` 1-ulp drift (an unsealed
deterministic tree fold).
"""
import gzip
import os

import pytest

from repro.analysis import hlo, ir
from repro.analysis.lint import (LintContext, all_rules, budget_for,
                                 load_budgets, run_rules)
from tests.conftest import run_multidevice

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


def _fixture(name):
    with gzip.open(os.path.join(FIXTURES, name), "rt") as f:
        return f.read()


# ---------------------------------------------------------------------------
# synthetic corpus helpers
# ---------------------------------------------------------------------------

_ADD_F32 = """
%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}
"""

_ADD_BF16 = """
%addb (a: bf16[], b: bf16[]) -> bf16[] {
  %a = bf16[] parameter(0)
  %b = bf16[] parameter(1)
  ROOT %s = bf16[] add(%a, %b)
}
"""

_MIN_BF16 = """
%minb (a: bf16[], b: bf16[]) -> bf16[] {
  %a = bf16[] parameter(0)
  %b = bf16[] parameter(1)
  ROOT %m = bf16[] minimum(%a, %b)
}
"""


def _mod(body, *, header="", computations=_ADD_F32):
    return f"HloModule synth{header}\n{computations}\n{body}"


def _ctx(optimized, lowered=None, budget=None, **config):
    cfg = {"chips_per_pod": 2, "n_buckets": 0, "grad_bytes": 0}
    cfg.update(config)
    return LintContext(optimized=ir.parse(optimized),
                       lowered=ir.parse(lowered) if lowered else None,
                       config=cfg, budget=budget)


def _rules_of(findings):
    return sorted({f.rule for f in findings})


# a plain (non-det, non-overlap) clean program: one intra-pod
# reduce-scatter + cross-pod all-reduce + all-gather in f32
_CLEAN_HIER = _mod("""
ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8] parameter(0)
  %rs = f32[4] reduce-scatter(%p0), replica_groups={{0,1},{2,3}}, dimensions={0}, to_apply=%add
  %ar = f32[4] all-reduce(%rs), replica_groups={{0,2},{1,3}}, to_apply=%add
  ROOT %ag = f32[8] all-gather(%ar), replica_groups={{0,1},{2,3}}, dimensions={0}
}
""")


def test_registry_has_the_five_rules():
    assert set(all_rules()) >= {
        "collective-budget", "deterministic-reduce", "donation-aliasing",
        "precision", "overlap-independence"}


def test_run_rules_rejects_unknown_rule():
    with pytest.raises(KeyError):
        run_rules(_ctx(_CLEAN_HIER), only=["not-a-rule"])


def test_clean_program_no_findings():
    assert run_rules(_ctx(_CLEAN_HIER)) == []


# ---------------------------------------------------------------------------
# collective-budget
# ---------------------------------------------------------------------------

def test_budget_flags_count_drift():
    """An extra all-reduce (vs the declared budget) fails with a
    diff-style message naming the kind and the delta."""
    budget = {"fixed": {"all-reduce": 1, "reduce-scatter": 1,
                        "all-gather": 1}}
    f = run_rules(_ctx(_CLEAN_HIER, budget=budget),
                  only=["collective-budget"])
    assert not f
    budget2 = {"fixed": {"reduce-scatter": 1, "all-gather": 1}}
    f = run_rules(_ctx(_CLEAN_HIER, budget=budget2),
                  only=["collective-budget"])
    assert _rules_of(f) == ["collective-budget"]
    assert "all-reduce: budget 0" in f[0].message
    assert "+1" in f[0].message


def test_budget_per_bucket_scaling():
    """per_bucket x n_buckets + fixed composes the expectation (the
    hier_bucketed '3 per bucket' declaration)."""
    body = _mod("""
ENTRY %main (p0: f32[8], p1: f32[8]) -> (f32[8], f32[8]) {
  %p0 = f32[8] parameter(0)
  %p1 = f32[8] parameter(1)
  %a0 = f32[8] all-reduce(%p0), replica_groups={{0,2},{1,3}}, to_apply=%add
  %a1 = f32[8] all-reduce(%p1), replica_groups={{0,2},{1,3}}, to_apply=%add
  %l = f32[8] all-reduce(%a0), replica_groups={{0,1,2,3}}, to_apply=%add
  ROOT %t = (f32[8], f32[8]) tuple(%a1, %l)
}
""")
    budget = {"fixed": {"all-reduce": 1}, "per_bucket": {"all-reduce": 1}}
    assert not run_rules(_ctx(body, budget=budget, n_buckets=2),
                         only=["collective-budget"])
    f = run_rules(_ctx(body, budget=budget, n_buckets=3),
                  only=["collective-budget"])
    assert f and "budget 4 (1 + 1/bucket x 3), got 3 (-1)" in f[0].message


def test_budget_full_gather_tripwire():
    """Payload above the declared grad-bytes multiple fails — the
    accidental param/master full-gather detector."""
    budget = {"fixed": {"all-reduce": 1, "reduce-scatter": 1,
                        "all-gather": 1},
              "max_operand_bytes_factor": 1.0}
    # operand bytes: 32 (rs) + 16 (ar) + 16 (ag) = 64 > 1.0 * 48
    f = run_rules(_ctx(_CLEAN_HIER, budget=budget, grad_bytes=48),
                  only=["collective-budget"])
    assert f and "full gather" in f[0].message
    assert not run_rules(_ctx(_CLEAN_HIER, budget=budget, grad_bytes=64),
                         only=["collective-budget"])


# ---------------------------------------------------------------------------
# deterministic-reduce
# ---------------------------------------------------------------------------

# the pinned gather + fixed-tree fold, sealed behind opt-barrier (the
# shape `collectives.deterministic.det_reduce_bucket_full` lowers to)
_DET_PRE_SEALED = _mod("""
ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8] parameter(0)
  %ag = f32[16] all-gather(%p0), replica_groups={{0,1,2,3}}, dimensions={0}
  %s0 = f32[8] slice(%ag), slice={[0:8]}
  %s1 = f32[8] slice(%ag), slice={[8:16]}
  %fold = f32[8] add(%s0, %s1)
  %t = (f32[8]) tuple(%fold)
  %seal = (f32[8]) opt-barrier(%t)
  ROOT %out = f32[8] get-tuple-element(%seal), index=0
}
""")

# PR 4 bug reconstruction: the same fold with no optimization_barrier —
# XLA is free to refold the tree, 1-ulp drift across factorizations
_DET_PRE_UNSEALED = _mod("""
ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8] parameter(0)
  %ag = f32[16] all-gather(%p0), replica_groups={{0,1,2,3}}, dimensions={0}
  %s0 = f32[8] slice(%ag), slice={[0:8]}
  %s1 = f32[8] slice(%ag), slice={[8:16]}
  ROOT %fold = f32[8] add(%s0, %s1)
}
""")

# gather-only optimized program (what det mode must compile to)
_DET_POST_CLEAN = _mod("""
ENTRY %main (p0: f32[8]) -> f32[16] {
  %p0 = f32[8] parameter(0)
  ROOT %ag = f32[16] all-gather(%p0), replica_groups={{0,1,2,3}}, dimensions={0}
}
""")


def test_det_rule_negative_sealed_fold():
    assert not run_rules(
        _ctx(_DET_POST_CLEAN, lowered=_DET_PRE_SEALED,
             deterministic_reduce=True), only=["deterministic-reduce"])


def test_det_rule_flags_missing_barrier():
    """The PR 4 drift: no optimization_barrier in the lowered program."""
    f = run_rules(_ctx(_DET_POST_CLEAN, lowered=_DET_PRE_UNSEALED,
                       deterministic_reduce=True),
                  only=["deterministic-reduce"])
    assert len(f) == 1 and "no optimization_barrier" in f[0].message


def test_det_rule_flags_barrier_without_gather_cone():
    """A barrier sealing something other than the gathered fold does not
    satisfy the contract."""
    body = _mod("""
ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8] parameter(0)
  %ag = f32[16] all-gather(%p0), replica_groups={{0,1,2,3}}, dimensions={0}
  %t = (f32[8]) tuple(%p0)
  %seal = (f32[8]) opt-barrier(%t)
  ROOT %out = f32[8] get-tuple-element(%seal), index=0
}
""")
    f = run_rules(_ctx(_DET_POST_CLEAN, lowered=body,
                       deterministic_reduce=True),
                  only=["deterministic-reduce"])
    assert len(f) == 1 and "no all-gather feeds" in f[0].message


def test_det_rule_flags_raw_all_reduce():
    """Any surviving all-reduce/reduce-scatter in a det program is a
    mesh-factorization-dependent reduction order."""
    f = run_rules(_ctx(_CLEAN_HIER, lowered=_DET_PRE_SEALED,
                       deterministic_reduce=True),
                  only=["deterministic-reduce"])
    kinds = {x.op for x in f}
    assert "ar" in kinds and "rs" in kinds


def test_det_rule_inactive_outside_det_mode():
    assert not run_rules(_ctx(_CLEAN_HIER, deterministic_reduce=False),
                         only=["deterministic-reduce"])


# ---------------------------------------------------------------------------
# donation-aliasing
# ---------------------------------------------------------------------------

_DONOR_PRE = _mod("""
ENTRY %main (p0: f32[8], p1: f32[8]) -> (f32[8], f32[8]) {
  %p0 = f32[8] parameter(0)
  %p1 = f32[8] parameter(1)
  %a = f32[8] add(%p0, %p1)
  %b = f32[8] multiply(%p0, %p1)
  ROOT %t = (f32[8], f32[8]) tuple(%a, %b)
}
""", header=", buffer_donor={ (0, {}), (1, {}) }")


def _post_aliased(alias_header):
    return _mod("""
ENTRY %main (p0: f32[8], p1: f32[8]) -> (f32[8], f32[8]) {
  %p0 = f32[8] parameter(0)
  %p1 = f32[8] parameter(1)
  %a = f32[8] add(%p0, %p1)
  %b = f32[8] multiply(%p0, %p1)
  ROOT %t = (f32[8], f32[8]) tuple(%a, %b)
}
""", header=", input_output_alias={ " + alias_header + " }")


def test_donation_negative_all_realized():
    post = _post_aliased("{0}: (0, {}, may-alias), "
                         "{1}: (1, {}, may-alias)")
    assert not run_rules(_ctx(post, lowered=_DONOR_PRE),
                         only=["donation-aliasing"])


def test_donation_flags_escaped_donor():
    """The PR 4 init_bucketed bug: a donated buffer kept alive by a
    live use never gets an input_output_alias entry — donation is
    silently dropped and peak memory grows."""
    post = _post_aliased("{0}: (0, {}, may-alias)")
    f = run_rules(_ctx(post, lowered=_DONOR_PRE),
                  only=["donation-aliasing"])
    assert len(f) == 1
    assert "parameter 1 escapes unaliased" in f[0].message


def test_donation_flags_double_alias():
    post = _post_aliased("{0}: (0, {}, may-alias), "
                         "{1}: (0, {}, may-alias)")
    f = run_rules(_ctx(post, lowered=None), only=["donation-aliasing"])
    assert len(f) == 1 and "two outputs" in f[0].message


def test_donation_silent_without_donors():
    """No donation offers (no lowered text, no declared list): nothing
    to check, no findings."""
    post = _post_aliased("{0}: (0, {}, may-alias)")
    assert not run_rules(_ctx(post), only=["donation-aliasing"])


# ---------------------------------------------------------------------------
# precision
# ---------------------------------------------------------------------------

def _bf16_reduce(groups, apply_comp="%addb"):
    return _mod(f"""
ENTRY %main (p0: bf16[8]) -> bf16[8] {{
  %p0 = bf16[8] parameter(0)
  ROOT %ar = bf16[8] all-reduce(%p0), replica_groups={groups}, to_apply={apply_comp}
}}
""", computations=_ADD_F32 + _ADD_BF16 + _MIN_BF16)


def test_precision_flags_bf16_accumulation():
    f = run_rules(_ctx(_bf16_reduce("{{0,1},{2,3}}")), only=["precision"])
    assert len(f) == 1 and "bf16" in f[0].message


def test_precision_negative_f32():
    assert not run_rules(_ctx(_CLEAN_HIER), only=["precision"])


def test_precision_allows_declared_bf16_slow_hop():
    """slow_compress_bits=16 declares the cross-pod hop bf16 — legal
    there, still illegal on intra-pod groups."""
    cross = _bf16_reduce("{{0,2},{1,3}}")
    intra = _bf16_reduce("{{0,1},{2,3}}")
    assert not run_rules(_ctx(cross, slow_compress_bits=16),
                         only=["precision"])
    assert run_rules(_ctx(intra, slow_compress_bits=16),
                     only=["precision"])


def test_precision_ignores_non_additive_reduction():
    """A bf16 min-reduction is not accumulation; only additive applies
    are gated."""
    assert not run_rules(_ctx(_bf16_reduce("{{0,1},{2,3}}", "%minb")),
                         only=["precision"])


# ---------------------------------------------------------------------------
# overlap-independence
# ---------------------------------------------------------------------------

_CHAINED_SLOW = _mod("""
ENTRY %main (p0: f32[8], p1: f32[8]) -> f32[8] {
  %p0 = f32[8] parameter(0)
  %p1 = f32[8] parameter(1)
  %ar0 = f32[8] all-reduce(%p0), replica_groups={{0,2},{1,3}}, to_apply=%add
  %mix = f32[8] add(%ar0, %p1)
  ROOT %ar1 = f32[8] all-reduce(%mix), replica_groups={{0,2},{1,3}}, to_apply=%add
}
""")

_INDEPENDENT_SLOW = _mod("""
ENTRY %main (p0: f32[8], p1: f32[8]) -> (f32[8], f32[8]) {
  %p0 = f32[8] parameter(0)
  %p1 = f32[8] parameter(1)
  %ar0 = f32[8] all-reduce(%p0), replica_groups={{0,2},{1,3}}, to_apply=%add
  %ar1 = f32[8] all-reduce(%p1), replica_groups={{0,2},{1,3}}, to_apply=%add
  ROOT %t = (f32[8], f32[8]) tuple(%ar0, %ar1)
}
""")


def test_overlap_flags_dependent_slow_collectives():
    f = run_rules(_ctx(_CHAINED_SLOW, overlap=True),
                  only=["overlap-independence"])
    assert len(f) == 1 and "cannot pipeline" in f[0].message
    assert f[0].op.endswith("ar1")


def test_overlap_negative_independent():
    assert not run_rules(_ctx(_INDEPENDENT_SLOW, overlap=True),
                         only=["overlap-independence"])


def test_overlap_warns_when_nothing_crosses_pods():
    intra = _mod("""
ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8] parameter(0)
  ROOT %ar = f32[8] all-reduce(%p0), replica_groups={{0,1},{2,3}}, to_apply=%add
}
""")
    f = run_rules(_ctx(intra, overlap=True),
                  only=["overlap-independence"])
    assert len(f) == 1 and f[0].severity == "warning"


def test_overlap_rule_inactive_without_overlap():
    assert not run_rules(_ctx(_CHAINED_SLOW, overlap=False),
                         only=["overlap-independence"])


# ---------------------------------------------------------------------------
# parser hardening (satellite: async collectives, nested fusions,
# multi-line op attrs)
# ---------------------------------------------------------------------------

def test_parse_async_pairing():
    body = _mod("""
ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8] parameter(0)
  %ars = f32[8] all-reduce-start(%p0), replica_groups={{0,2},{1,3}}, to_apply=%add
  ROOT %ard = f32[8] all-reduce-done(%ars)
}
""")
    m = ir.parse(body)
    assert m.async_pairs() == {"ars": "ard"}
    starts = [o for _, o in m.ops() if o.is_async_start]
    assert starts[0].collective_kind == "all-reduce"


def test_parse_nested_fusion_call_graph():
    body = _mod("""
%inner (q: f32[8]) -> f32[8] {
  %q = f32[8] parameter(0)
  ROOT %n = f32[8] negate(%q)
}

%outer (r: f32[8]) -> f32[8] {
  %r = f32[8] parameter(0)
  ROOT %c = f32[8] fusion(%r), kind=kLoop, calls=%inner
}

ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8] parameter(0)
  ROOT %f = f32[8] fusion(%p0), kind=kLoop, calls=%outer
}
""")
    m = ir.parse(body)
    f = m.entry.op("f")
    assert m.called_computations(f) == ["outer"]
    c = m.computations["outer"].op("c")
    assert m.called_computations(c) == ["inner"]


def test_parse_multiline_wrapped_attrs():
    """The printer wraps long replica_groups/backend_config attrs; the
    logical-line joiner must reassemble them (brackets inside quoted
    metadata strings must not skew the balance)."""
    body = _mod("""
ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8] parameter(0)
  ROOT %ar = f32[8] all-reduce(%p0), replica_groups={{0,2},
    {1,3}}, to_apply=%add,
    metadata={op_name="jit(main)/while[body]{nested}" source_file="x.py"}
}
""")
    m = ir.parse(body)
    ar = m.entry.op("ar")
    assert ar is not None and ar.is_collective
    assert ir.parse_replica_groups(ar.attrs) == [[0, 2], [1, 3]]


def test_compressed_mode_raises_not_implemented_multipod():
    out = run_multidevice("""
        from repro import parallel as PX
        import jax
        from repro import optim
        from repro.models.registry import build_model, get_config, \\
            reduced_config
        from repro.sharding import make_rules
        from repro.train import make_train_step
        mesh = PX.make_device_mesh((2, 2), ("pod", "data"))
        rules = make_rules(mesh, fsdp=False)
        model = build_model(reduced_config(get_config("llama3.2-1b")),
                            remat=False)
        ocfg = optim.AdamWConfig()
        try:
            make_train_step(model, ocfg, rules=rules,
                            cross_pod_mode="compressed")
        except NotImplementedError as e:
            assert "hier_bucketed" in str(e)
            assert "slow_compress_bits=8" in str(e)
            print("COMPRESSED_RAISES_OK")
        """, n_devices=4)
    assert "COMPRESSED_RAISES_OK" in out


# ---------------------------------------------------------------------------
# golden parse: real lowered train-step modules (tests/fixtures)
# ---------------------------------------------------------------------------

def test_golden_preopt_zero1_det_module():
    """Pre-optimization print of the zero1 + deterministic_reduce step
    (micro llama, (2,2) mesh, 2 buckets): donation offers, the sealing
    opt-barrier, gather-only collectives."""
    m = ir.parse(_fixture("train_step_zero1_det.pre.hlo.gz"))
    assert m.entry is not None and m.entry.name.startswith("main")
    # donate_argnums=(0,1): every params/opt leaf offered, batch not
    assert len(m.buffer_donors()) == 18
    barriers = [(c, o) for c, o in m.ops() if o.opcode == "opt-barrier"]
    assert len(barriers) == 1
    # deterministic contract already visible pre-opt: gathers, no raw
    # cross-replica reductions
    kinds = {o.collective_kind for _, o in m.ops() if o.is_collective}
    assert kinds == {"all-gather"}
    assert sum(1 for _, o in m.ops()
               if o.collective_kind == "all-gather") == 8


def test_golden_postopt_overlap_module():
    """Post-optimization print of the hier_bucketed + overlap step:
    realized aliasing, fusions, trip-counted whiles, and the slow-chain
    independence the overlap mode promises."""
    m = ir.parse(_fixture("train_step_hier_bucketed_overlap.post.hlo.gz"))
    assert m.entry is not None
    assert len(m.aliased_param_numbers()) == 45
    assert all(a.kind == "may-alias" for a in m.input_output_aliases())
    stats = hlo.analyze(m, chips_per_pod=2)
    # 3 collectives per bucket x 2 buckets + loss/gnorm all-reduce
    assert stats.collective_ops == {"reduce-scatter": 2, "all-reduce": 4,
                                    "all-gather": 2}
    assert stats.dot_flops > 0 and stats.hbm_bytes > 0
    trips = sorted({m.trip_count(o) for _, o in m.ops()
                    if o.opcode == "while"})
    assert 8 in trips                       # the microbatch/layer scans
    ch = hlo.slow_collective_chains(m, chips_per_pod=2)
    assert ch.n_slow == 3 and ch.independent


def test_golden_budget_cells_cover_matrix():
    """budgets.json declares every canonical matrix cell (the CI lint
    job would silently skip an undeclared cell's budget rule)."""
    budgets = load_budgets()
    for cell in ("xla", "hier", "hier_bucketed", "hier_bucketed_overlap",
                 "hier_bucketed_det", "zero1", "zero1_overlap",
                 "zero1_det"):
        b = budget_for(budgets, cell)
        assert b is not None, cell
        assert b.get("fixed") or b.get("per_bucket"), cell
    # the hier_bucketed contract from the ISSUE: 3 collectives per bucket
    hb = budget_for(budgets, "hier_bucketed")
    assert sum(hb["per_bucket"].values()) == 3
    # det cells must be all-gather-only by construction
    for cell in ("hier_bucketed_det", "zero1_det"):
        b = budget_for(budgets, cell)
        kinds = set(b["fixed"]) | set(b["per_bucket"])
        assert kinds == {"all-gather"}, (cell, kinds)
