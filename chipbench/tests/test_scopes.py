"""The split of device time by the program's scopes and of host time by
its spans: the metadata reader on the recorded TPU v5e fixture, the
attribution on hand-made traces, and the host reduction of a tiny CPU
run of each cell."""
import json
import os

import pytest

from chipbench import breakdown as B
from chipbench import run as R
from chipbench import scopes as S
from chipbench import trace as T
from chipbench.tests import tiny

FIX = os.path.join(os.path.dirname(__file__), "fixtures")
XPLANE = os.path.join(FIX, "one_chip.xplane.pb")


def test_reader_finds_tf_op_on_the_fixture():
    ops = S.tf_ops(XPLANE)
    assert list(ops) == ["/device:TPU:0"]
    by_name = {T.op_name(k): v for k, v in ops["/device:TPU:0"].items()}
    matmuls = [v for k, v in by_name.items()
               if k.startswith("convolution_tanh_fusion")]
    assert len(matmuls) == 3
    assert set(matmuls) == {"jit(step)/dot_general:"}
    assert by_name["reshape"] == "jit(step)/convert_element_type:"
    # every op the trace ran has its metadata
    (dev,) = T.load(XPLANE).devices
    assert {n for _, _, n in dev.ops} <= set(ops["/device:TPU:0"])


def test_reader_agrees_with_the_xplane_proto():
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    with open(XPLANE, "rb") as f:
        space.ParseFromString(f.read())
    want = {}
    for plane in space.planes:
        if not plane.name.startswith(T.DEVICE_PREFIX):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        ops = want[plane.name] = {}
        for em in plane.event_metadata.values():
            tf_op = next((st.str_value or names.get(st.ref_value, "")
                          for st in em.stats
                          if names.get(st.metadata_id) == S.TF_OP), "")
            if tf_op or em.name not in ops:
                ops[em.name] = tf_op
    assert S.tf_ops(XPLANE) == want


def test_fixture_without_scopes_reads_nothing():
    tr = T.load(XPLANE)
    window = T.mark_window(tr, 0, 4)
    split = S.device_split(tr, window, S.tf_ops(XPLANE))
    # a program without the scopes: no share is reported
    assert split.shares() == {}
    unscoped = split.scope_ns()[S.UNSCOPED]
    assert split.pct(unscoped + split.idle_ns) == pytest.approx(100.0)
    assert unscoped == pytest.approx(T.reduce(tr, window).busy_ns[0])


@pytest.mark.parametrize("tf_op,scope,remat", [
    ("jit(wrapped)/while/body/closed_call/jvp(head)/dot_general:",
     "head", False),
    ("jit(wrapped)/while/body/closed_call/transpose(jvp())/while/body/"
     "closed_call/checkpoint/slstm/while/body/closed_call/mul", "slstm",
     False),
    ("jit(wrapped)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/while/body/closed_call/mlstm/while/body/"
     "closed_call/jvp()/exp", "mlstm", True),
    ("jit(wrapped)/transpose(jvp(head))/mul;jit(wrapped)/optimizer/mul",
     "head", False),
    ("jit(wrapped)/optimizer/jit(clip)/max", "optimizer", False),
    ("jit(wrapped)/while/body/closed_call", None, False),
    ("", None, False),
])
def test_scope_of_strips_the_wrappers(tf_op, scope, remat):
    assert S.scope_of(tf_op) == (scope, remat)


def _hand_made():
    ops = [(0, 100, "%while.4 = (s32[]) while(%t)"),
           (10, 40, "%fusion.1 = f32[1] fusion()"),
           (40, 90, "%fusion.2 = f32[1] fusion()"),
           (120, 130, "%fusion.3 = f32[1] fusion()"),
           (130, 150, "%copy.5 = f32[1] copy()"),
           (170, 190, "%fusion.6 = f32[1] fusion()")]
    meta = {
        ops[0][2]: "jit(wrapped)/while",
        ops[1][2]: "jit(wrapped)/while/body/closed_call/transpose(jvp())"
                   "/while/body/closed_call/checkpoint/slstm/mul",
        ops[2][2]: "jit(wrapped)/while/body/closed_call/transpose(jvp())"
                   "/while/body/closed_call/checkpoint/"
                   "rematted_computation/attention/dot_general:",
        ops[3][2]: "jit(wrapped)/while/body/closed_call/jvp(head)/exp",
        ops[5][2]: "jit(wrapped)/optimizer/mul",
    }
    host = sorted([(0, 198, "train.step"), (0, 100, "train.dispatch"),
                   (100, 165, "train.device_wait"),
                   (155, 165, "$_array.py:631 __value")])
    tr = T.Trace(devices=[T.DeviceOps("/device:TPU:0", ops)], host=host,
                 marks=[(0.0, 0), (200.0, 1)])
    return tr, {"/device:TPU:0": meta}


def test_device_split_gives_each_op_its_own_time():
    tr, meta = _hand_made()
    split = S.device_split(tr, T.mark_window(tr, 0, 1), meta)
    # the while op counts its own 20 ns, the copy has no metadata
    assert split.scope_ns() == {"attention": 50, "mlp": 0, "mlstm": 0,
                                "slstm": 30, "head": 10, "optimizer": 20,
                                "unscoped": 20 + 20}
    assert split.recompute_ns == 50
    assert split.idle_ns == 200 - 150
    shares = split.shares()
    assert shares["attention_pct"] == 25.0
    assert shares["recompute_pct"] == 25.0
    total = sum(v for k, v in shares.items() if k != "recompute_pct")
    assert total == pytest.approx(100.0)
    # a window that cuts ops keeps the sum
    cut = S.device_split(tr, (30.0, 180.0), meta)
    assert sum(cut.scope_ns().values()) + cut.idle_ns == pytest.approx(
        150.0)
    both = S.merge([split, split])
    assert both.shares() == pytest.approx(shares)
    ((op, tf_op, secs),) = both.top(1)["attention"]
    assert (op, tf_op) == ("fusion.2", meta["/device:TPU:0"][
        tr.devices[0].ops[2][2]])
    assert secs == pytest.approx(100e-9)
    assert [op for op, _, _ in both.top()["unscoped"]] == ["while.4",
                                                           "copy.5"]


def test_idle_gaps_are_named_by_the_innermost_program_span():
    tr, _ = _hand_made()
    idle = S.idle_by_span(tr, T.mark_window(tr, 0, 1))
    # the gaps 100..120 and 150..170 lie under device_wait (the Python
    # frame at 160 is not a program span), 190..200 under the step
    assert idle == {"train.device_wait": 40, "train.step": 10}


def test_host_split_reads_the_window_outside_the_traced_steps():
    from repro import tracing
    rec = tracing.Recorder()
    ms = 1_000_000

    def add(name, a, b, parent=None):
        s = tracing.Span(name, a * ms, b * ms, parent)
        rec.add_span(s)
        return s

    add("train.init_state", 0, 500)
    add(tracing.COMPILE, 600, 900)
    for i, t0 in enumerate(range(1000, 6000, 1000)):
        step = add("train.step", t0, t0 + 900)
        add("train.data_wait", t0, t0 + 1 + i, step)
        add("train.device_wait", t0 + 100, t0 + 880, step)
    add(tracing.COMPILE, 3500, 3510)            # inside the window
    # window: steps 1..3 (t 2000..5000); step 2 (3000) is traced
    out = S.host_split(rec, (2.0, 5.0), [(3.0, 4.0)])
    assert out["steps"] == 2
    assert out["data_wait_ms"] == pytest.approx((2 + 4) / 2)
    assert out["host_ms_per_step"] == pytest.approx(120)
    assert out["window_compiles"] == 1
    assert out["setup_compile_s"] == pytest.approx(0.3)
    assert out["setup_init_s"] == pytest.approx(0.5)
    assert S.host_split(tracing.Recorder(), (2.0, 5.0)) == {}


@pytest.mark.parametrize("workload", ["xlstm125m.train.s2048b8",
                                      "stablelm16b.train.s4096b4"])
def test_split_of_a_tiny_cpu_run(workload):
    res, split = B.split_run(lambda: R.run_cell(tiny.cell(workload),
                                                peak={}))
    assert res["correct"], res["checks"]
    assert split["steps"] == res["attempted"]
    assert split["window_compiles"] == 0
    assert split["setup_compile_s"] > 0 and split["setup_init_s"] > 0
    assert 0 <= split["data_wait_ms"] and 0 < split["host_ms_per_step"]
    json.dumps(split)


@pytest.mark.parametrize("split,traced,lacks", [
    ({"steps": 3, "attention_pct": 80.0}, True, []),
    ({"steps": 3}, False, []),
    ({"steps": 3}, True, ["device scope shares"]),
    ({}, False, ["host split"]),
    ({}, True, ["host split", "device scope shares"]),
])
def test_breakdown_names_what_the_split_lacks(split, traced, lacks):
    assert B.missing(split, traced) == lacks
