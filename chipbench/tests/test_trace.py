"""The reduction from a profiler trace to busy time, exposed collective
time, top ops and idle gaps, on traces recorded on a TPU v5e by
``record_fixture.py`` and on hand-made intervals."""
import json
import os

from chipbench import trace as T

FIX = os.path.join(os.path.dirname(__file__), "fixtures")


def test_interval_arithmetic():
    assert T.union([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert T.measure([(0, 2), (1, 3), (10, 11)]) == 4
    assert T.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
    assert T.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert T.subtract([(0, 4)], []) == [(0, 4)]


def test_op_names_and_collectives():
    fus = "%fusion.3 = bf16[8,128]{1,0:T(8,128)} fusion(bf16[8,128] %p), kind=kLoop"
    ar = ("%all-reduce-start.1 = (f32[64]{0}, f32[64]{0}) all-reduce-start("
          "f32[64]{0} %x), replica_groups={{0,1,2,3}}")
    tup = "%copy-start = (bf16[2048]{0:T(1024)}, bf16[2048]{0}) copy-start(bf16[2048] %a)"
    assert T.op_name(fus) == "fusion.3" and T.op_kind(fus) == "fusion"
    assert T.op_kind(ar) == "all-reduce-start" and T.is_collective(ar)
    assert T.op_kind(tup) == "copy-start" and not T.is_collective(tup)


def test_exposed_collective_is_what_no_compute_covers():
    tr = T.Trace(
        devices=[T.DeviceOps("/device:TPU:0", [
            (0, 10, "%fusion = f32[1] fusion()"),
            (10, 14, "%all-gather.2 = f32[4] all-gather(f32[1] %a)"),
            (14, 20, "%fusion.1 = f32[1] fusion()"),
            (30, 40, "%all-reduce = f32[4] all-reduce(f32[4] %b)"),
        ])],
        host=[(0, 100, "$loop"), (21, 29, "$time sleep")],
        marks=[(0.0, 0), (50.0, 1)])
    red = T.reduce(tr, T.mark_window(tr, 0, 1))
    assert red.window_ns == 50
    assert red.busy_ns == [30]
    assert red.exposed_collective_ns == [14]
    # 20..30 while the host slept, 40..50 while it ran its loop
    assert sorted(red.gaps) == [("$loop", 10), ("$time sleep", 10)]
    bd = T.breakdown(red)
    assert bd["device_ops"][0] == ["fusion", 10e-9]


def test_nested_ops_count_their_own_time():
    # a scan over layers is a while op spanning the ops of its body
    ops = [(0, 100, "%while.4 = (s32[]) while(%t)"),
           (10, 40, "%fusion.1 = f32[1] fusion()"),
           (40, 90, "%fusion.2 = f32[1] fusion()"),
           (120, 130, "%fusion.1 = f32[1] fusion()")]
    assert sorted(T.self_times(ops)) == [("fusion.1", 10), ("fusion.1", 30),
                                         ("fusion.2", 50), ("while.4", 20)]
    tr = T.Trace(devices=[T.DeviceOps("/device:TPU:0", ops)],
                 host=[(0, 200, "$loop")], marks=[(0.0, 0), (200.0, 1)])
    red = T.reduce(tr, T.mark_window(tr, 0, 1))
    assert red.busy_ns == [110]
    assert red.op_ns == {"while.4": 20, "fusion.1": 40, "fusion.2": 50}


def test_one_chip_fixture():
    meta = json.load(open(os.path.join(FIX, "one_chip.json")))
    tr = T.load(os.path.join(FIX, "one_chip.xplane.pb"))
    assert len(tr.devices) == meta["n_devices"] == 1
    assert [i for _, i in tr.marks] == list(range(meta["steps"] + 1))
    lo, hi = T.mark_window(tr, 0, meta["steps"])
    red = T.reduce(tr, (lo, hi))
    # each step is one module run of four matmul fusions and a sum, then
    # the host sleeps GAP_S: the chip is busy a small share of the window
    assert 0 < red.busy_ns[0] < 0.05 * red.window_ns
    assert red.exposed_collective_ns == [0]
    sleeps = [ns for what, ns in red.gaps if what == "$time sleep"]
    assert len(sleeps) >= meta["steps"]
    assert all(ns >= meta["gap_s"] * 1e9 for ns in sleeps[:meta["steps"]])
    top = T.breakdown(red)["device_ops"]
    assert top[0][0].startswith(("convolution", "fusion"))
    # the four matmul fusions of each step took about the same time
    fus = [s for name, s in top if "fusion" in name]
    assert len(fus) >= 4 and max(fus) < 1.2 * min(fus[:4])
