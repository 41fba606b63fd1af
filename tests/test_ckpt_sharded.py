"""Unit tests for the sharded checkpoint subsystem (repro.ckpt).

Multidevice behavior (per-rank shard files, reshard restore) lives in
``tests/test_ckpt_reshard.py``; these cover the host-side machinery:
round-trips, the atomic commit protocol, restore policies, corruption
detection, legacy-format dispatch, crash-safe ``latest_step`` and the
elastic checkpoint handoff.
"""
import glob
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint as legacy
from repro import ckpt, optim
from repro import parallel as PX
from repro.core.leaves import TpuSliceTopology
from repro.elastic import plan_elastic_remesh


def _tree():
    return {"a": jnp.arange(8, dtype=jnp.float32),
            "b": {"c": jnp.ones((2, 3), jnp.bfloat16)},
            "none": None,
            "opt": optim.OptState(step=jnp.int32(3),
                                  mu={"a": jnp.zeros(4)},
                                  nu={"a": jnp.ones(4)}, master=None)}


def _assert_trees_equal(x, y):
    for a, b in zip(jax.tree.leaves(x), jax.tree.leaves(y)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_replicated_roundtrip_and_structure(tmp_path):
    tree = _tree()
    sdir = ckpt.step_dir(str(tmp_path), 5)
    assert ckpt.save_sharded(sdir, 5, tree) is None     # blocking
    assert ckpt.latest_step(str(tmp_path)) == 5
    assert ckpt.is_sharded_dir(sdir)
    step, restored = ckpt.restore_sharded(sdir, tree)
    assert step == 5
    _assert_trees_equal(tree, restored)
    assert restored["b"]["c"].dtype == jnp.bfloat16
    assert isinstance(restored["opt"], optim.OptState)
    assert restored["none"] is None


def test_async_save_commits_on_join(tmp_path):
    sdir = ckpt.step_dir(str(tmp_path), 2)
    t = ckpt.save_sharded(sdir, 2, {"x": jnp.arange(6.0)},
                          blocking=False)
    assert isinstance(t, threading.Thread)
    t.join()
    assert ckpt.latest_step(str(tmp_path)) == 2
    _, r = ckpt.restore_sharded(sdir, {"x": jnp.zeros(6)})
    np.testing.assert_array_equal(np.asarray(r["x"]), np.arange(6.0))


def test_restore_auto_dispatches_legacy(tmp_path):
    sdir = ckpt.step_dir(str(tmp_path), 7)
    legacy.save(sdir, 7, {"x": jnp.arange(4.0)})
    assert not ckpt.is_sharded_dir(sdir)
    step, r = ckpt.restore_auto(sdir, {"x": jnp.zeros(4)})
    assert step == 7
    np.testing.assert_array_equal(np.asarray(r["x"]), np.arange(4.0))


def test_pad_flat_and_zero_policies(tmp_path):
    # live prefix 100, saved padded to 128
    saved = {"m": jnp.concatenate([jnp.arange(100.0), jnp.zeros(28)])}
    sdir = ckpt.step_dir(str(tmp_path), 1)
    ckpt.save_sharded(sdir, 1, saved)
    # grow: align went 128 -> 160 (e.g. fast axis 2 -> deterministic 64)
    _, r = ckpt.restore_sharded(sdir, {"m": jnp.zeros(160)},
                                policy={"m": ckpt.PAD_FLAT})
    np.testing.assert_array_equal(
        np.asarray(r["m"]),
        np.concatenate([np.arange(100.0), np.zeros(60)]).astype(
            np.float32))
    # shrink: still past the live prefix, so nothing real is dropped
    _, r2 = ckpt.restore_sharded(sdir, {"m": jnp.zeros(104)},
                                 policy={"m": ckpt.PAD_FLAT})
    np.testing.assert_array_equal(np.asarray(r2["m"])[:100],
                                  np.arange(100.0, dtype=np.float32))
    # zero policy re-initializes on mismatch (hierarchical EF residuals)
    _, r3 = ckpt.restore_sharded(sdir, {"m": jnp.zeros(64)},
                                 policy={"m": ckpt.ZERO})
    assert not np.asarray(r3["m"]).any()
    # zero policy still restores real data when shapes match
    _, r4 = ckpt.restore_sharded(sdir, {"m": jnp.zeros(128)},
                                 policy={"m": ckpt.ZERO})
    np.testing.assert_array_equal(np.asarray(r4["m"])[:100],
                                  np.arange(100.0, dtype=np.float32))
    # default policy is exact: mismatch raises
    with pytest.raises(ckpt.CorruptCheckpointError, match="shape"):
        ckpt.restore_sharded(sdir, {"m": jnp.zeros(64)})
    with pytest.raises(ckpt.CorruptCheckpointError, match="missing"):
        ckpt.restore_sharded(sdir, {"other": jnp.zeros(4)})
    # pad_flat refuses to shrink through live data (live prefix is 100)
    with pytest.raises(ckpt.CorruptCheckpointError, match="truncate"):
        ckpt.restore_sharded(sdir, {"m": jnp.zeros(64)},
                             policy={"m": ckpt.PAD_FLAT})


def test_lost_shard_entries_detected(tmp_path):
    """A manifest that parses but lost shard entries (torn hand-edit,
    multi-host save missing one host) must refuse, not zero-fill."""
    import json
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = PX.make_device_mesh((1,), ("data",))
    arr = jax.device_put(jnp.arange(16.0), NamedSharding(mesh, P("data")))
    # force a 2-shard manifest by hand-splitting a replicated save
    sdir = ckpt.step_dir(str(tmp_path), 1)
    ckpt.save_sharded(sdir, 1, {"m": jnp.arange(16.0)})
    man_path = os.path.join(sdir, ckpt.MANIFEST)
    with open(man_path) as f:
        man = json.load(f)
    entry = man["leaves"]["m"]
    # rewrite as a sharded entry covering only half the array
    man["leaves"]["m"] = {
        "kind": "sharded", "shape": entry["shape"],
        "dtype": entry["dtype"], "spec": [],
        "shards": [{"file": entry["file"], "index": [[0, 8]],
                    "crc32": entry["crc32"]}]}
    with open(man_path, "w") as f:
        json.dump(man, f)
    with pytest.raises(ckpt.CorruptCheckpointError,
                       match="lost shard entries"):
        ckpt.restore_sharded(sdir, {"m": jnp.zeros(16)}, verify=False)


def test_corruption_detected(tmp_path):
    sdir = ckpt.step_dir(str(tmp_path), 1)
    ckpt.save_sharded(sdir, 1, {"m": jnp.arange(32.0)})
    fn = sorted(glob.glob(os.path.join(sdir, "m*.npy")))[0]
    arr = np.load(fn)
    arr[3] = 123.0
    np.save(fn, arr)
    with pytest.raises(ckpt.CorruptCheckpointError, match="checksum"):
        ckpt.restore_sharded(sdir, {"m": jnp.zeros(32)})


def test_latest_step_skips_torn_dirs(tmp_path):
    """Regression (PR-4 satellite): a crash mid-save must not break
    resume — neither a shard dir without a manifest, nor a torn temp dir
    awaiting its atomic rename, nor junk names may crash latest_step or
    win over the last committed step."""
    base = str(tmp_path)
    good = ckpt.step_dir(base, 10)
    ckpt.save_sharded(good, 10, {"x": jnp.arange(4.0)})
    # partially-written: files but no manifest (legacy-style crash)
    os.makedirs(os.path.join(base, "step_00000020"))
    np.save(os.path.join(base, "step_00000020", "x.npy"), np.zeros(4))
    # torn temp dir from the rename protocol — even WITH a manifest
    torn = os.path.join(base, "step_00000030.tmp-4242")
    os.makedirs(torn)
    with open(os.path.join(torn, "manifest.json"), "w") as f:
        f.write("{}")
    # junk that used to crash int(d.split('_')[1])
    os.makedirs(os.path.join(base, "step_final"))
    assert ckpt.latest_step(base) == 10
    assert legacy.latest_step(base) == 10       # same (shared) fix


def test_save_overwrites_same_step(tmp_path):
    sdir = ckpt.step_dir(str(tmp_path), 4)
    ckpt.save_sharded(sdir, 4, {"x": jnp.zeros(4)})
    ckpt.save_sharded(sdir, 4, {"x": jnp.arange(4.0)})
    _, r = ckpt.restore_sharded(sdir, {"x": jnp.zeros(4)})
    np.testing.assert_array_equal(np.asarray(r["x"]), np.arange(4.0))
    # the aside-rename protocol must not leave .old-* residue behind
    assert not [d for d in os.listdir(str(tmp_path)) if ".old-" in d]


def test_async_save_failure_surfaces_on_join(tmp_path):
    """A failed async write must raise at join, never pass silently —
    a swallowed ENOSPC would make a failed checkpoint look committed.
    Both formats share the re-raising writer."""
    blocker = tmp_path / "base"
    blocker.write_text("not a directory")
    t = ckpt.save_sharded(str(blocker / "step_00000001"), 1,
                          {"x": jnp.zeros(2)}, blocking=False)
    with pytest.raises(OSError):
        t.join()
    # the legacy format rides the same re-raising writer
    from repro.checkpoint import _WriterThread
    t2 = legacy.save(str(tmp_path / "ok"), 2, {"x": jnp.zeros(2)},
                     blocking=False)
    assert isinstance(t2, _WriterThread)
    t2.join()
    assert legacy.latest_step(str(tmp_path)) is None   # not a step_* dir
    _, r = legacy.restore(str(tmp_path / "ok"), {"x": jnp.zeros(2)})
    np.testing.assert_array_equal(np.asarray(r["x"]), np.zeros(2))


def test_python_scalar_leaves_roundtrip(tmp_path):
    """Templates may hold raw Python scalars (np.asarray-coerced on
    save); restore must handle leaves without .shape/.dtype."""
    tree = {"n": 3, "f": 2.5, "arr": jnp.arange(4.0)}
    sdir = ckpt.step_dir(str(tmp_path), 1)
    ckpt.save_sharded(sdir, 1, tree)
    step, r = ckpt.restore_sharded(sdir, tree)
    assert step == 1
    assert int(np.asarray(r["n"])) == 3
    assert float(np.asarray(r["f"])) == 2.5
    np.testing.assert_array_equal(np.asarray(r["arr"]), np.arange(4.0))


def test_legacy_restore_validates_shapes(tmp_path):
    """The gathered format cannot reshard: a template whose shapes moved
    must fail loudly, not return wrong-shaped arrays into the step."""
    sdir = ckpt.step_dir(str(tmp_path), 1)
    legacy.save(sdir, 1, {"m": jnp.arange(8.0)})
    with pytest.raises(ckpt.CorruptCheckpointError, match="reshard"):
        legacy.restore(sdir, {"m": jnp.zeros(12)})
    with pytest.raises(ckpt.CorruptCheckpointError, match="reshard"):
        ckpt.restore_auto(sdir, {"m": jnp.zeros(12)},
                          policy={"m": ckpt.PAD_FLAT})


def test_restore_rejects_changed_bucket_layout(tmp_path):
    """PAD_FLAT's copy-prefix rule is only exact under an unchanged
    leaf->bucket placement: restoring with a different bucket_bytes must
    refuse loudly, not scramble masters across bucket boundaries."""
    from repro.collectives import bucketing as BK
    leaves = {"w": jnp.arange(100.0), "b": jnp.arange(60.0)}
    lay_save = BK.plan_buckets(leaves, bucket_bytes=256, align=1)
    assert lay_save.n_buckets == 2
    sdir = ckpt.step_dir(str(tmp_path), 1)
    ckpt.save_sharded(sdir, 1, leaves, layout=lay_save)
    lay_big = BK.plan_buckets(leaves, bucket_bytes=4096, align=1)
    assert lay_big.n_buckets == 1
    with pytest.raises(ckpt.CorruptCheckpointError, match="bucket_bytes"):
        ckpt.restore_sharded(sdir, leaves, layout=lay_big)
    # the same layout passes validation and restores
    _, r = ckpt.restore_sharded(sdir, leaves, layout=lay_save)
    _assert_trees_equal(leaves, r)
    # requesting validation against a manifest with no recorded layout
    # must refuse, not silently skip the check
    sdir2 = ckpt.step_dir(str(tmp_path), 2)
    ckpt.save_sharded(sdir2, 2, leaves)            # layout=None
    with pytest.raises(ckpt.CorruptCheckpointError,
                       match="records no bucket layout"):
        ckpt.restore_sharded(sdir2, leaves, layout=lay_save)


def test_restore_rejects_dtype_mismatch(tmp_path):
    sdir = ckpt.step_dir(str(tmp_path), 1)
    ckpt.save_sharded(sdir, 1, {"m": jnp.arange(8, dtype=jnp.float32)})
    with pytest.raises(ckpt.CorruptCheckpointError, match="dtype"):
        ckpt.restore_sharded(sdir, {"m": jnp.zeros(8, jnp.bfloat16)})
    # ZERO policy re-initializes in the template dtype instead
    _, r = ckpt.restore_sharded(sdir, {"m": jnp.zeros(8, jnp.bfloat16)},
                                policy={"m": ckpt.ZERO})
    assert r["m"].dtype == jnp.bfloat16 and not np.asarray(r["m"]).any()
    # legacy format: same guard
    ldir = ckpt.step_dir(str(tmp_path), 2)
    legacy.save(ldir, 2, {"m": jnp.arange(8, dtype=jnp.float32)})
    with pytest.raises(ckpt.CorruptCheckpointError, match="dtype"):
        legacy.restore(ldir, {"m": jnp.zeros(8, jnp.bfloat16)})


def test_manifest_records_layout_and_mesh(tmp_path):
    from repro.collectives import bucketing as BK
    leaves = {"w": jnp.arange(10.0), "b": jnp.arange(4.0)}
    layout = BK.plan_buckets(leaves, bucket_bytes=64, align=8)
    sdir = ckpt.step_dir(str(tmp_path), 1)
    ckpt.save_sharded(sdir, 1, leaves, layout=layout)
    man = ckpt.read_manifest(sdir)
    assert man.layout["align"] == 8
    assert man.layout["bucket_sizes"] == list(layout.bucket_sizes)
    assert man.layout["live_sizes"] == ckpt.bucket_live_sizes(layout)
    assert len(man.layout["slots"]) == 2


def test_elastic_plan_names_checkpoint_handoff(tmp_path):
    topo = TpuSliceTopology(n_pods=1, hosts_per_pod=4, chips_per_host=4)
    leaves = topo.leaves()
    base = str(tmp_path)
    # no committed checkpoint: the remesh must refuse the handoff
    with pytest.raises(RuntimeError, match="no committed checkpoint"):
        plan_elastic_remesh(leaves, [(0, 1)], model_parallel=4,
                            ckpt_base_dir=base)
    ckpt.save_sharded(ckpt.step_dir(base, 30), 30, {"x": jnp.zeros(2)})
    # a torn later step must not win the handoff
    os.makedirs(os.path.join(base, "step_00000040.tmp-1"))
    plan = plan_elastic_remesh(leaves, [(0, 1)], model_parallel=4,
                               ckpt_base_dir=base)
    assert plan.handoff is not None
    assert plan.handoff.step == 30
    assert plan.handoff.sharded
    assert plan.handoff.step_dir == ckpt.step_dir(base, 30)
    # without a checkpoint dir the plan still works (handoff is None)
    assert plan_elastic_remesh(leaves, [(0, 1)],
                               model_parallel=4).handoff is None
