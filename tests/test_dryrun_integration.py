"""Dry-run machinery integration: lower+compile a real cell on a small
fake-device mesh and check the artifact contents end-to-end."""
import json

from tests.conftest import run_multidevice


def test_dryrun_cell_on_small_mesh():
    out = run_multidevice("""
        from repro import parallel as PX
        import os, json, tempfile
        # shrink the production mesh so the cell fits 8 fake devices
        import repro.launch.mesh as M
        import jax
        def small_mesh(*, multi_pod=False):
            if multi_pod:
                return PX.make_device_mesh((2, 2, 2), ("pod", "data", "model"))
            return PX.make_device_mesh((2, 4), ("data", "model"))
        M.make_production_mesh = small_mesh
        import repro.launch.dryrun as D
        D.make_production_mesh = small_mesh

        d = tempfile.mkdtemp()
        for mp in (False, True):
            meta = D.run_cell("llama3.2-1b", "train_4k", multi_pod=mp,
                              out_dir=d)
            assert meta["status"] == "ok", meta.get("error")
            assert meta["roofline"]["bound_s"] > 0
            assert meta["hlo"]["dot_flops_per_device"] > 0
            assert meta["hlo"]["collective_bytes_per_device"] > 0
            assert meta["memory"]["temp_bytes"] > 0
            if mp:
                assert meta["mesh"] == "2x16x16"  # label, mesh shrunk
        # knobs lower too (the §Perf iteration paths)
        meta = D.run_cell("llama3.2-1b", "train_4k", multi_pod=False,
                          seq_parallel=True, fsdp=False,
                          accum_override=1, use_master=False, out_dir=d)
        assert meta["status"] == "ok", meta.get("error")
        assert meta["knobs"]["seq_parallel"] is True
        # decode + skip cells
        meta = D.run_cell("llama3.2-1b", "decode_32k", multi_pod=False)
        assert meta["status"] == "ok", meta.get("error")
        meta = D.run_cell("llama3.2-1b", "long_500k", multi_pod=False)
        assert meta["status"] == "skipped"
        print("DRYRUN_OK")
        """, n_devices=8, timeout=540)
    assert "DRYRUN_OK" in out


def test_artifacts_complete_if_present(repo_root):
    """When the full sweep artifacts exist, assert the 40-cell coverage
    contract: every runnable cell ok on both meshes, skips documented."""
    import glob
    import os
    art = os.path.join(repo_root, "artifacts", "dryrun")
    files = [f for f in glob.glob(os.path.join(art, "*.json"))
             if len(os.path.basename(f)[:-5].split("__")) == 3]
    if len(files) < 80:
        import pytest
        pytest.skip("full sweep artifacts not present")
    by_status = {}
    for fn in files:
        with open(fn) as f:
            meta = json.load(f)
        by_status.setdefault(meta.get("status"), []).append(
            (meta["arch"], meta["shape"], meta["mesh"]))
    assert not by_status.get("error"), by_status.get("error")
    assert len(by_status.get("ok", [])) == 64
    skipped = by_status.get("skipped", [])
    assert len(skipped) == 16
    assert all(s[1] == "long_500k" for s in skipped)
