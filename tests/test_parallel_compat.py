"""The SPMD runtime layer: shard_map resolution, meshes, collectives."""
import jax
import jax.numpy as jnp
import numpy as np

from repro import parallel as PX
from tests.conftest import run_multidevice


def test_shard_map_resolves_on_this_jax():
    """``PX.shard_map`` is ``jax.shard_map``: ``axis_names`` selects the
    manual axes, which ``manual_axes`` reports inside the body only."""
    from jax.sharding import AxisType, PartitionSpec as P
    mesh = PX.make_device_mesh((1, 1), ("pod", "data"),
                               devices=jax.devices()[:1])
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
    seen = {}

    def body(x):
        seen["manual"] = PX.manual_axes()
        return x + PX.axis_size("pod")

    out = jax.jit(PX.shard_map(body, mesh=mesh, in_specs=P(),
                               out_specs=P(), check_vma=False,
                               axis_names={"pod"}))(jnp.zeros(2))
    np.testing.assert_array_equal(np.asarray(out), [1.0, 1.0])
    assert seen["manual"] == frozenset({"pod"})
    assert PX.manual_axes() == frozenset()


def test_shard_map_single_device_identity():
    mesh = PX.make_device_mesh((1,), ("d",), devices=jax.devices()[:1])
    from jax.sharding import PartitionSpec as P
    out = PX.shard_map(lambda x: x * 2, mesh=mesh,
                       in_specs=P(), out_specs=P(),
                       check_vma=False)(jnp.arange(4.0))
    np.testing.assert_array_equal(np.asarray(out), [0.0, 2.0, 4.0, 6.0])


def test_axis_helpers():
    assert PX.axis_tuple(None) == ()
    assert PX.axis_tuple("data") == ("data",)
    assert PX.axis_tuple(("pod", "data")) == ("pod", "data")
    mesh = PX.make_device_mesh((1,), ("d",), devices=jax.devices()[:1])
    assert PX.axes_size(mesh, "d") == 1
    assert PX.axes_size(mesh, None) == 1
    assert PX.axes_size(None, "d") == 1


def test_transport_tiers_consistent():
    # the analytic model and the runtime layer must price the same numbers
    from repro.collectives import transport as analytic
    assert analytic.SHM_STREAM_GBPS == PX.TIERS["SHM"].gbps
    assert analytic.NET_GBPS == PX.TIERS["NET"].gbps
    assert analytic.DCN_GBPS_PER_HOST == PX.TIERS["DCN"].gbps
    fast, slow = PX.fast_slow_axes(("pod", "data", "model"))
    assert fast == ("data", "model") and slow == "pod"
    assert PX.is_slow_axis("pod") and not PX.is_slow_axis("data")


def test_mesh_construction_multidevice():
    """1-, 2- and 4-device meshes on fake CPU devices."""
    out = run_multidevice("""
        import jax
        from repro import parallel as PX
        devs = jax.devices()
        for shape, names, n in (((1,), ("data",), 1),
                                ((2,), ("data",), 2),
                                ((2, 2), ("data", "model"), 4)):
            mesh = PX.make_device_mesh(shape, names, devices=devs[:n])
            assert tuple(mesh.axis_names) == names
            assert PX.axes_size(mesh, names) == n
        full = PX.make_device_mesh((2, 2), ("data", "model"))
        assert PX.axes_size(full, ("data", "model")) == 4
        print("MESH_OK")
        """, n_devices=4)
    assert "MESH_OK" in out


def test_psum_roundtrip_through_wrappers_multidevice():
    out = run_multidevice("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro import parallel as PX
        mesh = PX.make_device_mesh((4,), ("d",))

        def body(x):
            n = PX.axis_size("d")
            assert isinstance(n, int) and n == 4
            i = PX.axis_index("d")
            s = PX.psum(x, "d")
            m = PX.pmean(x, "d")
            hi = PX.pmax(x, "d")
            g = PX.all_gather(x, "d", gather_axis=0, tiled=False)
            shifted = PX.ppermute(x, "d", [(j, (j + 1) % 4)
                                           for j in range(4)])
            return s, m, hi, g.reshape(-1), shifted, i.astype(jnp.int32)[None]

        x = jnp.arange(4.0)
        s, m, hi, g, shifted, i = jax.jit(PX.shard_map(
            body, mesh=mesh, in_specs=P("d"),
            out_specs=(P("d"), P("d"), P("d"), P("d"), P("d"), P("d")),
            check_vma=False))(x)
        np.testing.assert_array_equal(np.asarray(s), [6.0] * 4)
        np.testing.assert_array_equal(np.asarray(m), [1.5] * 4)
        np.testing.assert_array_equal(np.asarray(hi), [3.0] * 4)
        # every shard gathered the full vector: 4 shards x 4 values
        np.testing.assert_array_equal(
            np.asarray(g), np.tile(np.arange(4.0), 4))
        np.testing.assert_array_equal(np.asarray(shifted),
                                      [3.0, 0.0, 1.0, 2.0])
        np.testing.assert_array_equal(np.asarray(i), [0, 1, 2, 3])
        print("PSUM_OK")
        """, n_devices=4)
    assert "PSUM_OK" in out


def test_psum_scatter_wrapper_multidevice():
    out = run_multidevice("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro import parallel as PX
        mesh = PX.make_device_mesh((4,), ("d",))

        def body(x):   # x: (4, k) per shard -> each shard keeps its row sum
            return PX.psum_scatter(x, "d", scatter_dimension=0, tiled=False)

        x = jnp.arange(32.0).reshape(4, 8)   # sharded: each shard (1, 8)
        y = jax.jit(PX.shard_map(
            body, mesh=mesh, in_specs=P("d"), out_specs=P("d"),
            check_vma=False))(jnp.tile(x, (4, 1)).reshape(16, 8))
        print("SCATTER_OK", np.asarray(y).shape)
        """, n_devices=4)
    assert "SCATTER_OK" in out
