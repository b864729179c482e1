"""parallel/stencil.py: halo_exchange vs a host-side pad/roll reference on
2- and 4-device emulated meshes, non-periodic boundary handling, and the
crop_halo round-trip (the ppermute ring the mesh-resident flagship rides)."""

import numpy as np
import pytest

pytestmark = pytest.mark.mesh


def _host_halo_reference(x, halo, axis, n_shards, mode="constant", fill=0):
    """What the sharded exchange must produce, computed with plain numpy:
    split the global array into shards along ``axis``, grow each with its
    true neighbors' boundary slabs, and pad the outer volume borders."""
    shards = np.split(x, n_shards, axis=axis)
    out = []
    for i, s in enumerate(shards):
        if i > 0:
            lo = np.take(shards[i - 1],
                         range(shards[i - 1].shape[axis] - halo,
                               shards[i - 1].shape[axis]), axis=axis)
        else:
            # volume low border: numpy-style reflect EXCLUDES the border
            # plane (np.pad mode='reflect'), constant uses fill
            lo_own = np.take(s, range(1, halo + 1), axis=axis)
            lo = (np.flip(lo_own, axis=axis) if mode == "reflect"
                  else np.full_like(lo_own, fill))
        if i < n_shards - 1:
            hi = np.take(shards[i + 1], range(halo), axis=axis)
        else:
            n_ax = s.shape[axis]
            hi_own = np.take(s, range(n_ax - halo - 1, n_ax - 1),
                             axis=axis)
            hi = (np.flip(hi_own, axis=axis) if mode == "reflect"
                  else np.full_like(hi_own, fill))
        out.append(np.concatenate([lo, s, hi], axis=axis))
    return out


def _run_exchange(x, halo, axis, n_shards, mode="constant", fill=0):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from cluster_tools_tpu.parallel.mesh import single_axis_mesh
    from cluster_tools_tpu.parallel.stencil import halo_exchange

    from jax import shard_map

    mesh = single_axis_mesh("space", n_shards)
    spec = [None] * x.ndim
    spec[axis] = "space"
    sp = P(*spec)

    def local(s):
        return halo_exchange(s, halo, axis, "space", mode=mode, fill=fill)

    grown = shard_map(local, mesh=mesh, in_specs=(sp,), out_specs=sp,
                      check_vma=False)(jnp.asarray(x))
    # shard_map concatenates the per-shard outputs along the sharded axis
    return np.split(np.asarray(grown), n_shards, axis=axis)


@pytest.mark.parametrize("n_shards", [2, 4])
@pytest.mark.parametrize("mode", ["constant", "reflect"])
def test_halo_exchange_matches_host_reference(n_shards, mode):
    rng = np.random.RandomState(0)
    x = rng.rand(8 * n_shards, 5, 6).astype("float32")
    halo = 2
    got = _run_exchange(x, halo, 0, n_shards, mode=mode, fill=0.0)
    want = _host_halo_reference(x, halo, 0, n_shards, mode=mode, fill=0.0)
    assert len(got) == n_shards
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_halo_exchange_nonperiodic_fill():
    """The first/last shards must see the FILL value, never the ring
    wrap-around of the opposite volume end."""
    n_shards, halo = 4, 3
    x = np.arange(4 * n_shards * 4, dtype="float32").reshape(4 * n_shards, 4)
    got = _run_exchange(x, halo, 0, n_shards, mode="constant", fill=-1.0)
    assert (got[0][:halo] == -1.0).all()
    assert (got[-1][-halo:] == -1.0).all()
    # and the interior halos are the true neighbors, not fill
    assert (got[1][:halo] == x[4 - halo:4]).all()


def test_crop_halo_round_trip():
    import jax.numpy as jnp

    from cluster_tools_tpu.parallel.stencil import crop_halo

    rng = np.random.RandomState(1)
    x = rng.rand(16, 6, 7).astype("float32")
    n_shards, halo = 4, 2
    grown = _run_exchange(x, halo, 0, n_shards)
    shards = np.split(x, n_shards, axis=0)
    for g, s in zip(grown, shards):
        back = np.asarray(crop_halo(jnp.asarray(g), halo, 0))
        np.testing.assert_array_equal(back, s)
    # halo=0 is the identity
    np.testing.assert_array_equal(
        np.asarray(crop_halo(jnp.asarray(x), 0, 0)), x)


def test_sharded_stencil_matches_dense():
    """sharded_stencil (exchange -> local fn -> crop) == the same stencil
    applied to the full array (away from the volume borders)."""
    import jax.numpy as jnp

    from cluster_tools_tpu.parallel.mesh import single_axis_mesh
    from cluster_tools_tpu.parallel.stencil import sharded_stencil

    rng = np.random.RandomState(2)
    x = rng.rand(16, 5, 5).astype("float32")

    def box3(a):  # 3-point mean along axis 0
        return (jnp.roll(a, 1, 0) + a + jnp.roll(a, -1, 0)) / 3.0

    mesh = single_axis_mesh("space", 4)
    f = sharded_stencil(box3, mesh, halo=1, axis=0, mesh_axis="space",
                        fill=0.0)
    got = np.asarray(f(jnp.asarray(x)))
    want = np.asarray(box3(jnp.asarray(x)))
    # interior rows see identical neighborhoods; border rows differ by
    # design (fill vs wrap), so compare away from the volume ends
    np.testing.assert_allclose(got[1:-1], want[1:-1], rtol=1e-6)
