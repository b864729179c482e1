"""Sharded-stencil primitive: halo exchange over a mesh axis via ppermute.

The reference's universal spatial pattern is "read outerBlock (with halo),
write innerBlock" through the filesystem (watershed/watershed.py:252-264,
inference/inference.py:202-232).  On TPU the volume lives sharded across
chips, and the halo read becomes a ring exchange over ICI — structurally
identical to ring/context-parallel sequence sharding (SURVEY §5.7), so it is
built once here and reused by every stencil-shaped workload (filters, EDT
seams, inference, two-pass watershed).

``halo_exchange`` runs *inside* a ``shard_map``-decorated function: each shard
sends its boundary slabs to its +1/-1 neighbors along the mesh axis and
concatenates the received slabs, growing the local array by ``halo`` on both
sides of ``axis``.  Non-periodic edges are padded with ``fill`` (the analog of
reflect/constant padding at volume borders).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _take(x: jnp.ndarray, axis: int, sl: slice) -> jnp.ndarray:
    idx = [slice(None)] * x.ndim
    idx[axis] = sl
    return x[tuple(idx)]


def device_varying(a: jnp.ndarray, mesh_axis: str) -> jnp.ndarray:
    """Mark ``a`` device-varying over ``mesh_axis`` inside shard_map (the
    ring collectives' loop carries must start varying)."""
    return jax.lax.pcast(a, (mesh_axis,), to="varying")


def halo_exchange(x: jnp.ndarray, halo: int, axis: int, mesh_axis: str,
                  fill: Any = 0, mode: str = "constant") -> jnp.ndarray:
    """Grow ``x`` by ``halo`` on both ends of ``axis`` with neighbor data.

    Must be called inside shard_map with ``mesh_axis`` a named mesh axis.
    ``mode``: 'constant' (pad with fill) or 'reflect' at the outer volume
    borders (reference: inference reflect-padding, inference.py:202-232).
    'reflect' mirrors excluding the border plane (numpy/jnp.pad 'reflect'
    semantics) and therefore needs ``halo <= x.shape[axis] - 1``.
    """
    if halo <= 0:
        return x
    n = jax.lax.axis_size(mesh_axis)
    idx = jax.lax.axis_index(mesh_axis)

    lo_slab = _take(x, axis, slice(0, halo))           # my low boundary
    hi_slab = _take(x, axis, slice(x.shape[axis] - halo, None))

    if n > 1:
        # send my high slab to the next shard (it becomes their low halo)
        recv_lo = jax.lax.ppermute(
            hi_slab, mesh_axis, [(i, (i + 1) % n) for i in range(n)])
        # send my low slab to the previous shard (their high halo)
        recv_hi = jax.lax.ppermute(
            lo_slab, mesh_axis, [(i, (i - 1) % n) for i in range(n)])
    else:
        recv_lo = lo_slab
        recv_hi = hi_slab

    if mode == "reflect":
        # numpy-style reflect: mirror EXCLUDING the border plane, the
        # same fold as jnp.pad(mode='reflect') and the blockwise chain's
        # volume-level reflect_indices (period 2n-2) — including it
        # would duplicate the border plane and silently diverge from
        # the per-block readers.  Requires halo <= size-1 on this axis
        # (same constraint jnp.pad imposes; callers clamp)
        size = x.shape[axis]
        pad_lo = jnp.flip(_take(x, axis, slice(1, halo + 1)), axis=axis)
        pad_hi = jnp.flip(_take(x, axis, slice(size - halo - 1, size - 1)),
                          axis=axis)
    else:
        pad_lo = jnp.full_like(lo_slab, fill)
        pad_hi = jnp.full_like(hi_slab, fill)

    # first/last shards have no ring neighbor on that side: use border padding
    lo = jnp.where(idx == 0, pad_lo, recv_lo) if n > 1 else pad_lo
    hi = jnp.where(idx == n - 1, pad_hi, recv_hi) if n > 1 else pad_hi
    return jnp.concatenate([lo, x, hi], axis=axis)


def crop_halo(x: jnp.ndarray, halo: int, axis: int) -> jnp.ndarray:
    """Drop ``halo`` from both ends of ``axis`` (write the innerBlock)."""
    if halo <= 0:
        return x
    return _take(x, axis, slice(halo, x.shape[axis] - halo))


def sharded_stencil(fn, mesh: Mesh, halo: int, axis: int = 0,
                    mesh_axis: str = "space", fill: Any = 0,
                    mode: str = "constant"):
    """Wrap a local stencil ``fn(block) -> block`` into a mesh-sharded op.

    The returned function takes a global array sharded over ``mesh_axis`` on
    ``axis``, performs the halo exchange, applies ``fn`` to the haloed local
    shard, and crops the halo back off — the single reusable primitive
    replacing the reference's outer/inner block machinery.
    """
    def local(x):
        grown = halo_exchange(x, halo, axis, mesh_axis, fill=fill, mode=mode)
        out = fn(grown)
        return crop_halo(out, halo, axis)

    def specs(ndim):
        spec = [None] * ndim
        spec[axis] = mesh_axis
        return P(*spec)

    def apply(x):
        sp = specs(x.ndim)
        return jax.shard_map(local, mesh=mesh, in_specs=(sp,),
                             out_specs=sp)(x)

    return apply
