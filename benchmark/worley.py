"""The default input generator: a CREMI-like boundary map.

A traffic mix is a JSON file of parameters under ``benchmark/traffic``;
this module reads it (``load_mix``).  A configuration that names no other
generator under ``"input"`` takes this one's ``generate(shape, seed,
mix)``; another generator, such as ``affinities``, builds on its cells
and map (``with_labels``).  The statistics are those of ``bench.synthetic_instance``
(Voronoi cells of ``cell_voxels`` voxels on average, ridges
``exp(-0.5 ((d2 - d1) / ridge_sigma)^2)`` from the distances to the nearest
and second-nearest cell centre, requantized to uint8), but the centres lie on
a jittered grid (Worley noise): one centre per grid cell, drawn uniformly in
its cell, so a voxel only looks at the centres of its 27 neighbouring grid
cells.  The per-voxel work runs in ``jax.numpy`` on the default device, one
grid row of cells at a time, with no gather: each of the 27 neighbour
offsets is a static slice of the small centre grid, broadcast over its
cells' voxels.

Optional ``noise_sigma`` adds Gaussian noise, smoothed in-plane with
``noise_smooth_px`` (for mixes that raise the fragment count).

The centres come from ``numpy.random.default_rng(seed)``, so any integer
seed works and the same seed gives the same bytes on the same backend.
"""

from __future__ import annotations

import json
import math
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
FAR = 1.0e6  # centre coordinate of the padding ring: never a nearest centre


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def grid_for(shape, grid_voxels: float):
    """(cells per axis, cell edge per axis): cubic cells of about
    ``grid_voxels`` voxels (a whole number of voxels per edge) covering the
    volume; the last cell of an axis may reach past its end."""
    edge = max(1, round(grid_voxels ** (1.0 / 3.0)))
    cells = tuple(max(1, math.ceil(s / edge)) for s in shape)
    return cells, (edge,) * len(shape)


def centres(cells, size, per_cell, rng) -> np.ndarray:
    """(per_cell, Gz+2, Gy+2, Gx+2, 3) float32 centre coordinates,
    ``per_cell`` per grid cell, uniform inside the cell (a cell past the
    volume's end keeps its centres, as the space beyond would); the padding
    ring sits at FAR."""
    out = np.full((per_cell,) + tuple(g + 2 for g in cells) + (3,), FAR,
                  np.float32)
    idx = np.stack(np.meshgrid(*[np.arange(g) for g in cells],
                               indexing="ij"), -1)
    pts = (idx + rng.random((per_cell,) + idx.shape)) * np.array(size)
    out[:, 1:-1, 1:-1, 1:-1] = pts.astype(np.float32)
    return out


@partial(jax.jit,
         static_argnames=("cells", "size", "ridge_sigma", "with_labels"))
def _row(cen, k, cells, size, ridge_sigma, with_labels):
    """Boundary (and nearest-centre id) for grid row ``k`` of cells:
    (size_z, Gy*size_y, Gx*size_x).  One loop step per (centre slot,
    neighbour offset), so the program stays small to compile."""
    per_cell = cen.shape[0]
    gz, gy, gx = cells
    sz, sy, sx = size
    z = (k * sz + jnp.arange(sz, dtype=jnp.float32))[:, None, None]
    y = jnp.arange(gy * sy, dtype=jnp.float32)[None, :, None]
    x = jnp.arange(gx * sx, dtype=jnp.float32)[None, None, :]
    big = jnp.float32(3.0e38)
    shape = (sz, gy * sy, gx * sx)
    rows = jax.lax.dynamic_slice_in_dim(cen, k, 3, axis=1)
    gyi = jnp.arange(gy)[:, None]
    gxi = jnp.arange(gx)[None, :]

    def step(j, carry):
        d1, d2, lab = carry
        p, rest = j // 27, j % 27
        dz, dy, dx = rest // 9, (rest // 3) % 3, rest % 3
        c = jax.lax.dynamic_slice(rows, (p, dz, dy, dx, 0),
                                  (1, 1, gy, gx, 3))[0, 0]  # (gy, gx, 3)
        up = jnp.broadcast_to(c[:, None, :, None, :], (gy, sy, gx, sx, 3))
        up = up.reshape(gy * sy, gx * sx, 3)
        d = ((z - up[None, :, :, 0]) ** 2 + (y - up[None, :, :, 1]) ** 2
             + (x - up[None, :, :, 2]) ** 2)
        closer = d < d1
        d2 = jnp.where(closer, d1, jnp.minimum(d2, d))
        d1 = jnp.where(closer, d, d1)
        if with_labels:
            # ids count the centres, 1-based, row-major
            cid = ((((k + dz - 1) * gy + gyi + dy - 1) * gx + gxi + dx - 1)
                   * per_cell + p + 1)
            cid = jnp.broadcast_to(cid[:, None, :, None], (gy, sy, gx, sx))
            lab = jnp.where(closer, cid.reshape(gy * sy, gx * sx), lab)
        return d1, d2, lab

    init = (jnp.full(shape, big), jnp.full(shape, big),
            jnp.zeros(shape if with_labels else (1, 1, 1), jnp.int32))
    d1, d2, lab = jax.lax.fori_loop(0, 27 * per_cell, step, init)
    ridge = jnp.sqrt(d2) - jnp.sqrt(d1)
    bnd = jnp.exp(-0.5 * (ridge / ridge_sigma) ** 2)
    return bnd, lab


@partial(jax.jit, static_argnames=("sigma",))
def _smooth_inplane(x, sigma):
    """Separable Gaussian over the two in-plane axes (shifted sums,
    reflect at the edges)."""
    r = max(int(4.0 * sigma + 0.5), 1)
    taps = np.exp(-0.5 * (np.arange(-r, r + 1) / sigma) ** 2)
    taps = (taps / taps.sum()).astype(np.float32)
    for ax in (1, 2):
        pad = [(0, 0)] * 3
        pad[ax] = (r, r)
        xp = jnp.pad(x, pad, mode="symmetric")
        n = x.shape[ax]
        x = sum(float(t) * jnp.take(xp, jnp.arange(i, i + n), axis=ax)
                for i, t in enumerate(taps))
    return x


def generate(shape, seed: int, mix: dict, with_labels: bool = False):
    """The mix's boundary map as a host uint8 array (and, with
    ``with_labels``, the int32 nearest-centre ids)."""
    shape = tuple(int(s) for s in shape)
    rng = np.random.default_rng(seed)
    per_cell = int(mix.get("centres_per_cell", 1))
    cells, size = grid_for(shape, float(mix["cell_voxels"]) * per_cell)
    cen = jnp.asarray(centres(cells, size, per_cell, rng))
    sigma = float(mix["ridge_sigma"])
    rows = [_row(cen, k, cells, size, sigma, with_labels)
            for k in range(cells[0])]
    bnd = jnp.concatenate([r[0] for r in rows], 0)
    bnd = bnd[:shape[0], :shape[1], :shape[2]]
    noise = float(mix.get("noise_sigma", 0.0))
    if noise:
        key = jax.random.key(int(rng.integers(0, 2 ** 31 - 1)))
        eps = jax.random.normal(key, bnd.shape, jnp.float32)
        smooth = float(mix.get("noise_smooth_px", 0.0))
        if smooth:
            eps = _smooth_inplane(eps, smooth)
            eps = eps / jnp.maximum(jnp.std(eps), 1e-6)
        bnd = jnp.clip(bnd + noise * eps, 0.0, 1.0)
    u8 = jnp.round(bnd * 255.0).astype(jnp.uint8)
    out = np.asarray(u8)
    if not with_labels:
        return out
    lab = jnp.concatenate([r[1] for r in rows], 0)
    return out, np.asarray(lab[:shape[0], :shape[1], :shape[2]])
