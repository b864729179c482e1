"""Seeded long-range affinities, the input of mutex watershed.

    generate(shape, seed, mix, offsets) -> (len(offsets), z, y, x) uint8

The cells and the boundary map ``b = u8 / 255`` are those that
``worley.generate`` makes from the same seed and mix (the multicut cells'
input on that seed).  Channel ``c`` holds, for each voxel ``x`` and its
partner ``x + offsets[c]``,

    aff(x) = [lab(x) == lab(x + o)] * (1 - max(b(x), b(x + o)))

quantized as ``round(255 aff)``: high inside a cell, low near its ridges,
0 across cells, as a CNN's affinity output is; 0 where the partner lies
outside the volume.  It reads only the mix's keys that ``worley`` reads,
so a mix without noise gives noise-free affinities.  Each channel is one
jitted program of static slices on the default device.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import worley


def _window(off, shape):
    """(anchor slices, partner slices, zero padding per axis) of the
    offset: anchors are the voxels whose partner lies inside."""
    sl_a, sl_b, pad = [], [], []
    for o, s in zip(off, shape):
        a0 = min(max(-o, 0), s)
        n = max(s - abs(o), 0)
        sl_a.append(slice(a0, a0 + n))
        sl_b.append(slice(a0 + o, a0 + o + n))
        pad.append((a0, s - a0 - n))
    return tuple(sl_a), tuple(sl_b), tuple(pad)


@partial(jax.jit, static_argnames=("off",))
def _channel(lab, u8, off):
    sl_a, sl_b, pad = _window(off, lab.shape)
    # round(255 (1 - max(b, b'))) with b = u8 / 255 is 255 - max(u8, u8')
    # exactly, so the quantized value is taken in integers
    aff = jnp.where(lab[sl_a] == lab[sl_b],
                    255 - jnp.maximum(u8[sl_a], u8[sl_b]), 0)
    return jnp.pad(aff.astype(jnp.uint8), pad)


def generate(shape, seed: int, mix: dict, offsets):
    """The affinities of ``offsets`` (a list of (dz, dy, dx)) as a host
    uint8 array of shape ``(len(offsets),) + shape``."""
    shape = tuple(int(s) for s in shape)
    u8, lab = worley.generate(shape, seed, mix, with_labels=True)
    u8, lab = jnp.asarray(u8), jnp.asarray(lab)
    out = np.empty((len(offsets),) + shape, np.uint8)
    for c, off in enumerate(offsets):
        out[c] = np.asarray(_channel(lab, u8, tuple(int(o) for o in off)))
    return out
