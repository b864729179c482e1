"""Mutex-watershed grid-graph edge extraction + segmentation.

TPU-native replacement for affogato's ``compute_mws_segmentation`` /
``MWSGridGraph.compute_nh_and_weights`` (reference:
utils/segmentation_utils.py:226-295, mutex_watershed/mws_blocks.py:136-174).
The split of labor follows SURVEY.md §7: edge weights, stride subsampling,
masking and noise run as one jitted device program over the affinity block
(pure slicing/elementwise — MXU-adjacent bandwidth work XLA fuses well);
the inherently sequential Kruskal-with-mutex-constraints clustering runs in
first-party C++ (native.mutex_clustering), exactly as the reference keeps it
in affogato's C++.

Edge semantics (the mutex-watershed paper's convention, which the
affogato wrapper reproduces by inverting attractive channels before an
ascending sort):

* channel ``c`` holds the affinity between anchor voxel ``i`` and voxel
  ``i + offsets[c]``; affinity 1 = same object;
* the first ``ndim`` channels (direct neighbors) give *attractive* edges
  with merge priority ``aff``;
* the remaining (long-range) channels give *mutex* edges with separation
  priority ``1 - aff``;
* all edges are processed jointly in descending priority order.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import native


def _offset_slices(off: Sequence[int], shape: Sequence[int]):
    """Anchor/partner slice tuples for one offset channel (in-bounds only)."""
    sl_a, sl_b = [], []
    for o, s in zip(off, shape):
        if o >= 0:
            sl_a.append(slice(0, s - o))
            sl_b.append(slice(o, s))
        else:
            sl_a.append(slice(-o, s))
            sl_b.append(slice(0, s + o))
    return tuple(sl_a), tuple(sl_b)


@partial(jax.jit, static_argnames=("offsets", "n_attractive", "strides",
                                   "randomize_strides", "have_mask",
                                   "noise_level"))
def _grid_edges_device(affs: jnp.ndarray, mask: jnp.ndarray, key: jnp.ndarray,
                       noise_level: float, offsets: Tuple[Tuple[int, ...], ...],
                       n_attractive: int, strides: Tuple[int, ...],
                       randomize_strides: bool, have_mask: bool):
    """Per-channel (u, v, w, valid) flat arrays; u/v are flat voxel indices.

    Mutex channels are subsampled on the stride grid (or a random subset of
    matching density when ``randomize_strides`` — reference config knob,
    mws_blocks.py:44).
    """
    shape = affs.shape[1:]
    ndim = len(shape)
    nvox = int(np.prod(shape))
    flat = jnp.arange(nvox, dtype=jnp.int32).reshape(shape)
    if noise_level > 0:
        affs = affs + noise_level * jax.random.uniform(key, affs.shape)
    out = []
    for c, off in enumerate(offsets):
        sl_a, sl_b = _offset_slices(off, shape)
        u = flat[sl_a].reshape(-1)
        v = flat[sl_b].reshape(-1)
        w = affs[c][sl_a].reshape(-1)
        valid = jnp.ones(u.shape, dtype=bool)
        if have_mask:
            valid &= mask[sl_a].reshape(-1) & mask[sl_b].reshape(-1)
        if c >= n_attractive:
            w = 1.0 - w
            if randomize_strides:
                density = 1.0 / float(np.prod(strides))
                kc = jax.random.fold_in(key, c)
                valid &= jax.random.uniform(kc, u.shape) < density
            elif any(s > 1 for s in strides):
                on_grid = jnp.ones(affs[c][sl_a].shape, dtype=bool)
                for ax in range(ndim):
                    pos = jnp.arange(on_grid.shape[ax]) + (sl_a[ax].start or 0)
                    sel = (pos % strides[ax]) == 0
                    shp = [1] * ndim
                    shp[ax] = on_grid.shape[ax]
                    on_grid &= sel.reshape(shp)
                valid &= on_grid.reshape(-1)
        out.append((u, v, w, valid))
    return out


def grid_graph_edges_host(affs: np.ndarray,
                          offsets: Sequence[Sequence[int]],
                          strides: Optional[Sequence[int]] = None,
                          mask: Optional[np.ndarray] = None,
                          id_offset: int = 0):
    """Host (numpy) edge extraction — same semantics as the device path
    for the deterministic cases (no noise, no randomized strides).

    ``id_offset`` shifts the flat voxel ids into a global frame (a
    shard-local origin's flat offset): sharded/mesh callers extract each
    shard's grid edges in its own window and concatenate without id
    collisions.

    The clustering consumer needs the FULL edge list in host memory, and
    the indices are pure arange arithmetic over data the host already
    read from the store — on link-attached accelerators the device
    detour would upload the affinities and download ~12 bytes/edge for
    arrays the host can produce for free (the reference keeps this whole
    stage in CPU C++ for the same reason, affogato)."""
    ndim = len(offsets[0])
    shape = affs.shape[1:]
    strides = tuple(int(s) for s in (strides or (1,) * ndim))
    if mask is not None:
        mask = np.asarray(mask).astype(bool)
    flat = np.arange(int(np.prod(shape)), dtype=np.int64).reshape(shape) \
        + np.int64(id_offset)
    uva, wa, uvm, wm = [], [], [], []
    for c, off in enumerate(offsets):
        sl_a, sl_b = _offset_slices(off, shape)
        u = flat[sl_a].reshape(-1)
        v = flat[sl_b].reshape(-1)
        # float32 arithmetic first, exactly like the device program —
        # computing 1-w in float64 would order some edge priorities
        # differently between the two impls
        w = affs[c][sl_a].reshape(-1).astype("float32")
        valid = np.ones(u.shape, bool)
        if mask is not None:
            valid &= (mask[sl_a].reshape(-1) & mask[sl_b].reshape(-1))
        if c >= ndim:
            w = np.float32(1.0) - w
            if any(s > 1 for s in strides):
                on_grid = np.ones(affs[c][sl_a].shape, bool)
                for ax in range(ndim):
                    pos = np.arange(on_grid.shape[ax]) \
                        + (sl_a[ax].start or 0)
                    sel = (pos % strides[ax]) == 0
                    shp = [1] * ndim
                    shp[ax] = on_grid.shape[ax]
                    on_grid &= sel.reshape(shp)
                valid &= on_grid.reshape(-1)
        uv = np.stack([u[valid], v[valid]], axis=1)
        (uva if c < ndim else uvm).append(uv)
        (wa if c < ndim else wm).append(w[valid].astype("float64"))

    def cat_uv(xs):
        return (np.concatenate(xs, axis=0) if xs
                else np.zeros((0, 2), dtype="int64"))

    return (cat_uv(uva), np.concatenate(wa) if wa else np.zeros(0),
            cat_uv(uvm), np.concatenate(wm) if wm else np.zeros(0))


def grid_graph_edges(affs: np.ndarray, offsets: Sequence[Sequence[int]],
                     strides: Optional[Sequence[int]] = None,
                     randomize_strides: bool = False,
                     mask: Optional[np.ndarray] = None,
                     noise_level: float = 0.0, seed: int = 0,
                     impl: str = "auto", id_offset: int = 0):
    """Extract (uv_attractive, w_attractive, uv_mutex, w_mutex) host arrays.

    ``impl='auto'`` uses the host path for the deterministic cases (see
    grid_graph_edges_host) and the device program when noise injection or
    randomized strides need the jax PRNG stream.  ``id_offset`` shifts
    voxel ids into a global frame (shard-local origins, see
    grid_graph_edges_host)."""
    if impl == "auto":
        impl = ("device" if (noise_level > 0 or randomize_strides)
                else "host")
    if impl == "host":
        return grid_graph_edges_host(affs, offsets, strides=strides,
                                     mask=mask, id_offset=id_offset)
    ndim = len(offsets[0])
    shape = affs.shape[1:]
    assert affs.shape[0] == len(offsets), (affs.shape, len(offsets))
    strides = tuple(int(s) for s in (strides or (1,) * ndim))
    have_mask = mask is not None
    mask_dev = jnp.asarray(
        mask.astype(bool) if have_mask else np.ones((1,) * ndim, bool))
    per_channel = _grid_edges_device(
        jnp.asarray(affs, dtype=jnp.float32), mask_dev,
        jax.random.PRNGKey(seed), float(noise_level),
        tuple(tuple(int(o) for o in off) for off in offsets),
        ndim, strides, bool(randomize_strides), have_mask)
    # FOUR concatenated downloads instead of four per channel: each
    # np.asarray is its own device round trip, and the per-channel
    # fetches made small-block extraction latency-bound
    lengths = [int(u.shape[0]) for u, _, _, _ in per_channel]
    u_all = np.asarray(jnp.concatenate(
        [u for u, _, _, _ in per_channel])).astype("int64") + id_offset
    v_all = np.asarray(jnp.concatenate(
        [v for _, v, _, _ in per_channel])).astype("int64") + id_offset
    w_all = np.asarray(jnp.concatenate([w for _, _, w, _ in per_channel]))
    ok_all = np.asarray(jnp.concatenate(
        [ok for _, _, _, ok in per_channel]))
    uva: List[np.ndarray] = []
    wa: List[np.ndarray] = []
    uvm: List[np.ndarray] = []
    wm: List[np.ndarray] = []
    pos = 0
    for c, ln in enumerate(lengths):
        sl = slice(pos, pos + ln)
        pos += ln
        sel = ok_all[sl]
        uv = np.stack([u_all[sl][sel], v_all[sl][sel]], axis=1)
        (uva if c < ndim else uvm).append(uv)
        (wa if c < ndim else wm).append(
            w_all[sl][sel].astype("float64"))
    def cat_uv(xs):
        return (np.concatenate(xs, axis=0) if xs
                else np.zeros((0, 2), dtype="int64"))

    return (cat_uv(uva), np.concatenate(wa) if wa else np.zeros(0),
            cat_uv(uvm), np.concatenate(wm) if wm else np.zeros(0))


@partial(jax.jit, static_argnames=("offsets", "strides", "seeded"))
def _sorted_edges_device(affs, seeds, offsets: Tuple[Tuple[int, ...], ...],
                         strides: Tuple[int, ...], seeded: bool):
    """Extract ALL grid edges and sort them by DESCENDING mutex-watershed
    priority on device, returning (u, v_packed) int32 streams the host
    union-find scan consumes directly (native.mutex_clustering_sorted).

    The host Kruskal's dominant cost is its stable_sort of tens of
    millions of 24-byte edge structs; the device does that sort as one
    fused key+payload sort and ships 8 bytes/edge back.  v_packed packs
    the partner index with the edge class: bit 30 = mutex edge, bit 29 =
    dropped (zero-affinity attractive or off-stride mutex; kept in the
    stream so the layout is static, skipped by the scan via u = -1).

    ``seeds`` (int32 volume, 0 = unseeded) boost intra-seed attractive
    edges above every data weight (the two-pass seeded variant); pass a
    dummy scalar array when ``seeded`` is False.
    """
    shape = affs.shape[1:]
    ndim = len(shape)
    flat = jnp.arange(int(np.prod(shape)), dtype=jnp.int32).reshape(shape)
    sflat = seeds.reshape(-1) if seeded else None
    us, vs, ws, ms, oks = [], [], [], [], []
    for c, off in enumerate(offsets):
        sl_a, sl_b = _offset_slices(off, shape)
        u = flat[sl_a].reshape(-1)
        v = flat[sl_b].reshape(-1)
        w = affs[c][sl_a].reshape(-1).astype(jnp.float32)
        is_mutex = c >= ndim
        valid = jnp.ones(u.shape, bool)
        if is_mutex:
            w = 1.0 - w
            if any(s > 1 for s in strides):
                on_grid = jnp.ones(affs[c][sl_a].shape, bool)
                for ax in range(ndim):
                    pos = jnp.arange(on_grid.shape[ax]) \
                        + (sl_a[ax].start or 0)
                    sel = (pos % strides[ax]) == 0
                    shp = [1] * ndim
                    shp[ax] = on_grid.shape[ax]
                    on_grid &= sel.reshape(shp)
                valid &= on_grid.reshape(-1)
        else:
            if seeded:
                su, sv = sflat[u], sflat[v]
                w = jnp.where((su != 0) & (su == sv), jnp.float32(2.0), w)
            # zero-affinity attractive edges carry no merge evidence
            # (deliberate deviation from affogato, see
            # mutex_watershed_segmentation)
            valid &= w > 0
        us.append(u)
        vs.append(v)
        ws.append(w)
        ms.append(jnp.full(u.shape, is_mutex, bool))
        oks.append(valid)
    u_all = jnp.concatenate(us)
    v_all = jnp.concatenate(vs)
    w_all = jnp.concatenate(ws)
    m_all = jnp.concatenate(ms)
    ok_all = jnp.concatenate(oks)
    # invalid edges sink to the end of the descending order
    key = jnp.where(ok_all, -w_all, jnp.float32(np.inf))
    u_s = jnp.where(ok_all, u_all, -1)
    v_packed = (v_all
                | (m_all.astype(jnp.int32) << 30)
                | ((~ok_all).astype(jnp.int32) << 29))
    _, u_sorted, vp_sorted = jax.lax.sort(
        [key, u_s, v_packed], num_keys=1, is_stable=True)
    return u_sorted, vp_sorted


@partial(jax.jit, static_argnames=("outer_shape", "offsets", "strides",
                                   "seeded"))
def _sorted_edges_resident_impl(vol, origin, seeds,
                                outer_shape: Tuple[int, ...],
                                offsets: Tuple[Tuple[int, ...], ...],
                                strides: Tuple[int, ...], seeded: bool):
    affs = jax.lax.dynamic_slice(
        vol, (0,) + tuple(origin[d] for d in range(len(outer_shape))),
        (vol.shape[0],) + outer_shape)
    u_sorted, vp_sorted = _sorted_edges_device(affs, seeds, offsets,
                                               strides, seeded)
    return u_sorted, vp_sorted, affs.sum()


def compact_seeds_int32(seeds: np.ndarray) -> np.ndarray:
    """Equality-preserving block-local relabel of seed ids to int32.

    The seeded pass-2 device path feeds uint64 GLOBAL labels
    (``block_id * offset_unit + 1 + rank``) as seeds; a plain
    ``astype('int32')`` wraps once ``block_id * offset_unit > 2^31``
    (~112 blocks at bench sizes), colliding distinct seeds (false
    ``su == sv`` boosts -> wrong merges) or wrapping a seed to 0 (seed
    lost).  Only EQUALITY matters inside ``_sorted_edges_device``, so a
    dense block-local relabel is exact: 0 (unseeded) stays 0, distinct
    ids stay distinct, and the result always fits int32 (a block holds
    < 2^29 voxels, enforced below)."""
    s = np.asarray(seeds)
    if s.size == 0 or int(s.max()) < (1 << 31):
        # common case (volumes below ~112 blocks): the cast is already
        # exact — skip the O(n log n) unique over the outer block
        return s.astype("int32")
    uniq, inv = np.unique(s, return_inverse=True)
    inv = inv.astype("int32").reshape(s.shape)
    if uniq.size and uniq[0] == 0:
        return inv
    return inv + 1  # no zeros present: keep every id nonzero


def _sorted_edges_resident(affs_dev, origin, outer_shape,
                           offsets, strides,
                           seeds: Optional[np.ndarray] = None):
    """Submit one block's extract+sort against the DEVICE-RESIDENT
    affinity volume without synchronizing: dynamic-slice the outer
    window, sort every grid edge by descending priority.  Returns
    (u_sorted, v_packed, block_affinity_sum) device handles — callers
    pipeline the host scan of block i with the device sort of i+1.
    The affinity sum reproduces the host path's skip-empty-block rule
    without a separate download."""
    import jax.numpy as jnp

    if int(np.prod(outer_shape)) >= (1 << 29):
        # v_packed carries the partner voxel index in bits 0-28 (flags at
        # 29/30): a larger outer block would silently corrupt the edge
        # stream.  Callers route oversized blocks to the host path
        raise ValueError(
            f"outer block {tuple(outer_shape)} has >= 2^29 voxels — the "
            "packed edge stream cannot address it; use the host path or "
            "shrink blocks")
    seeded = seeds is not None
    seeds_in = (jnp.asarray(compact_seeds_int32(seeds))
                if seeded else jnp.zeros((1,) * len(outer_shape), jnp.int32))
    return _sorted_edges_resident_impl(
        affs_dev, jnp.asarray(origin, dtype=jnp.int32), seeds_in,
        tuple(int(s) for s in outer_shape),
        tuple(tuple(int(o) for o in off) for off in offsets),
        tuple(int(s) for s in strides), seeded)


def mutex_watershed_scan_sorted(u, vp, shape,
                                mask: Optional[np.ndarray] = None):
    """Host half of the sorted finalize: the C++ union-find scan over a
    DOWNLOADED sorted edge stream; returns uint64 labels consecutive
    from 1 (0 on masked voxels).  Split from the downloads so pipelining
    callers can attribute the link transfer (``d2h-edges``) and this
    sequential host scan (``host-scan``) to separate stages — lumping
    both under a ``sync-`` stage mis-credited the host scan to the
    accelerator path (ADVICE r5)."""
    dropped = (vp >> 29) & 1
    u = np.where(dropped != 0, np.int32(-1), u)
    v = vp & np.int32((1 << 29) - 1)
    flags = ((vp >> 30) & 1).astype(np.uint8)
    n_nodes = int(np.prod(shape))
    cluster = native.mutex_clustering_sorted(n_nodes, u, v, flags)
    labels = cluster.reshape(shape)
    if mask is not None:
        labels = np.where(mask, labels + 1, 0)
    else:
        labels = labels + 1
    uniq, inv = np.unique(labels, return_inverse=True)
    if uniq.size and uniq[0] == 0:
        labels = inv.reshape(shape).astype("uint64")
    else:
        labels = (inv.reshape(shape) + 1).astype("uint64")
    return labels


def mutex_watershed_finalize_sorted(handles, shape, asum=None,
                                    mask: Optional[np.ndarray] = None):
    """Download one block's sorted edge stream and run the host scan.
    Returns (labels, affinity_sum): uint64 labels consecutive from 1
    (0 on masked voxels); when ``asum`` (a device handle) reports an
    all-zero block the scan is skipped and labels is None."""
    u_sorted, vp_sorted = handles
    a = float(np.asarray(asum)) if asum is not None else None
    if a == 0.0:
        return None, 0.0
    labels = mutex_watershed_scan_sorted(np.asarray(u_sorted),
                                         np.asarray(vp_sorted), shape,
                                         mask=mask)
    return labels, (a if a is not None else 1.0)


def mutex_watershed_segmentation(
        affs: np.ndarray, offsets: Sequence[Sequence[int]],
        strides: Optional[Sequence[int]] = None,
        randomize_strides: bool = False,
        mask: Optional[np.ndarray] = None,
        noise_level: float = 0.0, seed: int = 0,
        seeds: Optional[np.ndarray] = None,
        return_seed_assignments: bool = False):
    """Mutex watershed over an affinity volume.

    ``seeds`` (same shape as the volume, 0 = unseeded) implement the
    reference's two-pass seeded variant (utils/segmentation_utils.py:252-295):
    direct-neighbor edges inside one seed become maximally attractive, so a
    seed region is never split; distinct seeds *may* still merge when the
    affinities support it, and the caller reconciles those merges through the
    returned (segment_label, seed_label) assignments — mirroring the
    grid-graph ``set_seed_state``/two_pass_assignments protocol.

    Returns labels (uint64, consecutive from 1; 0 on masked-out voxels), and
    optionally the seed-assignment pairs.
    """
    shape = affs.shape[1:]
    uva, wa, uvm, wm = grid_graph_edges(
        affs, offsets, strides=strides, randomize_strides=randomize_strides,
        mask=mask, noise_level=noise_level, seed=seed)
    if seeds is not None:
        sflat = np.asarray(seeds).reshape(-1)
        su, sv = sflat[uva[:, 0]], sflat[uva[:, 1]]
        same_seed = (su != 0) & (su == sv)
        # above every data weight (affinities are normalized to [0, 1]);
        # grid_graph.intra_seed_weight = 1 equivalent
        wa = np.where(same_seed, 2.0, wa)
    # an attractive edge with zero affinity carries no merge evidence;
    # keeping it would let unconstrained clusters merge arbitrarily at the
    # bottom of the priority queue (deliberate deviation from affogato, which
    # processes zero-weight edges).  After seed boosting, so intra-seed edges
    # always survive.
    keep = wa > 0
    uva, wa = uva[keep], wa[keep]
    n_nodes = int(np.prod(shape))
    cluster = native.mutex_clustering(n_nodes, uva, wa, uvm, wm)
    labels = cluster.reshape(shape)
    if mask is not None:
        labels = np.where(mask, labels + 1, 0)
    else:
        labels = labels + 1
    # consecutive relabel, keep zeros
    uniq, inv = np.unique(labels, return_inverse=True)
    if uniq.size and uniq[0] == 0:
        labels = inv.reshape(shape).astype("uint64")
    else:
        labels = (inv.reshape(shape) + 1).astype("uint64")
    if not return_seed_assignments:
        return labels
    assignments = np.zeros((0, 2), dtype="uint64")
    if seeds is not None:
        sflat = np.asarray(seeds).reshape(-1)
        lflat = labels.reshape(-1)
        seeded = sflat != 0
        if seeded.any():
            assignments = np.unique(
                np.stack([lflat[seeded].astype("uint64"),
                          sflat[seeded].astype("uint64")], axis=1), axis=0)
    return labels, assignments
