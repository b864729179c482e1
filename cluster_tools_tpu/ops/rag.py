"""On-device region-adjacency-graph primitives.

TPU-native replacement for ``nifty.distributed.computeMergeableRegionGraph``
and the ndist feature-extraction entry points (reference:
graph/initial_sub_graphs.py:114-118, features/block_edge_features.py:113-141)
— the reference delegates per-block RAG extraction to a fused C++ IO+compute
call; here the *compute* is a jitted device program over the label block
(static shapes: every axis-neighbor pair is emitted with a validity mask) and
the host does only `np.unique` over the surviving pairs.

Face ownership: the pair between voxel ``i`` and ``i+1`` along an axis
belongs to the block that owns voxel ``i``; blocks read a +1 halo on their
upper faces (the reference's ``increaseRoi`` convention) so inter-block faces
are extracted exactly once globally.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def densify_labels(labels: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Map arbitrary (uint64) labels to dense int32 ids for device transfer.

    JAX silently truncates int64 inputs to int32 unless x64 is enabled, and
    watershed fragment labels carry per-block voxel offsets that exceed 2**31
    at cluster scale.  Device kernels therefore always run on dense per-block
    ids; callers map pair results back through the returned LUT.  Returns
    (lut, dense) with ``lut[dense] == labels`` and ``lut[0] == 0`` so the
    kernels' ignore-label-0 convention survives densification.
    """
    uniq, inv = np.unique(labels, return_inverse=True)
    inv = inv.reshape(labels.shape)
    if len(uniq) == 0 or uniq[0] != 0:
        uniq = np.concatenate([np.zeros(1, dtype=uniq.dtype), uniq])
        inv = inv + 1
    if len(uniq) >= 2 ** 31:  # one block can never hold this many labels
        raise ValueError("more than 2**31 distinct labels in one block")
    return uniq.astype("uint64"), inv.astype("int32")


def _axis_slices(ndim: int, axis: int, lo_size: int):
    lo = [slice(None)] * ndim
    hi = [slice(None)] * ndim
    lo[axis] = slice(0, lo_size)
    hi[axis] = slice(1, lo_size + 1)
    return tuple(lo), tuple(hi)


@partial(jax.jit, static_argnames=("ignore_label", "inner_shape"))
def label_pairs(labels: jnp.ndarray, ignore_label: bool = True,
                inner_shape: Optional[Tuple[int, ...]] = None):
    """All differing axis-neighbor label pairs in the block.

    ``labels`` is the haloed block (inner block + 1 voxel on upper faces where
    available).  ``inner_shape`` restricts pair *ownership* to faces whose
    first voxel lies in the inner block.  Returns (u, v, valid) flat arrays
    with u < v for valid entries; invalid slots are zero.
    """
    ndim = labels.ndim
    us: List[jnp.ndarray] = []
    vs: List[jnp.ndarray] = []
    ok: List[jnp.ndarray] = []
    inner = inner_shape or labels.shape
    for axis in range(ndim):
        size = labels.shape[axis] - 1
        if size <= 0:
            continue
        lo_sl, hi_sl = _axis_slices(ndim, axis, size)
        a = labels[lo_sl]
        b = labels[hi_sl]
        valid = a != b
        if ignore_label:
            valid &= (a != 0) & (b != 0)
        # ownership: first voxel inside the inner block (on every axis)
        for ax2 in range(ndim):
            lim = inner[ax2] if ax2 != axis else min(inner[ax2], size)
            if a.shape[ax2] > lim:
                idx = jnp.arange(a.shape[ax2]) < lim
                shape = [1] * ndim
                shape[ax2] = a.shape[ax2]
                valid &= idx.reshape(shape)
        u = jnp.minimum(a, b).reshape(-1)
        v = jnp.maximum(a, b).reshape(-1)
        m = valid.reshape(-1)
        us.append(jnp.where(m, u, 0))
        vs.append(jnp.where(m, v, 0))
        ok.append(m)
    return jnp.concatenate(us), jnp.concatenate(vs), jnp.concatenate(ok)


@partial(jax.jit, static_argnames=("ignore_label", "inner_shape"))
def boundary_pair_values(labels: jnp.ndarray, bmap: jnp.ndarray,
                         ignore_label: bool = True,
                         inner_shape: Optional[Tuple[int, ...]] = None):
    """Pairs plus boundary-map samples for edge-feature accumulation.

    Each owned face contributes TWO samples: the boundary-map value at both
    face voxels (nifty gridRag convention — an edge's statistics pool the
    boundary pixels on both sides).  Returns (u, v, value, valid) with the
    two samples concatenated — a thin expansion of
    :func:`boundary_pair_values_dual`, which owns the face convention.
    """
    u, v, va, vb, ok = boundary_pair_values_dual(
        labels, bmap, ignore_label=ignore_label, inner_shape=inner_shape)
    return (jnp.concatenate([u, u]), jnp.concatenate([v, v]),
            jnp.concatenate([va, vb]), jnp.concatenate([ok, ok]))


def affinity_pair_values(labels: jnp.ndarray, affs: jnp.ndarray,
                         offsets: Sequence[Sequence[int]],
                         ignore_label: bool = True,
                         inner_begin: Optional[Tuple[int, ...]] = None,
                         inner_shape: Optional[Tuple[int, ...]] = None):
    """Pairs + affinity samples for long-range offset channels.

    ``affs`` has shape (n_channels,) + labels.shape; channel c holds the
    affinity between anchor voxel i and voxel i + offsets[c].  One sample per
    valid (in-bounds, differing) pair whose *anchor* lies in the inner window
    ``[inner_begin, inner_begin + inner_shape)`` of the (two-sided-haloed)
    local block — each anchor is owned by exactly one block globally
    (reference: ndist extractBlockFeaturesFromAffinityMaps).
    """
    ndim = labels.ndim
    inner = inner_shape or labels.shape
    begin = inner_begin or (0,) * ndim
    us, vs, vals, ok = [], [], [], []
    for c, off in enumerate(offsets):
        sl_a = []
        sl_b = []
        for o, s in zip(off, labels.shape):
            if o >= 0:
                sl_a.append(slice(0, s - o))
                sl_b.append(slice(o, s))
            else:
                sl_a.append(slice(-o, s))
                sl_b.append(slice(0, s + o))
        a = labels[tuple(sl_a)]
        b = labels[tuple(sl_b)]
        fv = affs[c][tuple(sl_a)]
        valid = a != b
        if ignore_label:
            valid &= (a != 0) & (b != 0)
        for ax2 in range(ndim):
            # anchor position in the local (haloed) frame
            pos = jnp.arange(a.shape[ax2]) + sl_a[ax2].start
            owned = (pos >= begin[ax2]) & (pos < begin[ax2] + inner[ax2])
            shape = [1] * ndim
            shape[ax2] = a.shape[ax2]
            valid &= owned.reshape(shape)
        u = jnp.minimum(a, b).reshape(-1)
        v = jnp.maximum(a, b).reshape(-1)
        m = valid.reshape(-1)
        us.append(jnp.where(m, u, 0))
        vs.append(jnp.where(m, v, 0))
        vals.append(fv.reshape(-1))
        ok.append(m)
    return (jnp.concatenate(us), jnp.concatenate(vs),
            jnp.concatenate(vals), jnp.concatenate(ok))


# ---------------------------------------------------------------------------
# device-side segmented statistics
# ---------------------------------------------------------------------------
#
# The padded (u, v, value, ok) arrays are ~10x the block size; shipping them
# to the host made feature extraction transfer-bound.  Instead the per-edge
# reduction runs ON DEVICE:
# one lexsort groups samples by edge (and by value within an edge, giving
# exact quantiles), a segmented reduce emits fixed-capacity (e_max) compact
# tables, and only e_max x 12 numbers cross the link.


@partial(jax.jit, static_argnames=("cap",))
def compact_valid(ok, arrays, cap: int):
    """Compact the valid samples of several same-layout arrays into ``cap``
    slots: one shared cumsum computes each valid element's target slot,
    then every channel pays one scatter pass (invalid entries go OUT OF
    BOUNDS, ``mode='drop'`` — an in-bounds dump slot would serialize
    millions of colliding writes on TPU).  Entries past ``cap`` are
    counted in the overflow return.

    Each scatter is an O(n) pass (~0.3 s at the fused block's ~40M pair
    elements), so hot paths should MINIMIZE CHANNELS by packing several
    small fields into one int32 (see
    :func:`_edge_stats_hist_packed` — the uint8 flagship path packs
    (u,v) and (byte_a,byte_b) into two channels).  Gather-based
    alternatives were measured and rejected on real blocks: a
    ``searchsorted`` position discovery costs ~3.9 s (26 binary-search
    rounds of random gathers from the 156 MB cumsum) and row-scatter of
    an (n, 4) operand ~2.7 s.

    Returns ``(compacted_list, cok, overflow)`` (slot s holds the s-th
    valid sample; ``cok`` flags the populated slots)."""
    idx = jnp.cumsum(ok.astype(jnp.int32)) - 1
    tgt = jnp.where(ok & (idx < cap), idx, cap + 1)
    n_valid = jnp.sum(ok.astype(jnp.int32))
    cok = jnp.arange(cap, dtype=jnp.int32) < jnp.minimum(n_valid, cap)
    return ([jnp.zeros((cap + 1,), x.dtype).at[tgt].set(
        x, mode="drop")[:cap] for x in arrays],
        cok, jnp.maximum(n_valid - cap, 0))


@partial(jax.jit, static_argnames=("e_max",))
def _edge_stats_device(u, v, values, ok, e_max: int):
    n = u.shape[0]
    big = jnp.int32(2 ** 31 - 1)
    u_s = jnp.where(ok, u, big)
    v_s = jnp.where(ok, v, big)
    order = jnp.lexsort((values, v_s, u_s))
    u_o, v_o = u_s[order], v_s[order]
    x = values[order].astype(jnp.float32)
    valid = u_o != big
    prev_u = jnp.concatenate([jnp.full((1,), -1, u_o.dtype), u_o[:-1]])
    prev_v = jnp.concatenate([jnp.full((1,), -1, v_o.dtype), v_o[:-1]])
    starts = ((u_o != prev_u) | (v_o != prev_v)) & valid
    run_id = jnp.cumsum(starts.astype(jnp.int32)) - 1
    n_runs = run_id[-1] + 1
    # invalid samples and run overflow land in the dump bin e_max
    run_id = jnp.where(valid & (run_id < e_max), run_id, e_max)

    num = e_max + 1
    ones = jnp.where(run_id < e_max, 1.0, 0.0)
    count = jax.ops.segment_sum(
        jnp.where(run_id < e_max, 1, 0), run_id,
        num_segments=num).astype(jnp.float32)
    s1 = jax.ops.segment_sum(x * ones, run_id, num_segments=num)
    mn = jax.ops.segment_min(jnp.where(run_id < e_max, x, jnp.inf), run_id,
                             num_segments=num)
    mx = jax.ops.segment_max(jnp.where(run_id < e_max, x, -jnp.inf), run_id,
                             num_segments=num)
    pos = jnp.arange(n, dtype=jnp.int32)
    start_pos = jax.ops.segment_min(jnp.where(starts, pos, n), run_id,
                                    num_segments=num)
    uv_u = jax.ops.segment_min(jnp.where(run_id < e_max, u_o, big), run_id,
                               num_segments=num)
    uv_v = jax.ops.segment_min(jnp.where(run_id < e_max, v_o, big), run_id,
                               num_segments=num)

    cnt = count[:e_max]
    denom = jnp.maximum(cnt, 1.0)
    mean = s1[:e_max] / denom
    # variance via the centered second pass: the raw sum-of-squares form
    # cancels catastrophically in float32 for low-variance edges
    mean_full = jnp.concatenate([mean, jnp.zeros((1,), mean.dtype)])
    centered = (x - mean_full[run_id]) ** 2
    s2c = jax.ops.segment_sum(centered * ones, run_id, num_segments=num)
    var = jnp.maximum(s2c[:e_max] / denom, 0.0)
    sp = start_pos[:e_max]
    last = jnp.clip(sp + cnt.astype(jnp.int32) - 1, 0, n - 1)
    qs = []
    for q in _QS:
        # keep the base position integral: sp + float(q*(cnt-1)) promotes to
        # float32 and loses whole indices beyond 2**24 samples
        qoff = q * (cnt - 1.0)          # bounded by the run length: f32-safe
        lo_off = jnp.floor(qoff)
        lo = jnp.clip(sp + lo_off.astype(jnp.int32), 0, n - 1)
        hi = jnp.minimum(lo + 1, last)
        frac = qoff - lo_off
        qs.append(x[lo] * (1.0 - frac) + x[hi] * frac)
    feats = jnp.stack(
        [mean, var, mn[:e_max]] + qs + [mx[:e_max], cnt], axis=1)
    uv = jnp.stack([uv_u[:e_max], uv_v[:e_max]], axis=1)
    overflow = jnp.sum(jnp.where((run_id == e_max) & valid, 1, 0))
    return uv, feats, jnp.minimum(n_runs, e_max), overflow


def boundary_pair_values_dual(labels: jnp.ndarray, bmap: jnp.ndarray,
                              ignore_label: bool = True,
                              inner_shape: Optional[Tuple[int, ...]] = None):
    """Like :func:`boundary_pair_values` but each face pair appears ONCE
    with BOTH side samples as separate columns — half the pair-array
    length, so the downstream compaction passes touch half the elements.
    Returns (u, v, value_a, value_b, valid).  This is the CORE extractor:
    the two-sample variant is a thin expansion of it, so the
    face-ownership convention lives in exactly one place."""
    ndim = labels.ndim
    us, vs, va, vb, ok = [], [], [], [], []
    inner = inner_shape or labels.shape
    for axis in range(ndim):
        size = labels.shape[axis] - 1
        if size <= 0:
            continue
        lo_sl, hi_sl = _axis_slices(ndim, axis, size)
        a, b = labels[lo_sl], labels[hi_sl]
        fa, fb = bmap[lo_sl], bmap[hi_sl]
        valid = a != b
        if ignore_label:
            valid &= (a != 0) & (b != 0)
        for ax2 in range(ndim):
            lim = inner[ax2] if ax2 != axis else min(inner[ax2], size)
            if a.shape[ax2] > lim:
                idx = jnp.arange(a.shape[ax2]) < lim
                shape = [1] * ndim
                shape[ax2] = a.shape[ax2]
                valid &= idx.reshape(shape)
        u = jnp.minimum(a, b).reshape(-1)
        v = jnp.maximum(a, b).reshape(-1)
        m = valid.reshape(-1)
        us.append(jnp.where(m, u, 0))
        vs.append(jnp.where(m, v, 0))
        va.append(fa.reshape(-1))
        vb.append(fb.reshape(-1))
        ok.append(m)
    return (jnp.concatenate(us), jnp.concatenate(vs),
            jnp.concatenate(va), jnp.concatenate(vb), jnp.concatenate(ok))


def plane_face_pairs(lab_a: jnp.ndarray, lab_b: jnp.ndarray,
                     valid: Optional[jnp.ndarray] = None,
                     ignore_label: bool = True):
    """Face pairs between two OPPOSING boundary planes of adjacent
    subproblems (blocks or mesh shards): ``lab_a[i]`` and ``lab_b[i]``
    are the labels of the two voxels straddling the face.  This is the
    device-side form of the host face scan in FusedFaceAssembly — the
    mesh-resident program feeds it the ``ppermute``-received neighbor
    plane, so cross-shard edges join the same collective edge-feature
    reduction as interior pairs instead of a host stitching pass.

    Returns flat ``(u, v, ok)`` with u < v for valid entries (the pair
    (i, i+1) belongs to the subproblem owning voxel i — the reference's
    ownership rule; the caller masks out subproblems without a real
    upper neighbor via ``valid``)."""
    ok = lab_a != lab_b
    if ignore_label:
        ok &= (lab_a != 0) & (lab_b != 0)
    if valid is not None:
        ok &= valid
    u = jnp.minimum(lab_a, lab_b).reshape(-1)
    v = jnp.maximum(lab_a, lab_b).reshape(-1)
    m = ok.reshape(-1)
    return jnp.where(m, u, 0), jnp.where(m, v, 0), m


def _hist_finish(hist, u_o, v_o, run_id, valid, n_runs, e_max: int):
    """Shared tail of the histogram edge statistics: exact
    mean/var/min/max and position-interpolated quantiles from per-edge
    256-bin histograms (hist still carries the flat dump bin), plus the
    per-edge (u, v) and overflow accounting.  One implementation for the
    single- and dual-sample front ends — the stats math must stay
    bit-compatible between them."""
    big = jnp.int32(2 ** 31 - 1)
    num = e_max + 1
    hist = hist[:e_max * 256].reshape(e_max, 256).astype(jnp.float32)
    cnt = hist.sum(axis=1)
    denom = jnp.maximum(cnt, 1.0)
    levels = (jnp.arange(256, dtype=jnp.float32) / 255.0)
    mean = (hist @ levels) / denom
    # centered second moment (the raw sum-of-squares form cancels
    # catastrophically in float32 for low-variance edges)
    diff = levels[None, :] - mean[:, None]
    var = jnp.maximum((hist * diff * diff).sum(axis=1) / denom, 0.0)
    has = hist > 0
    first = jnp.argmax(has, axis=1)
    last = 255 - jnp.argmax(has[:, ::-1], axis=1)
    mn = jnp.where(cnt > 0, levels[first], jnp.inf)
    mx = jnp.where(cnt > 0, levels[last], -jnp.inf)
    cum = jnp.cumsum(hist, axis=1)

    def value_at(pos):
        # value of the pos-th (0-based) sample in the edge's sorted
        # multiset: first bin whose cumulative count exceeds pos
        idx = jnp.sum((cum <= pos[:, None]).astype(jnp.int32), axis=1)
        return levels[jnp.clip(idx, 0, 255)]

    qs = []
    for q in _QS:
        qoff = q * (cnt - 1.0)
        lo_off = jnp.floor(qoff)
        frac = qoff - lo_off
        lo_v = value_at(lo_off)
        hi_v = value_at(jnp.minimum(lo_off + 1.0, cnt - 1.0))
        qs.append(lo_v * (1.0 - frac) + hi_v * frac)

    uv_u = jax.ops.segment_min(jnp.where(run_id < e_max, u_o, big), run_id,
                               num_segments=num)
    uv_v = jax.ops.segment_min(jnp.where(run_id < e_max, v_o, big), run_id,
                               num_segments=num)
    feats = jnp.stack([mean, var, mn] + qs + [mx, cnt], axis=1)
    uv = jnp.stack([uv_u[:e_max], uv_v[:e_max]], axis=1)
    overflow = jnp.sum(jnp.where((run_id == e_max) & valid, 1, 0))
    return uv, feats, jnp.minimum(n_runs, e_max), overflow


@partial(jax.jit, static_argnames=("e_max",))
def _edge_stats_hist_dual(u, v, bins_a_u8, bins_b_u8, ok, e_max: int):
    """Histogram edge statistics over DUAL-sample pairs (each compacted
    slot carries the boundary bytes of both face sides): identical
    results to :func:`_edge_stats_hist_device` fed the two-sample
    expansion, at half the grouping-sort length."""
    n = u.shape[0]
    big = jnp.int32(2 ** 31 - 1)
    u_s = jnp.where(ok, u, big)
    v_s = jnp.where(ok, v, big)
    order = jnp.lexsort((v_s, u_s))
    u_o, v_o = u_s[order], v_s[order]
    ba = bins_a_u8[order].astype(jnp.int32)
    bb = bins_b_u8[order].astype(jnp.int32)
    valid = u_o != big
    prev_u = jnp.concatenate([jnp.full((1,), -1, u_o.dtype), u_o[:-1]])
    prev_v = jnp.concatenate([jnp.full((1,), -1, v_o.dtype), v_o[:-1]])
    starts = ((u_o != prev_u) | (v_o != prev_v)) & valid
    run_id = jnp.cumsum(starts.astype(jnp.int32)) - 1
    n_runs = run_id[-1] + 1
    run_id = jnp.where(valid & (run_id < e_max), run_id, e_max)

    ones = jnp.ones((n,), jnp.int32)
    hidx_a = jnp.where(run_id < e_max, run_id * 256 + ba, e_max * 256)
    hidx_b = jnp.where(run_id < e_max, run_id * 256 + bb, e_max * 256)
    hist = (jax.ops.segment_sum(ones, hidx_a,
                                num_segments=e_max * 256 + 1)
            + jax.ops.segment_sum(ones, hidx_b,
                                  num_segments=e_max * 256 + 1))
    return _hist_finish(hist, u_o, v_o, run_id, valid, n_runs, e_max)


@partial(jax.jit, static_argnames=("e_max",))
def _edge_stats_hist_packed(key, vab, ok, e_max: int):
    """Histogram edge statistics over PACKED dual-sample pairs: ``key``
    carries ``u * 32768 + v`` (requires every dense label < 2^15 — the
    caller guards this; any block that dense would overflow ``e_max``
    anyway) and ``vab`` carries ``byte_a * 256 + byte_b``.  Identical
    results to :func:`_edge_stats_hist_dual`, but the compaction upstream
    pays TWO scatter passes instead of four and the grouping sort is a
    single-key sort with one payload operand instead of a two-key
    lexsort — the pair-statistics stage was the hottest piece of the
    fused block program (calibration r5: 1.56 s of the 2.8 s block)."""
    n = key.shape[0]
    big = jnp.int32(2 ** 31 - 1)
    k_s = jnp.where(ok, key, big)
    k_o, vab_o = jax.lax.sort([k_s, vab], num_keys=1)
    valid = k_o != big
    prev = jnp.concatenate([jnp.full((1,), -1, k_o.dtype), k_o[:-1]])
    starts = (k_o != prev) & valid
    run_id = jnp.cumsum(starts.astype(jnp.int32)) - 1
    n_runs = run_id[-1] + 1
    run_id = jnp.where(valid & (run_id < e_max), run_id, e_max)

    ba = vab_o >> 8
    bb = vab_o & 255
    ones = jnp.ones((n,), jnp.int32)
    hidx_a = jnp.where(run_id < e_max, run_id * 256 + ba, e_max * 256)
    hidx_b = jnp.where(run_id < e_max, run_id * 256 + bb, e_max * 256)
    hist = (jax.ops.segment_sum(ones, hidx_a,
                                num_segments=e_max * 256 + 1)
            + jax.ops.segment_sum(ones, hidx_b,
                                  num_segments=e_max * 256 + 1))
    u_o = k_o >> 15
    v_o = k_o & 32767
    return _hist_finish(hist, u_o, v_o, run_id, valid, n_runs, e_max)


@partial(jax.jit, static_argnames=("e_max",))
def _edge_stats_hist_device(u, v, bins_u8, ok, e_max: int):
    """Per-edge statistics via 256-bin histograms — EXACT for uint8
    boundary maps (the reference's CNN-output convention), and ~2x
    cheaper than :func:`_edge_stats_device`: the lexsort drops the value
    key (2-key grouping sort instead of 3-key full sort) and quantiles
    come from per-edge histogram cumsums instead of sorted-position
    gathers, reproducing the same position-interpolation formula
    (``q*(cnt-1)`` with linear interpolation) bit-compatibly for
    discrete values."""
    n = u.shape[0]
    big = jnp.int32(2 ** 31 - 1)
    u_s = jnp.where(ok, u, big)
    v_s = jnp.where(ok, v, big)
    order = jnp.lexsort((v_s, u_s))
    u_o, v_o = u_s[order], v_s[order]
    b = bins_u8[order].astype(jnp.int32)
    valid = u_o != big
    prev_u = jnp.concatenate([jnp.full((1,), -1, u_o.dtype), u_o[:-1]])
    prev_v = jnp.concatenate([jnp.full((1,), -1, v_o.dtype), v_o[:-1]])
    starts = ((u_o != prev_u) | (v_o != prev_v)) & valid
    run_id = jnp.cumsum(starts.astype(jnp.int32)) - 1
    n_runs = run_id[-1] + 1
    run_id = jnp.where(valid & (run_id < e_max), run_id, e_max)

    hidx = jnp.where(run_id < e_max, run_id * 256 + b, e_max * 256)
    hist = jax.ops.segment_sum(jnp.ones((n,), jnp.int32), hidx,
                               num_segments=e_max * 256 + 1)
    return _hist_finish(hist, u_o, v_o, run_id, valid, n_runs, e_max)


def device_edge_stats(u, v, values, ok, e_max: int = 65536):
    """Compact per-edge statistics computed on device.

    Returns (uv [E, 2] int32 dense labels, features [E, 10] float64) with
    E = number of distinct valid edges; raises when the block holds more
    than ``e_max`` edges (raise e_max or shrink blocks).

    Inputs are padded to the next power of two so every (clipped) border
    block shares one compiled program instead of one per shape."""
    return device_edge_stats_finalize(
        device_edge_stats_submit(u, v, values, ok, e_max=e_max), e_max)


def _pad_pow2(arr, n_pad, fill=None):
    n = int(arr.shape[0])
    if n == n_pad:
        return arr
    if fill is None:
        return jnp.pad(arr, (0, n_pad - n))
    return jnp.pad(arr, (0, n_pad - n), constant_values=fill)


def _should_compact(n: int, compact: Optional[bool]) -> bool:
    import os

    if compact is not None:
        return compact
    return (n >= (1 << 20)
            and os.environ.get("CTT_RAG_COMPACT", "1") != "0")


def device_edge_stats_submit(u, v, values, ok, e_max: int = 65536,
                             compact: Optional[bool] = None):
    """Enqueue the edge-stats device program WITHOUT synchronizing: returns
    the device result handles so callers can pipeline several blocks (jax
    async dispatch overlaps block i+1's compute with block i's readback).
    Pass the
    handles to :func:`device_edge_stats_finalize`.

    Large sample arrays (>= 2^20, after the shared power-of-two padding
    that keeps the compile classes bounded) are first COMPACTED to the
    valid entries: the sort then runs on n/4 instead of n.  Semantics are
    identical — the stats sort re-orders everything anyway.  A capacity
    overflow (boundary fraction > 25% of all samples — pathological for
    label volumes) raises at finalize; set ``compact=False`` or
    ``CTT_RAG_COMPACT=0`` for such inputs."""
    return device_edge_stats_submit_multi(
        u, v, ok, [values], e_max=e_max, compact=compact)[0]


def device_edge_stats_submit_multi(u, v, ok, values_list,
                                   e_max: int = 65536,
                                   compact: Optional[bool] = None):
    """Like :func:`device_edge_stats_submit` for SEVERAL value channels
    sharing one (u, v, ok) pair layout (the filter-bank features path):
    the pair padding and compaction targets are computed once and every
    channel only pays its own scatter + sort."""
    n = int(u.shape[0])
    n_pad = 1 << max(int(np.ceil(np.log2(max(n, 1)))), 4)
    u = _pad_pow2(u, n_pad)
    v = _pad_pow2(v, n_pad)
    ok = _pad_pow2(ok, n_pad, fill=False)
    if _should_compact(n_pad, compact):
        cap = max(n_pad // 4, 1 << 14)
        (compacted, cok, overflow) = compact_valid(
            ok, [u, v] + [_pad_pow2(x, n_pad) for x in values_list], cap)
        cu, cv = compacted[0], compacted[1]
        return [("compact",
                 _edge_stats_device(cu, cv, cx, cok, e_max=e_max),
                 overflow, cap)
                for cx in compacted[2:]]
    return [("full",
             _edge_stats_device(u, v, _pad_pow2(x, n_pad), ok, e_max=e_max))
            for x in values_list]


def device_edge_stats_finalize(handles, e_max: int = 65536):
    """Synchronize one submitted edge-stats program and return the compact
    host (uv, features) tables."""
    if handles[0] == "compact":
        _, inner, cap_overflow, cap = handles
        if int(cap_overflow) > 0:
            raise RuntimeError(
                f"boundary samples exceeded the compaction capacity {cap} "
                "(boundary fraction > 25%); pass compact=False or set "
                "CTT_RAG_COMPACT=0 for this volume")
        handles = ("full", inner)
    uv, feats, n_runs, overflow = handles[1]
    if int(overflow) > 0:
        raise RuntimeError(
            f"block has more than e_max={e_max} distinct edges; "
            "increase e_max or use smaller blocks")
    n = int(n_runs)
    return (np.asarray(uv)[:n].astype("int64"),
            np.asarray(feats)[:n].astype("float64"))


def device_unique_edges(u, v, ok, e_max: int = 65536) -> np.ndarray:
    """Compact unique (u, v) edge list computed on device (the RAG
    extraction reduction; same sort machinery, no values).

    Synchronous convenience API: blocks on the device result.  Pipelined
    callers should use :func:`device_edge_stats_submit` /
    :func:`device_edge_stats_finalize` instead (as InitialSubGraphs does)
    so consecutive blocks overlap."""
    uv, _ = device_edge_stats(u, v, jnp.zeros_like(u, jnp.float32), ok,
                               e_max=e_max)
    return uv


# ---------------------------------------------------------------------------
# host-side pair extraction (the reference-faithful CPU path: plain numpy
# slicing, compact output — selected by task config ``impl: 'host'``)
# ---------------------------------------------------------------------------


def _host_axis_pairs(labels: np.ndarray, ignore_label: bool,
                     inner_shape) -> List[Tuple[np.ndarray, ...]]:
    ndim = labels.ndim
    inner = inner_shape or labels.shape
    out = []
    for axis in range(ndim):
        size = labels.shape[axis] - 1
        if size <= 0:
            continue
        lo = [slice(None)] * ndim
        hi = [slice(None)] * ndim
        lo[axis] = slice(0, size)
        hi[axis] = slice(1, size + 1)
        a, b = labels[tuple(lo)], labels[tuple(hi)]
        valid = a != b
        if ignore_label:
            valid &= (a != 0) & (b != 0)
        for ax2 in range(ndim):
            lim = inner[ax2] if ax2 != axis else min(inner[ax2], size)
            if a.shape[ax2] > lim:
                sl = [slice(None)] * ndim
                sl[ax2] = slice(lim, None)
                valid[tuple(sl)] = False
        out.append((a, b, valid, tuple(lo)))
    return out


def host_label_pairs(labels: np.ndarray, ignore_label: bool = True,
                     inner_shape=None) -> np.ndarray:
    """Numpy analog of :func:`label_pairs` + dedup: the compact sorted
    (u, v) edge table of the block, computed entirely on host."""
    pairs = []
    for a, b, valid, _ in _host_axis_pairs(labels, ignore_label,
                                           inner_shape):
        av, bv = a[valid], b[valid]
        pairs.append(np.stack([np.minimum(av, bv), np.maximum(av, bv)],
                              axis=1))
    if not pairs:
        return np.zeros((0, 2), "uint64")
    return np.unique(np.concatenate(pairs), axis=0)


def host_boundary_edge_features(labels: np.ndarray, bmap: np.ndarray,
                                ignore_label: bool = True,
                                inner_shape=None
                                ) -> Tuple[np.ndarray, np.ndarray]:
    """Numpy analog of boundary_pair_values + device_edge_stats: per-edge
    (uv, features) tables via :func:`segmented_stats` (two samples per face
    voxel pair, the nifty gridRag convention)."""
    ndim = labels.ndim
    us, vs, xs = [], [], []
    for a, b, valid, lo in _host_axis_pairs(labels, ignore_label,
                                            inner_shape):
        axis = next(d for d in range(ndim) if lo[d] != slice(None))
        hi_sl = list(lo)
        hi_sl[axis] = slice(1, a.shape[axis] + 1)
        av, bv = a[valid], b[valid]
        u, v = np.minimum(av, bv), np.maximum(av, bv)
        for side in (lo, tuple(hi_sl)):
            us.append(u)
            vs.append(v)
            xs.append(bmap[side][valid])
    if not us:
        return np.zeros((0, 2), "int64"), np.zeros((0, N_FEATURES),
                                                   "float64")
    u = np.concatenate(us)
    v = np.concatenate(vs)
    x = np.concatenate(xs).astype("float64")
    uv = np.stack([u, v], axis=1)
    uniq, inv = np.unique(uv, axis=0, return_inverse=True)
    feats = segmented_stats(inv, x, len(uniq))
    return uniq.astype("int64"), feats


# ---------------------------------------------------------------------------
# host-side segmented statistics (fallback / oracle for tests)
# ---------------------------------------------------------------------------

FEATURE_NAMES = ("mean", "variance", "min", "q10", "q25", "q50", "q75", "q90",
                 "max", "count")
N_FEATURES = len(FEATURE_NAMES)
_QS = (0.1, 0.25, 0.5, 0.75, 0.9)


def unique_pairs(u: np.ndarray, v: np.ndarray):
    """Deduplicated ``(u, v)`` rows plus inverse indices.

    Packed-u64-key path (``np.unique`` on a 1-D key array is ~10x the
    ``axis=0`` structured sort at face-table sizes) with a structured
    fallback for ids past 2^32.  ONE home for the idiom — the fused face
    assembly and the server's in-memory tail both merge edge tables
    through it."""
    u = np.asarray(u)
    v = np.asarray(v)
    if len(v) == 0:
        return np.zeros((0, 2), "uint64"), np.zeros((0,), "int64")
    if v.max() < (1 << 32):
        keys = (u.astype("uint64") << np.uint64(32)) | v.astype("uint64")
        ukeys, inv = np.unique(keys, return_inverse=True)
        uniq = np.stack([ukeys >> np.uint64(32),
                         ukeys & np.uint64(0xFFFFFFFF)], axis=1)
    else:
        pairs = np.stack([u.astype("uint64"), v.astype("uint64")], axis=1)
        uniq, inv = np.unique(pairs, axis=0, return_inverse=True)
    return uniq.astype("uint64"), inv


def segmented_stats(edge_index: np.ndarray, values: np.ndarray,
                    n_edges: int) -> np.ndarray:
    """Per-edge [mean, var, min, q10..q90, max, count] over samples.

    Sort-based: one lexsort by (edge, value), then reduceat for moments and
    fractional indexing for exact interpolated quantiles per segment.
    """
    out = np.zeros((n_edges, N_FEATURES), dtype="float64")
    if len(edge_index) == 0:
        return out
    order = np.lexsort((values, edge_index))
    e = edge_index[order]
    x = values[order].astype("float64")
    starts = np.flatnonzero(np.r_[True, e[1:] != e[:-1]])
    seg_ids = e[starts]
    counts = np.diff(np.r_[starts, len(e)])
    sums = np.add.reduceat(x, starts)
    sqs = np.add.reduceat(x * x, starts)
    mean = sums / counts
    var = np.maximum(sqs / counts - mean ** 2, 0.0)
    out[seg_ids, 0] = mean
    out[seg_ids, 1] = var
    out[seg_ids, 2] = x[starts]                      # min (sorted within seg)
    out[seg_ids, 8] = x[starts + counts - 1]         # max
    for qi, q in enumerate(_QS):
        pos = starts + q * (counts - 1)
        lo = np.floor(pos).astype(np.int64)
        hi = np.minimum(lo + 1, starts + counts - 1)
        frac = pos - lo
        out[seg_ids, 3 + qi] = x[lo] * (1 - frac) + x[hi] * frac
    out[seg_ids, 9] = counts
    return out


def merge_feature_blocks(partials: Sequence[Tuple[np.ndarray, np.ndarray]],
                         n_edges: int, n_feats: int = N_FEATURES
                         ) -> np.ndarray:
    """Combine per-block feature rows into global per-edge features.

    ``partials`` = iterable of (edge_ids, features[E_b, n_feats]), where the
    columns are one or more 9-wide stat groups ([mean, variance, min,
    q10, q25, q50, q75, q90, max] — one group per filter response in the
    filter-bank features path) followed by a single shared sample-count
    column.  Mean/variance merge exactly (count-weighted moments); min/max
    elementwise; quantiles merge as count-weighted means — an approximation
    (exact distributed quantiles would need the raw samples; the reference's
    C++ merge makes the same trade, nifty mergeFeatureBlocks).
    """
    n_groups = (n_feats - 1) // 9
    assert n_groups * 9 + 1 == n_feats, n_feats
    cnt = np.zeros(n_edges, "float64")
    s1 = np.zeros((n_edges, n_groups), "float64")    # Σ w·mean
    s2 = np.zeros((n_edges, n_groups), "float64")    # Σ w·(var + mean²)
    mn = np.full((n_edges, n_groups), np.inf)
    mx = np.full((n_edges, n_groups), -np.inf)
    qs = np.zeros((n_edges, n_groups, len(_QS)), "float64")
    for edge_ids, feats in partials:
        # zero-count rows (edges with no samples in this block) must not
        # pollute min/max/moments
        nz = feats[:, -1] > 0
        edge_ids, feats = edge_ids[nz], feats[nz]
        if len(edge_ids) == 0:
            continue
        w = feats[:, -1]
        np.add.at(cnt, edge_ids, w)
        for gi in range(n_groups):
            base = 9 * gi
            np.add.at(s1[:, gi], edge_ids, w * feats[:, base])
            np.add.at(s2[:, gi], edge_ids,
                      w * (feats[:, base + 1] + feats[:, base] ** 2))
            np.minimum.at(mn[:, gi], edge_ids, feats[:, base + 2])
            np.maximum.at(mx[:, gi], edge_ids, feats[:, base + 8])
            for qi in range(len(_QS)):
                np.add.at(qs[:, gi, qi], edge_ids,
                          w * feats[:, base + 3 + qi])
    out = np.zeros((n_edges, n_feats), "float64")
    nz = cnt > 0
    for gi in range(n_groups):
        base = 9 * gi
        out[nz, base] = s1[nz, gi] / cnt[nz]
        out[nz, base + 1] = np.maximum(
            s2[nz, gi] / cnt[nz] - out[nz, base] ** 2, 0.0)
        out[nz, base + 2] = mn[nz, gi]
        out[nz, base + 8] = mx[nz, gi]
        for qi in range(len(_QS)):
            out[nz, base + 3 + qi] = qs[nz, gi, qi] / cnt[nz]
    out[:, -1] = cnt
    return out
