"""Blockwise distance-transform watershed.

Re-specification of the reference's ``watershed/`` package
(watershed/watershed.py): per block (with halo) — read boundary/affinity map,
threshold + Euclidean distance transform, seeds from smoothed-DT maxima,
seeded watershed on a height map mixing boundary evidence and inverted DT,
size filter, per-block label offset, write inner block.  All pixel compute
runs on device (ops/edt.py, ops/filters.py, ops/watershed.py); under
``target='tpu'`` the whole per-block pipeline is one jitted program.

2d variants (``apply_dt_2d`` / ``apply_ws_2d``, for anisotropic EM stacks)
process z-slices via vmap over the z axis — the reference loops slices in
Python (watershed.py:211-230); here it is one batched device call.
"""

from __future__ import annotations

import os
from functools import lru_cache
from typing import Any, Dict, Optional

import numpy as np

from ..core.blocking import Blocking
from ..core.runtime import BlockTask
from ..core.storage import file_reader
from ..core.workflow import Task
from .relabel import RelabelWorkflow


def _normalize_input(data: np.ndarray, cfg) -> np.ndarray:
    """Channel agglomeration + range normalization + optional inversion —
    the single policy shared by every reader (reference:
    watershed.py:267-283 _read_data)."""
    if data.ndim == 4:
        agglo = cfg.get("agglomerate_channels", "mean")
        data = data.max(axis=0) if agglo == "max" else data.mean(axis=0)
    mx = data.max()
    if mx > 1.0:
        data = data / 255.0 if mx <= 255 else data / mx
    if cfg.get("invert_inputs", False):
        data = 1.0 - data
    return data


def as_normalized_float(block: np.ndarray) -> np.ndarray:
    """Raw-path inverse: a uint8 block back to the [0,1] float scale the
    device pipeline uses (shared by every raw-read fallback site)."""
    if block.dtype == np.uint8:
        return block.astype("float32") / 255.0
    return np.asarray(block)


def _channel_slice(ds, cfg):
    cb = cfg.get("channel_begin", 0)
    ce = cfg.get("channel_end", None)
    return slice(cb, ds.shape[0] if ce is None else ce)


def _read_input(ds, bb, cfg) -> np.ndarray:
    """Read + normalize boundary evidence (clipped bounding-box variant)."""
    if ds.ndim == len(bb) + 1:
        data = ds[(_channel_slice(ds, cfg),) + bb].astype("float32")
    else:
        data = ds[bb].astype("float32")
    return _normalize_input(data, cfg)


def reflect_indices(start: int, stop: int, n: int) -> np.ndarray:
    """Volume-level reflection indices for ``range(start, stop)`` over an
    axis of length n: out-of-volume positions fold back as the mirror of
    the WHOLE axis (period 2n-2), so every reader of a block's outer
    window — per-block store reads and resident-volume slicing alike —
    sees identical phantom content (reflecting only the clipped block
    read would make the phantom depend on the block's clip)."""
    idx = np.arange(start, stop)
    if n == 1:
        return np.zeros_like(idx)
    period = 2 * n - 2
    j = np.mod(idx, period)
    return np.where(j < n, j, period - j)


def read_outer_reflect(ds, begin, block_shape, halo) -> np.ndarray:
    """Read ``[begin-halo, begin+block_shape+halo)`` with out-of-volume
    parts filled by volume-level reflection (see reflect_indices)."""
    shape = ds.shape[-len(begin):]
    ridx = [reflect_indices(b - h, b + bs + h, n)
            for b, h, bs, n in zip(begin, halo, block_shape, shape)]
    los = [int(r.min()) for r in ridx]
    his = [int(r.max()) + 1 for r in ridx]
    data = np.asarray(ds[tuple(slice(lo, hi) for lo, hi in zip(los, his))])
    if all(len(r) == hi - lo and (np.diff(r) == 1).all()
           for r, lo, hi in zip(ridx, los, his)):
        return data  # interior block: contiguous read, no gather
    return data[np.ix_(*[r - lo for r, lo in zip(ridx, los)])]


def _read_padded_input(ds, block, cfg, halo, raw: bool = False) -> np.ndarray:
    """Read the block at the uniform outer shape (reflect-padded at volume
    borders), same normalization policy as _read_input.  ``raw=True`` skips
    the host-side float conversion for 3d uint8 stores — the streamed
    device pipeline normalizes on device, so only a quarter of the bytes
    cross the host->device link."""
    from .inference import load_with_halo

    if ds.ndim == len(block.begin) + 1:
        data = load_with_halo(
            ds, block.begin, cfg["block_shape"], halo,
            channel_slice=_channel_slice(ds, cfg)).astype("float32")
    else:
        data = read_outer_reflect(ds, block.begin, cfg["block_shape"], halo)
        # the device pipeline always divides uint8 by 255, so the raw path
        # is only taken when that matches _normalize_input's data-dependent
        # rule (max > 1); degenerate {0,1} blocks go through the host rule
        if raw and data.dtype == np.uint8 and data.max() > 1 \
                and not cfg.get("invert_inputs", False):
            return data
        data = data.astype("float32")
    return _normalize_input(data, cfg)


def suppress_maxima(points: np.ndarray, radii: np.ndarray) -> np.ndarray:
    """Greedy distance-based non-maximum suppression of seed candidates
    (reference: watershed.py:199-203, nifty nonMaximumDistanceSuppression):
    in decreasing distance-transform order, a candidate is dropped when it
    lies inside the dt-radius of an already accepted (stronger) maximum.
    Returns the kept ``(K, ndim)`` integer coordinates."""
    if len(points) == 0:
        return points
    order = np.argsort(-radii)
    pts = points[order].astype("float64")
    rad = radii[order].astype("float64")
    kept = [0]
    for i in range(1, len(pts)):
        kp = pts[kept]
        d2 = ((kp - pts[i]) ** 2).sum(axis=1)
        if not (d2 < rad[kept] ** 2).any():
            kept.append(i)
    return points[order[kept]]


def run_ws_block(data: np.ndarray, cfg: Dict[str, Any],
                 mask: Optional[np.ndarray] = None) -> np.ndarray:
    """The per-block watershed pipeline (reference: _ws_block
    watershed.py:285-341), device compute with host glue."""
    import jax.numpy as jnp

    from ..ops.components import connected_components
    from ..ops.edt import distance_transform_edt
    from ..ops.filters import gaussian, local_maxima
    from ..ops.watershed import (seeded_watershed, seeded_watershed_batched,
                                 size_filter)

    import jax

    threshold = cfg.get("threshold", 0.25)
    sigma_seeds = cfg.get("sigma_seeds", 2.0)
    sigma_weights = cfg.get("sigma_weights", 2.0)
    min_size = cfg.get("size_filter", 25)
    alpha = cfg.get("alpha", 0.8)
    pixel_pitch = cfg.get("pixel_pitch")
    dt_2d = cfg.get("apply_dt_2d", False)
    ws_2d = cfg.get("apply_ws_2d", False)

    x = jnp.asarray(data)
    jmask = None if mask is None else jnp.asarray(mask.astype(bool))

    # distance to boundaries (vigra distanceTransform equivalent)
    fg = x < threshold
    if jmask is not None:
        fg = fg & jmask
    if dt_2d or ws_2d:
        # per-slice 2d EDT via the axes parameter: slices fold into the
        # scanline batch (a vmap here would scramble the Pallas kernel's
        # grid indices — ops/edt.py handles the batching natively)
        dt = distance_transform_edt(fg, axes=(1, 2))
    else:
        sampling = tuple(pixel_pitch) if pixel_pitch else None
        dt = distance_transform_edt(fg, sampling=sampling)

    # height map: boundary evidence blended with inverted DT
    # (reference fit_to_hmap/_make_hmap, utils/volume_utils.py:294-391)
    hmap = gaussian(x, sigma_weights) if sigma_weights else x
    dmax = jnp.maximum(dt.max(), 1e-6)
    height = alpha * hmap + (1.0 - alpha) * (1.0 - dt / dmax)

    if ws_2d:
        # independent watershed per z-slice (reference: watershed.py:211-230
        # loops slices; here one vmapped device program).  Per-slice labels
        # are made unique across slices by a per-slice offset.
        dt_smooth = (jax.vmap(lambda d: gaussian(d, sigma_seeds))(dt)
                     if sigma_seeds else dt)
        maxima = jax.vmap(lambda d, f: local_maxima(d, 2) & f)(dt_smooth, fg)
        # seed clusters are tiny: stencil propagation beats pointer jumping
        seeds = jax.vmap(lambda m: connected_components(
            m, connectivity=2, method="propagation"))(maxima)
        ws = seeded_watershed_batched(height, seeds, jmask, connectivity=1)
        # per-slice offsets in host uint64: device int32 would overflow for
        # n_slices * slice_size >= 2**31 (large in-plane blocks)
        ws = np.array(ws).astype(np.uint64)
        slice_size = np.uint64(np.prod(data.shape[1:]))
        offsets = (np.arange(data.shape[0], dtype=np.uint64)
                   * slice_size)[:, None, None]
        ws = np.where(ws > 0, ws + offsets, 0)
    else:
        # seeds: connected maxima clusters of the smoothed DT (tiny
        # clusters: stencil propagation beats gather-heavy pointer jumping)
        dt_smooth = gaussian(dt, sigma_seeds) if sigma_seeds else dt
        maxima = local_maxima(dt_smooth, radius=2) & fg
        if cfg.get("non_maximum_suppression", False):
            # distance-based suppression of weaker maxima (reference:
            # watershed.py:179-207 nonMaximumDistanceSuppression path).
            # Suppression runs over one representative per connected
            # maxima component (the component's highest-dt voxel), so a
            # plateau contributes a single candidate — same baseline as
            # the plain path — and candidate counts stay small (hundreds
            # per block): a cheap host step between two device programs.
            comp = np.asarray(connected_components(
                maxima, connectivity=len(data.shape),
                method="propagation"))
            pts = np.argwhere(comp > 0)
            if len(pts):
                radii = np.asarray(dt)[tuple(pts.T)]
                cids = comp[tuple(pts.T)]
                order = np.lexsort((-radii, cids))
                first = np.r_[True, np.diff(cids[order]) != 0]
                reps = pts[order[first]]
                kept = suppress_maxima(reps, radii[order[first]])
            else:
                kept = pts
            seeds_np = np.zeros(data.shape, "int32")
            seeds_np[tuple(kept.T)] = np.arange(1, len(kept) + 1)
            seeds = jnp.asarray(seeds_np)
        else:
            seeds = connected_components(maxima,
                                         connectivity=len(data.shape),
                                         method="propagation")
        method = _ws_algorithm(cfg)
        if method == "coarse" and jmask is None and data.ndim == 3:
            # shared watershed core with the fused pipeline
            # (workflows/fused_pipeline._resident_program): identical
            # composition -> identical fragment partitions, and the size
            # filter is integrated in the coarse solve
            from ..ops.watershed import seeded_watershed_coarse

            labels, ok = seeded_watershed_coarse(
                height, seeds, min_size=min_size or 0,
                refine_rounds=int(cfg.get("refine_rounds", 3)),
                factor=int(cfg.get("coarse_factor", 2)))
            if ok:
                return np.array(labels).astype("uint64")
            ws = np.array(seeded_watershed(height, seeds, jmask,
                                           connectivity=1))
        else:
            ws = np.array(seeded_watershed(
                height, seeds, jmask, connectivity=1,
                method=None if method == "coarse" else method))
    if min_size:
        ws = size_filter(ws, np.asarray(height), min_size,
                         mask=None if mask is None else mask.astype(bool),
                         per_slice=ws_2d)
    return ws.astype("uint64")


def run_ws_block_host(data: np.ndarray, cfg: Dict[str, Any],
                      mask: Optional[np.ndarray] = None) -> np.ndarray:
    """Per-block DT watershed on HOST scipy C kernels — the CPU analog of
    the device pipeline, built from the reference's kernel family.

    C implementations stand in one-for-one: scipy distance_transform_edt
    for vigra distanceTransform, gaussian_filter for gaussianSmoothing,
    maximum_filter for localMaxima3D, label for
    labelVolumeWithBackground, and the native C++ bucket-queue priority
    flood for watershedsNew (scipy's own watershed_ift ignores its cost
    image in current scipy and is unusable; reference:
    watershed/watershed.py:139-249).  Selected by task config
    ``impl: 'host'`` — the measured stand-in for the reference's
    ``target='local'`` per-block compute in the benchmark baseline
    (vigra/nifty are not installable here), and a working CPU fallback
    for machines without an accelerator.

    Composition notes (kept IDENTICAL to this framework's device
    pipeline so the bench's device<->CPU quality delta isolates the
    watershed implementation, at the cost of three deviations from the
    reference's defaults): the boundary map is smoothed BEFORE blending
    with the inverted DT (the reference's _make_hmap smooths the blended
    map, watershed.py:163-170), seed maxima use a 5x5x5 window (vigra
    localMaxima3D is 3x3x3), and DT/WS run in 3d (the reference defaults
    apply_dt_2d/apply_ws_2d to true for anisotropic stacks)."""
    from scipy import ndimage

    from ..native import seeded_watershed_u8

    threshold = cfg.get("threshold", 0.25)
    sigma_seeds = cfg.get("sigma_seeds", 2.0)
    sigma_weights = cfg.get("sigma_weights", 2.0)
    min_size = cfg.get("size_filter", 25)
    alpha = cfg.get("alpha", 0.8)
    pitch = cfg.get("pixel_pitch")

    fg = data < threshold
    if mask is not None:
        fg &= mask
    dt = ndimage.distance_transform_edt(fg, sampling=pitch).astype("float32")
    hmap = (ndimage.gaussian_filter(data, sigma_weights)
            if sigma_weights else data)
    height = alpha * hmap + (1.0 - alpha) * (1.0 - dt / max(dt.max(), 1e-6))
    dts = ndimage.gaussian_filter(dt, sigma_seeds) if sigma_seeds else dt
    maxima = (dts >= ndimage.maximum_filter(dts, size=5)) & fg
    seeds, _ = ndimage.label(maxima, structure=np.ones((3,) * data.ndim,
                                                       bool))
    hq = np.clip((height - height.min())
                 / max(float(height.max() - height.min()), 1e-6) * 255,
                 0, 255).astype("uint8")
    markers = seeds.astype("int64")
    if mask is not None:
        markers[~mask] = -1  # barrier: the flood never enters the mask
    ws = seeded_watershed_u8(hq, markers)
    if min_size:
        ids, counts = np.unique(ws[ws > 0], return_counts=True)
        small = set(ids[counts < min_size].tolist())
        if small:
            kept = np.where(np.isin(ws, list(small)), 0, ws)
            ws = seeded_watershed_u8(hq, kept)
    ws[ws < 0] = 0
    return ws.astype("uint64")


def iter_ws_blocks_stream(blocks, cfg: Dict[str, Any]):
    """Process a stream of 3d blocks through ONE fused jitted watershed
    pipeline with async dispatch, yielding results in input order: block
    i+1's host->device transfer and compute overlap block i's device->host
    readback (jax's async dispatch queues everything; only the final np
    conversions synchronize).  This is the deployment pattern of the
    blockwise tasks (the inference task's IO/compute overlap, SURVEY §3.4)
    — per-block latency is hidden, the metric is stream throughput.

    3d path only: 2d modes, masks, NMS and pixel_pitch need run_ws_block."""
    import jax.numpy as jnp

    unsupported = [k for k in ("apply_dt_2d", "apply_ws_2d", "pixel_pitch",
                               "non_maximum_suppression") if cfg.get(k)]
    if unsupported:
        raise ValueError(
            f"iter_ws_blocks_stream supports the plain 3d pipeline only; "
            f"{unsupported} need run_ws_block")
    import jax

    from ..core.runtime import stream_window
    from ..ops.watershed import size_filter

    min_size = int(cfg.get("size_filter", 25) or 0)
    # the fused on-device size filter (bincount + regrow in the jitted
    # program) avoids the height/label host round-trip that dominates on
    # accelerators, but its full-length bincount and second flood are a
    # net loss on the CPU backend — there the host size filter is faster.
    # cfg["fuse_size_filter"] overrides the backend default (tests force
    # both paths on the CPU mesh).
    algo = _ws_algorithm(cfg)
    fuse_filter = cfg.get("fuse_size_filter")
    if fuse_filter is None:
        fuse_filter = jax.default_backend() != "cpu"
    if algo == "coarse":
        fuse_filter = True  # integrated in the coarse solve
    pipeline = _ws_pipeline_3d(
        float(cfg.get("threshold", 0.25)),
        float(cfg.get("sigma_seeds", 2.0)),
        float(cfg.get("sigma_weights", 2.0)),
        float(cfg.get("alpha", 0.8)),
        min_size if fuse_filter else 0,
        return_height=not fuse_filter and bool(min_size),
        ws_method=algo, refine_rounds=int(cfg.get("refine_rounds", 3)),
        coarse_factor=int(cfg.get("coarse_factor", 2)))

    def submit(b):
        return b, pipeline(jnp.asarray(b))

    def _fallback(b):
        # capacity overflow (pathological height field): redo this block
        # through the always-correct per-block path — forcing the
        # exact-capacity basins algorithm (re-running the coarse solve
        # that just overflowed would waste a full device pass)
        return run_ws_block(as_normalized_float(b),
                            {**cfg, "ws_algorithm": "basins"})

    def drain(entry):
        b, handles = entry
        if fuse_filter or not min_size:
            ws, ok = handles
            if not bool(ok):
                return _fallback(b)
            return np.asarray(ws).astype("uint64")
        ws, height, ok = handles
        if not bool(ok):
            return _fallback(b)
        return size_filter(np.asarray(ws), np.asarray(height),
                           min_size).astype("uint64")

    # bounded look-ahead: dispatch a few blocks ahead, drain as results are
    # consumed — unbounded queueing would hold every output buffer in HBM
    # (~150 MB per reference-size block)
    yield from stream_window(
        blocks,
        submit,                                      # queued async
        drain,
        window=int(cfg.get("stream_window", 3)))


def run_ws_blocks_stream(blocks, cfg: Dict[str, Any]):
    """List-returning wrapper over :func:`iter_ws_blocks_stream`."""
    return list(iter_ws_blocks_stream(blocks, cfg))


@lru_cache(maxsize=8)
def _ws_pipeline_3d(threshold: float, sigma_seeds: float,
                    sigma_weights: float, alpha: float, min_size: int = 0,
                    return_height: bool = False, ws_method: str = "basins",
                    refine_rounds: int = 3, coarse_factor: int = 2):
    """Cached fused jitted pipeline — one compile per parameter set (the
    jit cache lives on the returned function, so re-creating the closure per
    call would recompile every time).  With ``min_size`` the size filter is
    fused in: per-label device bincount + one regrow pass over the same
    height map — no height/label round-trip to the host."""
    import jax
    import jax.numpy as jnp

    from ..ops.components import connected_components
    from ..ops.edt import distance_transform_edt
    from ..ops.filters import gaussian, local_maxima
    from ..ops.watershed import seeded_watershed

    @jax.jit
    def pipeline(x):
        if x.dtype == jnp.uint8:
            # device-side normalization of quantized boundary maps (the
            # host read path ships the raw bytes: 4x less link traffic)
            x = x.astype(jnp.float32) * (1.0 / 255.0)
        fg = x < threshold
        dt = distance_transform_edt(fg)
        hmap = gaussian(x, sigma_weights) if sigma_weights else x
        height = alpha * hmap + (1.0 - alpha) * (
            1.0 - dt / jnp.maximum(dt.max(), 1e-6))
        dt_smooth = gaussian(dt, sigma_seeds) if sigma_seeds else dt
        maxima = local_maxima(dt_smooth, radius=2) & fg
        seeds = connected_components(maxima, connectivity=3,
                                     method="propagation")
        if ws_method == "coarse":
            # shared watershed core with the fused pipeline
            # (workflows/fused_pipeline._resident_program) — identical
            # composition, size filter integrated
            from ..ops.watershed import _coarse_impl

            ws, ok = _coarse_impl(height, seeds, min_size, refine_rounds,
                                  coarse_factor)
        elif ws_method == "basins":
            # the basin formulation fuses the size filter: small fragments
            # are stripped and re-merged in ~2 extra cheap rounds instead
            # of a full second watershed pass.  Tight capacities for speed;
            # the ok flag is surfaced so the streaming drain can redo an
            # overflowing block through the always-correct path
            from ..ops.watershed import _basins_impl

            n = int(np.prod(fg.shape))
            ws, ok = _basins_impl(height, seeds, None, 1, 64, min_size,
                                  max(n // 64, 1024), max(n // 8, 4096))
        else:
            ok = jnp.bool_(True)
            ws = seeded_watershed(height, seeds, None, connectivity=1,
                                  method=ws_method)
            if min_size:
                # label ids are bounded by the voxel count (CC roots + 1),
                # so a fixed-length bincount stays shape-static under jit
                counts = jnp.bincount(ws.ravel().astype(jnp.int32),
                                      length=int(np.prod(x.shape)) + 1)
                small = counts < min_size
                small = small.at[0].set(False)
                kept = jnp.where(small[ws], 0, ws)
                ws = seeded_watershed(height, kept, None, connectivity=1,
                                      method=ws_method)
        if return_height:  # for a host-side size filter downstream
            return ws, height, ok
        return ws, ok

    return pipeline


def _ws_algorithm(cfg) -> str:
    """Resolve the watershed ALGORITHM ('coarse'/'basins'/'flood') from
    task config or the CTT_WS_METHOD env; distinct from the fused task's
    execution-strategy ws_method (device/hybrid/legacy), whose values
    fall through to the default."""
    m = (cfg.get("ws_algorithm") or cfg.get("ws_method")
         or os.environ.get("CTT_WS_METHOD", "coarse"))
    return m if m in ("coarse", "basins", "flood") else "coarse"


def run_ws_block_seeded(data: np.ndarray, cfg: Dict[str, Any],
                        initial_seeds: np.ndarray, label_offset: int,
                        mask: Optional[np.ndarray] = None) -> np.ndarray:
    """Watershed continuing existing labels: ``initial_seeds`` (uint64,
    0 = free) keep their ids; new seeds from DT maxima in unlabeled areas get
    ids offset by ``label_offset`` (reference: two_pass_watershed.py:210-255
    ``_ws_pass2`` / ``_apply_watershed_with_seeds``).  3d only — the 2d
    variants cannot propagate seeds across slices."""
    import jax.numpy as jnp

    from ..ops.components import connected_components
    from ..ops.edt import distance_transform_edt
    from ..ops.filters import gaussian, local_maxima
    from ..ops.rag import densify_labels
    from ..ops.watershed import seeded_watershed

    if cfg.get("apply_dt_2d") or cfg.get("apply_ws_2d"):
        raise ValueError(
            "two-pass watershed supports 3d only: per-slice 2d watershed "
            "cannot continue seeds across slices — disable apply_dt_2d/"
            "apply_ws_2d or use the single-pass task")
    threshold = cfg.get("threshold", 0.25)
    sigma_seeds = cfg.get("sigma_seeds", 2.0)
    sigma_weights = cfg.get("sigma_weights", 2.0)
    alpha = cfg.get("alpha", 0.8)
    pixel_pitch = cfg.get("pixel_pitch")

    x = jnp.asarray(data)
    jmask = None if mask is None else jnp.asarray(mask.astype(bool))
    fg = x < threshold
    if jmask is not None:
        fg = fg & jmask
    sampling = tuple(pixel_pitch) if pixel_pitch else None
    dt = distance_transform_edt(fg, sampling=sampling)
    hmap = gaussian(x, sigma_weights) if sigma_weights else x
    dmax = jnp.maximum(dt.max(), 1e-6)
    height = alpha * hmap + (1.0 - alpha) * (1.0 - dt / dmax)

    # densify initial seeds to 1..k for the device program (lut[0] == 0)
    lut, dense_init = densify_labels(initial_seeds)
    k = len(lut) - 1

    seeded_area = jnp.asarray(initial_seeds > 0)
    dt_smooth = gaussian(dt, sigma_seeds) if sigma_seeds else dt
    maxima = local_maxima(dt_smooth, radius=2) & fg & ~seeded_area
    new_cc = connected_components(maxima, connectivity=data.ndim,
                                  method="propagation")
    combined = jnp.where(jnp.asarray(dense_init) > 0, jnp.asarray(dense_init),
                         jnp.where(new_cc > 0, new_cc + k, 0))
    ws = np.asarray(seeded_watershed(height, combined, jmask, connectivity=1))

    # map back: 1..k -> original seed ids; >k -> compacted + offset
    out = np.zeros(ws.shape, dtype="uint64")
    init_part = (ws >= 1) & (ws <= k)
    if k:
        out[init_part] = lut[ws[init_part]]
    new_part = ws > k
    if new_part.any():
        new_ids = np.unique(ws[new_part])
        if cfg.get("id_budget") and len(new_ids) >= cfg["id_budget"]:
            raise RuntimeError(
                f"{len(new_ids)} new seeds exceed the per-block id budget "
                f"{cfg['id_budget']} — labels would collide across blocks")
        out[new_part] = (np.searchsorted(new_ids, ws[new_part])
                         .astype("uint64") + np.uint64(label_offset) + 1)

    # size-filter NEW fragments only (continued seeds are protected — they
    # are partial views of segments that extend beyond this block), then
    # regrow the survivors: keeps pass-1/pass-2 fragment statistics aligned
    # (run_ws_block applies the same filter to all fragments)
    min_size = cfg.get("size_filter", 0)
    if min_size and new_part.any():
        ids, sizes = np.unique(out[new_part], return_counts=True)
        small = ids[sizes < min_size]
        if len(small):
            drop = np.isin(out, small)
            out[drop] = 0
            lut2, dense2 = densify_labels(out)
            regrown = np.asarray(seeded_watershed(
                height, jnp.asarray(dense2), jmask, connectivity=1))
            out = lut2[regrown]
    return out


class WatershedTask(BlockTask):
    """Blockwise DT watershed (reference: WatershedBase, watershed.py:34-110).

    Labels are made globally unique by offsetting with
    ``block_id * prod(block_shape)`` (reference: watershed.py:307); chain
    RelabelWorkflow (or use WatershedWorkflow) to compact them.

    ``pass_id``/``seeded`` implement the checkerboard two-pass variant
    (reference: two_pass_watershed.py:60-94): color-0 blocks run the plain
    pipeline; color-1 blocks read the pass-1 labels visible in their halo and
    continue them as seeds — block boundaries between the two colors need no
    stitching.
    """

    task_name = "watershed"
    #: None = all blocks (single pass); 0/1 = checkerboard color
    pass_id: Optional[int] = None
    seeded: bool = False

    def __init__(self, input_path: str, input_key: str, output_path: str,
                 output_key: str, mask_path: str = "", mask_key: str = "", **kw):
        self.input_path = input_path
        self.input_key = input_key
        self.output_path = output_path
        self.output_key = output_key
        self.mask_path = mask_path
        self.mask_key = mask_key
        super().__init__(**kw)

    @staticmethod
    def default_task_config():
        conf = BlockTask.default_task_config()
        conf.update({
            "threshold": 0.25, "apply_dt_2d": False, "apply_ws_2d": False,
            "sigma_seeds": 2.0, "sigma_weights": 2.0, "size_filter": 25,
            "alpha": 0.8, "halo": [4, 32, 32], "pixel_pitch": None,
            "non_maximum_suppression": False,
            "invert_inputs": False, "agglomerate_channels": "mean",
            "channel_begin": 0, "channel_end": None,
        })
        return conf

    def run_impl(self):
        with file_reader(self.input_path, "r") as f:
            in_shape = f[self.input_key].shape
        shape = list(in_shape[1:] if len(in_shape) == 4 else in_shape)
        block_shape = self.global_block_shape()[-len(shape):]
        with file_reader(self.output_path) as f:
            f.require_dataset(self.output_key, shape=shape, chunks=block_shape,
                              dtype="uint64")
        block_list = self.blocks_in_volume(shape, block_shape)
        if self.pass_id is not None:
            colors = Blocking(shape, block_shape).checkerboard()
            allowed = set(block_list)
            block_list = [b for b in colors[self.pass_id] if b in allowed]
        self.run_jobs(block_list, {
            "input_path": self.input_path, "input_key": self.input_key,
            "output_path": self.output_path, "output_key": self.output_key,
            "mask_path": self.mask_path, "mask_key": self.mask_key,
            "shape": shape, "block_shape": block_shape,
            "seeded": self.seeded,
        }, n_jobs=self.max_jobs)

    @classmethod
    def process_job(cls, job_id: int, job_config: Dict[str, Any], log_fn):
        cfg = job_config["config"]
        blocking = Blocking(cfg["shape"], cfg["block_shape"])
        halo = cfg.get("halo") or [0] * blocking.ndim
        halo = halo[-blocking.ndim:]
        f_in = file_reader(cfg["input_path"], "r")
        f_out = file_reader(cfg["output_path"])
        ds_in, ds_out = f_in[cfg["input_key"]], f_out[cfg["output_key"]]
        mask = None
        if cfg.get("mask_path"):
            from ..core.volume_views import load_mask

            mask = load_mask(cfg["mask_path"], cfg["mask_key"], cfg["shape"])

        label_offset_unit = np.uint64(np.prod(cfg["block_shape"]))
        seeded = cfg.get("seeded", False)
        # blocks are loaded at the UNIFORM outer shape (volume borders
        # reflect-padded, like the inference task): every block shares one
        # compiled device program instead of one per clipped border shape
        from .inference import load_with_halo

        outer_shape = tuple(b + 2 * h
                            for b, h in zip(cfg["block_shape"], halo))

        def _write_result(block_id: int, ws: np.ndarray) -> None:
            block = blocking.get_block(block_id)
            inner_sl = tuple(slice(h, h + (b.stop - b.start))
                             for h, b in zip(halo, block.bb))
            inner = ws[inner_sl]
            # compact to 1..k (k <= inner voxel count < offset unit), THEN
            # offset for global uniqueness (reference: watershed.py:307) —
            # uncompacted CC root indices range over the larger outer block
            # and would collide across blocks
            nonzero = np.unique(inner[inner > 0])
            compact = np.searchsorted(nonzero, inner).astype("uint64") + 1
            compact[inner == 0] = 0
            compact = np.where(
                compact > 0,
                compact + np.uint64(block_id) * label_offset_unit, 0)
            ds_out[block.bb] = compact
            log_fn(f"processed block {block_id}")

        # plain 3d path: stream every block of the job through one fused
        # jitted pipeline with async dispatch — transfers and compute of
        # consecutive blocks overlap, hiding per-block device latency
        streamable = (not seeded and mask is None
                      and cfg.get("impl") != "host"
                      and not cfg.get("apply_dt_2d")
                      and not cfg.get("apply_ws_2d")
                      and not cfg.get("pixel_pitch")
                      and not cfg.get("non_maximum_suppression"))
        if streamable and job_config.get("target") == "mesh":
            # SPMD rounds over the device mesh: one block per device, the
            # SAME fused pipeline vmapped — results are bit-identical to
            # the inline streaming path (tests/test_mesh_exec.py)
            import jax
            import jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..core.runtime import prefetch_iter
            from ..ops.watershed import size_filter
            from ..parallel.mesh import blocks_mesh

            n_dev = len(jax.devices())
            mesh = blocks_mesh(n_dev)
            sharding = NamedSharding(mesh, P("blocks"))
            min_size = int(cfg.get("size_filter", 25) or 0)
            algo = _ws_algorithm(cfg)
            fuse_filter = cfg.get("fuse_size_filter")
            if fuse_filter is None:
                fuse_filter = jax.default_backend() != "cpu"
            if algo == "coarse":
                fuse_filter = True  # integrated in the coarse solve
            pipeline = _ws_pipeline_3d(
                float(cfg.get("threshold", 0.25)),
                float(cfg.get("sigma_seeds", 2.0)),
                float(cfg.get("sigma_weights", 2.0)),
                float(cfg.get("alpha", 0.8)),
                min_size if fuse_filter else 0,
                return_height=not fuse_filter and bool(min_size),
                ws_method=algo,
                refine_rounds=int(cfg.get("refine_rounds", 3)),
                coarse_factor=int(cfg.get("coarse_factor", 2)))
            batched = jax.jit(jax.vmap(pipeline))

            block_ids = list(job_config["block_list"])
            reads = prefetch_iter(
                block_ids,
                lambda bid: _read_padded_input(
                    ds_in, blocking.get_block(bid), cfg, halo, raw=True))
            pending_ids: List[int] = []
            pending: List[np.ndarray] = []

            def _flush():
                if not pending:
                    return
                if len({b.dtype for b in pending}) > 1:
                    # a degenerate block came back float (host-normalized);
                    # normalize the uint8 ones so the round is uniform
                    pending[:] = [as_normalized_float(b)
                                  for b in pending]
                batch = np.stack(
                    pending + [pending[-1]] * (n_dev - len(pending)))
                dev = jax.device_put(jnp.asarray(batch), sharding)
                out = batched(dev)
                if fuse_filter or not min_size:
                    ws_all, oks = out
                    heights = None
                else:
                    ws_all, heights, oks = out
                    heights = np.asarray(heights)
                ws_all = np.asarray(ws_all)
                oks = np.asarray(oks)
                for k, bid in enumerate(pending_ids):
                    if not oks[k]:
                        # capacity overflow: always-correct per-block redo
                        # (basins forced: the coarse solve just overflowed)
                        ws = run_ws_block(as_normalized_float(pending[k]),
                                          {**cfg, "ws_algorithm": "basins"})
                    else:
                        ws = ws_all[k]
                        if heights is not None:
                            ws = size_filter(ws, heights[k], min_size)
                    _write_result(bid, ws.astype("uint64"))
                    log_fn(f"processed block {bid}")
                pending.clear()
                pending_ids.clear()

            for bid, data in zip(block_ids, reads):
                pending_ids.append(bid)
                pending.append(data)
                if len(pending) == n_dev:
                    _flush()
            _flush()
            return

        if streamable:
            from ..core.runtime import prefetch_iter

            block_ids = list(job_config["block_list"])
            # threaded read look-ahead: block i+2's store read overlaps
            # block i's device compute and block i-1's write
            reads = prefetch_iter(
                block_ids,
                lambda bid: _read_padded_input(
                    ds_in, blocking.get_block(bid), cfg, halo, raw=True))
            for bid, ws in zip(block_ids,
                               iter_ws_blocks_stream(reads, cfg)):
                _write_result(bid, ws)
            return

        for block_id in job_config["block_list"]:
            block = blocking.get_block(block_id)
            bh = blocking.get_block_with_halo(block_id, halo)
            data = _read_padded_input(ds_in, block, cfg, halo)
            bmask = None
            if mask is not None:
                m = np.asarray(mask[bh.outer.bb]) > 0
                if not m.any():
                    log_fn(f"processed block {block_id}")
                    continue
                # edge-replicate onto the uniform frame (same geometry the
                # reflect-padded data read uses)
                lo_pad = [h - (b - o.start)
                          for h, b, o in zip(halo, block.begin, bh.outer.bb)]
                hi_pad = [os_ - lp - (o.stop - o.start)
                          for os_, lp, o in zip(outer_shape, lo_pad,
                                                bh.outer.bb)]
                bmask = np.pad(m, list(zip(lo_pad, hi_pad)), mode="edge")
            # actual (clipped) inner extent within the uniform frame
            inner_sl = tuple(slice(h, h + (b.stop - b.start))
                             for h, b in zip(halo, block.bb))
            if seeded:
                # pass-2: labels already written by the other checkerboard
                # color act as seeds; same-color owners (possibly being
                # written concurrently) are masked out so the result is
                # order-independent.  Seeds pad with 0 (reflecting would
                # duplicate label ids).
                seeds = load_with_halo(ds_out, block.begin,
                                       cfg["block_shape"], halo,
                                       padding_mode="constant")
                own_color = sum(blocking.block_grid_position(block_id)) % 2
                grids = np.meshgrid(
                    *[(np.arange(b - h, b - h + o)) // bs
                      for b, h, o, bs in zip(block.begin, halo, outer_shape,
                                             cfg["block_shape"])],
                    indexing="ij")
                seeds[sum(grids) % 2 == own_color] = 0
                ws = run_ws_block_seeded(
                    data, {**cfg, "id_budget": int(label_offset_unit)}, seeds,
                    int(np.uint64(block_id) * label_offset_unit), bmask)
                ds_out[block.bb] = ws[inner_sl]
                log_fn(f"processed block {block_id}")
                continue
            if cfg.get("impl") == "host":
                ws = run_ws_block_host(data, cfg, bmask)
            else:
                ws = run_ws_block(data, cfg, bmask)
            _write_result(block_id, ws)


class WatershedPass1Task(WatershedTask):
    """Checkerboard color-0 blocks, plain pipeline (two_pass_watershed pass 0)."""

    task_name = "watershed_pass1"
    pass_id = 0


class WatershedPass2Task(WatershedTask):
    """Checkerboard color-1 blocks, seeded by the pass-1 labels in the halo
    (reference: two_pass_watershed.py:210-255)."""

    task_name = "watershed_pass2"
    pass_id = 1
    seeded = True


class WatershedFromSeedsTask(BlockTask):
    """Blockwise seeded watershed from a precomputed seed volume (reference:
    watershed_from_seeds.py:25 — grow given seeds over the boundary map; no
    new seeds, no offsets: seed ids are already globally consistent)."""

    task_name = "watershed_from_seeds"

    def __init__(self, input_path: str, input_key: str, seeds_path: str,
                 seeds_key: str, output_path: str, output_key: str,
                 mask_path: str = "", mask_key: str = "", **kw):
        self.input_path = input_path
        self.input_key = input_key
        self.seeds_path = seeds_path
        self.seeds_key = seeds_key
        self.output_path = output_path
        self.output_key = output_key
        self.mask_path = mask_path
        self.mask_key = mask_key
        super().__init__(**kw)

    @staticmethod
    def default_task_config():
        conf = BlockTask.default_task_config()
        conf.update({"halo": [2, 16, 16], "sigma_weights": 2.0,
                     "invert_inputs": False, "agglomerate_channels": "mean",
                     "channel_begin": 0, "channel_end": None})
        return conf

    def run_impl(self):
        with file_reader(self.input_path, "r") as f:
            in_shape = f[self.input_key].shape
        shape = list(in_shape[1:] if len(in_shape) == 4 else in_shape)
        block_shape = self.global_block_shape()[-len(shape):]
        with file_reader(self.output_path) as f:
            f.require_dataset(self.output_key, shape=shape, chunks=block_shape,
                              dtype="uint64")
        block_list = self.blocks_in_volume(shape, block_shape)
        self.run_jobs(block_list, {
            "input_path": self.input_path, "input_key": self.input_key,
            "seeds_path": self.seeds_path, "seeds_key": self.seeds_key,
            "output_path": self.output_path, "output_key": self.output_key,
            "mask_path": self.mask_path, "mask_key": self.mask_key,
            "shape": shape, "block_shape": block_shape,
        }, n_jobs=self.max_jobs)

    @classmethod
    def process_job(cls, job_id: int, job_config: Dict[str, Any], log_fn):
        import jax.numpy as jnp

        from ..ops.filters import gaussian
        from ..ops.watershed import seeded_watershed

        cfg = job_config["config"]
        blocking = Blocking(cfg["shape"], cfg["block_shape"])
        halo = (cfg.get("halo") or [0] * blocking.ndim)[-blocking.ndim:]
        f_in = file_reader(cfg["input_path"], "r")
        f_seeds = file_reader(cfg["seeds_path"], "r")
        f_out = file_reader(cfg["output_path"])
        ds_in = f_in[cfg["input_key"]]
        ds_seeds = f_seeds[cfg["seeds_key"]]
        ds_out = f_out[cfg["output_key"]]
        mask = None
        if cfg.get("mask_path"):
            from ..core.volume_views import load_mask

            mask = load_mask(cfg["mask_path"], cfg["mask_key"], cfg["shape"])

        sigma = cfg.get("sigma_weights", 2.0)
        for block_id in job_config["block_list"]:
            bh = blocking.get_block_with_halo(block_id, halo)
            data = _read_input(ds_in, bh.outer.bb, cfg)
            bmask = None
            if mask is not None:
                bmask = np.asarray(mask[bh.outer.bb]) > 0
                if not bmask.any():
                    log_fn(f"processed block {block_id}")
                    continue
            seeds = np.asarray(ds_seeds[bh.outer.bb])
            # densify (seed ids are arbitrary uint64; device wants int32)
            from ..ops.rag import densify_labels

            lut, dense = densify_labels(seeds)
            if len(lut) == 1:  # only the reserved 0 entry: no seeds here
                log_fn(f"processed block {block_id}")
                continue
            height = gaussian(jnp.asarray(data), sigma) if sigma else \
                jnp.asarray(data)
            ws = np.asarray(seeded_watershed(
                height, jnp.asarray(dense),
                None if bmask is None else jnp.asarray(bmask),
                connectivity=1))
            out = lut[ws]
            ds_out[bh.inner.bb] = out[bh.inner_local.bb]
            log_fn(f"processed block {block_id}")


class AgglomerateTask(BlockTask):
    """Block-local RAG agglomeration of watershed fragments (reference:
    watershed/agglomerate.py:129+ — gridRag + accumulateEdgeMeanAndLength +
    mala/edge-weighted agglo policy + projectScalarNodeDataToPixels).

    TPU split: edge extraction + per-edge mean boundary evidence run on
    device (ops/rag), the priority-queue agglomeration in first-party C++
    (native.agglomerative_clustering).  Fragment ids are re-offset per block
    (the workflow relabels afterwards, as in the reference)."""

    task_name = "agglomerate"

    def __init__(self, input_path: str, input_key: str, labels_path: str,
                 labels_key: str, output_path: str, output_key: str, **kw):
        self.input_path = input_path
        self.input_key = input_key
        self.labels_path = labels_path
        self.labels_key = labels_key
        self.output_path = output_path
        self.output_key = output_key
        super().__init__(**kw)

    @staticmethod
    def default_task_config():
        conf = BlockTask.default_task_config()
        conf.update({"threshold": 0.9, "size_regularizer": 0.5,
                     "invert_inputs": False, "agglomerate_channels": "mean",
                     "channel_begin": 0, "channel_end": None})
        return conf

    def run_impl(self):
        with file_reader(self.labels_path, "r") as f:
            shape = list(f[self.labels_key].shape)
        block_shape = self.global_block_shape()[-len(shape):]
        with file_reader(self.output_path) as f:
            f.require_dataset(self.output_key, shape=shape, chunks=block_shape,
                              dtype="uint64")
        block_list = self.blocks_in_volume(shape, block_shape)
        self.run_jobs(block_list, {
            "input_path": self.input_path, "input_key": self.input_key,
            "labels_path": self.labels_path, "labels_key": self.labels_key,
            "output_path": self.output_path, "output_key": self.output_key,
            "shape": shape, "block_shape": block_shape,
        }, n_jobs=self.max_jobs)

    @classmethod
    def process_job(cls, job_id: int, job_config: Dict[str, Any], log_fn):
        import jax.numpy as jnp

        from .. import native
        from ..ops.rag import boundary_pair_values, densify_labels

        cfg = job_config["config"]
        blocking = Blocking(cfg["shape"], cfg["block_shape"])
        f_in = file_reader(cfg["input_path"], "r")
        f_lab = file_reader(cfg["labels_path"], "r")
        f_out = file_reader(cfg["output_path"])
        ds_in = f_in[cfg["input_key"]]
        ds_lab = f_lab[cfg["labels_key"]]
        ds_out = f_out[cfg["output_key"]]
        threshold = cfg.get("threshold", 0.9)
        size_reg = cfg.get("size_regularizer", 0.5)
        offset_unit = np.uint64(np.prod(cfg["block_shape"]))

        for block_id in job_config["block_list"]:
            block = blocking.get_block(block_id)
            labels = np.asarray(ds_lab[block.bb])
            lut, dense = densify_labels(labels)
            n_nodes = len(lut)
            if n_nodes <= 1:
                ds_out[block.bb] = labels
                log_fn(f"processed block {block_id}")
                continue
            bmap = _read_input(ds_in, block.bb, cfg)
            u, v, val, ok = boundary_pair_values(
                jnp.asarray(dense), jnp.asarray(bmap))
            m = np.asarray(ok)
            uv_all = np.stack([np.asarray(u)[m], np.asarray(v)[m]], axis=1)
            vals = np.asarray(val)[m].astype("float64")
            if len(uv_all) == 0:
                ds_out[block.bb] = labels
                log_fn(f"processed block {block_id}")
                continue
            # per-(dense) edge mean + size; drop edges to the ignore label 0
            uv, inv = np.unique(uv_all, axis=0, return_inverse=True)
            sums = np.bincount(inv, weights=vals, minlength=len(uv))
            sizes = np.bincount(inv, minlength=len(uv)).astype("float64")
            keep = (uv[:, 0] != 0) & (uv[:, 1] != 0)
            uv, sums, sizes = uv[keep], sums[keep], sizes[keep]
            node_sizes = np.bincount(dense.ravel(),
                                     minlength=n_nodes).astype("float64")
            clusters = native.agglomerative_clustering(
                n_nodes, uv, sums / np.maximum(sizes, 1), edge_sizes=sizes,
                node_sizes=node_sizes, threshold=threshold,
                size_regularizer=size_reg)
            # keep 0 as background, compact cluster ids, offset per block
            clusters = clusters.astype("uint64")
            nz = np.unique(clusters[1:]) if n_nodes > 1 else clusters
            remap = np.searchsorted(nz, clusters).astype("uint64") + 1
            remap[0] = 0
            out = remap[dense] + np.where(remap[dense] > 0,
                                          np.uint64(block_id) * offset_unit,
                                          np.uint64(0))
            ds_out[block.bb] = out
            log_fn(f"processed block {block_id}")


class WatershedWorkflow(Task):
    """[TwoPass]Watershed -> [Agglomerate] -> RelabelWorkflow (reference:
    watershed/watershed_workflow.py:20-60)."""

    def __init__(self, input_path: str, input_key: str, output_path: str,
                 output_key: str, tmp_folder: str, config_dir: str,
                 max_jobs: int = 1, target: str = "local",
                 mask_path: str = "", mask_key: str = "",
                 two_pass: bool = False, agglomeration: bool = False,
                 dependency: Optional[Task] = None):
        self.input_path = input_path
        self.input_key = input_key
        self.output_path = output_path
        self.output_key = output_key
        self.mask_path = mask_path
        self.mask_key = mask_key
        if two_pass and agglomeration:
            raise ValueError(
                "two_pass and agglomeration are mutually exclusive: the "
                "block-local agglomerate re-offsets ids per block, splitting "
                "every segment the seeded pass-2 stitched across faces")
        self.two_pass = two_pass
        self.agglomeration = agglomeration
        self.tmp_folder = tmp_folder
        self.config_dir = config_dir
        self.max_jobs = max_jobs
        self.target = target
        self.dependency = dependency
        super().__init__()

    def requires(self):
        common = dict(tmp_folder=self.tmp_folder, config_dir=self.config_dir,
                      max_jobs=self.max_jobs, target=self.target)
        ws_kwargs = dict(
            input_path=self.input_path, input_key=self.input_key,
            output_path=self.output_path, output_key=self.output_key,
            mask_path=self.mask_path, mask_key=self.mask_key)
        if self.two_pass:
            p1 = WatershedPass1Task(dependency=self.dependency, **ws_kwargs,
                                    **common)
            dep: Task = WatershedPass2Task(dependency=p1, **ws_kwargs,
                                           **common)
        else:
            dep = WatershedTask(dependency=self.dependency, **ws_kwargs,
                                **common)
        if self.agglomeration:
            # in-place: block-local transform, each block reads and rewrites
            # only its own chunk-aligned region (single-writer invariant
            # holds; reference chains a separate agglomerate dataset,
            # agglomerate.py:129+, but the copy buys nothing here)
            dep = AgglomerateTask(
                input_path=self.input_path, input_key=self.input_key,
                labels_path=self.output_path, labels_key=self.output_key,
                output_path=self.output_path, output_key=self.output_key,
                dependency=dep, **common)
        return RelabelWorkflow(
            input_path=self.output_path, input_key=self.output_key,
            identifier="relabel_ws", dependency=dep, **common)

    def output(self):
        from ..core.workflow import FileTarget

        return FileTarget(os.path.join(self.tmp_folder,
                                       "write_relabel_ws.status"))
