"""Plain reference for the blockwise distance-transform watershed, and the
comparison that decides ``correct`` for the cells that write its fragments.

Imports nothing of the program and takes nothing it made: it reads the
input the harness generated and recomputes each sampled block's fragments
with numpy and scipy, as the configuration states them:

* the block's outer window is ``[begin - halo, begin + block + halo)``, with
  positions outside the volume folded back by reflection over the whole axis
  (period ``2n - 2``);
* ``x = u8 / 255``, foreground ``x < threshold``, exact Euclidean distance
  ``dt`` of every foreground voxel to the nearest background voxel;
* ``height = alpha * G(x, sigma_weights) + (1 - alpha) * (1 - dt / max dt)``,
  ``G`` a separable Gaussian of radius ``int(4 sigma + 0.5)`` with
  half-sample reflection at the window's edges, each pass's input and taps
  rounded as ``conv_operands`` says (``"bfloat16"``: JAX's default
  precision for a float32 convolution on the TPU) and summed in float32;
* seeds: voxels with ``G(dt, sigma_seeds) >= max over the 5^3 window``
  that are foreground, labelled by 26-connected components (ids increasing
  with each component's first voxel in C order);
* the watershed at ``coarse_factor``x coarse resolution: heights mean-pooled
  and seed ids max-pooled over ``f^3`` cells (edge-padded heights, zero-padded
  seeds), a steepest-descent forest on the coarse grid (ties by height, then
  index; a seeded cell points only within its own seed id), and a seeded
  minimum spanning forest over the basins, the weight between two basins
  being their lowest saddle ``min max(h_u, h_v)`` over 6-neighbour pairs;
  fragments smaller than ``max(size_filter // f^3, 1)`` coarse cells lose
  their seed and re-attach;
* the coarse labels upsampled by ``f`` and ``refine_rounds`` full-resolution
  sweeps in which each voxel adopts the label of its lowest labelled
  6-neighbour where that neighbour lies strictly lower.

``precision="float32"`` is the configuration's precision.  ``"bfloat16"``
is the control: every floating-point array the reference makes is rounded
to bfloat16 as it is made.
"""

from __future__ import annotations

import math
import os
import sys

import numpy as np

BIG = np.float32(np.finfo(np.float32).max)
# the six face neighbours in the order the refinement sweeps visit them
FACE_OFFSETS = ((1, 0, 0), (-1, 0, 0), (0, 1, 0), (0, -1, 0), (0, 0, 1),
                (0, 0, -1))


def rounder(precision: str):
    if precision == "float32":
        return lambda a: np.asarray(a, np.float32)
    if precision == "bfloat16":
        import ml_dtypes

        return lambda a: np.asarray(a, np.float32).astype(
            ml_dtypes.bfloat16).astype(np.float32)
    raise ValueError(f"unknown precision {precision!r}")


# ---------------------------------------------------------------------------
# geometry
# ---------------------------------------------------------------------------

def blocks(shape, block_shape):
    """Every block as (begin, end), C order over the block grid."""
    grid = [math.ceil(s / b) for s, b in zip(shape, block_shape)]
    out = []
    for pos in np.ndindex(*grid):
        begin = tuple(p * b for p, b in zip(pos, block_shape))
        end = tuple(min(b0 + b, s) for b0, b, s in zip(begin, block_shape,
                                                       shape))
        out.append((begin, end))
    return out


def reflect(start: int, stop: int, n: int) -> np.ndarray:
    idx = np.arange(start, stop)
    if n == 1:
        return np.zeros_like(idx)
    period = 2 * n - 2
    j = np.mod(idx, period)
    return np.where(j < n, j, period - j)


def outer_window(vol: np.ndarray, begin, block_shape, halo) -> np.ndarray:
    idx = [reflect(b - h, b + bs + h, n)
           for b, bs, h, n in zip(begin, block_shape, halo, vol.shape)]
    return vol[np.ix_(*idx)]


# ---------------------------------------------------------------------------
# the per-block pipeline
# ---------------------------------------------------------------------------

def gaussian(x: np.ndarray, sigma: float, rnd, opnd) -> np.ndarray:
    """Separable Gaussian; ``opnd`` rounds each pass's operands (the input
    and the taps), ``rnd`` its float32 output."""
    from scipy import ndimage

    r = max(int(4.0 * sigma + 0.5), 1)
    taps = np.exp(-0.5 * (np.arange(-r, r + 1) / sigma) ** 2)
    taps = opnd(taps / taps.sum())
    out = x
    for ax in range(x.ndim):
        out = rnd(ndimage.correlate1d(opnd(out), taps, axis=ax,
                                      mode="reflect", output=np.float32))
    return out


def overlap(off, shape):
    """(source, destination) slices such that dst[i] pairs with src[i] =
    i + off, both inside the array."""
    src, dst = [], []
    for o, n in zip(off, shape):
        src.append(slice(max(o, 0), n + min(o, 0)))
        dst.append(slice(max(-o, 0), n - max(o, 0)))
    return tuple(src), tuple(dst)


def shifted(a: np.ndarray, off, fill) -> np.ndarray:
    """out[i] = a[i + off], ``fill`` outside."""
    out = np.full_like(a, fill)
    src, dst = overlap(off, a.shape)
    out[dst] = a[src]
    return out


def _find(parent, i):
    root = i
    while parent[root] != root:
        root = parent[root]
    while parent[i] != root:
        parent[i], i = root, parent[i]
    return root


def coarse_watershed(hc: np.ndarray, sc: np.ndarray, min_size: int):
    """Seeded watershed on the coarse grid: steepest-descent basins, then a
    seeded minimum spanning forest over the basins by lowest saddle."""
    n = hc.size
    seeded = sc > 0
    hh = np.where(seeded, -BIG, hc)
    idx = np.arange(n, dtype=np.int64).reshape(hc.shape)
    best_h, best_i = hh.copy(), idx.copy()
    for off in FACE_OFFSETS:
        nh = shifted(hh, off, BIG)
        ni = shifted(idx, off, n)
        ns = shifted(sc, off, 0)
        allowed = ~(seeded & (ns != sc))
        better = allowed & ((nh < best_h) | ((nh == best_h) & (ni < best_i)))
        best_h = np.where(better, nh, best_h)
        best_i = np.where(better, ni, best_i)
    root = best_i.reshape(-1)
    while True:
        nxt = root[root]
        if np.array_equal(nxt, root):
            break
        root = nxt
    basins, basin_of = np.unique(root, return_inverse=True)
    seed_of_basin = sc.reshape(-1)[basins]
    size_of_basin = np.bincount(basin_of, minlength=len(basins))
    grid = basin_of.reshape(hc.shape)
    # lowest saddle between every pair of touching basins
    us, vs, ws = [], [], []
    for ax in range(hc.ndim):
        a = [slice(None)] * hc.ndim
        b = [slice(None)] * hc.ndim
        a[ax], b[ax] = slice(0, -1), slice(1, None)
        ga, gb = grid[tuple(a)].ravel(), grid[tuple(b)].ravel()
        s = np.maximum(hc[tuple(a)], hc[tuple(b)]).ravel()
        keep = ga != gb
        us.append(np.minimum(ga, gb)[keep])
        vs.append(np.maximum(ga, gb)[keep])
        ws.append(s[keep])
    u, v, w = np.concatenate(us), np.concatenate(vs), np.concatenate(ws)
    order = np.lexsort((v, u, w))
    u, v, w = u[order], v[order], w[order]
    pair = u.astype(np.int64) * len(basins) + v
    _, first = np.unique(pair, return_index=True)
    first.sort()
    u, v = u[first].tolist(), v[first].tolist()

    def flood(labels):
        parent = list(range(len(basins)))
        lab = list(labels)
        for a, b in zip(u, v):
            ra, rb = _find(parent, a), _find(parent, b)
            if ra == rb or (lab[ra] and lab[rb]):
                continue
            parent[rb] = ra
            lab[ra] = lab[ra] or lab[rb]
        roots = np.array([_find(parent, i) for i in range(len(basins))])
        return roots, np.asarray(lab)[roots]

    roots, out = flood(seed_of_basin.tolist())
    if min_size > 1:
        sizes = np.bincount(roots, weights=size_of_basin,
                            minlength=len(basins))
        small = sizes[roots] < min_size
        if small.any():
            _, out = flood(np.where(small, 0, out).tolist())
    return out[basin_of].reshape(hc.shape)


def ws_block(outer_u8: np.ndarray, cfg: dict, precision: str = "float32"):
    """Fragment labels (int64, 0 = none) of one outer window."""
    from scipy import ndimage

    rnd = rounder(precision)
    # the convolutions' operands: the configuration's ``conv_operands``
    # (JAX's default precision on the TPU rounds them to bfloat16); the
    # control rounds them with everything else
    opnd = rounder("bfloat16" if precision == "bfloat16"
                   else cfg.get("conv_operands", "float32"))
    thr = rnd(np.float32(cfg["threshold"]))
    alpha = float(cfg["alpha"])
    f = int(cfg["coarse_factor"])
    x = rnd(outer_u8.astype(np.float32) / np.float32(255.0))
    fg = x < thr
    dt = rnd(ndimage.distance_transform_edt(fg, return_indices=False))
    dmax = max(float(dt.max()), 1e-6)
    hmap = gaussian(x, float(cfg["sigma_weights"]), rnd, opnd)
    height = rnd(rnd(alpha * hmap)
                 + rnd((1.0 - alpha) * rnd(1.0 - rnd(dt / np.float32(dmax)))))
    dts = gaussian(dt, float(cfg["sigma_seeds"]), rnd, opnd)
    maxima = (dts >= ndimage.maximum_filter(dts, size=5, mode="constant",
                                            cval=-np.inf)) & fg
    seeds, _ = ndimage.label(maxima, structure=np.ones((3, 3, 3), bool))
    pads = [(0, (f - s % f) % f) for s in height.shape]
    hp = np.pad(height, pads, mode="edge")
    sp = np.pad(seeds, pads)
    cs = tuple(s // f for s in hp.shape)
    hc = rnd(hp.reshape(cs[0], f, cs[1], f, cs[2], f).astype(np.float64)
             .mean((1, 3, 5)))
    sc = sp.reshape(cs[0], f, cs[1], f, cs[2], f).max((1, 3, 5))
    wsc = coarse_watershed(hc, sc, max(int(cfg["size_filter"]) // f ** 3, 1))
    wsc = wsc.astype(np.int32)
    ws = np.repeat(np.repeat(np.repeat(wsc, f, 0), f, 1), f, 2)
    ws = ws[tuple(slice(0, s) for s in height.shape)]
    for _ in range(int(cfg["refine_rounds"])):
        best_h, best_l = height.copy(), ws.copy()
        for off in FACE_OFFSETS:
            src, dst = overlap(off, ws.shape)
            nh, nl = height[src], ws[src]
            better = (nh < best_h[dst]) & (nl > 0)
            np.copyto(best_h[dst], nh, where=better)
            np.copyto(best_l[dst], nl, where=better)
        ws = best_l
    return ws


def ws_block_inner(args):
    """Worker entry: (outer window, cfg, precision, begin, end, halo) ->
    ``{"frag": fragments}`` of the block's own (clipped) region."""
    outer, cfg, precision, begin, end, halo = args
    ws = ws_block(outer, cfg, precision)
    return {"frag": ws[tuple(slice(h, h + e - b)
                             for h, b, e in zip(halo, begin, end))]}


def run_blocks(vol: np.ndarray, picked, cfg: dict, precision: str,
               workers: int, fn=ws_block_inner):
    """``fn`` over the picked blocks (by default their reference
    fragments), in worker processes that import only numpy and scipy (the
    parent may hold the chip)."""
    import multiprocessing as mp

    tasks = [(outer_window(vol, b, cfg["block_shape"], cfg["halo"]), cfg,
              precision, b, e, cfg["halo"]) for b, e in picked]
    if workers <= 1:
        return [fn(t) for t in tasks]
    ctx = mp.get_context("spawn")
    with ctx.Pool(min(workers, len(tasks))) as pool:
        out = pool.map(fn, tasks, chunksize=1)
    return out


# ---------------------------------------------------------------------------
# the number compared
# ---------------------------------------------------------------------------

def voi(a: np.ndarray, b: np.ndarray) -> float:
    """Variation of information (bits) between two labelings of the same
    voxels; label 0 is a label like any other."""
    _, ia = np.unique(a.ravel(), return_inverse=True)
    _, ib = np.unique(b.ravel(), return_inverse=True)
    nb = int(ib.max()) + 1
    _, cnt = np.unique(ia.astype(np.int64) * nb + ib, return_counts=True)
    n = float(a.size)
    pa = np.bincount(ia) / n
    pb = np.bincount(ib) / n
    pab = cnt / n

    def h(p):
        p = p[p > 0]
        return float(-(p * np.log2(p)).sum())

    return 2.0 * h(pab) - h(pa) - h(pb)


def default_workers() -> int:
    """Four at most: each holds ~1.8 GB at the reference block size
    (CPU, PR 22), beside the parent that holds the chip."""
    return max(1, min(4, (os.cpu_count() or 2) - 1))


# ---------------------------------------------------------------------------
# the comparison that decides ``correct``
# ---------------------------------------------------------------------------

def block_params(cfg: dict) -> dict:
    ref = cfg["reference"]
    return dict(ref["params"], block_shape=tuple(
        cfg["global_config"]["block_shape"]), halo=tuple(ref["halo"]))


def sample_blocks(cfg: dict, seed: int):
    """The blocks compared, drawn from the seed; always at least one block
    that the volume's edge clips, where the reflected halo matters."""
    shape = tuple(cfg["shape"])
    every = blocks(shape, cfg["global_config"]["block_shape"])
    k = min(int(cfg["reference"]["sample_blocks"]), len(every))
    rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), 20261015])
    picked = set(rng.choice(len(every), k, replace=False).tolist())
    clipped = [i for i, (b, e) in enumerate(every)
               if any(x - y < s for x, y, s in zip(
                   e, b, cfg["global_config"]["block_shape"]))]
    if clipped and not picked & set(clipped):
        picked.pop()
        picked.add(int(rng.choice(clipped)))
    return [every[i] for i in sorted(picked)]


def reference_blocks(vol, cfg, seed, precision="float32", workers=None,
                     fn=ws_block_inner):
    """The sampled blocks and, for each, ``fn``'s outputs by name."""
    picked = sample_blocks(cfg, seed)
    outs = run_blocks(vol, picked, block_params(cfg), precision,
                      workers or default_workers(), fn)
    return picked, outs


def compare(vol, chain_dirs, cfg, seed, precision="float32", workers=None,
            fn=ws_block_inner):
    """The numbers compared: for each output the configuration names
    (``reference.outputs``: name -> store key), the worst VOI over every
    chain of the window and every sampled block between what the chain
    wrote to its store and the reference's."""
    picked, refs = reference_blocks(vol, cfg, seed, precision, workers, fn)
    return score(picked, refs, chain_dirs, cfg)


def score(picked, refs, chain_dirs, cfg):
    """``compare``'s numbers for reference outputs already computed."""
    import n5

    keys = cfg["reference"]["outputs"]
    vois = {name: [] for name in keys}
    for d in chain_dirs:
        for (b, e), ref in zip(picked, refs):
            for name, key in keys.items():
                try:
                    got = n5.read(os.path.join(d, "out.n5"), key, b, e)
                except Exception as exc:  # nothing written: no reading
                    print(f"bench: {d} {key} unreadable: {exc}",
                          file=sys.stderr)
                    vois[name].append(None)
                    continue
                vois[name].append(voi(got, ref[name]))
    print(f"bench: VOI per chain and block {vois} (blocks "
          f"{[b for b, _ in picked]})", file=sys.stderr)
    return {f"{name}_voi_max": (max(v) if v and None not in v else None)
            for name, v in vois.items()}
