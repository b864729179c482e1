"""Plain reference for two-pass mutex watershed, and the comparison that
decides ``correct`` for the cells that run ``TwoPassMwsWorkflow``.

Imports nothing of the program and takes nothing it made but the chain's
output that it checks.  From the input the harness generated (uint8
affinities, channel ``c`` pairing each voxel ``x`` with ``x + offsets[c]``)
it recomputes the whole volume's labels as the configuration states them:

* blocks of ``block_shape`` on the volume, each with an outer window of
  ``halo`` more voxels per side, clipped at the volume's edge; a block's
  colour is the parity of the sum of its grid position (a checkerboard);
* pass 1: each block of colour 0 runs mutex watershed (Wolf et al. 2018,
  Algorithm 1: ``mws_kruskal.cpp``, the first ``ndim`` channels attractive
  with priority ``aff``, the rest mutex with ``1 - aff``, ``aff = u8 / 255``
  in float32, one stable descending sort, zero-priority attractive edges
  dropped) over its outer window, and its labels inside the block are
  kept, distinct from every other block's;
* pass 2: each block of colour 1 reads the kept labels in its outer window
  as seeds, except where a voxel belongs to a block of its own colour;
  direct edges inside one seed go above every data priority; its labels
  inside the block are kept, and each (label, seed) pair met on a seeded
  voxel of the window is recorded;
* the recorded pairs join labels (connected components over the pairs),
  and each voxel's final label is its kept label's component.

``precision="float32"`` is the configuration's precision.  The answer
depends only on the order of the edges, that is on the order of the 256
uint8 levels, which bfloat16 keeps whole (its 8 significant bits hold
every ``u8 / 255`` distinct and in order).  So :data:`CONTROL`, the name
under which the harness's control (``control.py``) asks for the precision
below the configuration's, takes the level below that: 7 bits, each
affinity's lowest bit cleared (128 levels), which makes neighbouring
levels ties and reorders them.

The comparison counts, over the whole volume, the reference segments that
the chain's output splits (``split_segments``) and the chain's segments
that merge reference segments (``merged_segments``).  Both semantics are
exact (no floating-point sums, one total order of the edges), so equal
partitions read 0 and 0, and any departure reads at least 1.
"""

from __future__ import annotations

import ctypes
import hashlib
import math
import os
import subprocess
import sys
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "mws_kruskal.cpp")
#: the harness's work directory, where the C++ is built at first use
WORK = os.path.join(os.path.dirname(os.path.dirname(HERE)), ".bench_work")
_LIB = None
_LIB_LOCK = threading.Lock()
#: the precision name the harness's control asks for: 7-bit affinities
CONTROL = "bfloat16"


def library():
    """``mws_kruskal.cpp`` compiled with ``g++ -O2`` into the work
    directory (named by the source's hash) and loaded by ctypes, once per
    process, whichever thread asks first."""
    global _LIB
    with _LIB_LOCK:
        if _LIB is None:
            _LIB = _build_and_load()
    return _LIB


def _build_and_load():
    with open(SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    path = os.path.join(WORK, "refs", f"mws_kruskal-{digest}.so")
    if not os.path.exists(path):
        os.makedirs(os.path.dirname(path), exist_ok=True)
        # another process may build it at the same time: each publishes
        # a whole file by rename
        tmp = f"{path}.{os.getpid()}"
        subprocess.run(["g++", "-O2", "-std=c++17", "-shared", "-fPIC", SRC,
                        "-o", tmp], check=True, capture_output=True)
        os.replace(tmp, path)
    lib = ctypes.CDLL(path)
    i64 = ctypes.c_int64
    ptr = np.ctypeslib.ndpointer
    lib.mws_block.argtypes = [
        ptr(np.uint8, flags="C_CONTIGUOUS"), i64,
        ptr(np.int64, flags="C_CONTIGUOUS"), i64, i64, i64, i64,
        ctypes.c_void_p, ptr(np.int64, flags="C_CONTIGUOUS")]
    lib.mws_block.restype = i64
    return lib


def mws(affs: np.ndarray, offsets, seeds=None, precision="float32"):
    """Labels (int64, 1..k numbered by first voxel in C order) of mutex
    watershed over the window ``affs`` (channels, z, y, x) of uint8."""
    if precision not in ("float32", CONTROL):
        raise ValueError(f"unknown precision {precision!r}")
    affs = np.ascontiguousarray(affs, np.uint8)
    if precision == CONTROL:
        affs = affs & np.uint8(0xFE)
    shape = affs.shape[1:]
    offs = np.ascontiguousarray(offsets, np.int64).reshape(-1, 3)
    labels = np.empty(shape, np.int64)
    if seeds is not None:
        seeds = np.ascontiguousarray(seeds, np.int64)
    library().mws_block(affs, len(offs), offs, len(shape), *shape,
                        None if seeds is None else seeds.ctypes.data, labels)
    return labels


# ---------------------------------------------------------------------------
# the two-pass protocol
# ---------------------------------------------------------------------------

def blocks(shape, block_shape):
    """Every block as (grid position, begin, end), C order over the grid."""
    grid = [math.ceil(s / b) for s, b in zip(shape, block_shape)]
    out = []
    for pos in np.ndindex(*grid):
        begin = tuple(p * b for p, b in zip(pos, block_shape))
        end = tuple(min(b0 + b, s) for b0, b, s in zip(begin, block_shape,
                                                       shape))
        out.append((pos, begin, end))
    return out


def two_pass(vol: np.ndarray, offsets, block_shape, halo,
             precision="float32", workers=None):
    """The final labels (int64, one per component, not consecutive) of the
    whole volume ``vol`` (channels, z, y, x)."""
    shape = vol.shape[1:]
    every = blocks(shape, block_shape)
    kept = np.zeros(shape, np.int64)
    pairs = []
    n_ids = 0   # labels kept so far: a block's are n_ids + 1, n_ids + 2, ...

    def windows(begin, end):
        """(outer window, the block inside it) as slices."""
        ob = tuple(slice(max(b - h, 0), min(e + h, s))
                   for b, e, h, s in zip(begin, end, halo, shape))
        return ob, tuple(slice(b - o.start, e - o.start)
                         for b, e, o in zip(begin, end, ob))

    def run(k, seeded):
        pos, begin, end = every[k]
        ob, _ = windows(begin, end)
        seeds = None
        if seeded:
            seeds = kept[ob].copy()
            owner = sum(np.meshgrid(*[np.arange(o.start, o.stop) // b
                                      for o, b in zip(ob, block_shape)],
                                    indexing="ij", sparse=True)) % 2
            seeds[owner == sum(pos) % 2] = 0
        return mws(vol[(slice(None),) + ob], offsets, seeds, precision), seeds

    with ThreadPoolExecutor(workers or default_workers()) as pool:
        for colour in (0, 1):
            ks = [k for k, (pos, _, _) in enumerate(every)
                  if sum(pos) % 2 == colour]
            # every block of a colour reads the kept labels before any of
            # its own are kept
            done = list(pool.map(lambda k: run(k, colour == 1), ks))
            for k, (lab, seeds) in zip(ks, done):
                _, begin, end = every[k]
                _, inner = windows(begin, end)
                kept[tuple(slice(b, e) for b, e in zip(begin, end))] = \
                    lab[inner] + n_ids
                if seeds is not None:
                    on = seeds != 0
                    pairs.append(np.unique(np.stack(
                        [lab[on] + n_ids, seeds[on]]), axis=1))
                n_ids += int(lab.max(initial=0))
    if not pairs:
        return kept
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    a, b = np.concatenate(pairs, axis=1)
    graph = coo_matrix((np.ones(len(a), bool), (a, b)),
                       shape=(n_ids + 1, n_ids + 1))
    _, comp = connected_components(graph, directed=False)
    return comp[kept].astype(np.int64)


def default_workers() -> int:
    """Four at most: each block holds ~3 GB (its edges, sets, labels)."""
    return max(1, min(4, (os.cpu_count() or 2) - 1))


# ---------------------------------------------------------------------------
# the numbers compared
# ---------------------------------------------------------------------------

def mismatch(ref: np.ndarray, got: np.ndarray):
    """(reference segments split by ``got``, ``got``'s segments that merge
    reference segments): the overlaps of the two labelings beyond a
    one-to-one match."""
    r = ref.ravel().astype(np.int64)
    g = got.ravel()
    if g.max(initial=0) >= 2 ** 31:
        g = np.unique(g, return_inverse=True)[1]
    g = g.astype(np.int64)
    m = int(g.max(initial=0)) + 1
    both = np.unique(r * m + g)
    return (len(both) - len(np.unique(both // m)),
            len(both) - len(np.unique(both % m)))


def params(cfg: dict):
    if any(int(s) != 1 for s in cfg["reference"].get("strides", [1, 1, 1])):
        raise ValueError("the reference takes every mutex edge (strides 1)")
    return (cfg["input"]["args"]["offsets"],
            tuple(cfg["global_config"]["block_shape"]),
            tuple(cfg["reference"]["halo"]))


def reference_blocks(vol, cfg, seed, precision="float32", workers=None):
    """The region compared (the whole volume, as one (begin, end)) and the
    reference's final labels of it by output name.  ``seed`` is unused:
    every block is recomputed."""
    offsets, block_shape, halo = params(cfg)
    labels = two_pass(vol, offsets, block_shape, halo, precision, workers)
    name = next(iter(cfg["reference"]["outputs"]))
    return [((0,) * labels.ndim, labels.shape)], [{name: labels}]


def compare(vol, chain_dirs, cfg, seed, precision="float32", workers=None):
    """The numbers compared: the worst ``split_segments`` and
    ``merged_segments`` over every chain of the window, between the chain's
    store output and the reference."""
    picked, refs = reference_blocks(vol, cfg, seed, precision, workers)
    return score(picked, refs, chain_dirs, cfg)


def score(picked, refs, chain_dirs, cfg):
    """``compare``'s numbers for reference labels already computed."""
    import n5

    (name, key), = cfg["reference"]["outputs"].items()
    worst = {"split_segments": [], "merged_segments": []}
    for d in chain_dirs:
        for (b, e), ref in zip(picked, refs):
            try:
                got = n5.read(os.path.join(d, "out.n5"), key, b, e)
            except Exception as exc:  # nothing written: no reading
                print(f"bench: {d} {key} unreadable: {exc}", file=sys.stderr)
                for v in worst.values():
                    v.append(None)
                continue
            split, merged = mismatch(ref[name], got)
            worst["split_segments"].append(split)
            worst["merged_segments"].append(merged)
    print(f"bench: mismatches per chain {worst}", file=sys.stderr)
    return {k: (max(v) if v and None not in v else None)
            for k, v in worst.items()}
