"""Plain reference for the multicut cells, and the comparison that decides
``correct`` for them.

Imports nothing of the program and takes nothing it made.  For each sampled
block it recomputes the fragments as ``dt_watershed`` states them, then, on
the block's own (clipped) region:

* the region adjacency graph: every pair of 6-neighbour voxels inside the
  block whose fragments differ (label 0 ignored) is a face of the edge
  between the two fragments;
* the edge feature: the mean of ``u8 / 255`` over both voxels of every face
  of the edge (the boundary mean);
* the cost: ``log((1 - p) / p) + log((1 - beta) / beta)`` with
  ``p = (1 - 2e-3) * mean + 1e-3``; positive costs attract;
* the multicut: greedy additive edge contraction (repeatedly contract the
  most attractive edge, summing the costs of parallel edges, until no
  edge's cost is positive), then greedy single-node moves while one lowers
  the energy.

The block's segmentation is its fragments mapped through the multicut.  The
program solves each block's graph this way too, then a global problem over
the blocks' segments; the block-local solve stands for it, since the
volume's cells are convex and lie inside one block's region or cross its
faces.

The numbers compared: the worst sampled block's VOI between the chain's
own fragments (``ws``) and the reference's, and the worst sampled block's
VOI between the chain's own segmentation (``seg``), read back from its
store, and the reference's.
"""

from __future__ import annotations

import heapq

import numpy as np

from refs import dt_watershed as W


def rag_costs(ws: np.ndarray, raw: np.ndarray, beta: float, rnd):
    """(uv, costs) of the block's region adjacency graph: dense node ids
    ``0..n-1`` over the sorted nonzero fragment ids ``nodes``."""
    x = raw.astype(np.float64) / 255.0
    us, vs, sums = [], [], []
    for ax in range(ws.ndim):
        lo = [slice(None)] * ws.ndim
        hi = [slice(None)] * ws.ndim
        lo[ax], hi[ax] = slice(0, -1), slice(1, None)
        a, b = ws[tuple(lo)], ws[tuple(hi)]
        ok = (a != b) & (a != 0) & (b != 0)
        us.append(np.minimum(a, b)[ok])
        vs.append(np.maximum(a, b)[ok])
        sums.append(x[tuple(lo)][ok] + x[tuple(hi)][ok])
    nodes = np.unique(ws[ws != 0])
    u = np.searchsorted(nodes, np.concatenate(us))
    v = np.searchsorted(nodes, np.concatenate(vs))
    s = np.concatenate(sums)
    if len(u) == 0:
        return nodes, np.zeros((0, 2), np.int64), np.zeros(0, np.float32)
    uv, inv = np.unique(np.stack([u, v], 1), axis=0, return_inverse=True)
    inv = inv.reshape(-1)
    mean = rnd(np.bincount(inv, weights=s) / (2.0 * np.bincount(inv)))
    p = (1.0 - 2e-3) * mean.astype(np.float64) + 1e-3
    costs = rnd(np.log((1.0 - p) / p) + np.log((1.0 - beta) / beta))
    return nodes, uv.astype(np.int64), costs


def gaec(n: int, uv: np.ndarray, costs: np.ndarray) -> np.ndarray:
    """Greedy additive edge contraction; returns a cluster id per node."""
    adj = [dict() for _ in range(n)]
    for (u, v), c in zip(uv.tolist(), costs.astype(np.float64).tolist()):
        adj[u][v] = adj[u].get(v, 0.0) + c
        adj[v][u] = adj[v].get(u, 0.0) + c
    parent = list(range(n))
    heap = [(-c, u, v) for u in range(n) for v, c in adj[u].items()
            if u < v and c > 0]
    heapq.heapify(heap)
    while heap:
        neg, u, v = heapq.heappop(heap)
        if parent[u] != u or parent[v] != v or adj[u].get(v) != -neg:
            continue  # an endpoint was contracted, or the cost changed
        if len(adj[u]) < len(adj[v]):
            u, v = v, u
        parent[v] = u
        del adj[u][v]
        for w, c in adj[v].items():
            if w == u:
                continue
            del adj[w][v]
            c = adj[u].get(w, 0.0) + c
            adj[u][w] = adj[w][u] = c
            if c > 0:
                heapq.heappush(heap, (-c, min(u, w), max(u, w)))
        adj[v] = {}

    def find(i):
        while parent[i] != i:
            i = parent[i]
        return i

    return np.array([find(i) for i in range(n)], np.int64)


def node_moves(n: int, uv: np.ndarray, costs: np.ndarray,
               labels: np.ndarray, max_passes: int = 50) -> np.ndarray:
    """Move single nodes to the neighbouring cluster (or a cluster of their
    own) that lowers the energy most, until no move lowers it."""
    adj = [[] for _ in range(n)]
    for (u, v), c in zip(uv.tolist(), costs.astype(np.float64).tolist()):
        adj[u].append((v, c))
        adj[v].append((u, c))
    lab = labels.tolist()
    fresh = max(lab, default=-1) + 1
    for _ in range(max_passes):
        moved = False
        for i in range(n):
            to = {}
            for j, c in adj[i]:
                to[lab[j]] = to.get(lab[j], 0.0) + c
            here = to.get(lab[i], 0.0)
            best, gain = None, 1e-9
            if -here > gain:  # a cluster of its own
                best, gain = -1, -here
            for lj, s in sorted(to.items()):
                if lj != lab[i] and s - here > gain:
                    best, gain = lj, s - here
            if best is not None:
                if best == -1:
                    best, fresh = fresh, fresh + 1
                lab[i] = best
                moved = True
        if not moved:
            break
    return np.asarray(lab, np.int64)


def multicut_block(ws: np.ndarray, raw: np.ndarray, cfg: dict,
                   precision: str) -> np.ndarray:
    """The block's segmentation: fragments mapped through the multicut of
    their region adjacency graph (0 stays 0)."""
    rnd = W.rounder(precision)
    nodes, uv, costs = rag_costs(ws, raw, float(cfg["beta"]), rnd)
    lab = gaec(len(nodes), uv, costs)
    lab = node_moves(len(nodes), uv, costs, lab)
    seg = np.zeros(ws.shape, np.int64)
    fg = ws != 0
    seg[fg] = lab[np.searchsorted(nodes, ws[fg])] + 1
    return seg


def mc_block_inner(args):
    """Worker entry: (outer window, cfg, precision, begin, end, halo) ->
    ``{"frag": fragments, "seg": segmentation}`` of the block's own
    region."""
    outer, cfg, precision, begin, end, halo = args
    inner = tuple(slice(h, h + e - b) for h, b, e in zip(halo, begin, end))
    ws = W.ws_block(outer, cfg, precision)[inner]
    return {"frag": ws, "seg": multicut_block(ws, outer[inner], cfg,
                                              precision)}


def reference_blocks(vol, cfg, seed, precision="float32", workers=None):
    return W.reference_blocks(vol, cfg, seed, precision, workers,
                              mc_block_inner)


def compare(vol, chain_dirs, cfg, seed, precision="float32", workers=None):
    return W.compare(vol, chain_dirs, cfg, seed, precision, workers,
                     mc_block_inner)


score = W.score
