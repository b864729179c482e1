"""First-party native kernels (C++), loaded via ctypes.

Replaces the reference's external pybind11 wheels for combinatorial work
(nifty solvers/ufd, affogato MWS — SURVEY §2.3).  The shared library is
compiled on demand with g++ (no pybind11 in the image; the C API is flat
arrays).  Every entry point has a pure-numpy/scipy fallback so the framework
degrades gracefully where no compiler exists.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "src", "solvers.cpp")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False


def _lib_path() -> Optional[str]:
    """Content-addressed build artifact: the library name embeds the source
    hash, so a stale binary (e.g. from a previous checkout — git does not
    preserve mtimes) can never be loaded for edited sources."""
    try:
        with open(_SRC, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
    except OSError:
        return None
    return os.path.join(_HERE, f"libctt_native-{digest}.so")


def _build(lib_path: str) -> bool:
    # per-process tmp name: concurrent workers may build simultaneously on
    # first use; each publishes a complete file via atomic rename
    tmp = f"{lib_path}.tmp.{os.getpid()}"
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", _SRC, "-o", tmp]
    try:
        res = subprocess.run(cmd, capture_output=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired):
        return False
    if res.returncode != 0:
        return False
    os.replace(tmp, lib_path)
    for name in os.listdir(_HERE):  # drop superseded build artifacts
        if (name.startswith("libctt_native-") and name.endswith(".so")
                and os.path.join(_HERE, name) != lib_path):
            try:
                os.unlink(os.path.join(_HERE, name))
            except OSError:
                pass
    return True


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    with _lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            return None
        lib_path = _lib_path()
        if lib_path is None or (not os.path.exists(lib_path)
                                and not _build(lib_path)):
            _build_failed = True
            return None
        lib = ctypes.CDLL(lib_path)
        i64 = ctypes.c_int64
        p_i64 = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
        p_f64 = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")
        p_u64 = np.ctypeslib.ndpointer(np.uint64, flags="C_CONTIGUOUS")
        lib.ufd_merge_pairs.argtypes = [i64, i64, p_i64, p_u64]
        lib.mc_gaec.argtypes = [i64, i64, p_i64, p_f64, p_u64]
        lib.mc_gaec.restype = i64
        lib.mc_kl_refine.argtypes = [i64, i64, p_i64, p_f64, p_u64, i64,
                                     ctypes.c_double]
        lib.mc_kl_refine.restype = i64
        lib.mc_objective.argtypes = [i64, i64, p_i64, p_f64, p_u64]
        lib.mc_objective.restype = ctypes.c_double
        lib.mws_clustering.argtypes = [i64, i64, p_i64, p_f64, i64, p_i64,
                                       p_f64, p_u64]
        lib.mws_clustering.restype = i64
        p_i32 = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
        lib.mws_clustering_packed.argtypes = [i64, i64, p_i32, p_i32, p_u64]
        lib.mws_clustering_packed.restype = i64
        lib.graph_watershed.argtypes = [i64, i64, p_i64, p_f64, p_u64]
        lib.lmc_gaec.argtypes = [i64, i64, p_i64, p_f64, i64, p_i64, p_f64,
                                 p_u64]
        lib.lmc_gaec.restype = i64
        lib.lmc_kl_refine.argtypes = [i64, i64, p_i64, p_f64, i64, p_i64,
                                      p_f64, p_u64, i64, ctypes.c_double]
        lib.lmc_kl_refine.restype = i64
        lib.agglomerate_edge_weighted.argtypes = [
            i64, i64, p_i64, p_f64, p_f64, p_f64, ctypes.c_double,
            ctypes.c_double, p_u64]
        lib.agglomerate_edge_weighted.restype = i64
        p_u8 = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
        lib.skeletonize_3d.argtypes = [p_u8, i64, i64, i64]
        lib.seeded_watershed_u8.argtypes = [p_u8, i64, i64, i64, p_i64]
        lib.size_filter_u8.argtypes = [p_u8, i64, i64, i64, p_i64, i64]
        _lib = lib
        return _lib


def have_native() -> bool:
    return _load() is not None


def _as_uv(uv_ids: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(uv_ids, dtype=np.int64).reshape(-1, 2)


# ---------------------------------------------------------------------------
# union-find
# ---------------------------------------------------------------------------

def ufd_merge_pairs(n_nodes: int, pairs: np.ndarray) -> np.ndarray:
    """Root label per node after merging all pairs (boost_ufd equivalent)."""
    pairs = _as_uv(pairs)
    lib = _load()
    if lib is not None:
        out = np.empty(n_nodes, dtype=np.uint64)
        lib.ufd_merge_pairs(n_nodes, len(pairs), pairs, out)
        return out
    # fallback: sparse connected components
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components as sparse_cc

    graph = coo_matrix((np.ones(len(pairs), bool),
                        (pairs[:, 0], pairs[:, 1])),
                       shape=(n_nodes, n_nodes))
    _, roots = sparse_cc(graph, directed=False)
    # normalize roots to "smallest member id" semantics? not required by
    # callers; any component representative works
    return roots.astype(np.uint64)


# ---------------------------------------------------------------------------
# multicut
# ---------------------------------------------------------------------------

def multicut_gaec(n_nodes: int, uv_ids: np.ndarray,
                  costs: np.ndarray) -> np.ndarray:
    """Greedy additive edge contraction (nifty greedyAdditive equivalent)."""
    uv = _as_uv(uv_ids)
    costs = np.ascontiguousarray(costs, dtype=np.float64)
    lib = _load()
    if lib is not None:
        out = np.empty(n_nodes, dtype=np.uint64)
        lib.mc_gaec(n_nodes, len(uv), uv, costs, out)
        return out
    return _py_gaec(n_nodes, uv, costs)


def multicut_kernighan_lin(n_nodes: int, uv_ids: np.ndarray,
                           costs: np.ndarray, warmstart: bool = True,
                           max_passes: int = 50,
                           time_limit: float = 0.0) -> np.ndarray:
    """GAEC warmstart + Kernighan-Lin-style greedy node moves (the nifty
    multicutKernighanLin role: polish a partition with local search).
    ``time_limit`` (seconds, 0 = none) bounds the refinement passes — the
    reference's time-limited solver visitor (segmentation_utils.py:166-181);
    the warmstart always completes, so a valid partition is returned."""
    uv = _as_uv(uv_ids)
    costs = np.ascontiguousarray(costs, dtype=np.float64)
    labels = (multicut_gaec(n_nodes, uv, costs) if warmstart
              else np.zeros(n_nodes, dtype=np.uint64))
    lib = _load()
    if lib is not None:
        labels = np.ascontiguousarray(labels, dtype=np.uint64)
        lib.mc_kl_refine(n_nodes, len(uv), uv, costs, labels, max_passes,
                         float(time_limit or 0.0))
        return labels
    return _py_moves(n_nodes, uv, costs, labels, max_passes,
                     time_limit=time_limit)


def multicut_objective(uv_ids: np.ndarray, costs: np.ndarray,
                       labels: np.ndarray) -> float:
    """Sum of costs over cut edges (the minimized energy)."""
    uv = _as_uv(uv_ids)
    cut = labels[uv[:, 0]] != labels[uv[:, 1]]
    return float(np.asarray(costs)[cut].sum())


def _py_gaec(n_nodes: int, uv: np.ndarray, costs: np.ndarray) -> np.ndarray:
    """Heap-based python fallback (small problems only)."""
    import heapq

    adj = [dict() for _ in range(n_nodes)]
    for (u, v), c in zip(uv, costs):
        if u == v:
            continue
        adj[u][v] = adj[u].get(v, 0.0) + c
        adj[v][u] = adj[v].get(u, 0.0) + c
    parent = np.arange(n_nodes)

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    heap = [(-w, u, v) for u in range(n_nodes)
            for v, w in adj[u].items() if v > u and w > 0]
    heapq.heapify(heap)
    while heap:
        nw, u, v = heapq.heappop(heap)
        w = -nw
        ru, rv = find(u), find(v)
        if ru == rv:
            continue
        cur = adj[ru].get(rv)
        if cur is None or cur != w or {u, v} != {ru, rv}:
            if cur is not None and cur > 0:
                heapq.heappush(heap, (-cur, min(ru, rv), max(ru, rv)))
            continue
        if len(adj[ru]) < len(adj[rv]):
            ru, rv = rv, ru
        parent[rv] = ru
        adj[ru].pop(rv, None)
        adj[rv].pop(ru, None)
        for n, nw2 in adj[rv].items():
            adj[n].pop(rv, None)
            acc = adj[ru].get(n, 0.0) + nw2
            adj[ru][n] = acc
            adj[n][ru] = acc
            if acc > 0:
                heapq.heappush(heap, (-acc, min(ru, n), max(ru, n)))
        adj[rv].clear()
    roots = np.array([find(i) for i in range(n_nodes)])
    _, labels = np.unique(roots, return_inverse=True)
    return labels.astype(np.uint64)


def _py_moves(n_nodes: int, uv: np.ndarray, costs: np.ndarray,
              labels: np.ndarray, max_passes: int,
              time_limit: float = 0.0) -> np.ndarray:
    import time as _time

    deadline = _time.monotonic() + time_limit if time_limit else None
    labels = labels.astype(np.uint64).copy()
    nbrs = [dict() for _ in range(n_nodes)]
    for (u, v), c in zip(uv, costs):
        nbrs[u][v] = nbrs[u].get(v, 0.0) + c
        nbrs[v][u] = nbrs[v].get(u, 0.0) + c
    next_label = int(labels.max()) + 1 if n_nodes else 0
    for _ in range(max_passes):
        if deadline is not None and _time.monotonic() > deadline:
            break
        improved = False
        for x in range(n_nodes):
            if not nbrs[x]:
                continue
            comp_w = {}
            for n, w in nbrs[x].items():
                comp_w[labels[n]] = comp_w.get(labels[n], 0.0) + w
            own = labels[x]
            w_own = comp_w.get(own, 0.0)
            best_gain, best_label = -w_own, next_label
            for lbl, w in comp_w.items():
                if lbl != own and w - w_own > best_gain + 1e-12:
                    best_gain, best_label = w - w_own, lbl
            if best_gain > 1e-12:
                labels[x] = best_label
                if best_label == next_label:
                    next_label += 1
                improved = True
        if not improved:
            break
    return labels


# ---------------------------------------------------------------------------
# lifted multicut
# ---------------------------------------------------------------------------

def lifted_multicut_gaec(n_nodes: int, uv_ids: np.ndarray, costs: np.ndarray,
                         lifted_uv_ids: np.ndarray,
                         lifted_costs: np.ndarray) -> np.ndarray:
    """Greedy additive contraction for the lifted multicut objective
    (nifty liftedMulticutGreedyAdditive equivalent): only local edges are
    contracted; priorities include the lifted cost between components."""
    uv = _as_uv(uv_ids)
    luv = _as_uv(lifted_uv_ids)
    c = np.ascontiguousarray(costs, dtype=np.float64)
    lc = np.ascontiguousarray(lifted_costs, dtype=np.float64)
    lib = _load()
    if lib is not None:
        out = np.empty(n_nodes, dtype=np.uint64)
        lib.lmc_gaec(n_nodes, len(uv), uv, c, len(luv), luv, lc, out)
        return out
    return _py_lmc_gaec(n_nodes, uv, c, luv, lc)


def lifted_multicut_kernighan_lin(n_nodes: int, uv_ids: np.ndarray,
                                  costs: np.ndarray,
                                  lifted_uv_ids: np.ndarray,
                                  lifted_costs: np.ndarray,
                                  warmstart: bool = True,
                                  max_passes: int = 50,
                                  time_limit: float = 0.0) -> np.ndarray:
    """Lifted GAEC warmstart + KL-style node moves over the lifted objective
    (nifty liftedMulticutKernighanLin equivalent)."""
    uv = _as_uv(uv_ids)
    luv = _as_uv(lifted_uv_ids)
    c = np.ascontiguousarray(costs, dtype=np.float64)
    lc = np.ascontiguousarray(lifted_costs, dtype=np.float64)
    labels = (lifted_multicut_gaec(n_nodes, uv, c, luv, lc) if warmstart
              else np.zeros(n_nodes, dtype=np.uint64))
    lib = _load()
    if lib is not None:
        labels = np.ascontiguousarray(labels, dtype=np.uint64)
        lib.lmc_kl_refine(n_nodes, len(uv), uv, c, len(luv), luv, lc,
                          labels, max_passes, float(time_limit or 0.0))
        return labels
    return _py_lmc_moves(n_nodes, uv, c, luv, lc, labels, max_passes,
                         time_limit=time_limit)


def lifted_objective(uv_ids: np.ndarray, costs: np.ndarray,
                     lifted_uv_ids: np.ndarray, lifted_costs: np.ndarray,
                     labels: np.ndarray) -> float:
    uv = _as_uv(uv_ids)
    luv = _as_uv(lifted_uv_ids)
    e = float(np.asarray(costs)[labels[uv[:, 0]] != labels[uv[:, 1]]].sum())
    if len(luv):
        e += float(np.asarray(lifted_costs)[
            labels[luv[:, 0]] != labels[luv[:, 1]]].sum())
    return e


def _py_lmc_gaec(n_nodes, uv, c, luv, lc):
    import heapq

    adj = [dict() for _ in range(n_nodes)]
    lift = [dict() for _ in range(n_nodes)]
    for (u, v), w in zip(uv, c):
        if u != v:
            adj[u][v] = adj[u].get(v, 0.0) + w
            adj[v][u] = adj[u][v]
    for (u, v), w in zip(luv, lc):
        if u != v:
            lift[u][v] = lift[u].get(v, 0.0) + w
            lift[v][u] = lift[u][v]
    parent = np.arange(n_nodes)

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    def pair_w(a, b):
        return adj[a].get(b, 0.0) + lift[a].get(b, 0.0)

    heap = [(-pair_w(u, v), u, v) for u in range(n_nodes)
            for v in adj[u] if v > u and pair_w(u, v) > 0]
    heapq.heapify(heap)
    while heap:
        nw, u, v = heapq.heappop(heap)
        w = -nw
        ru, rv = find(u), find(v)
        if ru == rv or rv not in adj[ru]:
            continue
        live = pair_w(ru, rv)
        if live != w or u != min(ru, rv) or v != max(ru, rv):
            if live > 0:
                heapq.heappush(heap, (-live, min(ru, rv), max(ru, rv)))
            continue
        parent[rv] = ru
        adj[ru].pop(rv, None)
        adj[rv].pop(ru, None)
        lift[ru].pop(rv, None)
        lift[rv].pop(ru, None)
        for store in (adj, lift):
            for n, w2 in store[rv].items():
                store[n].pop(rv, None)
                store[ru][n] = store[ru].get(n, 0.0) + w2
                store[n][ru] = store[ru][n]
            store[rv].clear()
        for n in adj[ru]:
            pw = pair_w(ru, n)
            if pw > 0:
                heapq.heappush(heap, (-pw, min(ru, n), max(ru, n)))
    roots = np.array([find(i) for i in range(n_nodes)])
    _, labels = np.unique(roots, return_inverse=True)
    return labels.astype(np.uint64)


def _py_lmc_moves(n_nodes, uv, c, luv, lc, labels, max_passes,
                  time_limit: float = 0.0):
    import time as _time

    deadline = _time.monotonic() + time_limit if time_limit else None
    labels = labels.astype(np.uint64).copy()
    local = [dict() for _ in range(n_nodes)]
    lifted = [dict() for _ in range(n_nodes)]
    for (u, v), w in zip(uv, c):
        local[u][v] = local[u].get(v, 0.0) + w
        local[v][u] = local[v].get(u, 0.0) + w
    for (u, v), w in zip(luv, lc):
        lifted[u][v] = lifted[u].get(v, 0.0) + w
        lifted[v][u] = lifted[v].get(u, 0.0) + w
    next_label = int(labels.max()) + 1 if n_nodes else 0
    for _ in range(max_passes):
        if deadline is not None and _time.monotonic() > deadline:
            break
        improved = False
        for x in range(n_nodes):
            if not local[x]:
                continue
            comp_w = {}
            cands = set()
            for n, w in local[x].items():
                comp_w[labels[n]] = comp_w.get(labels[n], 0.0) + w
                cands.add(labels[n])
            for n, w in lifted[x].items():
                comp_w[labels[n]] = comp_w.get(labels[n], 0.0) + w
            own = labels[x]
            w_own = comp_w.get(own, 0.0)
            best_gain, best_label = -w_own, next_label
            for lbl in cands:
                if lbl != own and comp_w[lbl] - w_own > best_gain + 1e-12:
                    best_gain, best_label = comp_w[lbl] - w_own, lbl
            if best_gain > 1e-12:
                labels[x] = best_label
                if best_label == next_label:
                    next_label += 1
                improved = True
        if not improved:
            break
    return labels


# ---------------------------------------------------------------------------
# mutex watershed
# ---------------------------------------------------------------------------

def mutex_clustering(n_nodes: int, uv_attractive: np.ndarray,
                     w_attractive: np.ndarray, uv_mutex: np.ndarray,
                     w_mutex: np.ndarray) -> np.ndarray:
    """Kruskal-style mutex watershed over explicit edge lists
    (affogato compute_mws_clustering equivalent)."""
    uva = _as_uv(uv_attractive)
    uvm = _as_uv(uv_mutex)
    wa = np.ascontiguousarray(w_attractive, dtype=np.float64)
    wm = np.ascontiguousarray(w_mutex, dtype=np.float64)
    lib = _load()
    if lib is not None:
        out = np.empty(n_nodes, dtype=np.uint64)
        lib.mws_clustering(n_nodes, len(uva), uva, wa, len(uvm), uvm, wm, out)
        return out
    return _py_mws(n_nodes, uva, wa, uvm, wm)


def mutex_clustering_packed(n_nodes: int, u: np.ndarray,
                            v_packed: np.ndarray) -> np.ndarray:
    """Mutex-watershed union-find scan over a PRE-SORTED edge stream
    (descending priority; the device extracted and sorted the edges), read
    as it comes down (``ops/mws._sorted_edges_device``): ``u[i] < 0`` or
    bit 29 of ``v_packed[i]`` marks a dropped edge, bit 30 a mutex edge,
    and bits 0-28 hold the partner.  Only the inherently sequential scan
    stays on the host — the std::stable_sort of tens of millions of
    24-byte edge structs was the dominant cost of
    :func:`mutex_clustering`."""
    u = np.ascontiguousarray(u, dtype=np.int32)
    v_packed = np.ascontiguousarray(v_packed, dtype=np.int32)
    lib = _load()
    if lib is not None:
        out = np.empty(n_nodes, dtype=np.uint64)
        lib.mws_clustering_packed(n_nodes, len(u), u, v_packed, out)
        return out
    # pure-python fallback: rebuild (uv, w) lists in stream order with a
    # descending fake priority so _py_mws's sort is a stable no-op
    keep = (u >= 0) & ((v_packed >> 29) & 1 == 0)
    pri = np.arange(int(keep.sum()), 0, -1, dtype="float64")
    am = (v_packed[keep] >> 30) & 1 != 0
    uv = np.stack([u[keep], v_packed[keep] & ((1 << 29) - 1)],
                  axis=1).astype("int64")
    return _py_mws(n_nodes, uv[~am], pri[~am], uv[am], pri[am])


def _py_mws(n_nodes, uva, wa, uvm, wm):
    order_a = [(w, u, v, False) for (u, v), w in zip(uva, wa)]
    order_m = [(w, u, v, True) for (u, v), w in zip(uvm, wm)]
    edges = sorted(order_a + order_m, key=lambda e: -e[0])
    parent = np.arange(n_nodes)

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    mutex = [set() for _ in range(n_nodes)]
    for w, u, v, is_mutex in edges:
        ru, rv = find(u), find(v)
        if ru == rv:
            continue
        if is_mutex:
            mutex[ru].add(rv)
            mutex[rv].add(ru)
        else:
            if rv in mutex[ru]:
                continue
            if len(mutex[ru]) < len(mutex[rv]):
                ru, rv = rv, ru
            parent[rv] = ru
            for c in mutex[rv]:
                mutex[c].discard(rv)
                if c != ru:
                    mutex[c].add(ru)
                    mutex[ru].add(c)
            mutex[rv].clear()
    roots = np.array([find(i) for i in range(n_nodes)])
    _, labels = np.unique(roots, return_inverse=True)
    return labels.astype(np.uint64)


# ---------------------------------------------------------------------------
# agglomerative clustering
# ---------------------------------------------------------------------------

def agglomerative_clustering(n_nodes: int, uv_ids: np.ndarray,
                             edge_weights: np.ndarray,
                             edge_sizes: Optional[np.ndarray] = None,
                             node_sizes: Optional[np.ndarray] = None,
                             threshold: float = 0.5,
                             size_regularizer: float = 0.0) -> np.ndarray:
    """Edge-weighted agglomeration of a RAG: merge the lowest size-weighted
    mean boundary weight while it is below ``threshold``
    (nifty.graph.agglo edgeWeighted/mala cluster-policy equivalent,
    reference: utils/segmentation_utils.py:298-321).  Returns dense labels."""
    uv = _as_uv(uv_ids)
    w = np.ascontiguousarray(edge_weights, dtype=np.float64)
    es = np.ascontiguousarray(
        edge_sizes if edge_sizes is not None else np.ones(len(uv)),
        dtype=np.float64)
    ns = np.ascontiguousarray(
        node_sizes if node_sizes is not None else np.ones(n_nodes),
        dtype=np.float64)
    lib = _load()
    if lib is not None:
        out = np.empty(n_nodes, dtype=np.uint64)
        lib.agglomerate_edge_weighted(n_nodes, len(uv), uv, w, es, ns,
                                      float(threshold),
                                      float(size_regularizer), out)
        return out
    return _py_agglomerate(n_nodes, uv, w, es, ns, threshold,
                           size_regularizer)


def _py_agglomerate(n_nodes, uv, w, es, ns, threshold, size_regularizer):
    import heapq

    adj = [dict() for _ in range(n_nodes)]
    for (u, v), ww, s in zip(uv, w, es):
        if u == v:
            continue
        ws0, s0 = adj[u].get(v, (0.0, 0.0))
        adj[u][v] = (ws0 + ww * s, s0 + s)
        adj[v][u] = adj[u][v]
    nsize = ns.copy()
    parent = np.arange(n_nodes)

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    def prio(ru, rv, ws, s):
        p = ws / s
        if size_regularizer > 0:
            hm = 2.0 / (1.0 / nsize[ru] + 1.0 / nsize[rv])
            p *= (hm / 2.0) ** size_regularizer
        return p

    heap = [(prio(u, v, ws, s), u, v)
            for u in range(n_nodes) for v, (ws, s) in adj[u].items() if v > u]
    heapq.heapify(heap)
    while heap:
        p, u, v = heapq.heappop(heap)
        if p >= threshold:
            break
        ru, rv = find(u), find(v)
        if ru == rv:
            continue
        acc = adj[ru].get(rv)
        if acc is None:
            continue
        live = prio(ru, rv, *acc)
        if live != p or u != min(ru, rv) or v != max(ru, rv):
            heapq.heappush(heap, (live, min(ru, rv), max(ru, rv)))
            continue
        if len(adj[ru]) < len(adj[rv]):
            ru, rv = rv, ru
        parent[rv] = ru
        nsize[ru] += nsize[rv]
        adj[ru].pop(rv, None)
        adj[rv].pop(ru, None)
        for n, (ws2, s2) in adj[rv].items():
            adj[n].pop(rv, None)
            ws0, s0 = adj[ru].get(n, (0.0, 0.0))
            adj[ru][n] = (ws0 + ws2, s0 + s2)
            adj[n][ru] = adj[ru][n]
            heapq.heappush(heap, (prio(ru, find(n), *adj[ru][n]),
                                  min(ru, n), max(ru, n)))
        adj[rv].clear()
    roots = np.array([find(i) for i in range(n_nodes)])
    _, labels = np.unique(roots, return_inverse=True)
    return labels.astype(np.uint64)


# ---------------------------------------------------------------------------
# skeletonization
# ---------------------------------------------------------------------------

def seeded_watershed_u8(height: np.ndarray, seeds: np.ndarray) -> np.ndarray:
    """Seeded 3d priority-flood watershed over a uint8 height map — the
    vigra ``watershedsNew`` algorithm (reference: utils/volume_utils.py:124)
    as a C++ monotone bucket-queue flood; the reference-faithful CPU
    watershed for ``impl='host'`` task configs.  Returns int64 labels
    (seeds preserved, every seed-connected voxel labeled, 6-connectivity).
    """
    if height.ndim != 3:
        raise ValueError("seeded_watershed_u8 expects a 3d volume")
    hq = np.ascontiguousarray(height, dtype=np.uint8)
    labels = np.ascontiguousarray(seeds, dtype=np.int64).copy()
    lib = _load()
    if lib is not None:
        lib.seeded_watershed_u8(hq, *hq.shape, labels)
        return labels
    # fallback without a compiler: the level-ordered flood formulation
    # (ops/watershed.py) on the CPU jax backend — same flooding semantics,
    # slower than the C++ bucket queue.  Negative labels are barriers in
    # the C++ convention: express them as a mask so the flood never enters,
    # and restore them in the output.
    import jax.numpy as jnp

    from ..ops.watershed import seeded_watershed_flood

    if labels.size and labels.max() >= 2 ** 31:
        raise ValueError("python fallback is int32-seeded; relabel first")
    barrier = labels < 0
    out = seeded_watershed_flood(
        jnp.asarray(hq.astype("float32")),
        jnp.asarray(np.where(barrier, 0, labels).astype("int32")),
        mask=jnp.asarray(~barrier))
    out = np.asarray(out).astype(np.int64)
    out[barrier] = labels[barrier]
    return out


def size_filter_u8(height: np.ndarray, labels: np.ndarray,
                   min_size: int) -> np.ndarray:
    """Remove fragments below ``min_size`` and regrow their voxels from
    the surviving neighborhood by a LOCAL priority flood (touches only the
    removed voxels; the reference regrows with a second full watershed).
    Requires the native library (callers fall back to ops.size_filter)."""
    if not have_native():
        raise RuntimeError("size_filter_u8 needs the native library")
    hq = np.ascontiguousarray(height, dtype=np.uint8)
    out = np.ascontiguousarray(labels, dtype=np.int64).copy()
    _load().size_filter_u8(hq, *hq.shape, out, int(min_size))
    return out


def skeletonize_3d(volume: np.ndarray) -> np.ndarray:
    """Thin a 3d binary volume to a 1-voxel skeleton by topological
    border-peeling (skimage skeletonize_3d equivalent; the reference's
    skeletons component uses that — skeletons/skeletonize.py:129-157)."""
    if volume.ndim != 3:
        raise ValueError("skeletonize_3d expects a 3d volume")
    vol = np.ascontiguousarray(volume != 0, dtype=np.uint8)
    lib = _load()
    if lib is not None:
        lib.skeletonize_3d(vol, *vol.shape)
        return vol.astype(bool)
    return _py_skeletonize(vol)


def _py_skeletonize(vol: np.ndarray) -> np.ndarray:
    """Python fallback: same directional border-peeling with simple-point
    tests (slow; small per-object bounding boxes only)."""
    from scipy import ndimage

    vol = vol.astype(bool)
    struct26 = np.ones((3, 3, 3), bool)
    struct6 = ndimage.generate_binary_structure(3, 1)

    def simple_point(padded, z, y, x):
        nb = padded[z - 1:z + 2, y - 1:y + 2, x - 1:x + 2].copy()
        center = nb[1, 1, 1]
        assert center
        nb[1, 1, 1] = False
        lab, n_obj = ndimage.label(nb, structure=struct26)
        if n_obj != 1:
            return False
        bg = ~nb
        bg[1, 1, 1] = False
        # 18-neighborhood only (drop corners)
        manhattan = np.add.outer(np.add.outer(
            np.abs(np.arange(3) - 1), np.abs(np.arange(3) - 1)),
            np.abs(np.arange(3) - 1))
        bg &= manhattan <= 2
        lab_bg, _ = ndimage.label(bg, structure=struct6)
        face_ids = {lab_bg[0, 1, 1], lab_bg[2, 1, 1], lab_bg[1, 0, 1],
                    lab_bg[1, 2, 1], lab_bg[1, 1, 0], lab_bg[1, 1, 2]}
        face_ids.discard(0)
        return len(face_ids) == 1

    changed = True
    while changed:
        changed = False
        for axis in range(3):
            for direction in (-1, 1):
                padded = np.pad(vol, 1)
                shifted = np.roll(padded, direction, axis=axis)
                border = padded & ~shifted
                n_nb = ndimage.convolve(padded.astype(np.uint8),
                                        struct26.astype(np.uint8),
                                        mode="constant") - padded
                cand = np.stack(np.nonzero(border & (n_nb > 1)), 1)
                for z, y, x in cand:
                    if not padded[z, y, x]:
                        continue
                    nbh = padded[z - 1:z + 2, y - 1:y + 2, x - 1:x + 2]
                    if (nbh.sum() - 1) <= 1:
                        continue
                    if simple_point(padded, z, y, x):
                        padded[z, y, x] = False
                        changed = True
                vol = padded[1:-1, 1:-1, 1:-1]
    return vol


# ---------------------------------------------------------------------------
# graph watershed
# ---------------------------------------------------------------------------

def graph_watershed(n_nodes: int, uv_ids: np.ndarray, edge_weights: np.ndarray,
                    seeds: np.ndarray, grow_smallest_first: bool = True
                    ) -> np.ndarray:
    """Seeded watershed on a graph (nifty edgeWeightedWatershedsSegmentation
    equivalent).  ``grow_smallest_first=True`` floods across the lowest
    boundary evidence first (the reference's convention with probability
    weights, postprocess/graph_watershed_assignments.py:172)."""
    uv = _as_uv(uv_ids)
    w = np.ascontiguousarray(edge_weights, dtype=np.float64)
    if grow_smallest_first:
        w = -w
    out = np.ascontiguousarray(seeds, dtype=np.uint64).copy()
    lib = _load()
    if lib is not None:
        lib.graph_watershed(n_nodes, len(uv), uv, w, out)
        return out
    # fallback: heap-based python
    import heapq

    adj = [[] for _ in range(n_nodes)]
    for (u, v), ww in zip(uv, w):
        adj[u].append((v, ww))
        adj[v].append((u, ww))
    heap = []
    for i in range(n_nodes):
        if out[i]:
            for n, ww in adj[i]:
                if not out[n]:
                    heapq.heappush(heap, (-ww, i, n))
    while heap:
        nw, frm, to = heapq.heappop(heap)
        if out[to]:
            continue
        out[to] = out[frm]
        for n, ww in adj[to]:
            if not out[n]:
                heapq.heappush(heap, (-ww, to, n))
    return out
