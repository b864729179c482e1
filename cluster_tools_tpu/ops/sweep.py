"""Gather-free seeded watershed via directional bottleneck scans.

TPU-native replacement for the priority-flood watershed (reference:
vigra ``watershedsNew`` via utils/volume_utils.py:123-139 and
watershed/watershed.py:211-249).  The flood's label assignment is the
bottleneck (minimax) shortest-path forest: a voxel joins the seed whose
path minimizes the maximum height along the way (watershed cuts, Cousty
et al.) — and bottleneck costs form a (min, max) semiring, so the
recurrence

    out[i] = min(state[i], max(out[i-1], h[i]))

composes ASSOCIATIVELY along grid lines.  Each sweep is one
``lax.associative_scan`` over an axis (forward or reverse), which XLA
lowers to log-depth vectorized passes: label fronts cross an entire grid
line per sweep with ZERO random gathers.  Six directional sweeps
(Gauss-Seidel: each feeds the next) make one round; rounds repeat until
the monotone-decreasing state reaches its fixpoint.  Basin diameters in
EM fragments are tens of voxels, so a handful of rounds converge — vs
the ~80 ms/19M-element random gathers that made pointer-jumping
formulations (`ops/watershed.seeded_watershed_basins`) gather-bound.

The path cost is Meyer's TOPOGRAPHIC DISTANCE (Meyer '94 — the standard
shortest-path-forest characterization of the watershed transform): each
step into voxel ``v`` from neighbor ``u`` costs
``max(0, h[v] - h[u]) * 256 + 1`` — total ascent, with a per-step unit
so plateaus resolve by geodesic BFS distance exactly like a flood
front.  On smooth height fields the minimum-ascent path follows the
gradient, so basins match the gradient-descent watershed (a pure
bottleneck/minimax cost does NOT: every voxel above the lowest saddle
is bottleneck-tied between basins and the labeling collapses to
arbitrary tie-breaks — measured VI ~1.0 vs the flood on CREMI-like
data, vs ~0.1 for topographic distance).  Min-plus path composition is
exactly associative, so each directional sweep is one
``associative_scan``; labels ride as a separate lexicographic
tie-break leaf.

A transit flag threaded through the scan keeps labels from crossing
masked voxels (composition over (value, barrier) pairs stays
associative); the same algebra with zero step costs yields connected
components by min-index propagation (`sweep_cc`).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_INF = np.uint32(0xFFFFFFFF)
#: packed path cost, lexicographic: total ascent (anchored at the seed's
#: own height, 14 bits) | steps since the last ascent (9 bits) | total
#: steps (9 bits), all saturating.  The three levels earn their place:
#: ascent alone ties every above-saddle voxel between basins;
#: steps-since-last-ascent divides contested level bands like the flood
#: front's BFS; total steps breaks the remaining tie at a fresh riser
#: (where both fronts just reset) toward the nearer basin.
CLIMB_BITS, RSTEP_BITS, TSTEP_BITS = 14, 9, 9
_CLIMB_MAX = np.uint32((1 << CLIMB_BITS) - 1)
_RSTEP_MAX = np.uint32((1 << RSTEP_BITS) - 1)
_TSTEP_MAX = np.uint32((1 << TSTEP_BITS) - 1)


def _lex_min(P1, lab1, P2, lab2):
    take1 = (P1 < P2) | ((P1 == P2) & (lab1 <= lab2))
    return jnp.where(take1, P1, P2), jnp.where(take1, lab1, lab2)


def _pack(climb, rsteps, tsteps):
    # tsteps saturates one short of its field max: a fully saturated pack
    # would otherwise equal the _INF unreachable sentinel exactly
    return ((jnp.minimum(climb, _CLIMB_MAX) << (RSTEP_BITS + TSTEP_BITS))
            | (jnp.minimum(rsteps, _RSTEP_MAX) << TSTEP_BITS)
            | jnp.minimum(tsteps, _TSTEP_MAX - 1))


def _transfer(P, C, t, L):
    """Move a carried front across a segment with total ascent C,
    trailing no-ascent run t, and length L: ascent accumulates; the
    reset-step counter restarts at t when the segment ascends, else
    grows by L; total steps always grow by L.  INF stays absorbing."""
    climb = (P >> (RSTEP_BITS + TSTEP_BITS)) + C
    rsteps = jnp.where(C > 0, t, ((P >> TSTEP_BITS) & _RSTEP_MAX) + L)
    tsteps = (P & _TSTEP_MAX) + L
    return jnp.where(P == _INF, _INF, _pack(climb, rsteps, tsteps))


def _ws_combine(left, right):
    """Compose two min-plus path segments.

    An element is ``(A, lab, C, t, L, m)``: (A, lab) = cheapest packed
    (ascent, reset-steps, total-steps, label) ending at the segment's
    last voxel from a source WITHIN the segment; (C, t, L) = segment
    metadata (total ascent, trailing no-ascent run, length); m = segment
    free of masked voxels.  Represents
    ``f(carry) = min(A, m ? transfer(carry) : INF)``.  Associative up to
    exact packed-cost ties — the class the flood itself resolves by
    queue order.
    """
    A1, l1, C1, t1, L1, m1 = left
    A2, l2, C2, t2, L2, m2 = right
    moved = jnp.where(m2, _transfer(A1, C2, t2, L2), _INF)
    A, lab = _lex_min(moved, l1, A2, l2)
    C = jnp.minimum(C1 + C2, _CLIMB_MAX)
    t = jnp.where(C2 > 0, t2, jnp.minimum(t1 + L2, _RSTEP_MAX))
    L = jnp.minimum(L1 + L2, _TSTEP_MAX)
    return A, lab, C, t, L, m1 & m2


def _cc_combine(left, right):
    A1, m1 = left
    A2, m2 = right
    return jnp.minimum(A2, jnp.where(m2, A1, _INF)), m1 & m2


def _step_elems(hq: jnp.ndarray, axis: int, reverse: bool):
    """Per-voxel segment metadata for a directional sweep: the ascent
    entering voxel i from its predecessor, and the trailing no-ascent
    run (0 after an ascent, else 1).  Line-leading voxels have no
    predecessor; their metadata only matters for carries, which start
    at INF there."""
    h = hq.astype(jnp.int32)
    off = [0] * h.ndim
    off[axis] = 1 if reverse else -1
    from .components import _shifted

    prev = _shifted(h, off, 255)
    climb = jnp.maximum(h - prev, 0).astype(jnp.uint32)
    t = jnp.where(climb > 0, jnp.uint32(0), jnp.uint32(1))
    return climb, t


def _ws_round(state_A, state_lab, hq, m, pin_A, pin_lab, seeded,
              ndim: int):
    """One Gauss-Seidel round: 2*ndim directional scans, seeds re-pinned
    after each (a foreign front must not relabel a seed)."""
    ones = jnp.ones(hq.shape, jnp.uint32)
    for axis in range(ndim):
        for reverse in (False, True):
            C, t = _step_elems(hq, axis, reverse)
            state_A, state_lab, _, _, _, _ = jax.lax.associative_scan(
                _ws_combine, (state_A, state_lab, C, t, ones, m),
                axis=axis, reverse=reverse)
            state_A = jnp.where(seeded, pin_A, state_A)
            state_lab = jnp.where(seeded, pin_lab, state_lab)
    return state_A, state_lab


@partial(jax.jit, static_argnames=("max_rounds", "min_size", "k_cap"))
def sweep_watershed_impl(
    hq: jnp.ndarray,
    seeds: jnp.ndarray,
    mask: Optional[jnp.ndarray],
    max_rounds: int = 24,
    min_size: int = 0,
    k_cap: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Jittable core: uint8 heights, dense int32 seed ids (< 2^24).

    Returns ``(labels int32, converged bool)``.  Unreachable voxels
    (outside mask, or cut off by it) keep label 0.  ``min_size`` strips
    fragments below the threshold and re-floods their voxels from the
    surviving fragments (the reference's watershed-and-size-filter,
    utils/volume_utils.py:123-139); it requires a static ``k_cap`` bound
    on the seed-id space for the on-device size histogram.
    """
    shape = hq.shape
    ndim = len(shape)
    m = jnp.ones(shape, bool) if mask is None else mask.astype(bool)
    seeded = (seeds > 0) & m
    # a seed's cost starts at its OWN height: within a basin the ascent
    # total to v is then ~ h[v] regardless of seed depth, so a deep/high
    # seed cannot "ride a contour" into a neighbor's above-saddle
    # shoulder for free (seed-cost-0 variants lose whole shoulder bands
    # to the deepest neighbor: measured VI ~1.0 vs the flood)
    pin_A = jnp.where(seeded, _pack(hq.astype(jnp.uint32),
                                    jnp.uint32(0), jnp.uint32(0)), _INF)
    pin_lab = jnp.where(seeded, seeds.astype(jnp.uint32), _INF)

    def run_rounds(A, lab, pA, plab, pinned):
        def body(carry):
            cA, clab, _, it = carry
            nA, nlab = _ws_round(cA, clab, hq, m, pA, plab, pinned, ndim)
            return (nA, nlab, jnp.any((nA != cA) | (nlab != clab)),
                    it + 1)

        A, lab, changed, _ = jax.lax.while_loop(
            lambda c: c[2] & (c[3] < max_rounds), body,
            (A, lab, jnp.bool_(True), jnp.int32(0)))
        return A, lab, ~changed

    P, lab, converged = run_rounds(pin_A, pin_lab, pin_A, pin_lab, seeded)

    if min_size:
        if not k_cap:
            raise ValueError("min_size needs a static k_cap")
        labels = jnp.where(m & (P < _INF), lab,
                           0).astype(jnp.uint32)
        clipped = jnp.minimum(labels, jnp.uint32(k_cap)).astype(jnp.int32)
        sizes = jax.ops.segment_sum(
            jnp.where(m, 1, 0).reshape(-1), clipped.reshape(-1),
            num_segments=k_cap + 1)
        small = (sizes < min_size) & (sizes > 0)
        small = small.at[0].set(False)
        strip = small[clipped]
        # stripped voxels revert to unlabeled; surviving fragment BODIES
        # act as the new seed set (every labeled voxel is already a
        # fixpoint source), so the re-flood is just more rounds
        P = jnp.where(strip, _INF, P)
        lab = jnp.where(strip, _INF, lab)
        pinned2 = seeded & ~strip
        P, lab, conv2 = run_rounds(P, lab, pin_A, pin_lab, pinned2)
        converged &= conv2

    labels = jnp.where(m & (P < _INF), lab, 0)
    return labels.astype(jnp.int32), converged


@partial(jax.jit, static_argnames=("max_rounds",))
def sweep_cc_impl(mask: jnp.ndarray, max_rounds: int = 32):
    """Connected components (face connectivity) by min-linear-index
    propagation with the same directional-scan machinery.  Returns
    ``(labels int32 — root_index + 1, 0 outside mask —, converged)``;
    identical labeling contract to ``ops.components.connected_components``.
    """
    shape = mask.shape
    ndim = len(shape)
    n = int(np.prod(shape))
    m = mask.astype(bool)
    idx = jnp.arange(n, dtype=jnp.uint32).reshape(shape)
    state0 = jnp.where(m, idx, _INF)

    def one_round(s):
        for axis in range(ndim):
            for reverse in (False, True):
                s, _ = jax.lax.associative_scan(
                    _cc_combine, (s, m), axis=axis, reverse=reverse)
        return s

    def body(carry):
        s, _, it = carry
        s2 = one_round(s)
        return s2, jnp.any(s2 != s), it + 1

    state, changed, _ = jax.lax.while_loop(
        lambda c: c[1] & (c[2] < max_rounds), body,
        (state0, jnp.bool_(True), jnp.int32(0)))
    labels = jnp.where(m, state + 1, 0).astype(jnp.int32)
    return labels, ~changed


def compact_ids(labels: jnp.ndarray, cap: int):
    """Dense-rank positive ids (device np.unique analog): presence flags +
    cumsum.  Ids must be < ``cap``.  Returns ``(dense int32 — 1..k, same
    zeros —, k)``."""
    flat = labels.reshape(-1).astype(jnp.int32)
    pres = jnp.zeros((cap + 2,), jnp.int32).at[
        jnp.minimum(flat, cap + 1)].set(1, mode="drop")
    pres = pres.at[0].set(0)
    rank = jnp.cumsum(pres)
    dense = jnp.where(flat > 0, rank[jnp.minimum(flat, cap + 1)], 0)
    return dense.reshape(labels.shape).astype(jnp.int32), rank[cap + 1]


def sweep_watershed(
    height,
    seeds,
    mask=None,
    connectivity: int = 1,
    min_size: int = 0,
    max_rounds: int = 48,
) -> jnp.ndarray:
    """Host-facing wrapper matching ``ops.watershed.seeded_watershed``:
    float heights (normalized to uint8 levels), arbitrary positive seed
    ids.  Quantization to 256 levels matches the hybrid path's uint8
    flood (the reference's own CNN outputs are uint8,
    inference/inference.py:235)."""
    if connectivity != 1:
        raise ValueError("sweep watershed propagates along faces "
                         "(connectivity=1)")
    height = jnp.asarray(height)
    seeds = jnp.asarray(seeds)
    if height.dtype == jnp.uint8:
        hq = height
    else:
        h = height.astype(jnp.float32)
        lo = h.min()
        hq = jnp.clip(jnp.round((h - lo) / jnp.maximum(h.max() - lo, 1e-6)
                                * 255.0), 0, 255).astype(jnp.uint8)
    n = int(np.prod(height.shape))
    # host-side dense compaction: this wrapper is the convenience path
    # (callers may pass arbitrary, e.g. globally-offset, seed ids that
    # exceed the device rank-scatter's id range); the fused hot path
    # calls sweep_watershed_impl directly with device-compacted ids
    seeds_np = np.asarray(seeds)
    uniq = np.unique(seeds_np)
    uniq = uniq[uniq > 0]
    k = len(uniq)
    # ctt-lint: disable=dtype-int32 (this IS the sanctioned compaction: searchsorted ranks are < k <= block voxel count, never raw global ids)
    dense = np.searchsorted(uniq, seeds_np).astype("int32") + 1
    dense[seeds_np <= 0] = 0
    dense = jnp.asarray(dense)
    if min_size:
        # pow2-rounded histogram size bounds recompiles across calls
        k_cap = 1 << max(int(np.ceil(np.log2(max(k, 2)))), 6)
    else:
        k_cap = 0
    dense_lab, converged = sweep_watershed_impl(
        hq, dense, mask, max_rounds=max_rounds, min_size=min_size,
        k_cap=k_cap)
    if not bool(converged):  # pathological serpentine plateaus
        dense_lab, _ = sweep_watershed_impl(
            hq, dense, mask, max_rounds=4 * max_rounds, min_size=min_size,
            k_cap=k_cap)
    # map dense ranks back to the caller's seed ids
    if uniq.size and uniq[-1] >= np.iinfo(np.int32).max:
        raise ValueError("seed ids exceed int32")
    lab = np.asarray(dense_lab)
    out = np.zeros(lab.shape, np.int64)
    fg = lab > 0
    out[fg] = uniq.astype(np.int64)[lab[fg] - 1]
    return jnp.asarray(out.astype(np.int32))


def rle_encode(flat: jnp.ndarray, cap: int):
    """Run-length encode a flat label array on device: returns
    ``(starts uint32[cap], values int32[cap], n_runs, ok)``.  Invalid
    slots scatter out of bounds (mode='drop') — fixed-cap buffers, the
    host downloads only the ``n_runs`` prefix (chunked dynamic slices).
    Segmentation volumes are piecewise constant, so runs ~ voxels /
    mean-run-length — an order of magnitude less link traffic than the
    dense grid."""
    n = int(flat.shape[0])
    brk = jnp.concatenate([jnp.ones((1,), bool), flat[1:] != flat[:-1]])
    tgt = jnp.cumsum(brk.astype(jnp.int32)) - 1
    n_runs = jnp.where(n > 0, tgt[-1] + 1, 0)
    ok = n_runs <= cap
    tgt = jnp.where(brk & (tgt < cap), tgt, cap + 2)
    starts = jnp.zeros((cap + 1,), jnp.uint32).at[tgt].set(
        jnp.arange(n, dtype=jnp.uint32), mode="drop")[:cap]
    values = jnp.zeros((cap + 1,), jnp.int32).at[tgt].set(
        flat.astype(jnp.int32), mode="drop")[:cap]
    return starts, values, n_runs, ok


def rle_decode(starts: np.ndarray, values: np.ndarray, total: int) -> np.ndarray:
    """Host-side inverse of :func:`rle_encode` (numpy repeat)."""
    starts = np.asarray(starts, dtype=np.int64)
    lengths = np.diff(np.append(starts, total))
    return np.repeat(np.asarray(values), lengths)


#: forced run break interval for the packed encoder: lengths must fit 16
#: bits, so runs are split at this stride (adds ~n/stride extra runs)
RLE_STRIDE = np.uint32(1 << 15)


def rle_encode_packed(flat: jnp.ndarray, cap: int):
    """Run-length encode label ids < 2^16 into ONE uint32 stream,
    ``length << 16 | value`` per run (runs force-split every RLE_STRIDE
    elements so lengths fit).  The host downloads the fixed-cap buffer
    with a single transfer — no device-side prefix program that would
    queue behind in-flight block programs — and decodes with one
    ``np.repeat``.  Returns ``(packed uint32[cap], n_runs, ok)``; ok
    is False on cap overflow OR ids >= 2^16 (caller falls back to a
    dense download)."""
    n = int(flat.shape[0])
    idx = jnp.arange(n, dtype=jnp.uint32)
    brk = jnp.concatenate([jnp.ones((1,), bool),
                           flat[1:] != flat[:-1]])
    brk |= (idx % RLE_STRIDE) == 0
    tgt = jnp.cumsum(brk.astype(jnp.int32)) - 1
    n_runs = jnp.where(n > 0, tgt[-1] + 1, 0)
    ok = (n_runs <= cap) & (flat.max() < (1 << 16))
    # run length AT each break position = next break index - own index,
    # from a reversed exclusive cummin of break indices — lengths then
    # ride the same packed word as the value, so the encoder pays ONE
    # O(n) scatter pass instead of two (starts + values)
    m = jnp.where(brk, idx, jnp.uint32(n))
    # lax.cummin is the lowered scan primitive; associative_scan's
    # recursive slicing formulation stalled the XLA compile at this
    # length
    nb = jax.lax.cummin(m, reverse=True)
    nb_next = jnp.concatenate([nb[1:], jnp.full((1,), n, jnp.uint32)])
    lengths = jnp.where(brk, nb_next - idx, 0)
    packed_full = (lengths << 16) | (flat.astype(jnp.uint32)
                                     & jnp.uint32(0xFFFF))
    tgt_c = jnp.where(brk & (tgt < cap), tgt, cap + 2)
    packed = jnp.zeros((cap + 1,), jnp.uint32).at[tgt_c].set(
        packed_full, mode="drop")[:cap]
    return packed, n_runs, ok


def rle_decode_packed(packed: np.ndarray, n_runs: int,
                      total: int) -> np.ndarray:
    """Host-side inverse of :func:`rle_encode_packed`."""
    arr = np.asarray(packed[:n_runs])
    lengths = (arr >> 16).astype(np.int64)
    values = (arr & 0xFFFF).astype(np.uint16)
    out = np.repeat(values, lengths)
    if out.size != total:
        raise ValueError(f"RLE decode size {out.size} != {total}")
    return out
