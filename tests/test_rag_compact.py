"""``ops/rag.compact_valid`` against a plain numpy compaction, and the
resident program's edge table against the per-slot scatter it replaced."""

import itertools

import numpy as np
import pytest

from cluster_tools_tpu.ops.rag import _COMPACT_TILE as T

DENSITIES = [0.0, 0.032, 0.25, 1.0]
LENGTHS = [T // 2 + 3, 5 * T + 19, 8 * T]          # n < T, ragged, whole
CAPS = ["below", "equal", "above"]
CHANNELS = [("int32",), ("float32",), ("int32", "float32"),
            ("int32", "int32", "float32")]
CASES = [(d, n, cap, CHANNELS[i % len(CHANNELS)]) for i, (d, n, cap) in
         enumerate(itertools.product(DENSITIES, LENGTHS, CAPS))]


def _reference(ok, arrays, cap):
    idx = np.flatnonzero(ok)[:cap]
    out = []
    for x in arrays:
        o = np.zeros(cap, x.dtype)
        o[:len(idx)] = x[idx]
        out.append(o)
    return out, np.arange(cap) < len(idx), max(int(ok.sum()) - cap, 0)


@pytest.mark.parametrize("density, n, cap_mode, dtypes", CASES,
                         ids=[f"d{d}-n{n}-{c}-{'+'.join(t)}"
                              for d, n, c, t in CASES])
def test_compact_valid_matches_numpy(density, n, cap_mode, dtypes):
    """Slot s holds the s-th valid sample of every channel, zeros past the
    valid count, ``cok`` flags the filled slots and the overflow counts
    what did not fit — whatever the density, the length against the tile
    width, the capacity against the valid count, and the channel types.
    Invalid slots hold nonzero values, so a leak shows."""
    import jax.numpy as jnp

    from cluster_tools_tpu.ops.rag import compact_valid

    rng = np.random.default_rng(n * 7 + len(dtypes))
    ok = rng.random(n) < density
    arrays = [rng.integers(1, 1 << 30, n, dtype=np.int32) if dt == "int32"
              else rng.standard_normal(n).astype(np.float32) for dt in dtypes]
    n_valid = int(ok.sum())
    cap = {"below": max(n_valid // 2, 1), "equal": max(n_valid, 1),
           "above": n_valid + 37}[cap_mode]
    got, cok, over = compact_valid(jnp.asarray(ok),
                                   [jnp.asarray(x) for x in arrays], cap)
    want, cok_r, over_r = _reference(ok, arrays, cap)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), w)
    np.testing.assert_array_equal(np.asarray(cok), cok_r)
    assert int(over) == over_r


def _per_slot_compact(ok, arrays, cap):
    """The former ``compact_valid``: one scatter update per slot, invalid
    slots sent out of bounds."""
    import jax.numpy as jnp

    idx = jnp.cumsum(ok.astype(jnp.int32)) - 1
    tgt = jnp.where(ok & (idx < cap), idx, cap + 1)
    n_valid = jnp.sum(ok.astype(jnp.int32))
    cok = jnp.arange(cap, dtype=jnp.int32) < jnp.minimum(n_valid, cap)
    return ([jnp.zeros((cap + 1,), x.dtype).at[tgt].set(
        x, mode="drop")[:cap] for x in arrays],
        cok, jnp.maximum(n_valid - cap, 0))


@pytest.mark.parametrize("in_dtype", ["uint8", "float32"])
def test_resident_table_unchanged_by_tiled_compaction(monkeypatch, in_dtype):
    """One small block through the resident program, on the packed uint8
    path and the float path: the combined meta + edge table + features,
    the run-length labels and the dense labels equal those built on the
    per-slot scatter."""
    import jax.numpy as jnp

    from cluster_tools_tpu.ops import rag
    from cluster_tools_tpu.workflows.fused_pipeline import _resident_program

    rng = np.random.default_rng(3)
    vol = rng.random((20, 36, 36))
    vol = ((vol * 255).astype(np.uint8) if in_dtype == "uint8"
           else vol.astype(np.float32))
    origin_extent = jnp.asarray([2, 5, 7, 12, 20, 20], jnp.int32)
    args = ((12, 20, 20), (2, 2, 2), in_dtype, 0.4, 2.0, 2.0, 0.8, 5, 4096,
            1 << 12, 3, 1 << 14, 2)

    def run():
        _resident_program.cache_clear()
        try:
            out = _resident_program(*args)(jnp.asarray(vol), origin_extent)
        finally:
            _resident_program.cache_clear()
        return [np.asarray(a) for a in out]

    tiled = run()
    monkeypatch.setattr(rag, "compact_valid", _per_slot_compact)
    per_slot = run()
    tbl = tiled[0]
    assert tbl[0, 1] > 10                       # edges found
    for a, b in zip(tiled, per_slot):
        np.testing.assert_array_equal(a, b)
