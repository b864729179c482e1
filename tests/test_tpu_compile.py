"""The flagship's device kernels compile for a TPU v5e chip, at real widths.

No chip is attached: the TPU compiler builds each program for a DESCRIBED
v5e (``jax.experimental.topologies``), which refuses what the chip's
compiler would refuse — misaligned Pallas tiles, fast-memory overuse,
programs that do not fit HBM.  Nothing runs, so nothing here is a result
or a time.  Widths are the flagship's: the [50,512,512] reference block
with the default halo [4,32,32] (outer block [58,576,576]), u8 input,
``bench.run_chain``'s pair_cap 2^21 / coarse_factor 4 / 6 refine rounds.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and a worker that describes it
while collecting would hand the other xdist workers different tests.
"""

import os

import numpy as np
import pytest

OUTER = (58, 576, 576)
INNER = (50, 512, 512)
HALO = (4, 32, 32)
E_MAX = 16384
PAIR_CAP = 1 << 21
RLE_CAP = 1 << 20
COARSE = 4


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e host, with JAX's persistent cache off:
    a compile for a described chip is written to the cache but cannot be
    read back without one."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, np.dtype(dtype), sharding=sharding)


@pytest.mark.parametrize("shape", [OUTER, INNER],
                         ids=["outer-58x576x576", "block-50x512x512"])
def test_pallas_edt_compiles(one_chip, shape):
    """The Pallas min-plus EDT over all three axes — the only EDT path on a
    TPU (``ops/edt._use_pallas``)."""
    from cluster_tools_tpu.ops.edt import _edt_impl

    compiled = _edt_impl.lower(_spec(shape, bool, one_chip), None, 65536,
                               None, True).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _pairs_hist_packed(dense, x):
    """The resident program's uint8 pairs+hist stage: dual-sample boundary
    pairs, packed compaction, packed histogram edge statistics."""
    from cluster_tools_tpu.ops.rag import (boundary_pair_values_dual,
                                           compact_valid)

    u, v, va, vb, okp = boundary_pair_values_dual(dense, x)
    vab = va.astype(np.int32) * 256 + vb.astype(np.int32)
    (ckey, cvab), cok, over = compact_valid(okp, [u * 32768 + v, vab],
                                            PAIR_CAP)
    return _hist_packed(ckey, cvab, cok), over


def _hist_packed(key, vab, ok):
    """The histogram edge statistics over the compacted packed pairs."""
    from cluster_tools_tpu.ops.rag import _edge_stats_hist_packed

    return _edge_stats_hist_packed(key, vab, ok, e_max=E_MAX)


def _dense_relabel(inner, extent):
    """The per-block dense relabel of the coarse watershed's ids, masked
    to the block's clipped extent (an argument, as in the program)."""
    from cluster_tools_tpu.ops.watershed import (dense_relabel,
                                                 extent_valid_mask)

    bound = int(np.prod([-(-o // COARSE) for o in OUTER]))
    valid = extent_valid_mask(inner.shape, extent=extent)
    return dense_relabel(inner, bound, valid=valid)


def _rle(flat):
    from cluster_tools_tpu.ops.sweep import rle_encode_packed

    return rle_encode_packed(flat, RLE_CAP)


def _stage_args(stage, sh):
    if stage == "dense_relabel":
        return _dense_relabel, (_spec(INNER, np.int32, sh),
                                _spec((3,), np.int32, sh))
    if stage == "hist_packed":
        return _hist_packed, (_spec((PAIR_CAP,), np.int32, sh),
                              _spec((PAIR_CAP,), np.int32, sh),
                              _spec((PAIR_CAP,), bool, sh))
    if stage == "pairs_hist_packed":
        return _pairs_hist_packed, (_spec(INNER, np.int32, sh),
                                    _spec(INNER, np.uint8, sh))
    return _rle, (_spec((int(np.prod(INNER)),), np.int32, sh),)


# the TPU backend spends ~50 s generating code for each of the two stages
# that stream the block's full 13-39 M-element arrays, so they run with
# the whole-program compile, outside tier-1
@pytest.mark.parametrize("stage", [
    "dense_relabel", "hist_packed",
    pytest.param("pairs_hist_packed", marks=pytest.mark.slow),
    pytest.param("rle_encode", marks=pytest.mark.slow)])
def test_fused_stage_compiles(one_chip, stage):
    """Stages of the per-block resident program, each alone at the outer
    block's widths."""
    import jax

    fn, args = _stage_args(stage, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.slow
def test_resident_program_compiles(one_chip, monkeypatch):
    """The whole streamed per-block program at ``bench.run_chain``'s
    settings against the CREMI-padded (158,1600,1600) resident volume;
    ~2 min of host CPU.  The program asks ``jax.default_backend()`` for
    its EDT path and sees the CPU here, so the test steers it to the
    TPU's."""
    from cluster_tools_tpu.ops import edt
    from cluster_tools_tpu.workflows.fused_pipeline import _resident_program

    monkeypatch.setattr(edt, "_use_pallas", lambda: True)
    _resident_program.cache_clear()
    try:
        prog = _resident_program(OUTER, HALO, "uint8", 0.4, 2.0, 2.0, 0.8,
                                 50, E_MAX, RLE_CAP, 6, PAIR_CAP, COARSE)
        compiled = prog.lower(_spec((158, 1600, 1600), np.uint8, one_chip),
                              _spec((6,), np.int32, one_chip)).compile()
    finally:
        _resident_program.cache_clear()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
