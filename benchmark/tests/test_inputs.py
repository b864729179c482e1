"""A configuration's input: the ``"input"`` hook of the harness, the
affinity generator against a plain numpy oracle, and N5 stores of any
rank."""

import json
import os

import numpy as np
import pytest

import affinities
import n5
import run
import worley

SEED = 2 ** 33 + 23  # wider than 32 bits, as a benchmark seed may be
# the neighbourhood a mutex-watershed deployment uses: 3 direct, 9 long
OFFSETS = [(-1, 0, 0), (0, -1, 0), (0, 0, -1),
           (-2, 0, 0), (0, -3, 0), (0, 0, -3),
           (-3, 0, 0), (0, -9, 0), (0, 0, -9),
           (-4, 0, 0), (0, -27, 0), (0, 0, -27)]
SHAPE = (24, 72, 80)


@pytest.fixture(scope="module")
def clean():
    return worley.load_mix("clean")


def small(cfg, shape, block):
    return dict(cfg, shape=list(shape), global_config=dict(
        cfg["global_config"], block_shape=list(block)))


@pytest.mark.parametrize("config", ["cremi_a_multicut", "cremi_a_watershed"])
def test_existing_configurations_take_the_boundary_map(config):
    cfg = run.load_json(os.path.join(run.HERE, "configs", config + ".json"))
    assert "input" not in cfg
    gen, args = run.input_spec(cfg)
    assert gen is worley and args == {}


@pytest.mark.parametrize("config", ["cremi_a_multicut", "cremi_a_watershed"])
def test_default_input_is_the_direct_boundary_map(tmp_path, clean, config):
    """Through the hook, the bytes of a direct ``worley.generate`` call, in
    chunks of the block, with the N5 metadata the harness always wrote."""
    cfg = run.load_json(os.path.join(run.HERE, "configs", config + ".json"))
    cfg = small(cfg, (20, 64, 70), (8, 32, 32))
    setup = {}
    vol = run.make_input(cfg, clean, SEED, str(tmp_path / "in.n5"), setup)
    direct = worley.generate((20, 64, 70), SEED, clean)
    assert vol.dtype == np.uint8 and np.array_equal(vol, direct)
    assert np.array_equal(n5.read(str(tmp_path / "in.n5"), "bmap"), direct)
    with open(tmp_path / "in.n5" / "bmap" / "attributes.json") as f:
        meta = json.load(f)
    assert meta == {"dimensions": [70, 64, 20], "blockSize": [32, 32, 8],
                    "dataType": "uint8", "compression": {"type": "raw"}}
    assert set(setup) == {"generate_s", "store_s"}


def test_configuration_names_its_generator(tmp_path, clean):
    """A configuration with ``"input"`` gets that module's array, stored in
    one chunk of the block per channel."""
    base = run.load_json(os.path.join(run.HERE, "configs",
                                      "cremi_a_multicut.json"))
    cfg = small(base, (16, 40, 36), (8, 32, 32))
    cfg["input"] = {"generator": "affinities",
                    "args": {"offsets": OFFSETS[:4]}}
    vol = run.make_input(cfg, clean, SEED, str(tmp_path / "a.n5"))
    assert vol.shape == (4, 16, 40, 36)
    assert np.array_equal(vol, affinities.generate((16, 40, 36), SEED, clean,
                                                   OFFSETS[:4]))
    with open(tmp_path / "a.n5" / "bmap" / "attributes.json") as f:
        assert json.load(f)["blockSize"] == [32, 32, 8, 1]
    assert np.array_equal(n5.read(str(tmp_path / "a.n5"), "bmap"), vol)


@pytest.mark.parametrize("name", ["no_such_generator", "../run", "os"])
def test_unknown_generator_is_refused(name):
    with pytest.raises(SystemExit):
        run.input_spec({"name": "x", "input": {"generator": name}})


def oracle(lab, u8, off):
    """The affinity of one offset in float64, each voxel's partner looked
    up by its index."""
    b = u8.astype(np.float64) / 255.0
    x = np.indices(lab.shape).reshape(3, -1)
    p = x + np.array(off)[:, None]
    inside = np.all((p >= 0) & (p < np.array(lab.shape)[:, None]), axis=0)
    x, p = tuple(x[:, inside]), tuple(p[:, inside])
    aff = np.zeros(lab.shape)
    aff[x] = (lab[x] == lab[p]) * (1.0 - np.maximum(b[x], b[p]))
    return np.round(255.0 * aff).astype(np.uint8)


@pytest.fixture(scope="module")
def affs(clean):
    u8, lab = worley.generate(SHAPE, SEED, clean, with_labels=True)
    return u8, lab, affinities.generate(SHAPE, SEED, clean, OFFSETS)


@pytest.mark.parametrize("c", range(len(OFFSETS)))
def test_affinities_match_the_oracle(affs, c):
    u8, lab, got = affs
    assert got.shape == (len(OFFSETS),) + SHAPE and got.dtype == np.uint8
    assert np.array_equal(got[c], oracle(lab, u8, OFFSETS[c]))


def test_direct_affinities_are_zero_across_cells(affs):
    u8, lab, got = affs
    assert len(np.unique(lab)) > 2, "the volume holds too few cells"
    for c, axis in enumerate((0, 1, 2)):
        cut = np.diff(lab, axis=axis) != 0     # lab(x-1) != lab(x)
        inner = np.take(got[c], np.arange(1, SHAPE[axis]), axis=axis)
        assert cut.any() and not inner[cut].any()
        assert not np.take(got[c], 0, axis=axis).any()  # partner outside
        assert inner[~cut].mean() > 100  # high inside a cell


def test_affinities_follow_the_seed(clean):
    a = affinities.generate((12, 40, 40), 5, clean, OFFSETS[:3])
    assert np.array_equal(a, affinities.generate((12, 40, 40), 5, clean,
                                                 OFFSETS[:3]))
    assert not np.array_equal(a, affinities.generate((12, 40, 40), 6, clean,
                                                     OFFSETS[:3]))


def test_offset_past_the_volume_gives_zeros(clean):
    a = affinities.generate((6, 20, 20), 5, clean, [(0, -27, 0), (7, 0, 0)])
    assert a.shape == (2, 6, 20, 20) and not a.any()


def test_n5_round_trip_4d_per_channel_chunks(tmp_path):
    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, (3, 10, 21, 17), dtype=np.uint8)
    path = str(tmp_path / "x.n5")
    n5.write(path, "affs", data, (1, 4, 8, 8))
    with open(tmp_path / "x.n5" / "affs" / "attributes.json") as f:
        meta = json.load(f)
    assert meta["dimensions"] == [17, 21, 10, 3]
    assert meta["blockSize"] == [8, 8, 4, 1]
    assert np.array_equal(n5.read(path, "affs"), data)
    assert np.array_equal(n5.read(path, "affs", (1, 2, 3, 4), (3, 9, 20, 15)),
                          data[1:3, 2:9, 3:20, 4:15])
    # one chunk file per channel, in N5's fastest-first grid
    assert sorted(os.listdir(tmp_path / "x.n5" / "affs" / "0" / "0" / "0")) \
        == ["0", "1", "2"]


def test_n5_write_refuses_chunks_of_another_rank(tmp_path):
    with pytest.raises(ValueError):
        n5.write(str(tmp_path / "y.n5"), "a", np.zeros((2, 4, 4, 4), np.uint8),
                 (4, 4, 4))
