"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip hardware is not available in CI; sharding/collective paths are
validated on a virtual CPU mesh (xla_force_host_platform_device_count), the
standard JAX technique for testing pjit/shard_map layouts without TPUs.
Must run before the first jax import anywhere in the test session.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"  # force: tests never touch accelerators
_flags = os.environ.get("XLA_FLAGS", "")
# CTT_NO_VIRTUAL_MESH=1 opts out of the virtual mesh (e.g. to mimic a true
# single-device host); tests marked ``mesh`` then self-skip
if "xla_force_host_platform_device_count" not in _flags \
        and os.environ.get("CTT_NO_VIRTUAL_MESH") != "1":
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# hermetic executable-cache state: an inherited warm disk tier would turn
# the suite's compile-count assertions (EXEC_CACHE_STATS / exec_cache
# status telemetry) into disk hits; tests opt in per-fixture instead
os.environ.pop("CTT_EXEC_CACHE_DIR", None)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """Tests marked ``mesh`` need the emulated multi-device mesh: they
    self-skip when ``--xla_force_host_platform_device_count`` is not in
    XLA_FLAGS (a true single-device host, or CTT_NO_VIRTUAL_MESH=1)."""
    if "xla_force_host_platform_device_count" in os.environ.get(
            "XLA_FLAGS", ""):
        return
    skip = pytest.mark.skip(
        reason="needs --xla_force_host_platform_device_count (emulated "
               "multi-device mesh)")
    for item in items:
        if "mesh" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _telemetry_reset():
    """Telemetry state is module-global (like the stage accumulators,
    which tests consume via deltas): restore disabled / default ring /
    real clock after every test so a test that arms the recorder cannot
    leak spans into the next one."""
    yield
    from cluster_tools_tpu.core import telemetry

    telemetry.reset()


@pytest.fixture()
def tmp_workdir(tmp_path):
    """tmp_folder + config_dir pair with a small-block global config."""
    from cluster_tools_tpu.core.config import ConfigDir

    tmp_folder = str(tmp_path / "tmp")
    config_dir = str(tmp_path / "configs")
    cfg = ConfigDir(config_dir)
    cfg.write_global_config({"block_shape": [10, 10, 10], "max_num_retries": 0})
    return tmp_folder, config_dir
