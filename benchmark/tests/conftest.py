"""The benchmark's own tests run on the CPU at small sizes:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _own_workdir(monkeypatch, tmp_path):
    """Each test runs its cells in a workdir of its own, so that tests in
    parallel processes never share one."""
    import run

    monkeypatch.setattr(run, "WORK", str(tmp_path / "bench_work"))
