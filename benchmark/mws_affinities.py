"""The input of the two-pass mutex-watershed cells: ``affinities.generate``,
for a program whose chain fits a run.

    generate(shape, seed, mix, offsets) -> (len(offsets), z, y, x) uint8

The same array as ``affinities.generate`` on the same arguments.  Before it
makes anything it checks that the program scans the device's packed edge
stream (``cluster_tools_tpu.native.mutex_clustering_packed``).  A program
without that scan keeps one hash set per voxel and scans a pass's blocks
one after another, at ~0.8 us per edge: about 20 minutes for one chain of
``cremi_a_mws``, longer than any run may take.  Such a program stops here
with a non-zero exit, seconds after start, instead of being killed at the
run's time limit.
"""

from __future__ import annotations

import affinities


def require_packed_scan():
    """Exit (code 1) unless the program has the packed mutex scan."""
    from cluster_tools_tpu import native

    if not callable(getattr(native, "mutex_clustering_packed", None)):
        raise SystemExit(
            "bench: this program has no packed mutex scan "
            "(cluster_tools_tpu.native.mutex_clustering_packed); its "
            "two-pass mutex watershed chain takes longer than a run may")


def generate(shape, seed: int, mix: dict, offsets):
    require_packed_scan()
    return affinities.generate(shape, seed, mix, offsets)
