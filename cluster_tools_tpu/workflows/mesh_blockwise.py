"""Mesh execution of blockwise workflows: one outer block per device.

The ``target='mesh'`` runtime — the TPU-native replacement for the
reference's one-batch-job-per-block fan-out (cluster_tasks.py:447-490
sbatch per job; :493-533 process pool).  Instead of scheduling independent
jobs, the blockwise phase runs as SPMD programs over a
``jax.sharding.Mesh``:

* per ROUND, ``n_devices`` consecutive blocks are sharded one-per-device
  and the per-block kernel (CC, watershed pipeline) runs vmapped inside
  one program;
* per-block label counts become global id offsets with an all-gather
  exclusive scan ON DEVICE (the SURVEY §7 mapping of the reference's
  ``merge_offsets.py:100-137`` cumsum to a psum-style collective);
* the face planes between round-adjacent blocks travel over ICI with
  ``lax.ppermute`` and the cross-block merge pairs are emitted on device
  (the §7 mapping of ``block_faces.py:87-137``); faces the round topology
  does not cover (other axes, round boundaries) fall back to the host
  face scan.

The global union-find and the relabel + write stay host tasks running the
SAME code as ``target='local'``, and every per-block kernel is the same
program ``target='tpu'`` runs — so the final segmentation is
bit-identical to the per-block execution targets (asserted by
tests/test_mesh_exec.py and dryrun check #7).
"""

from __future__ import annotations

import json
import os
from functools import lru_cache
from typing import Any, Dict, List

import numpy as np

from ..core.blocking import Blocking
from ..core.config import write_config
from ..core.runtime import BlockTask
from ..core.storage import file_reader


@lru_cache(maxsize=4)
def _cc_round_program(n_dev: int, block_shape, connectivity: int):
    """One SPMD program per (mesh size, block shape): vmapped per-block CC,
    on-device count scan, ppermute face-plane exchange."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..ops.components import connected_components
    from ..parallel.mesh import blocks_mesh

    mesh = blocks_mesh(n_dev)
    spec = P("blocks")

    def per_device(masks):
        # masks: (1, *block_shape) — this device's block of the round
        labels = jax.vmap(
            lambda m: connected_components(m, connectivity=connectivity)
        )(masks)
        flat = labels.reshape(labels.shape[0], -1)
        idx = jnp.arange(flat.shape[1], dtype=jnp.int32)[None]
        count = jnp.sum(flat == idx + 1, axis=1).astype(jnp.int32)

        # on-device exclusive scan of per-block counts over the mesh axis
        # (merge_offsets.py cumsum -> all-gather + masked sum over ICI)
        all_counts = jax.lax.all_gather(count, "blocks")  # (n_dev, 1)
        me = jax.lax.axis_index("blocks")
        offset = jnp.sum(jnp.where(jnp.arange(n_dev)[:, None] < me,
                                   all_counts, 0))

        # face exchange: my block's LAST plane along the fastest axis goes
        # to the next device over ICI; I receive the previous block's plane
        last_plane = labels[:, ..., -1]
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
        prev_plane = jax.lax.ppermute(last_plane, "blocks", perm)
        first_plane = labels[:, ..., 0]
        return labels, count, offset[None], prev_plane, first_plane

    # the CC while_loop carries per-device state; the varying-manual-axes
    # check cannot see through the data-dependent loop
    fn = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(spec,),
        out_specs=(spec, spec, spec, spec, spec),
        check_vma=False,
    )
    jitted = jax.jit(fn)

    def run(batch_masks_np):
        sharding = NamedSharding(mesh, P("blocks"))
        batch = jax.device_put(jnp.asarray(batch_masks_np), sharding)
        return jitted(batch)

    return run


class MeshBlockComponents(BlockTask):
    """Fused mesh phase of ThresholdedComponentsWorkflow: per-block CC +
    offsets + round-covered face pairs in SPMD rounds (replaces
    BlockComponents + MergeOffsets and part of BlockFaces under
    ``target='mesh'``)."""

    task_name = "mesh_block_components"

    def __init__(self, input_path: str, input_key: str, output_path: str,
                 output_key: str, threshold: float, offsets_path: str,
                 threshold_mode: str = "greater", **kw):
        self.input_path = input_path
        self.input_key = input_key
        self.output_path = output_path
        self.output_key = output_key
        self.threshold = threshold
        self.threshold_mode = threshold_mode
        self.offsets_path = offsets_path
        super().__init__(**kw)

    @staticmethod
    def default_task_config():
        conf = BlockTask.default_task_config()
        conf.update({"connectivity": 1, "n_devices": None})
        return conf

    def run_impl(self):
        with file_reader(self.input_path, "r") as f:
            shape = list(f[self.input_key].shape)
        block_shape = self.global_block_shape()[-len(shape):]
        with file_reader(self.output_path) as f:
            f.require_dataset(self.output_key, shape=shape,
                              chunks=block_shape, dtype="uint64")
        block_list = self.blocks_in_volume(shape, block_shape)
        self.run_jobs(block_list, {
            "input_path": self.input_path, "input_key": self.input_key,
            "output_path": self.output_path, "output_key": self.output_key,
            "threshold": self.threshold,
            "threshold_mode": self.threshold_mode,
            "offsets_path": self.offsets_path,
            "shape": shape, "block_shape": block_shape,
        }, n_jobs=1)

    @classmethod
    def process_job(cls, job_id: int, job_config: Dict[str, Any], log_fn):
        import jax

        from ..ops.components import threshold_volume

        cfg = job_config["config"]
        blocking = Blocking(cfg["shape"], cfg["block_shape"])
        block_list = list(job_config["block_list"])
        connectivity = int(cfg.get("connectivity", 1))
        n_dev = int(cfg.get("n_devices") or len(jax.devices()))
        bs = tuple(cfg["block_shape"])
        x_axis = blocking.ndim - 1

        f_in = file_reader(cfg["input_path"], "r")
        f_out = file_reader(cfg["output_path"])
        ds_in, ds_out = f_in[cfg["input_key"]], f_out[cfg["output_key"]]

        program = _cc_round_program(n_dev, bs, connectivity)

        max_ids = np.zeros(blocking.n_blocks, dtype="uint64")
        offsets = np.zeros(blocking.n_blocks, dtype="uint64")
        luts: Dict[int, np.ndarray] = {}
        pair_chunks: List[np.ndarray] = []
        covered_faces: List[List[int]] = []
        # raw (uncompacted) pair staging: (block_a, block_b, raw plane pair)
        staged: List[tuple] = []
        round_base = np.uint64(0)  # labels before this round (device scan
        #                            handles WITHIN-round order over ICI)

        for r0 in range(0, len(block_list), n_dev):
            round_ids = block_list[r0:r0 + n_dev]
            batch = np.zeros((n_dev,) + bs, bool)
            for i, bid in enumerate(round_ids):
                block = blocking.get_block(bid)
                data = np.asarray(ds_in[block.bb])
                # host threshold: a plain compare, exactly equal to the
                # device threshold_volume — avoids a synchronous per-block
                # device round trip before the SPMD round launches
                bin_mask = np.asarray(
                    threshold_volume(data, cfg["threshold"],
                                     cfg.get("threshold_mode", "greater")))
                if bin_mask.shape != bs:
                    pad = [(0, b - s) for b, s in zip(bs, bin_mask.shape)]
                    bin_mask = np.pad(bin_mask, pad, constant_values=False)
                batch[i] = bin_mask

            labels, counts, offsets_dev, prev_planes, first_planes = (
                np.asarray(a) for a in program(batch))

            for i, bid in enumerate(round_ids):
                block = blocking.get_block(bid)
                lab = labels[i][tuple(slice(0, s) for s in block.shape)]
                uniques = np.unique(lab)
                nonzero = uniques[uniques > 0]
                out = np.searchsorted(nonzero, lab).astype("uint64") + 1
                out[lab == 0] = 0
                ds_out[block.bb] = out
                max_ids[bid] = nonzero.size
                luts[bid] = nonzero
                # the device count must agree with the host compaction —
                # the on-device scan IS the offsets source of truth.
                # A real exception, not an assert: python -O would strip
                # the only guard reconciling scan offsets with compaction
                if int(counts[i]) != nonzero.size:
                    raise RuntimeError(
                        f"block {bid}: device label count {int(counts[i])}"
                        f" != host compaction {nonzero.size}")
                offsets[bid] = round_base + np.uint64(int(offsets_dev[i]))
                log_fn(f"processed block {bid}")
            round_base += np.uint64(int(counts[:len(round_ids)].sum()))

            # round-covered faces: device i holds block round_ids[i-1]'s
            # last x-plane (via ppermute); a pair is real when the two
            # blocks are x-grid neighbors
            for i in range(1, len(round_ids)):
                a, b = round_ids[i - 1], round_ids[i]
                if blocking.neighbor_id(a, x_axis, +1) != b:
                    continue
                # clip the uniform planes to the REAL (unpadded) extents
                shape_a = blocking.get_block(a).shape
                shape_b = blocking.get_block(b).shape
                clip = tuple(slice(0, min(sa, sb)) for sa, sb in
                             zip(shape_a[:-1], shape_b[:-1]))
                pa = prev_planes[i][clip]
                pb = first_planes[i][clip]
                # the face exists only where block a is full-width in x
                if shape_a[-1] == bs[-1]:
                    staged.append((a, b, pa, pb))
                    covered_faces.append([int(a), int(b)])

        # cross-check: the device scan composed across rounds must equal
        # the reference cumsum (merge_offsets.py semantics)
        check = np.zeros(blocking.n_blocks, dtype="uint64")
        np.cumsum(max_ids[:-1], out=check[1:])
        processed = np.asarray(block_list)
        if not (offsets[processed] == check[processed]).all():
            bad = processed[offsets[processed] != check[processed]][:5]
            raise RuntimeError(
                "device offset scan diverged from the reference cumsum "
                f"at blocks {bad.tolist()}")

        for a, b, pa, pb in staged:
            fg = (pa > 0) & (pb > 0)
            if not fg.any():
                continue
            # map raw root labels -> compacted block-local ids
            ca = np.searchsorted(luts[a], pa[fg]).astype("uint64") + 1
            cb = np.searchsorted(luts[b], pb[fg]).astype("uint64") + 1
            pairs = np.stack([ca + offsets[a], cb + offsets[b]], axis=1)
            pair_chunks.append(np.unique(pairs, axis=0))

        pairs_out = (np.concatenate(pair_chunks, axis=0) if pair_chunks
                     else np.zeros((0, 2), "uint64"))
        np.save(os.path.join(job_config["tmp_folder"],
                             "block_faces_assignments_job_mesh.npy"),
                pairs_out)

        empty_blocks = np.nonzero(max_ids == 0)[0].tolist()
        write_config(cfg["offsets_path"],
                     {"offsets": offsets.tolist(),
                      "empty_blocks": empty_blocks,
                      "n_labels": int(max_ids.sum()),
                      "covered_faces": covered_faces})
        log_fn(f"mesh CC: {len(block_list)} blocks over {n_dev} devices, "
               f"{int(max_ids.sum())} labels, "
               f"{len(covered_faces)} faces on device")
