"""Distributed connected components over a thresholded map.

Re-specification of the reference's ``thresholded_components/`` package
(SURVEY.md §3.5): per-block CC (+ max id) -> prefix-sum offsets -> face
merges -> global union-find -> relabel + write.  TPU-first differences:

* per-block CC runs **on device** (ops/components.py: hooking +
  pointer-jumping union-find in pure JAX), with blocks batched into one
  vmapped program under ``target='tpu'`` instead of one subprocess each
  (reference: skimage.label per block, block_components.py:143-180);
* the global pair-merge uses scipy's sparse CC over the face-pair graph
  (vectorized C) instead of an interpreted union-find loop — the C++
  union-find arrives with the multicut solver suite and slots in here.

The offsets -> faces -> merge -> write shape recurs in mutex-watershed
stitching and overlap stitching (reference two_pass_assignments.py,
stitch_faces.py); those reuse these tasks' machinery.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

import numpy as np

from ..core.blocking import Blocking, iterate_faces
from ..core.config import write_config
from ..core.runtime import BlockTask
from ..core.storage import file_reader
from ..core.workflow import Task
from .write import WriteAssignments


class BlockComponents(BlockTask):
    """Threshold + per-block connected components (reference:
    block_components.py).  Writes per-block labels (1..max_id consecutive
    within the block) and a per-job JSON of block max-ids."""

    task_name = "block_components"

    def __init__(self, input_path: str, input_key: str, output_path: str,
                 output_key: str, threshold: float,
                 threshold_mode: str = "greater",
                 mask_path: str = "", mask_key: str = "", **kw):
        self.input_path = input_path
        self.input_key = input_key
        self.output_path = output_path
        self.output_key = output_key
        self.threshold = threshold
        self.threshold_mode = threshold_mode
        self.mask_path = mask_path
        self.mask_key = mask_key
        super().__init__(**kw)

    @staticmethod
    def default_task_config():
        conf = BlockTask.default_task_config()
        conf.update({"connectivity": 1, "batch_size": 8, "channel": None})
        return conf

    def run_impl(self):
        with file_reader(self.input_path, "r") as f:
            shape = list(f[self.input_key].shape)
        if self.task_config.get("channel") is not None:
            shape = shape[1:]
        block_shape = self.global_block_shape()[-len(shape):]
        with file_reader(self.output_path) as f:
            f.require_dataset(self.output_key, shape=shape, chunks=block_shape,
                              dtype="uint64")
        block_list = self.blocks_in_volume(shape, block_shape)
        self.run_jobs(block_list, {
            "input_path": self.input_path, "input_key": self.input_key,
            "output_path": self.output_path, "output_key": self.output_key,
            "threshold": self.threshold, "threshold_mode": self.threshold_mode,
            "mask_path": self.mask_path, "mask_key": self.mask_key,
            "shape": shape, "block_shape": block_shape,
        }, n_jobs=self.max_jobs)

    @classmethod
    def process_job(cls, job_id: int, job_config: Dict[str, Any], log_fn):
        import jax.numpy as jnp

        from ..ops.components import (
            connected_components_batched, threshold_volume,
        )

        cfg = job_config["config"]
        blocking = Blocking(cfg["shape"], cfg["block_shape"])
        block_list = job_config["block_list"]
        connectivity = int(cfg.get("connectivity", 1))
        batch_size = max(int(cfg.get("batch_size", 8)), 1)
        channel = cfg.get("channel")

        f_in = file_reader(cfg["input_path"], "r")
        f_out = file_reader(cfg["output_path"])
        ds_in, ds_out = f_in[cfg["input_key"]], f_out[cfg["output_key"]]
        mask = None
        if cfg.get("mask_path"):
            from ..core.volume_views import load_mask

            mask = load_mask(cfg["mask_path"], cfg["mask_key"], cfg["shape"])

        max_ids: Dict[int, int] = {}
        bs = tuple(cfg["block_shape"])
        for i in range(0, len(block_list), batch_size):
            batch_ids = block_list[i:i + batch_size]
            batch_masks = []
            batch_blocks = []
            for bid in batch_ids:
                block = blocking.get_block(bid)
                bb = block.bb
                if channel is not None:
                    data = ds_in[(slice(channel, channel + 1),) + bb][0]
                else:
                    data = ds_in[bb]
                bin_mask = np.asarray(
                    threshold_volume(jnp.asarray(data), cfg["threshold"],
                                     cfg["threshold_mode"]))
                if mask is not None:
                    bin_mask &= (mask[bb] > 0)
                # pad boundary blocks to the uniform batch shape (background
                # padding cannot bridge components)
                if bin_mask.shape != bs:
                    pad = [(0, b - s) for b, s in zip(bs, bin_mask.shape)]
                    bin_mask = np.pad(bin_mask, pad, constant_values=False)
                batch_masks.append(bin_mask)
                batch_blocks.append(block)
            labels = np.asarray(connected_components_batched(
                jnp.asarray(np.stack(batch_masks)), connectivity=connectivity))
            for bid, block, lab in zip(batch_ids, batch_blocks, labels):
                lab = lab[tuple(slice(0, s) for s in block.shape)]
                # consecutive within the block so offsets stay dense
                uniques = np.unique(lab)
                nonzero = uniques[uniques > 0]
                out = np.searchsorted(nonzero, lab).astype("uint64") + 1
                out[lab == 0] = 0
                ds_out[block.bb] = out
                max_ids[bid] = int(nonzero.size)
                log_fn(f"processed block {bid}")

        path = os.path.join(job_config["tmp_folder"],
                            f"block_components_max_ids_job_{job_id}.json")
        write_config(path, max_ids)


class ResidentBlockComponents(BlockTask):
    """Config-2 fast path: threshold + per-block CC against a
    DEVICE-RESIDENT volume (the flagship's resident treatment applied to
    the CC chain, VERDICT r4 item 4).  The volume uploads once; each
    block's jitted program dynamic-slices its window, thresholds, labels
    components, dense-relabels (presence + cumsum rank), and RLE-packs
    the labels so only runs cross the link; the host decodes, stages the
    block in the fragment cache (BlockFaces + the final write then
    compose from memory), and streams the store write on a writer
    thread.  Because a single job owns the device, the per-block max-ids
    fold into the exclusive-offset JSON inline — MergeOffsets is
    subsumed.  Labels are block-local (1..k, offsets applied by
    BlockFaces/Write exactly as for BlockComponents), so the chain's
    semantics are unchanged (reference: block_components.py:143-180 +
    merge_offsets.py:100-137)."""

    task_name = "block_components"

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
        conf.update({"connectivity": 1, "rle_cap": 1 << 20,
                     "stream_window": 3})
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
        from concurrent.futures import ThreadPoolExecutor
        from functools import lru_cache

        import jax
        import jax.numpy as jnp

        from ..core.runtime import stage, stage_bytes, stream_window
        from ..ops.sweep import rle_decode_packed
        from .fused_pipeline import _fragment_cache_put

        cfg = job_config["config"]
        blocking = Blocking(cfg["shape"], cfg["block_shape"])
        connectivity = int(cfg.get("connectivity", 1))
        rle_cap = int(cfg.get("rle_cap", 1 << 20))
        bs = tuple(cfg["block_shape"])
        n_block = int(np.prod(bs))
        threshold = float(cfg["threshold"])
        mode = cfg["threshold_mode"]

        f_in = file_reader(cfg["input_path"], "r")
        f_out = file_reader(cfg["output_path"])
        ds_in, ds_out = f_in[cfg["input_key"]], f_out[cfg["output_key"]]

        with stage("store-read"):
            vol = np.asarray(ds_in[...])
        stage_bytes("store-read", vol.nbytes)
        # grid-aligned zero padding: dynamic_slice CLAMPS out-of-bounds
        # origins (silently shifting border blocks); the extent mask in
        # the program zeroes the pad before labeling
        gshape = [-(-s // b) * b for s, b in zip(cfg["shape"], bs)]
        if gshape != list(vol.shape):
            volp = np.zeros(gshape, vol.dtype)
            volp[tuple(slice(0, s) for s in vol.shape)] = vol
        else:
            volp = vol
        with stage("h2d-upload"):
            vol_dev = jnp.asarray(volp)
        stage_bytes("h2d-upload", volp.nbytes)

        @lru_cache(maxsize=2)
        def program():
            from ..ops.components import (connected_components,
                                          threshold_volume)
            from ..ops.sweep import rle_encode_packed

            def run(v, origin_extent):
                origin = origin_extent[:3]
                extent = origin_extent[3:]
                x = jax.lax.dynamic_slice(
                    v, tuple(origin[d] for d in range(len(bs))), bs)
                m = threshold_volume(x, threshold, mode)
                # clipped border blocks: zero the padded remainder so
                # phantom components never enter the labeling
                for d in range(len(bs)):
                    coord = jnp.arange(bs[d])
                    shp = [1] * len(bs)
                    shp[d] = bs[d]
                    m &= (coord < extent[d]).reshape(shp)
                lab = connected_components(m, connectivity=connectivity)
                flat = lab.reshape(-1)
                pres = jnp.zeros((n_block + 2,), jnp.int32).at[flat].set(
                    1, mode="drop")
                pres = pres.at[0].set(0)
                rank = jnp.cumsum(pres)
                dense = jnp.where(flat > 0, rank[flat],
                                  0).astype(jnp.int32)
                k = rank[-1]
                packed, n_rle, rle_ok = rle_encode_packed(dense, rle_cap)
                meta = jnp.stack([k, n_rle,
                                  rle_ok.astype(jnp.int32)])
                return meta, packed, dense.reshape(bs)

            return jax.jit(run)

        max_ids: Dict[int, int] = {}
        write_futures = []

        def _write(bb, arr):
            with stage("store-write"):
                ds_out[bb] = arr
            stage_bytes("store-write", arr.nbytes)

        cache_key = (os.path.abspath(cfg["output_path"]),
                     cfg["output_key"])

        def submit(bid):
            block = blocking.get_block(bid)
            oe = jnp.asarray(
                list(block.begin) + [e - b for b, e in zip(block.begin,
                                                           block.end)],
                dtype=jnp.int32)
            with stage("dispatch"):
                return bid, program()(vol_dev, oe)

        def drain(entry):
            bid, handles = entry
            meta_d, packed_d, dense_d = handles
            block = blocking.get_block(bid)
            real = tuple(slice(0, e - b) for b, e in zip(block.begin,
                                                         block.end))
            with stage("sync-execute"):
                meta = np.asarray(meta_d)
            k_i, n_rle, rle_ok = (int(x) for x in meta)
            if rle_ok:
                with stage("d2h-rle"):
                    packed = np.asarray(packed_d)
                stage_bytes("d2h-rle", packed.nbytes)
                dense_np = rle_decode_packed(
                    packed, n_rle, n_block).reshape(bs)
            else:
                with stage("d2h-dense"):
                    dense_np = np.asarray(dense_d)
                stage_bytes("d2h-dense", dense_np.nbytes)
            local = dense_np[real]
            local = local.astype("uint16" if k_i < 65536 else "uint32")
            _fragment_cache_put(cache_key + (bid,), local, 0, block.bb)
            write_futures.append(
                writer.submit(_write, block.bb, local.astype("uint64")))
            max_ids[bid] = k_i
            log_fn(f"processed block {bid}")

        with ThreadPoolExecutor(1) as writer:
            for _ in stream_window(list(job_config["block_list"]),
                                   submit, drain,
                                   window=int(cfg.get("stream_window", 3))):
                pass
            for fut in write_futures:
                fut.result()

        # inline MergeOffsets: this single job saw every block
        n_blocks = blocking.n_blocks
        ids = np.zeros(n_blocks, dtype="uint64")
        for bid, mx in max_ids.items():
            ids[bid] = mx
        offsets = np.zeros(n_blocks, dtype="uint64")
        np.cumsum(ids[:-1], out=offsets[1:])
        write_config(cfg["offsets_path"],
                     {"offsets": offsets.tolist(),
                      "empty_blocks": np.nonzero(ids == 0)[0].tolist(),
                      "n_labels": int(ids.sum())})


class MergeOffsets(BlockTask):
    """Global job: per-block max ids -> exclusive prefix offsets, empty-block
    list, total label count (reference: merge_offsets.py:100-137)."""

    task_name = "merge_offsets"
    global_task = True
    allow_retry = False

    def __init__(self, n_blocks: int, offsets_path: str, **kw):
        self.n_blocks = n_blocks
        self.offsets_path = offsets_path
        super().__init__(**kw)

    def run_impl(self):
        self.run_jobs(None, {
            "tmp_root": self.tmp_folder, "n_blocks": self.n_blocks,
            "offsets_path": self.offsets_path,
        })

    @classmethod
    def process_job(cls, job_id: int, job_config: Dict[str, Any], log_fn):
        cfg = job_config["config"]
        tmp = cfg["tmp_root"]
        max_ids = np.zeros(cfg["n_blocks"], dtype="uint64")
        for name in os.listdir(tmp):
            if (name.startswith("block_components_max_ids_job_")
                    and name.endswith(".json")):
                with open(os.path.join(tmp, name)) as f:
                    for bid, mx in json.load(f).items():
                        max_ids[int(bid)] = mx
        offsets = np.zeros(cfg["n_blocks"], dtype="uint64")
        np.cumsum(max_ids[:-1], out=offsets[1:])
        empty_blocks = np.nonzero(max_ids == 0)[0].tolist()
        n_labels = int(max_ids.sum())
        write_config(cfg["offsets_path"],
                     {"offsets": offsets.tolist(),
                      "empty_blocks": empty_blocks,
                      "n_labels": n_labels})
        log_fn(f"n_labels: {n_labels}, empty blocks: {len(empty_blocks)}")


class BlockFaces(BlockTask):
    """Per-block face scan: equal-position voxel pairs across each lower face
    whose labels are both foreground become merge requests
    (label_a + offset_a, label_b + offset_b) (reference: block_faces.py:87-137)."""

    task_name = "block_faces"

    def __init__(self, path: str, key: str, offsets_path: str,
                 skip_covered: bool = False, **kw):
        self.path = path
        self.key = key
        self.offsets_path = offsets_path
        #: skip faces the mesh phase already merged on device (their block
        #: pairs are listed as ``covered_faces`` in the offsets JSON)
        self.skip_covered = skip_covered
        super().__init__(**kw)

    def run_impl(self):
        with file_reader(self.path, "r") as f:
            shape = list(f[self.key].shape)
        block_shape = self.global_block_shape()[-len(shape):]
        block_list = self.blocks_in_volume(shape, block_shape)
        self.run_jobs(block_list, {
            "path": self.path, "key": self.key,
            "offsets_path": self.offsets_path,
            "skip_covered": self.skip_covered,
            "shape": shape, "block_shape": block_shape,
        }, n_jobs=self.max_jobs)

    @classmethod
    def process_job(cls, job_id: int, job_config: Dict[str, Any], log_fn):
        cfg = job_config["config"]
        blocking = Blocking(cfg["shape"], cfg["block_shape"])
        with open(cfg["offsets_path"]) as f:
            off_data = json.load(f)
        offsets = np.asarray(off_data["offsets"], dtype="uint64")
        covered = (set(map(tuple, off_data.get("covered_faces", [])))
                   if cfg.get("skip_covered") else set())
        ndim = blocking.ndim
        f = file_reader(cfg["path"], "r")
        ds = f[cfg["key"]]

        from .fused_pipeline import fragment_cache_get

        def face_plane(bb, owner_bid):
            """One face plane, from the resident pass's in-RAM staging
            when this process ran it, else from the store."""
            ent = fragment_cache_get(cfg["path"], cfg["key"], owner_bid,
                                     expect_bb=blocking.get_block(
                                         owner_bid).bb)
            if ent is not None:
                local, off0, obb = ent
                rel = tuple(slice(s.start - o.start, s.stop - o.start)
                            for s, o in zip(bb, obb))
                out = local[rel].astype("uint64")
                if off0:
                    out[out > 0] += np.uint64(off0)
                return out.ravel()
            return None

        pairs: List[np.ndarray] = []
        for block_id in job_config["block_list"]:
            for face in iterate_faces(blocking, block_id, halo=[1] * ndim):
                if (face.block_a, face.block_b) in covered:
                    continue
                # absolute plane bbs of the two face sides
                bb_a = tuple(
                    slice(o.start + (f_.start or 0),
                          o.start + (f_.stop if f_.stop is not None
                                     else (o.stop - o.start)))
                    for o, f_ in zip(face.outer_bb, face.face_a))
                bb_b = tuple(
                    slice(o.start + (f_.start or 0),
                          o.start + (f_.stop if f_.stop is not None
                                     else (o.stop - o.start)))
                    for o, f_ in zip(face.outer_bb, face.face_b))
                la = face_plane(bb_a, face.block_a)
                lb = face_plane(bb_b, face.block_b)
                if la is None or lb is None:
                    region = ds[face.outer_bb]
                    la = region[face.face_a].ravel().astype("uint64")
                    lb = region[face.face_b].ravel().astype("uint64")
                fg = (la != 0) & (lb != 0)
                if not fg.any():
                    continue
                pa = la[fg] + offsets[face.block_a]
                pb = lb[fg] + offsets[face.block_b]
                pairs.append(np.unique(np.stack([pa, pb], axis=1), axis=0))
            log_fn(f"processed block {block_id}")
        out = (np.concatenate(pairs, axis=0) if pairs
               else np.zeros((0, 2), dtype="uint64"))
        np.save(os.path.join(job_config["tmp_folder"],
                             f"block_faces_assignments_job_{job_id}.npy"), out)


class MergeAssignments(BlockTask):
    """Global union-find over all face pairs -> consecutive assignment table
    (reference: merge_assignments.py:95-147, boost_ufd + relabelConsecutive).
    Implemented as sparse-graph CC (vectorized C via scipy) over the label-id
    graph."""

    task_name = "merge_assignments"
    global_task = True
    allow_retry = False

    def __init__(self, offsets_path: str, assignment_path: str, **kw):
        self.offsets_path = offsets_path
        self.assignment_path = assignment_path
        super().__init__(**kw)

    def run_impl(self):
        self.run_jobs(None, {
            "tmp_root": self.tmp_folder,
            "offsets_path": self.offsets_path,
            "assignment_path": self.assignment_path,
        })

    @classmethod
    def process_job(cls, job_id: int, job_config: Dict[str, Any], log_fn):
        from scipy.sparse import coo_matrix
        from scipy.sparse.csgraph import connected_components as sparse_cc

        cfg = job_config["config"]
        with open(cfg["offsets_path"]) as f:
            n_labels = json.load(f)["n_labels"]
        pair_arrays = []
        for name in os.listdir(cfg["tmp_root"]):
            if (name.startswith("block_faces_assignments_job_")
                    and name.endswith(".npy")):
                pair_arrays.append(
                    np.load(os.path.join(cfg["tmp_root"], name)))
        pairs = (np.concatenate(pair_arrays, axis=0) if pair_arrays
                 else np.zeros((0, 2), dtype="uint64"))
        n_nodes = n_labels + 1  # ids are 1-based; 0 is background
        graph = coo_matrix(
            (np.ones(len(pairs), dtype=bool),
             (pairs[:, 0].astype("int64"), pairs[:, 1].astype("int64"))),
            shape=(n_nodes, n_nodes))
        _, roots = sparse_cc(graph, directed=False)
        # every id keeps 0-root only if it IS background: separate bg from
        # whatever component contains node 0 (no pairs ever touch id 0)
        roots = roots.astype("uint64")
        # consecutive relabel, background stays 0
        fg_roots = roots[1:]
        uniques = np.unique(fg_roots)
        table = np.zeros(n_nodes, dtype="uint64")
        table[1:] = np.searchsorted(uniques, fg_roots) + 1
        np.save(cfg["assignment_path"], table)
        log_fn(f"merged {len(pairs)} pairs over {n_labels} labels -> "
               f"{len(uniques)} components")


class ThresholdedComponentsWorkflow(Task):
    """Chain: BlockComponents -> MergeOffsets -> BlockFaces ->
    MergeAssignments -> Write (reference:
    thresholded_components_workflow.py:17-103)."""

    def __init__(self, input_path: str, input_key: str, output_path: str,
                 output_key: str, threshold: float, tmp_folder: str,
                 config_dir: str, max_jobs: int = 1, target: str = "local",
                 threshold_mode: str = "greater", mask_path: str = "",
                 mask_key: str = "", assignment_key: str = "assignments",
                 dependency: Optional[Task] = None):
        self.input_path = input_path
        self.input_key = input_key
        self.output_path = output_path
        self.output_key = output_key
        self.threshold = threshold
        self.threshold_mode = threshold_mode
        self.mask_path = mask_path
        self.mask_key = mask_key
        self.tmp_folder = tmp_folder
        self.config_dir = config_dir
        self.max_jobs = max_jobs
        self.target = target
        self.dependency = dependency
        super().__init__()

    def _common(self):
        return dict(tmp_folder=self.tmp_folder, config_dir=self.config_dir,
                    max_jobs=self.max_jobs, target=self.target)

    def requires(self):
        offsets_path = os.path.join(self.tmp_folder, "cc_offsets.json")
        assignment_path = os.path.join(self.tmp_folder, "cc_assignments.npy")
        with file_reader(self.input_path, "r") as f:
            shape = list(f[self.input_key].shape)
        from ..core.config import ConfigDir

        block_shape = ConfigDir(self.config_dir).global_config()["block_shape"]
        n_blocks = Blocking(shape, block_shape[-len(shape):]).n_blocks

        if self.target == "tpu" and not self.mask_path:
            import jax

            # CTT_FORCE_RESIDENT=1 exercises the resident path on the CPU
            # backend (the hermetic test suite; on CPU the device detour
            # has no win, so it is opt-in there)
            if (jax.default_backend() != "cpu"
                    or os.environ.get("CTT_FORCE_RESIDENT") == "1"):
                # resident fast path: one device pass (threshold + CC +
                # RLE downloads) with inline offsets, faces + final write
                # composing from the in-RAM staging (VERDICT r4 item 4)
                t2 = ResidentBlockComponents(
                    input_path=self.input_path, input_key=self.input_key,
                    output_path=self.output_path,
                    output_key=self.output_key,
                    threshold=self.threshold,
                    threshold_mode=self.threshold_mode,
                    offsets_path=offsets_path,
                    dependency=self.dependency, **self._common())
                t3 = BlockFaces(path=self.output_path, key=self.output_key,
                                offsets_path=offsets_path, dependency=t2,
                                **self._common())
                t4 = MergeAssignments(offsets_path=offsets_path,
                                      assignment_path=assignment_path,
                                      dependency=t3, **self._common())
                t5 = WriteAssignments(
                    input_path=self.output_path, input_key=self.output_key,
                    output_path=self.output_path,
                    output_key=self.output_key,
                    assignment_path=assignment_path,
                    offsets_path=offsets_path,
                    identifier="cc", dependency=t4, **self._common())
                return t5
        if self.target == "mesh" and not self.mask_path:
            # SPMD phase: per-block CC + on-device offset scan + ICI face
            # exchange in one program per round (workflows/mesh_blockwise);
            # the remaining (other-axis / round-boundary) faces go through
            # the host scan with the device-covered pairs skipped
            from .mesh_blockwise import MeshBlockComponents

            t2 = MeshBlockComponents(
                input_path=self.input_path, input_key=self.input_key,
                output_path=self.output_path, output_key=self.output_key,
                threshold=self.threshold,
                threshold_mode=self.threshold_mode,
                offsets_path=offsets_path,
                dependency=self.dependency, **self._common())
            t3 = BlockFaces(path=self.output_path, key=self.output_key,
                            offsets_path=offsets_path, skip_covered=True,
                            dependency=t2, **self._common())
        else:
            t1 = BlockComponents(
                input_path=self.input_path, input_key=self.input_key,
                output_path=self.output_path, output_key=self.output_key,
                threshold=self.threshold, threshold_mode=self.threshold_mode,
                mask_path=self.mask_path, mask_key=self.mask_key,
                dependency=self.dependency, **self._common())
            t2 = MergeOffsets(n_blocks=n_blocks, offsets_path=offsets_path,
                              dependency=t1, **self._common())
            t3 = BlockFaces(path=self.output_path, key=self.output_key,
                            offsets_path=offsets_path, dependency=t2,
                            **self._common())
        t4 = MergeAssignments(offsets_path=offsets_path,
                              assignment_path=assignment_path,
                              dependency=t3, **self._common())
        t5 = WriteAssignments(
            input_path=self.output_path, input_key=self.output_key,
            output_path=self.output_path, output_key=self.output_key,
            assignment_path=assignment_path, offsets_path=offsets_path,
            identifier="cc", dependency=t4, **self._common())
        return t5

    def output(self):
        from ..core.workflow import FileTarget

        return FileTarget(os.path.join(self.tmp_folder, "write_cc.status"))
