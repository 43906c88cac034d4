"""Checkpointing through the SCISPACE workspace — the paper's technique as a
first-class framework feature.

Two write paths, mirroring the paper's §III-B3 exactly:

- **workspace mode** ("SCISPACE" in the paper's figures): every shard write
  goes through :class:`~repro.core.workspace.Workspace` — the five-op FUSE
  sequence + metadata RPCs per file.  Globally visible immediately.
- **native mode (LW+MEU)** — shards are written straight into the pod's
  local store (:class:`~repro.core.workspace.NativeSession`, no RPC in the
  data path); one batched :class:`~repro.core.meu.MEU` export afterwards
  publishes the metadata.  This is the paper's native-data-access path, and
  the checkpoint-stall benchmark shows the same win the paper reports.

Checkpoints are **self-describing scidata containers** (one per pod-shard):
leaf arrays keyed by their pytree path, attrs carrying (run, step, arch,
shard, n_shards, leaf split axes).  Discovery — "find the latest checkpoint
of run X" — is an SDS attribute query, never a directory crawl: restart
after failure costs one search + shard reads.

Sharding scheme: each leaf splits on its largest dimension divisible by
``n_shards`` (axis recorded per leaf); leaves too small to split go to
shard 0 whole.  Restore reassembles full arrays and ``device_put``s with
the *target* mesh's shardings — elastic re-meshing (pod loss/gain, new
topology) is therefore reshard-on-load by construction.
"""

from __future__ import annotations

import io
import json
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core.meu import MEU
from repro.core.workspace import NativeSession, Workspace

__all__ = ["CheckpointManager", "CheckpointInfo"]


def _flatten_with_paths(tree) -> List[Tuple[str, Any]]:
    from repro.distributed.sharding import path_of

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [(path_of(kp), leaf) for kp, leaf in flat]


def _split_axis(shape: Tuple[int, ...], n_shards: int) -> Optional[int]:
    """Largest dim divisible by n_shards (prefer later dims: params are
    [units, in, out] and splitting 'out' keeps rows contiguous)."""
    best = None
    for d in range(len(shape)):
        if shape[d] % n_shards == 0 and shape[d] >= n_shards:
            if best is None or shape[d] >= shape[best]:
                best = d
    return best


@dataclass
class CheckpointInfo:
    run: str
    step: int
    path: str
    n_shards: int


class CheckpointManager:
    """Save/restore train state through a SCISPACE collaboration.

    ``mode`` is ``'native'`` (LW+MEU, default — the paper's fast path) or
    ``'workspace'`` (synchronous global writes — the paper's baseline).
    """

    def __init__(
        self,
        collab,
        *,
        run: str,
        home_dc: str,
        collaborator: str = "trainer",
        mode: str = "native",
        n_shards: int = 2,
        base: str = "/ckpt",
    ):
        assert mode in ("native", "workspace")
        self.collab = collab
        self.run = run
        self.home_dc = home_dc
        self.mode = mode
        self.n_shards = n_shards
        self.base = base.rstrip("/")
        self.collaborator = collaborator
        # workspace mode indexes inline (the paper's Inline-Sync write path);
        # native mode indexes offline after the MEU export (LW-Offline).
        # A restore reads each shard byte once, so remote shards bypass the
        # client chunk cache and read-ahead: filling the cache with
        # multi-GB extents only copies them (again and again as stripes merge).
        self.ws = Workspace(
            collab, collaborator, home_dc,
            extraction_mode="inline-sync" if mode == "workspace" else "none",
            chunk_cache_bytes=0,
            readahead=False,
        )
        self.native = NativeSession(collab.dc(home_dc), collaborator)
        self.meu = MEU(collab, collab.dc(home_dc), collaborator)

    # -- save -------------------------------------------------------------------
    def _shard_payloads(self, state) -> List[Tuple[Dict[str, np.ndarray], Dict[str, Any]]]:
        leaves = _flatten_with_paths(state)
        shards: List[Dict[str, np.ndarray]] = [dict() for _ in range(self.n_shards)]
        split_axes: Dict[str, int] = {}
        for path, leaf in leaves:
            arr = np.asarray(leaf)
            ax = _split_axis(arr.shape, self.n_shards) if arr.ndim else None
            if ax is None:
                shards[0][path] = arr
                split_axes[path] = -1
            else:
                for s, piece in enumerate(np.split(arr, self.n_shards, axis=ax)):
                    shards[s][path] = piece
                split_axes[path] = ax
        metas = []
        for s in range(self.n_shards):
            metas.append(
                {
                    "kind": "checkpoint",
                    "run": self.run,
                    "step": -1,  # filled at save()
                    "shard": s,
                    "n_shards": self.n_shards,
                    "split_axes": json.dumps(split_axes),
                }
            )
        return list(zip(shards, metas))

    def _path(self, step: int, shard: int) -> str:
        return f"{self.base}/{self.run}/step{step:08d}/shard{shard}.sci"

    def save(self, state, step: int) -> Dict[str, float]:
        """Returns timing/accounting for the benchmark harness."""
        t0 = time.perf_counter()
        payloads = self._shard_payloads(state)
        t_pack = time.perf_counter() - t0

        t1 = time.perf_counter()
        total_bytes = 0
        for s, (arrays, attrs) in enumerate(payloads):
            attrs = dict(attrs, step=step)
            path = self._path(step, s)
            if self.mode == "workspace":
                total_bytes += self.ws.write_scidata(path, arrays, attrs)
            else:
                total_bytes += self.native.write_scidata(path, arrays, attrs)
        t_write = time.perf_counter() - t1

        t2 = time.perf_counter()
        export_report = None
        if self.mode == "native":
            # one batched metadata export publishes the new step (§III-B3)
            export_report = self.meu.export(f"{self.base}/{self.run}")
            # LW-Offline indexing so the step is SDS-discoverable (§III-B5)
            paths = [self._path(step, s) for s in range(self.n_shards)]
            self.collab.dc(self.home_dc).offline_index(paths)
        # workspace mode indexed inline during the writes (Inline-Sync)
        t_publish = time.perf_counter() - t2

        return {
            "bytes": float(total_bytes),
            "pack_s": t_pack,
            "write_s": t_write,
            "publish_s": t_publish,
            "total_s": t_pack + t_write + t_publish,
            "meu_rpcs": float(export_report.rpc_calls) if export_report else 0.0,
        }

    # -- discovery + restore -------------------------------------------------------
    def list_steps(self) -> List[int]:
        """SDS attribute query — no directory crawling (§III-B5)."""
        rows = self.ws.search(f"run = {self.run}")
        steps = sorted({int(r["attrs"]["step"]) for r in rows if "step" in r.get("attrs", {})})
        return steps

    def latest_step(self) -> Optional[int]:
        steps = self.list_steps()
        return steps[-1] if steps else None

    def restore(self, state_like, step: Optional[int] = None, *, shardings=None):
        """Rebuild a state pytree; reshard-on-load when ``shardings`` given."""
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError(f"no checkpoints for run {self.run!r}")
        # read every shard through the workspace (any pod can restore any run)
        shard_arrays: List[Dict[str, np.ndarray]] = []
        split_axes: Dict[str, int] = {}
        for s in range(self.n_shards):
            path = self._path(step, s)
            attrs = self.ws.read_attrs(path)
            split_axes = json.loads(attrs["split_axes"])
            arrays = {}
            from repro.core.scidata import read_header

            entry = self.ws.stat(path)
            dc = self.collab.dc(entry["dc_id"])
            hdr = read_header(dc.backend, path)
            for d in hdr.datasets:
                arrays[d["name"]] = self.ws.read_dataset(path, d["name"])
            shard_arrays.append(arrays)

        leaves = _flatten_with_paths(state_like)
        treedef = jax.tree_util.tree_structure(state_like)
        targets = (
            treedef.flatten_up_to(shardings) if shardings is not None else [None] * len(leaves)
        )
        rebuilt = []
        for (path, like), target in zip(leaves, targets):
            # pop each shard's piece and place each leaf as soon as it is
            # rebuilt: the host holds the read shards plus one leaf, never a
            # second whole copy of the state
            ax = split_axes[path]
            if ax < 0:
                arr = shard_arrays[0].pop(path)
            else:
                arr = np.concatenate([sa.pop(path) for sa in shard_arrays], axis=ax)
            if hasattr(like, "shape"):
                # scidata stores 0-d arrays as [1] (ascontiguousarray quirk)
                arr = arr.reshape(like.shape)
            if hasattr(like, "dtype"):
                arr = arr.astype(like.dtype, copy=False)
            rebuilt.append(arr if target is None else jax.device_put(arr, target))
        return jax.tree_util.tree_unflatten(treedef, rebuilt)
