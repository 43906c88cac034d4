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

import json
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core.meu import MEU
from repro.core.scidata import read_header
from repro.core.workspace import NativeSession, Workspace
from repro.train.telemetry import TELEMETRY, span

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
        # saves and restores report into the train layer's bundle (see
        # repro.train.telemetry); the collaboration's trace tools find its
        # spans beside the workspace's
        collab.register_telemetry(TELEMETRY)
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
        self.native = NativeSession(collab.dc(home_dc), collaborator, tracer=TELEMETRY.tracer)
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
        """Write ``state`` as step ``step``; returns its size and the
        seconds of its spans (``write_s`` sums the shards' ``ckpt.write``)."""
        with span("ckpt.save", step=step) as sp_save:
            with span("ckpt.pack"):
                payloads = self._shard_payloads(state)
            writer = self.ws if self.mode == "workspace" else self.native
            total_bytes, write_s = 0, 0.0
            for s, (arrays, attrs) in enumerate(payloads):
                with span("ckpt.write", shard=s) as sp:
                    n = writer.write_scidata(self._path(step, s), arrays, dict(attrs, step=step))
                    sp.tags["bytes"] = n
                total_bytes += n
                write_s += sp.end - sp.start
            with span("ckpt.publish") as sp_publish:
                if self.mode == "native":
                    # one batched metadata export publishes the new step (§III-B3)
                    with span("meu.export"):
                        self.meu.export(f"{self.base}/{self.run}")
                    # LW-Offline indexing so the step is SDS-discoverable (§III-B5)
                    with span("ckpt.index"):
                        paths = [self._path(step, s) for s in range(self.n_shards)]
                        self.collab.dc(self.home_dc).offline_index(paths)
                # workspace mode indexed inline during the writes (Inline-Sync)
        return {
            "bytes": float(total_bytes),
            "write_s": write_s,
            "publish_s": sp_publish.end - sp_publish.start,
            "total_s": sp_save.end - sp_save.start,
        }

    # -- discovery + restore -------------------------------------------------------
    def list_steps(self) -> List[int]:
        """SDS attribute query — no directory crawling (§III-B5)."""
        with span("ckpt.discover"):
            rows = self.ws.search(f"run = {self.run}")
        steps = sorted({int(r["attrs"]["step"]) for r in rows if "step" in r.get("attrs", {})})
        return steps

    def latest_step(self) -> Optional[int]:
        steps = self.list_steps()
        return steps[-1] if steps else None

    def restore(self, state_like, step: Optional[int] = None, *, shardings=None):
        """Rebuild a state pytree; reshard-on-load when ``shardings`` given.

        Three phases under one ``ckpt.restore`` span: read every shard;
        assemble each leaf on the host and start its copy to the devices
        (``ckpt.put``); wait for the copies (the returned tree is ready)."""
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError(f"no checkpoints for run {self.run!r}")
        traced = TELEMETRY.tracer.enabled
        with span("ckpt.restore", step=step, shards=self.n_shards) as sp_restore:
            # read every shard through the workspace (any pod can restore any run)
            shard_arrays: List[Dict[str, np.ndarray]] = []
            split_axes: Dict[str, int] = {}
            total_bytes = 0
            datapath = self.ws.datapath
            for s in range(self.n_shards):
                with span("ckpt.read", shard=s) as sp:
                    wire0 = datapath.wire_seconds
                    path = self._path(step, s)
                    attrs = self.ws.read_attrs(path)
                    split_axes = json.loads(attrs["split_axes"])
                    entry = self.ws.stat(path)
                    dc = self.collab.dc(entry["dc_id"])
                    hdr = read_header(dc.backend, path)
                    arrays = {d["name"]: self.ws.read_dataset(path, d["name"]) for d in hdr.datasets}
                    if traced:
                        sp.tags["bytes"] = sum(a.nbytes for a in arrays.values())
                        sp.wire_s = datapath.wire_seconds - wire0
                        total_bytes += sp.tags["bytes"]
                shard_arrays.append(arrays)

            leaves = _flatten_with_paths(state_like)
            treedef = jax.tree_util.tree_structure(state_like)
            targets = (
                treedef.flatten_up_to(shardings) if shardings is not None else [None] * len(leaves)
            )
            with span("ckpt.assemble"):
                # pop each shard's piece and start each leaf's copy to its
                # device as soon as the leaf is rebuilt: the copies overlap
                # the next leaves' assembly, and the host holds the read
                # shards plus one leaf, never a second whole copy of the state
                rebuilt = []
                for (path, like), target in zip(leaves, targets):
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
                    if target is not None:
                        with span("ckpt.put"):
                            arr = jax.device_put(arr, target)
                    rebuilt.append(arr)
            with span("ckpt.place"):
                # the copies the assembly did not hide
                rebuilt = jax.block_until_ready(rebuilt)
            if traced:
                sp_restore.tags["bytes"] = total_bytes
        return jax.tree_util.tree_unflatten(treedef, rebuilt)
