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
from concurrent import futures
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core.meu import MEU
from repro.core.scidata import submit_copy
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
        """Rebuild a state pytree; reshard-on-load when ``shardings`` given
        (host arrays, byte for byte as saved, without).

        Under one ``ckpt.restore`` span: open every shard (``ckpt.open``: one
        stat, one header read); then, largest leaf first, allocate the leaf
        once and have ``read_dataset`` read each shard's piece of it straight
        into its slot, on the copy threads (``ckpt.read`` waits for them);
        cast it and start its copy to the devices (``ckpt.assemble`` >
        ``ckpt.put``) while the next leaf's pieces are read; last, wait for
        the copies (``ckpt.place``)."""
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError(f"no checkpoints for run {self.run!r}")
        leaves = _flatten_with_paths(state_like)
        treedef = jax.tree_util.tree_structure(state_like)
        targets = (
            treedef.flatten_up_to(shardings) if shardings is not None else [None] * len(leaves)
        )
        datapath = self.ws.datapath
        with span("ckpt.restore", step=step, shards=self.n_shards) as sp_restore:
            wire0 = datapath.wire_seconds
            shards = []
            try:
                for s in range(self.n_shards):
                    with span("ckpt.open", shard=s) as sp:
                        wire_open = datapath.wire_seconds
                        shards.append(self.ws.open_scidata(self._path(step, s)))
                        sp.wire_s = datapath.wire_seconds - wire_open
                rebuilt = self._read_leaves(shards, leaves, targets, sp_restore.tags)
            finally:
                for h in shards:
                    h.close()
            with span("ckpt.place"):
                # the copies the reads did not hide
                rebuilt = jax.block_until_ready(rebuilt)
            sp_restore.wire_s = datapath.wire_seconds - wire0
        return jax.tree_util.tree_unflatten(treedef, rebuilt)

    def _read_leaves(self, shards, leaves, targets, tags: Dict[str, Any]) -> List[Any]:
        """Each leaf read from the open ``shards`` and its copy to its target
        started; ``tags`` gain the bytes read and those read straight into
        their leaf."""
        split_axes = json.loads(shards[0].attrs["split_axes"])
        stored_bytes: Dict[str, int] = {}
        for h in shards:
            for d in h.sci.datasets:
                stored_bytes[d["name"]] = stored_bytes.get(d["name"], 0) + d["nbytes"]
        tags.update(bytes=0, into_bytes=0)
        rebuilt: List[Any] = [None] * len(leaves)

        def start(i: int):
            """Allocate leaf ``i`` and queue the reads of its pieces."""
            path, like = leaves[i]
            ax = split_axes[path]
            holders = shards[:1] if ax < 0 else shards
            stored = holders[0].sci.dataset(path)["dtype"]
            want = np.dtype(getattr(like, "dtype", stored))
            # a type stored by its ``dtype.str`` alone (bfloat16: '<V2') is read as itself
            dest = np.empty(np.shape(like), want if want.str == stored else stored)
            slots = [dest] if ax < 0 else np.split(dest, len(shards), axis=ax)
            for h, slot in zip(holders, slots):
                h.into[path] = slot
            reads = [submit_copy(self.ws.read_dataset, h.path, path) for h in holders]
            return i, dest, slots, reads

        def finish(i: int, dest: np.ndarray, slots, reads) -> None:
            with span("ckpt.read", leaf=leaves[i][0]) as sp:
                for slot, read in zip(slots, reads):
                    got = read.result()
                    if got is slot:
                        tags["into_bytes"] += slot.nbytes
                    else:
                        # a wrapper of ``read_dataset`` handed back an array of
                        # its own: that is what was read
                        np.copyto(slot, got.reshape(slot.shape))
            sp.tags["bytes"] = dest.nbytes
            tags["bytes"] += dest.nbytes
            with span("ckpt.assemble"):
                arr = dest.astype(getattr(leaves[i][1], "dtype", dest.dtype), copy=False)
                if targets[i] is not None:
                    with span("ckpt.put"):
                        arr = jax.device_put(arr, targets[i])
            rebuilt[i] = arr

        # the pieces of the next leaf are read while this one is placed
        pending = []
        try:
            for i in sorted(range(len(leaves)), key=lambda i: -stored_bytes[leaves[i][0]]):
                pending.append(start(i))
                if len(pending) > 1:
                    finish(*pending[0])
                    pending.pop(0)
            while pending:
                finish(*pending[0])
                pending.pop(0)
        finally:
            # after a failed read, the reads still running end before the shards close
            futures.wait([read for *_, reads in pending for read in reads])
        return rebuilt
