"""A checkpoint restore reads each stored piece straight into its leaf.

- a cross-DC restore (saved at ``pod0``, restored at ``pod1``) over the
  in-memory and the POSIX store gives back the saved state leaf for leaf,
  byte for byte: split leaves (on axis 0 and on a later axis), unsplit
  ones, 0-d ones, the integer step, as host arrays or on one device;
- the ``ckpt.restore`` span's ``into_bytes`` counts the bytes read
  straight into their leaf: all of them, unless ``read_dataset`` hands back
  an array of its own, which is then what the leaf gets;
- the data path's read into an array moves, counts and charges what a
  cache-off ``read_range`` of the same bytes does, on a modeled link and
  store, resumes a cut transfer under a retry policy, and fails as it does
  when every mover is down; a restore is charged what the parent's reads
  of the same bytes were, without its repeated header reads;
- a read into an array never sees a torn file while the file is
  rewritten, and never makes the writer raise; reads of many datasets of
  one container run in parallel.
"""

import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import ml_dtypes
import numpy as np
import pytest

from repro.core import Collaboration, RetryPolicy, Workspace
from repro.core.backends import MemoryBackend
from repro.core.rpc import Channel, RpcUnavailable
from repro.core.scidata import dataset_range
from repro.train import TELEMETRY, CheckpointManager

SHARDS = 4


def _state():
    rng = np.random.default_rng(7)
    return {
        "embed": rng.standard_normal((8, 6)).astype(np.float32),  # split on axis 0
        "w": rng.standard_normal((3, 5, 8)).astype(np.float32),  # split on axis 2
        "odd": rng.standard_normal((3, 5)).astype(np.float32),  # unsplit
        "scale": np.float32(1.5),  # 0-d
        "half": rng.standard_normal((2, 12)).astype(ml_dtypes.bfloat16),  # split on axis 1
        "step": np.int32(11),  # the integer step
    }


def _collab(tmp_path=None, *, channel=None, store_gbps=0.0):
    policy = None if channel is None else (lambda a, b: channel if a != b else Channel())
    collab = Collaboration(channel_policy=policy)
    for dc in ("pod0", "pod1"):
        root = None if tmp_path is None else str(tmp_path / dc)
        collab.add_datacenter(dc, root=root, n_dtns=2, store_gbps=store_gbps)
    return collab


def _restore_span():
    return [s for s in TELEMETRY.spans.spans() if s.name == "ckpt.restore"][-1]


@pytest.mark.parametrize("store", ["memory", "posix"])
@pytest.mark.parametrize("placed", [False, True])
def test_cross_dc_restore_gives_back_the_saved_state(tmp_path, store, placed):
    collab = _collab(tmp_path if store == "posix" else None)
    state = _state()
    CheckpointManager(collab, run="r", home_dc="pod0", n_shards=SHARDS).save(state, 3)
    mgr = CheckpointManager(collab, run="r", home_dc="pod1", n_shards=SHARDS)
    like = jax.eval_shape(lambda: state)
    device = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    shardings = jax.tree.map(lambda _: device, state) if placed else None
    got = mgr.restore(like, 3, shardings=shardings)
    for name, want in state.items():
        leaf = got[name]
        assert isinstance(leaf, jax.Array if placed else np.ndarray), name
        leaf = np.asarray(leaf)
        assert leaf.shape == np.shape(want) and leaf.dtype == np.asarray(want).dtype, name
        assert leaf.tobytes() == np.asarray(want).tobytes(), name
    # every byte crossed the data path from pod0
    stats = mgr.ws.data_stats()
    assert stats["bytes_read"] > sum(np.asarray(x).nbytes for x in state.values())
    span = _restore_span()
    assert span.tags["bytes"] == sum(np.asarray(x).nbytes for x in state.values())
    assert span.tags["into_bytes"] == span.tags["bytes"]
    # the containers were closed again
    assert not mgr.ws._open
    mgr.ws.close()
    collab.close()


def test_home_dc_restore_reads_off_its_store(collab):
    state = _state()
    CheckpointManager(collab, run="h", home_dc="dc0", n_shards=SHARDS).save(state, 1)
    mgr = CheckpointManager(collab, run="h", home_dc="dc0", n_shards=SHARDS)
    got = mgr.restore(jax.eval_shape(lambda: state), 1)
    for name, want in state.items():
        assert np.asarray(got[name]).tobytes() == np.asarray(want).tobytes(), name
    assert _restore_span().tags["into_bytes"] == _restore_span().tags["bytes"]
    mgr.ws.close()


# -- the data path ------------------------------------------------------------

LINK = Channel(name="wan", latency_s=1e-3, gbps=2.0, stream_gbps=0.5)


def _remote_file(collab, home, data, tag="f"):
    """``data`` stored at the DC that is not ``home``; its path."""
    other = next(dc for dc in collab.datacenters if dc != home)
    path = f"/far/{tag}.bin"
    collab.dc(other).backend.write(path, data)
    return other, path


def _counts(dp):
    return dp.remote_reads, dp.bytes_read, dp.wire_seconds


@pytest.mark.parametrize("link, store_gbps", [(LINK, 0.0), (None, 4.0), (LINK, 4.0)])
@pytest.mark.parametrize("strided", [False, True])
def test_read_into_moves_and_charges_what_read_range_does(link, store_gbps, strided):
    collab = _collab(channel=link, store_gbps=store_gbps)
    data = os.urandom(96 << 10)
    dc, path = _remote_file(collab, "pod1", data)
    offset, n = 4096, 64 << 10
    kw = dict(chunk_cache_bytes=0, readahead=False, stripe_bytes=16 << 10)
    by_range = Workspace(collab, "a", "pod1", **kw).datapath
    by_into = Workspace(collab, "b", "pod1", **kw).datapath
    want = by_range.read_range(dc, path, offset, n)
    full = np.empty((8, 4096 if strided else 2048), np.float32)
    dest = np.split(full, 2, axis=1)[0] if strided else full
    assert dest.nbytes == n and dest.flags.c_contiguous != strided
    by_into.read_range_into(dc, path, offset, dest)
    assert dest.tobytes() == want
    assert _counts(by_into) == _counts(by_range)
    assert by_into.wire_seconds > 0
    collab.close()


def test_read_into_resumes_a_cut_transfer():
    collab = _collab()
    data = os.urandom(32 << 10)
    dc, path = _remote_file(collab, "pod1", data)
    policy = RetryPolicy(max_attempts=20, base_s=0.01, cap_s=0.05, timeout_s=0.0, deadline_s=10.0)
    ws = Workspace(collab, "b", "pod1", chunk_cache_bytes=0, readahead=False,
                   stripe_bytes=4 << 10, retry=policy)
    backend = collab.dc(dc).backend
    ids = [d.dtn_id for d in collab.dc(dc).dtns]
    real = backend.read_into_deferred
    calls = []

    def cut(path, dest, offset=0):
        calls.append(offset)
        if len(calls) == 1:
            # every mover dies while the stream is in flight, then recovers
            for i in ids:
                collab.crash_dtn(i)
            t = threading.Timer(0.005, lambda: [collab.restart_dtn(i) for i in ids])
            t.daemon = True
            t.start()
        return real(path, dest, offset=offset)

    backend.read_into_deferred = cut
    dest = np.zeros(len(data) - 100, np.uint8)
    try:
        ws.datapath.read_range_into(dc, path, 100, dest)
    finally:
        backend.read_into_deferred = real
    assert dest.tobytes() == data[100:]
    # the cut stream was not confirmed: it was fetched again, whole
    assert calls == [100, 100]
    st = ws.datapath.stats()
    assert st["interrupted_transfers"] >= 1 and st["transfer_retries"] >= 1
    ws.close()
    collab.close()


def test_read_into_fails_as_read_range_does_with_every_mover_down():
    collab = _collab()
    dc, path = _remote_file(collab, "pod1", os.urandom(4096))
    ws = Workspace(collab, "b", "pod1", chunk_cache_bytes=0, readahead=False)
    for d in collab.dc(dc).dtns:
        collab.crash_dtn(d.dtn_id)
    with pytest.raises(RpcUnavailable):
        ws.datapath.read_range(dc, path, 0, 4096)
    with pytest.raises(RpcUnavailable):
        ws.datapath.read_range_into(dc, path, 0, np.empty(4096, np.uint8))
    assert ws.datapath.remote_reads == 0
    ws.close()
    collab.close()


def test_read_into_past_the_end_raises():
    collab = _collab()
    dc, path = _remote_file(collab, "pod1", os.urandom(100))
    ws = Workspace(collab, "b", "pod1", chunk_cache_bytes=0)
    with pytest.raises(EOFError):
        ws.datapath.read_range_into(dc, path, 50, np.empty(51, np.uint8))
    ws.close()
    collab.close()


def test_restore_is_charged_what_the_parents_reads_of_its_bytes_were():
    """On a modeled link and store, a restore's reads count and charge what
    one header read of each shard plus one ``read_range`` of each stored
    piece do: the reads the parent made, less its repeated header reads."""
    collab = _collab(channel=LINK, store_gbps=4.0)
    state = _state()
    CheckpointManager(collab, run="c", home_dc="pod0", n_shards=SHARDS).save(state, 2)
    mgr = CheckpointManager(collab, run="c", home_dc="pod1", n_shards=SHARDS)
    mgr.restore(jax.eval_shape(lambda: state), 2)
    restored = _counts(mgr.ws.datapath)
    ref = Workspace(collab, "ref", "pod1", chunk_cache_bytes=0, readahead=False)
    for s in range(SHARDS):
        path = mgr._path(2, s)
        sci = ref.open_scidata(path).sci
        for entry in sci.datasets:
            offset, nbytes = dataset_range(sci, entry)
            ref.datapath.read_range("pod0", path, offset, nbytes)
    charged = _counts(ref.datapath)
    assert restored[:2] == charged[:2]
    assert restored[2] == pytest.approx(charged[2], rel=1e-9) and charged[2] > 0
    mgr.ws.close()
    ref.close()
    collab.close()


# -- the store -----------------------------------------------------------------


def test_read_into_never_sees_a_torn_file_and_never_fails_the_writer():
    """A writer alternates full rewrites (every byte ``v``) with partial
    writes that also grow the file (every byte from 1 on ``v + 1``); each
    read sees one whole state or the other."""
    store = MemoryBackend("dc0")
    n = 1 << 20
    store.write("/f", bytes(n))
    stop = threading.Event()
    errors = []

    def writer():
        v = 0
        try:
            while not stop.is_set():
                v = (v + 2) % 250
                store.write("/f", bytes([v]) * n)
                store.write("/f", bytes([v + 1]) * n, offset=1)
        except Exception as exc:  # noqa: BLE001 - any failure is the finding
            errors.append(exc)

    t = threading.Thread(target=writer)
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    t.start()
    dest = np.empty(n, np.uint8)
    try:
        for _ in range(300):
            store.read_into("/f", dest)
            head, rest = int(dest[0]), dest[1:]
            assert (rest == rest[0]).all()
            assert int(rest[0]) in (head, head + 1)
    finally:
        stop.set()
        t.join(timeout=10)
        sys.setswitchinterval(switch)
    assert not t.is_alive() and not errors
    assert not store._reading


def test_parallel_reads_of_one_container_are_correct(collab):
    writer = Workspace(collab, "alice", "dc0")
    reader = Workspace(collab, "bob", "dc1", chunk_cache_bytes=0, readahead=False)
    path = next(f"/proj/many{i}.sci" for i in range(500)
                if collab.owner_dtn(f"/proj/many{i}.sci").dc_id == "dc0")
    rng = np.random.default_rng(3)
    arrays = {f"d{j}": rng.standard_normal((64, 33 + j)) for j in range(24)}
    writer.write_scidata(path, arrays, {"project": "ocean"})
    handle = reader.open_scidata(path)
    assert handle.attrs == {"project": "ocean"}
    reads = reader.data_stats()["remote_reads"]
    halves = {name: np.empty((64, 2 * arr.shape[1])) for name, arr in arrays.items()}
    for name, half in halves.items():
        handle.into[name] = np.split(half, 2, axis=1)[1]  # a strided slot
    with ThreadPoolExecutor(8) as pool:
        got = list(pool.map(lambda name: reader.read_dataset(path, name), arrays))
    for (name, arr), out in zip(arrays.items(), got):
        assert out.base is halves[name]  # read into the slot it was given
        np.testing.assert_array_equal(out, arr)
    assert not handle.into
    # one read a dataset: no stat or header read again
    assert reader.data_stats()["remote_reads"] == reads + len(arrays)
    # with no slot given, a fresh array; a wrong slot is refused
    np.testing.assert_array_equal(reader.read_dataset(path, "d3"), arrays["d3"])
    handle.into["d0"] = np.empty((64, 34))
    with pytest.raises(ValueError):
        reader.read_dataset(path, "d0")
    with pytest.raises(KeyError):
        reader.read_dataset(path, "nope")
    # closed, it reads as before: a stat, the header and the dataset
    handle.close()
    np.testing.assert_array_equal(reader.read_dataset(path, "d1"), arrays["d1"])
    assert reader.data_stats()["remote_reads"] == reads + len(arrays) + 1 + 3
    writer.close()
    reader.close()


def test_a_read_that_hands_back_its_own_array_is_what_the_leaf_gets(collab, monkeypatch):
    """Whatever ``read_dataset`` returns is what a restore takes, as a tool
    that wraps it expects; such pieces are not counted in ``into_bytes``."""
    real = Workspace.read_dataset
    monkeypatch.setattr(Workspace, "read_dataset", lambda self, p, n: real(self, p, n) + 1)
    state = {"w": np.arange(24, dtype=np.float32).reshape(4, 6), "b": np.zeros(3, np.float32)}
    CheckpointManager(collab, run="w", home_dc="dc0", n_shards=2).save(state, 1)
    mgr = CheckpointManager(collab, run="w", home_dc="dc1", n_shards=2)
    got = mgr.restore(jax.eval_shape(lambda: state), 1)
    for name, want in state.items():
        np.testing.assert_array_equal(got[name], want + 1)
    assert _restore_span().tags["into_bytes"] == 0
    mgr.ws.close()


def test_a_failed_read_fails_the_restore_and_closes_its_shards(collab, monkeypatch):
    state = _state()
    CheckpointManager(collab, run="f", home_dc="dc0", n_shards=SHARDS).save(state, 1)
    mgr = CheckpointManager(collab, run="f", home_dc="dc1", n_shards=SHARDS)
    real, calls = mgr.ws.datapath.read_range_into, []

    def failing(*args):
        calls.append(args)
        if len(calls) == 3:
            raise RpcUnavailable("mover lost")
        return real(*args)

    monkeypatch.setattr(mgr.ws.datapath, "read_range_into", failing)
    with pytest.raises(RpcUnavailable):
        mgr.restore(jax.eval_shape(lambda: state), 1)
    assert not mgr.ws._open
    n = len(calls)
    time.sleep(0.05)
    assert len(calls) == n  # no read was left running
    mgr.ws.close()
