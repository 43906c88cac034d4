"""The data plane: striped lanes, consistent chunk cache, read-ahead.

The acceptance bar mirrors the attr cache's: byte identity through every
(stripe, lane, cache) configuration, and a cache hit that is *never* stale —
a remote collaborator's write, an MEU export, or a delete must be observed
by the next local read even when the bytes were cached (or in flight) here.
"""

import os
import threading
import time

import numpy as np
import pytest

from repro.core import ChunkCache, Collaboration, DataPath, NativeSession, Workspace
from repro.core.datapath import (
    CHUNK_CACHE_BYTES,
    DATA_LANES,
    STRIPE_BYTES,
    merge_ranges,
    subtract_ranges,
)
from repro.core.metadata import path_hash
from repro.core.rpc import Channel, RpcError


def _remote_path(collab, home_dc: str, tag: str) -> str:
    """A path whose owner DTN lives in a DC other than ``home_dc``."""
    for i in range(500):
        p = f"/proj/{tag}{i}.bin"
        if collab.owner_dtn(p).dc_id != home_dc:
            return p
    raise RuntimeError("no remote-owned path found")


def _wait(predicate, timeout_s=5.0):
    deadline = time.time() + timeout_s
    while not predicate():
        if time.time() > deadline:
            raise AssertionError("timed out waiting for condition")
        time.sleep(0.001)


# -- lane model ---------------------------------------------------------------
def test_channel_split_shares_bandwidth_keeps_latency():
    ch = Channel(name="cross", latency_s=1e-3, gbps=100.0, stream_gbps=5.0)
    lanes = ch.split(4)
    assert len(lanes) == 4
    assert all(l.latency_s == ch.latency_s for l in lanes)  # latency overlaps
    assert sum(l.gbps for l in lanes) == pytest.approx(ch.gbps)  # capacity splits
    assert all(l.stream_gbps == ch.stream_gbps for l in lanes)  # own window each
    # a window-bound link: one lane moves at stream rate, four lanes aggregate
    one = ch.payload_seconds(1 << 20)
    agg = max(l.payload_seconds((1 << 20) // 4) for l in lanes)
    assert agg < one / 2


def test_channel_split_degenerate():
    free = Channel()
    assert len(free.split(1)) == 1
    assert free.split(3)[0].gbps == float("inf")
    assert Channel(gbps=8.0).split(0)[0].gbps == 8.0  # clamped to >= 1 lane


def test_range_utilities():
    assert merge_ranges([(5, 10), (0, 6), (12, 13), (9, 12)]) == [(0, 13)]
    assert merge_ranges([(3, 3), (1, 2)]) == [(1, 2)]  # empty ranges dropped
    assert subtract_ranges([(0, 100)], [(10, 20), (50, 60)]) == [
        (0, 10),
        (20, 50),
        (60, 100),
    ]
    assert subtract_ranges([(0, 10)], [(0, 10)]) == []
    assert subtract_ranges([(0, 10)], []) == [(0, 10)]


# -- ChunkCache unit ----------------------------------------------------------
def test_chunk_cache_extents_coalesce_and_serve():
    cc = ChunkCache(1 << 20)
    cc.pin("/f")
    gen = cc.gen_of("/f")
    assert cc.read("/f", 0, 4) is None
    assert cc.insert("/f", gen, 0, b"abcd", size=10)
    assert cc.insert("/f", gen, 4, b"efgh")  # adjacent: coalesces
    assert cc.insert("/f", gen, 8, b"ij")
    assert cc.read("/f", 0, 10) == b"abcdefghij"
    assert cc.read("/f", 3, 7) == b"defg"
    assert cc.missing("/f", 0, 10) == []
    assert cc.size_of("/f") == 10
    cc.unpin("/f")


def test_chunk_cache_missing_reports_gaps():
    cc = ChunkCache(1 << 20)
    cc.pin("/f")
    gen = cc.gen_of("/f")
    cc.insert("/f", gen, 10, b"x" * 10)
    cc.insert("/f", gen, 40, b"y" * 10)
    assert cc.missing("/f", 0, 60) == [(0, 10), (20, 40), (50, 60)]
    assert cc.read("/f", 0, 60) is None  # gaps → miss
    cc.unpin("/f")


def test_chunk_cache_generation_discards_stale_fill():
    cc = ChunkCache(1 << 20)
    cc.pin("/f")
    gen = cc.gen_of("/f")
    cc.drop("/f")  # invalidation arrives while the fill is in flight
    assert not cc.insert("/f", gen, 0, b"stale")
    assert cc.read("/f", 0, 5) is None
    assert cc.stats()["stale_inserts"] == 1
    cc.unpin("/f")


def test_chunk_cache_epoch_fence_invalidates_older_bytes():
    cc = ChunkCache(1 << 20)
    cc.pin("/f")
    cc.insert("/f", cc.gen_of("/f"), 0, b"old!", epoch=1)
    cc.unpin("/f")
    assert cc.read("/f", 0, 4) == b"old!"
    # a reader that has witnessed epoch 3 must not be served epoch-1 bytes
    cc.pin("/f", min_epoch=3)
    assert cc.read("/f", 0, 4) is None
    cc.unpin("/f")


def test_chunk_cache_lru_evicts_by_bytes_but_not_pinned():
    cc = ChunkCache(100)
    for i in range(3):
        cc.pin(f"/f{i}")
        cc.insert(f"/f{i}", cc.gen_of(f"/f{i}"), 0, bytes(40))
        cc.unpin(f"/f{i}")
    assert cc.data_bytes() <= 100
    assert cc.stats()["evictions"] >= 1
    assert cc.read("/f0", 0, 40) is None  # oldest went first
    # pinned records survive even when the cache overflows
    cc.pin("/pinned")
    cc.insert("/pinned", cc.gen_of("/pinned"), 0, bytes(90))
    cc.insert("/pinned", cc.gen_of("/pinned"), 90, bytes(90))
    assert cc.read("/pinned", 0, 180) is not None
    cc.unpin("/pinned")


def test_chunk_cache_bus_interface_drops_by_hash():
    cc = ChunkCache(1 << 20)
    cc.pin("/a/b")
    cc.insert("/a/b", cc.gen_of("/a/b"), 0, b"data")
    cc.unpin("/a/b")
    assert cc.invalidate_hashes([path_hash("/other")]) == 0
    assert cc.read("/a/b", 0, 4) == b"data"
    assert cc.invalidate_hashes([path_hash("/a/b")]) == 1
    assert cc.read("/a/b", 0, 4) is None


def test_chunk_cache_disabled_rejects_inserts():
    cc = ChunkCache(0)
    assert not cc.enabled
    cc.pin("/f")
    assert not cc.insert("/f", cc.gen_of("/f"), 0, b"x")
    cc.unpin("/f")


# -- striped transfer byte identity ------------------------------------------
@pytest.mark.parametrize(
    "stripe,lanes,cache",
    [
        (256 << 10, 4, 128 << 20),  # defaults
        (1 << 10, 2, 128 << 20),    # many small stripes
        (1 << 20, 8, 0),            # stripe > file, cache off
        (0, 1, 0),                  # single-shot path restored
        (4096, 3, 4096),            # cache smaller than the file (evicts)
    ],
)
def test_striped_roundtrip_byte_identity(collab, stripe, lanes, cache):
    """Striped write → striped read ≡ the original bytes, every config."""
    rng = np.random.default_rng(stripe + lanes)
    writer = Workspace(
        collab, "alice", "dc0",
        stripe_bytes=stripe, data_lanes=lanes, chunk_cache_bytes=cache,
    )
    reader = Workspace(
        collab, "bob", "dc1",
        stripe_bytes=stripe, data_lanes=lanes, chunk_cache_bytes=cache,
    )
    for size in (0, 1, 4095, 4096, 4097, 100_000):
        path = _remote_path(collab, "dc1", f"id{stripe}_{lanes}_{size}_")
        data = rng.bytes(size)
        writer.write(path, data)
        assert reader.read(path) == data, (stripe, lanes, cache, size)
        assert reader.read(path) == data  # repeat (cached path when enabled)
    writer.close()
    reader.close()


def test_striped_write_lands_identical_at_remote_pfs(collab):
    """The remote DC's PFS holds exactly the written bytes (chunk order +
    offset-0 truncate compose correctly), including a shorter rewrite."""
    ws = Workspace(collab, "alice", "dc0", stripe_bytes=1 << 10, data_lanes=4)
    path = _remote_path(collab, "dc0", "w")
    dc_id = collab.owner_dtn(path).dc_id
    native = NativeSession(collab.dc(dc_id), "local")
    big = os.urandom(10_000)
    ws.write(path, big)
    assert native.read(path) == big
    small = os.urandom(1_500)
    ws.write(path, small)
    assert native.read(path) == small  # no stale tail from the 10 KB version
    ws.close()


# -- cache consistency --------------------------------------------------------
def test_cache_hit_never_stale_remote_write(collab):
    """THE acceptance bar: remote write → local cached read observes it,
    with the chunk cache enabled by default."""
    alice = Workspace(collab, "alice", "dc0")
    bob = Workspace(collab, "bob", "dc1")
    path = _remote_path(collab, "dc1", "stale")
    alice.write(path, b"version-1")
    assert bob.read(path) == b"version-1"
    assert bob.read(path) == b"version-1"  # now a cache hit
    assert bob.data_stats()["cache_hits"] >= 1
    alice.write(path, b"version-2!!")  # publishes invalidation by path hash
    assert bob.read(path) == b"version-2!!"
    alice.close()
    bob.close()


def test_own_write_readback_is_a_cache_hit(collab):
    """Write-through: a mount's own remote write is re-readable from its
    cache (its own publication must not evict its own fresh bytes)."""
    ws = Workspace(collab, "alice", "dc0")
    path = _remote_path(collab, "dc0", "own")
    ws.write(path, b"mine" * 100)
    before = ws.data_stats()
    assert ws.read(path) == b"mine" * 100
    after = ws.data_stats()
    assert after["cache_hits"] == before["cache_hits"] + 1
    assert after["bytes_read"] == before["bytes_read"]  # zero wire bytes
    ws.close()


def test_cache_invalidated_on_delete(collab):
    alice = Workspace(collab, "alice", "dc0")
    bob = Workspace(collab, "bob", "dc1")
    path = _remote_path(collab, "dc1", "del")
    alice.write(path, b"doomed")
    assert bob.read(path) == b"doomed"  # cached at bob
    alice.delete(path)
    assert bob.stat(path) is None
    with pytest.raises(FileNotFoundError):
        bob.read(path)
    # recreation with new bytes must not resurrect the cached old ones
    alice.write(path, b"reborn!")
    assert bob.read(path) == b"reborn!"
    alice.close()
    bob.close()


def test_deleting_owner_drops_own_cache(collab):
    ws = Workspace(collab, "alice", "dc0")
    path = _remote_path(collab, "dc0", "owndel")
    ws.write(path, b"bytes")
    assert ws.read(path) == b"bytes"
    ws.delete(path)
    assert ws.datapath.cache.read(path, 0, 5) is None
    ws.close()


def test_meu_export_invalidates_chunk_caches(collab):
    """Native (LW) writes are invisible until export — and the export's
    invalidation wave must evict stale cached bytes of re-used paths."""
    from repro.core import MEU

    alice = Workspace(collab, "alice", "dc0")
    bob = Workspace(collab, "bob", "dc1")
    path = _remote_path(collab, "dc1", "meu")
    alice.write(path, b"workspace-v1")
    assert bob.read(path) == b"workspace-v1"
    # native overwrite at the owning DC, then export
    dc_id = collab.owner_dtn(path).dc_id
    native = NativeSession(collab.dc(dc_id), "carol")
    native.write(path, b"native-v2rev")
    MEU(collab, collab.dc(dc_id), "carol").export("/proj")
    assert bob.read(path) == b"native-v2rev"
    alice.close()
    bob.close()


# -- read-ahead ---------------------------------------------------------------
def _scidata_fixture(collab, writer_home="dc0", reader_home="dc1"):
    writer = Workspace(collab, "alice", writer_home)
    reader = Workspace(collab, "bob", reader_home)
    path = None
    for i in range(500):
        p = f"/proj/sci{i}.sci"
        if collab.owner_dtn(p).dc_id != reader_home:
            path = p
            break
    # large enough that the payloads extend past the 64 KiB-aligned header
    # fetch — otherwise there is nothing left for read-ahead to move
    arrays = {
        f"d{j}": np.arange(j * 1000, j * 1000 + 30_000, dtype=np.float64)
        for j in range(3)
    }
    writer.write_scidata(path, arrays, {"project": "ocean", "rev": 1})
    return writer, reader, path, arrays


def test_readahead_prefetches_next_dataset(collab):
    writer, reader, path, arrays = _scidata_fixture(collab)
    assert reader.read_attrs(path)["project"] == "ocean"
    reader.datapath.drain_prefetch()
    stats = reader.data_stats()
    assert stats["prefetch_issued"] >= 1 and stats["prefetch_completed"] >= 1
    assert stats["prefetch_bytes"] > 0
    # the prefetched first dataset is served without new foreground bytes
    before = reader.data_stats()["bytes_read"]
    np.testing.assert_array_equal(reader.read_dataset(path, "d0"), arrays["d0"])
    assert reader.data_stats()["bytes_read"] == before
    # directory-ordered: reading d0 prefetched d1
    reader.datapath.drain_prefetch()
    before = reader.data_stats()["bytes_read"]
    np.testing.assert_array_equal(reader.read_dataset(path, "d1"), arrays["d1"])
    assert reader.data_stats()["bytes_read"] == before
    writer.close()
    reader.close()


def test_readahead_disabled_by_knob(collab):
    writer = Workspace(collab, "alice", "dc0")
    reader = Workspace(collab, "bob", "dc1", readahead=False)
    path = _remote_path(collab, "dc1", "noahead")
    arrays = {"d0": np.arange(100, dtype=np.float64)}
    writer.write_scidata(path, arrays, {"k": 1})
    reader.read_attrs(path)
    time.sleep(0.05)
    assert reader.data_stats()["prefetch_issued"] == 0
    writer.close()
    reader.close()


def test_readahead_midflight_invalidation_never_poisons(collab):
    """A prefetched chunk invalidated mid-flight must not land: the late
    insert is generation-fenced and the next read sees the new bytes."""
    writer, reader, path, arrays = _scidata_fixture(collab)
    gate = threading.Event()
    reader.datapath._insert_gate = gate
    try:
        reader.read_attrs(path)  # queues the d0 payload prefetch
        # the worker has fetched (prefetch_bytes ticks in _fetch) and is now
        # parked at the gate, *before* inserting into the cache
        _wait(lambda: reader.data_stats()["prefetch_bytes"] > 0)
        new_arrays = {k: v * -1.0 for k, v in arrays.items()}
        writer.write_scidata(path, new_arrays, {"project": "ocean", "rev": 2})
        gate.set()  # release the stale insert attempt
        reader.datapath.drain_prefetch()
    finally:
        reader.datapath._insert_gate = None
        gate.set()
    np.testing.assert_array_equal(reader.read_dataset(path, "d0"), new_arrays["d0"])
    assert reader.data_stats()["cache_stale_inserts"] >= 1
    writer.close()
    reader.close()


# -- failure handling ---------------------------------------------------------
def test_crash_dtn_mid_transfer_clean_error_no_poisoning(collab):
    alice = Workspace(collab, "alice", "dc0")
    bob = Workspace(collab, "bob", "dc1", stripe_bytes=1 << 10)
    path = _remote_path(collab, "dc1", "crash")
    data = os.urandom(10_000)
    alice.write(path, data)
    dc_id = collab.owner_dtn(path).dc_id
    dc = collab.dc(dc_id)
    crash_ids = [d.dtn_id for d in dc.dtns]
    real_read = dc.backend.read_deferred
    calls = {"n": 0}

    def crashing_read(*a, **kw):
        # the PFS stream read itself succeeds, but every mover dies before
        # the laned transfer completes — the post-fetch liveness check must
        # fail the whole transfer
        calls["n"] += 1
        for i in crash_ids:
            collab.crash_dtn(i)
        return real_read(*a, **kw)

    dc.backend.read_deferred = crashing_read
    try:
        with pytest.raises(RpcError):
            bob.read(path)
    finally:
        dc.backend.read_deferred = real_read
    # nothing partial was cached
    assert bob.datapath.cache.read(path, 0, len(data)) is None
    for i in crash_ids:
        collab.restart_dtn(i)
    assert bob.read(path) == data
    alice.close()
    bob.close()


def test_write_to_dead_dc_raises(collab):
    ws = Workspace(collab, "alice", "dc0")
    path = _remote_path(collab, "dc0", "deadw")
    dc = collab.dc(collab.owner_dtn(path).dc_id)
    ids = [d.dtn_id for d in dc.dtns]
    for i in ids:
        collab.crash_dtn(i)
    try:
        with pytest.raises(RpcError):
            ws.write(path, b"x" * 10)
    finally:
        for i in ids:
            collab.restart_dtn(i)
    ws.close()


# -- accounting (satellite: header reads are charged) -------------------------
def test_remote_header_reads_charged_on_data_channel(collab):
    writer, reader, path, arrays = _scidata_fixture(collab)
    cold = Workspace(collab, "carol", "dc1", chunk_cache_bytes=0, readahead=False)
    assert cold.data_stats()["bytes_read"] == 0
    cold.read_attrs(path)
    charged = cold.data_stats()["bytes_read"]
    assert charged > 0  # header bytes cross the data channel now
    cold.read_attrs(path)
    assert cold.data_stats()["bytes_read"] == 2 * charged  # no cache: charged again
    # with the cache, the repeat is legitimately free
    reader.read_attrs(path)
    got = reader.data_stats()["bytes_read"]
    reader.read_attrs(path)
    assert reader.data_stats()["bytes_read"] == got
    writer.close()
    reader.close()
    cold.close()


def test_local_reads_bypass_datapath(collab):
    ws = Workspace(collab, "alice", "dc0")
    for i in range(500):
        p = f"/proj/local{i}.bin"
        if collab.owner_dtn(p).dc_id == "dc0":
            ws.write(p, b"home bytes")
            assert ws.read(p) == b"home bytes"
            break
    stats = ws.data_stats()
    assert stats["remote_reads"] == 0 and stats["bytes_read"] == 0
    ws.close()


# -- knob plumbing ------------------------------------------------------------
def test_knobs_ride_config_to_workspace(collab):
    assert STRIPE_BYTES > 0 and DATA_LANES >= 1 and CHUNK_CACHE_BYTES > 0
    ws = Workspace(collab, "alice", "dc0")
    assert ws.datapath.stripe_bytes == STRIPE_BYTES
    assert ws.datapath.data_lanes == DATA_LANES
    assert ws.datapath.cache.max_bytes == CHUNK_CACHE_BYTES
    assert ws.datapath.readahead is True
    ws.close()
    ws = Workspace(
        collab, "bob", "dc0",
        stripe_bytes=STRIPE_BYTES // 2,
        data_lanes=DATA_LANES + 1,
        chunk_cache_bytes=CHUNK_CACHE_BYTES // 2,
        readahead=False,
    )
    assert ws.datapath.stripe_bytes == STRIPE_BYTES // 2
    assert ws.datapath.data_lanes == DATA_LANES + 1
    assert ws.datapath.cache.max_bytes == CHUNK_CACHE_BYTES // 2
    assert ws.datapath.readahead is False
    ws.close()
