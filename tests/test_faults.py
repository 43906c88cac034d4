"""Fault plane: deterministic injection, retry + idempotent dedup, breakers,
degraded replica failover, and resumable striped transfers.

The contracts under test:

- a seeded FaultPlan replays the same drops/duplicates at the RPC boundary,
  and a retrying workspace completes the workload byte-identical with every
  mutation applied exactly once (server-side rid dedup proves retried
  writes were suppressed, not re-executed);
- the write-back journal recovers from an *injected* torn append exactly
  like a real crash-mid-fsync: the intact prefix replays, the tail is
  discarded, and the file stays appendable;
- stat/ls/search fail over to home-DC replicas during an origin partition
  (fresh rows flagged ``degraded``, lagging rows flagged ``stale``) while
  ``failover=False`` keeps the fail-fast baseline;
- an interrupted striped transfer under retry resumes from the last
  completed stripe (reads) / last confirmed chunk (writes) and leaves zero
  pinned cache records and zero partial extents behind on failure.
"""

import os
import threading
import time

import pytest

from repro.core import (
    CANNED_PLANS,
    Collaboration,
    FaultPlan,
    RetryPolicy,
    RpcError,
    RpcUnavailable,
    TornWrite,
    Workspace,
    WriteBackJournal,
    canned_plan,
)
from repro.core.plane import CircuitBreaker

# fast, test-sized retry schedule: enough attempts/backoff to outlast the
# injected outages below, small enough to keep the suite quick
FAST = RetryPolicy(max_attempts=6, base_s=0.001, cap_s=0.02, timeout_s=0.0, deadline_s=5.0)


def _replicated():
    c = Collaboration()
    c.add_datacenter("dc0", n_dtns=2)
    c.add_datacenter("dc1", n_dtns=2)
    c.start_replication(max_age_s=0.02, poll_s=0.005)
    return c


def _path_owned_by(collab, dc_id, tag):
    for i in range(500):
        p = f"/shared/{tag}{i}.dat"
        if collab.owner_dtn(p).dc_id == dc_id:
            return p
    raise AssertionError(f"no path hashed to {dc_id}")


def _total_deduped(collab):
    return sum(d.metadata_server.deduped + d.discovery_server.deduped for d in collab.dtns)


# -- retry + exactly-once ------------------------------------------------------
def test_retry_rides_through_drops_byte_identical():
    c = _replicated()
    try:
        plan = FaultPlan(seed=7).drop(every=7).drop(every=11, replies=True)
        c.install_faults(plan)
        policy = RetryPolicy(max_attempts=8, base_s=0.001, cap_s=0.02, timeout_s=0.0,
                             deadline_s=5.0)
        ws = Workspace(c, "alice", "dc0", retry=policy)
        blobs = {}
        for i in range(8):
            p = f"/shared/drop{i}.dat"
            blobs[p] = os.urandom(256)
            ws.write(p, blobs[p])
        ws.flush()
        assert plan.dropped > 0 and plan.dropped_replies > 0
        # lost replies forced resends of *executed* mutations: the server's
        # rid window suppressed the replays instead of double-applying
        assert _total_deduped(c) > 0
        assert sum(cl.stats.retries for cl in ws.plane.clients()) > 0
        c.install_faults(None)
        for p, want in blobs.items():
            assert ws.read(p) == want
        ws.close()
    finally:
        c.close()


def test_duplicate_delivery_applies_once():
    c = _replicated()
    try:
        plan = FaultPlan(seed=1).duplicate(every=2)
        c.install_faults(plan)
        ws = Workspace(c, "bob", "dc1", retry=FAST)
        p = _path_owned_by(c, "dc1", "dup")
        ws.write(p, b"hello-once")
        ws.flush()
        assert plan.duplicated > 0
        assert _total_deduped(c) > 0  # the second delivery hit the rid cache
        c.install_faults(None)
        assert ws.read(p) == b"hello-once"
        ws.close()
    finally:
        c.close()


def test_crash_at_nth_call_with_restart_rides_through():
    c = _replicated()
    try:
        victim = next(d.dtn_id for d in c.dtns if d.dc_id == "dc1")
        plan = FaultPlan(seed=5).crash_dtn_at_call(victim, 5, restart_after_s=0.02)
        c.install_faults(plan)
        ws = Workspace(c, "alice", "dc0", retry=FAST)
        blobs = {}
        for i in range(10):
            p = f"/shared/crash{i}.dat"
            blobs[p] = os.urandom(128)
            ws.write(p, blobs[p])
        ws.flush()
        assert plan.crashes == 1
        for p, want in blobs.items():
            assert ws.read(p) == want
        ws.close()
    finally:
        c.close()


# -- torn journal appends (satellite 3) ---------------------------------------
def test_torn_journal_append_recovery(tmp_path):
    jpath = str(tmp_path / "wb.journal")
    plan = FaultPlan(seed=3).torn_journal_append(2, keep_fraction=0.4)
    hook = lambda n: plan.journal_torn_bytes(plan.next_journal_ordinal(), n)  # noqa: E731
    j = WriteBackJournal(jpath, fault_hook=hook)
    j.append("/a", {"size": 1}, epoch=1)
    j.append("/b", {"size": 2}, epoch=2)
    with pytest.raises(TornWrite):
        j.append("/c", {"size": 3}, epoch=3)
    assert plan.torn_writes == 1
    j.close()
    # recovery: the torn tail is discarded, the intact prefix replays, and
    # the truncated file is appendable again
    j2 = WriteBackJournal(jpath)
    assert set(j2.recover()) == {"/a", "/b"}
    j2.append("/d", {"size": 4}, epoch=4)
    j2.close()
    assert {r["path"] for r in WriteBackJournal.read_records(jpath)} == {"/a", "/b", "/d"}


# -- circuit breaker -----------------------------------------------------------
def test_circuit_breaker_state_machine():
    br = CircuitBreaker(threshold=2, cooldown_s=0.05)
    assert br.state == "closed" and br.allow()
    br.failure()
    assert br.state == "closed"
    br.failure()
    assert br.state == "open" and br.opened == 1
    assert not br.allow()
    time.sleep(0.06)
    assert br.state == "half-open"
    assert br.allow()  # the single half-open probe
    assert not br.allow()  # concurrent second probe denied
    br.failure()  # probe failed: re-open for another cooldown
    assert br.state == "open" and br.opened == 2
    time.sleep(0.06)
    assert br.allow()
    br.success()
    assert br.state == "closed" and br.allow()


# -- partition + degraded reads ------------------------------------------------
def _partitioned_reader(c, name, **kw):
    policy = RetryPolicy(max_attempts=2, base_s=0.0005, cap_s=0.002, timeout_s=0.0,
                         deadline_s=0.5)
    return Workspace(c, name, "dc0", retry=policy, **kw)


def test_partition_degraded_stat_ls_search():
    c = _replicated()
    try:
        writer = Workspace(c, "carol", "dc1")
        p = _path_owned_by(c, "dc1", "part")
        writer.write(p, b"payload")
        writer.tag(p, "quality", "gold")
        writer.flush()
        assert c.quiesce_replication()
        reader = _partitioned_reader(c, "dave")
        c.install_faults(FaultPlan(seed=0).partition("dc0", "dc1"))
        entry = reader.stat(p)
        assert entry is not None and entry.get("degraded") and not entry.get("stale")
        assert entry["replica"]["dtn"] in reader.plane.local_dtns
        assert p in {e["path"] for e in reader.find("/")}
        rows = reader.search("quality = gold")
        assert any(r["path"] == p for r in rows)
        assert all(r.get("degraded") for r in rows)
        rs = reader.resilience_stats()
        assert rs["degraded_reads"] >= 3
        c.install_faults(None)
        reader.close()
        writer.close()
    finally:
        c.close()


def test_partition_failfast_baseline_raises():
    c = _replicated()
    try:
        writer = Workspace(c, "carol", "dc1")
        p = _path_owned_by(c, "dc1", "ff")
        writer.write(p, b"payload")
        writer.flush()
        assert c.quiesce_replication()
        failfast = _partitioned_reader(c, "erin", failover=False)
        c.install_faults(FaultPlan(seed=0).partition("dc0", "dc1"))
        with pytest.raises(RpcError):
            failfast.stat(p)
        c.install_faults(None)
        failfast.close()
        writer.close()
    finally:
        c.close()


def test_degraded_stat_stale_flag_and_not_cached():
    c = _replicated()
    try:
        writer = Workspace(c, "carol", "dc1")
        p = _path_owned_by(c, "dc1", "stale")
        writer.write(p, b"v1")
        writer.flush()
        assert c.quiesce_replication()
        reader = _partitioned_reader(c, "dave")
        owner = c.owner_dtn(p).dtn_id
        # the reader has witnessed an epoch from the origin that no replica
        # has applied (a write acknowledged just before the partition)
        reader.plane.meta[owner].last_epoch = 1 << 30
        c.install_faults(FaultPlan(seed=0).partition("dc0", "dc1"))
        entry = reader.stat(p)
        assert entry is not None and entry.get("stale") and entry.get("degraded")
        assert entry["replica"]["behind"] > 0
        assert reader.resilience_stats()["stale_serves"] >= 1
        # stale rows are never cached: the next stat consults replicas again
        entry2 = reader.stat(p)
        assert entry2.get("stale")
        c.install_faults(None)
        reader.close()
        writer.close()
    finally:
        c.close()


def test_partition_warm_cache_serves_cold_read_fails_then_heals():
    c = _replicated()
    try:
        writer = Workspace(c, "carol", "dc1")
        warm = _path_owned_by(c, "dc1", "warm")
        cold = _path_owned_by(c, "dc1", "cold")
        blob = os.urandom(4096)
        writer.write(warm, blob)
        writer.write(cold, blob)
        writer.flush()
        assert c.quiesce_replication()
        reader = _partitioned_reader(c, "dave")
        assert reader.read(warm) == blob  # warms the chunk cache
        plan = FaultPlan(seed=0).partition("dc0", "dc1")
        c.install_faults(plan)
        # cached bytes stay readable through the partition...
        assert reader.read(warm) == blob
        # ...but a cold data read has nowhere to get bytes from
        with pytest.raises(RpcError):
            reader.read(cold)
        assert reader.data_stats()["transfer_retries"] >= 1
        plan.heal()
        assert reader.read(cold) == blob
        reader.close()
        writer.close()
    finally:
        c.close()


# -- resumable striped transfers (satellite 4) ---------------------------------
def test_striped_read_resumes_from_last_completed_stripe(collab):
    policy = RetryPolicy(max_attempts=6, base_s=0.01, cap_s=0.05, timeout_s=0.0,
                         deadline_s=5.0)
    ws = Workspace(collab, "bob", "dc0", retry=policy, stripe_bytes=1 << 10)
    dp = ws.datapath
    p = _path_owned_by(collab, "dc1", "resume")
    data = os.urandom(4096)
    collab.dc("dc1").backend.write(p, data, owner="carol")
    dc = collab.dc("dc1")
    ids = [d.dtn_id for d in dc.dtns]
    real = dc.backend.read_deferred
    calls = []

    def flaky(path, offset=0, length=-1):
        calls.append(offset)
        if len(calls) == 2:
            # every mover dies during the second stream, then recovers
            for i in ids:
                collab.crash_dtn(i)
            t = threading.Timer(0.005, lambda: [collab.restart_dtn(i) for i in ids])
            t.daemon = True
            t.start()
        return real(path, offset=offset, length=length)

    dc.backend.read_deferred = flaky
    try:
        parts = dp._fetch_resumable("dc1", p, [(0, 1024), (2048, 3072)])
    finally:
        dc.backend.read_deferred = real
    got = {off: bytes(d) for off, d in parts}
    assert got == {0: data[0:1024], 2048: data[2048:3072]}
    # the completed first stripe was NOT refetched: offsets show one initial
    # pass plus exactly one retry of the interrupted second stream
    assert calls == [0, 2048, 2048]
    st = dp.stats()
    assert st["interrupted_transfers"] >= 1 and st["transfer_retries"] >= 1
    ws.close()


def test_crash_mid_transfer_under_retry_no_pins_no_partial_cache(collab):
    policy = RetryPolicy(max_attempts=2, base_s=0.001, cap_s=0.002, timeout_s=0.0,
                         deadline_s=0.5)
    ws = Workspace(collab, "bob", "dc0", retry=policy, stripe_bytes=1 << 10)
    writer = Workspace(collab, "carol", "dc1")
    p = _path_owned_by(collab, "dc1", "pins")
    data = os.urandom(8192)
    writer.write(p, data)
    dc = collab.dc("dc1")
    ids = [d.dtn_id for d in dc.dtns]
    real = dc.backend.read_deferred

    def crashing(path, offset=0, length=-1):
        for i in ids:
            collab.crash_dtn(i)
        return real(path, offset=offset, length=length)

    dc.backend.read_deferred = crashing
    try:
        with pytest.raises(RpcError):
            ws.read(p)
    finally:
        dc.backend.read_deferred = real
    # retries exhausted: nothing pinned, nothing partial left in the cache
    assert ws.datapath.cache.pinned_count() == 0
    assert ws.datapath.cache.read(p, 0, len(data)) is None
    for i in ids:
        collab.restart_dtn(i)
    assert ws.read(p) == data
    ws.close()
    writer.close()


def test_striped_write_resumes_from_last_confirmed_chunk(collab):
    policy = RetryPolicy(max_attempts=6, base_s=0.01, cap_s=0.05, timeout_s=0.0,
                         deadline_s=5.0)
    ws = Workspace(collab, "bob", "dc0", retry=policy, stripe_bytes=1 << 10)
    dp = ws.datapath
    p = "/shared/wresume.dat"
    data = os.urandom(4096)  # 4 chunks at 1 KiB stripes
    dc = collab.dc("dc1")
    ids = [d.dtn_id for d in dc.dtns]
    real = dc.backend.write_deferred
    offsets = []

    def flaky(path, payload, offset=0, owner=""):
        offsets.append(offset)
        if len(offsets) == 3:
            for i in ids:
                collab.crash_dtn(i)
            t = threading.Timer(0.005, lambda: [collab.restart_dtn(i) for i in ids])
            t.daemon = True
            t.start()
        return real(path, payload, offset=offset, owner=owner)

    dc.backend.write_deferred = flaky
    try:
        dp.write("dc1", p, data, owner="bob")
    finally:
        dc.backend.write_deferred = real
    back, _ = dc.backend.read_deferred(p, offset=0, length=len(data))
    assert bytes(back) == data
    # chunk 0 shipped exactly once: the retry resumed at the unconfirmed
    # chunk (idempotent offset rewrite), not from byte zero
    assert offsets.count(0) == 1
    assert dp.stats()["transfer_retries"] >= 1
    ws.close()


# -- quiesce stall detection (satellite 2) -------------------------------------
def test_quiesce_crashed_peer_still_converges():
    c = _replicated()
    try:
        ws = Workspace(c, "alice", "dc0")
        for i in range(4):
            ws.write(f"/shared/q{i}.dat", b"x")
        ws.flush()
        assert c.quiesce_replication()
        # a crashed (already drained) peer must not block convergence: lag
        # accounting excludes down peers
        victim = c.dtns[-1].dtn_id
        c.crash_dtn(victim)
        late = next(
            f"/shared/qlate{i}.dat"
            for i in range(500)
            if c.owner_dtn(f"/shared/qlate{i}.dat").dtn_id != victim
        )
        ws.write(late, b"y")
        ws.flush()
        assert c.quiesce_replication()
        c.restart_dtn(c.dtns[-1].dtn_id)
        ws.close()
    finally:
        c.close()


def test_quiesce_stall_returns_false_promptly_with_reason():
    c = _replicated()
    try:
        # simulate the oscillation a mid-drain crash/flap produces: a pump
        # whose reported lag never shrinks although its sweeps "complete"
        pump = c.dtns[0].replica_pump
        pump.quiesce = lambda timeout_s=10.0: True
        pump.lag = lambda: 3
        t0 = time.time()
        assert c.quiesce_replication(timeout_s=30.0) is False
        assert time.time() - t0 < 5.0  # prompt, nowhere near the deadline
        assert c.quiesce_reason is not None and "no drain progress" in c.quiesce_reason
    finally:
        c.close()


# -- restart regression (satellite 1) ------------------------------------------
def test_restart_after_start_replication_while_down_rejoins_mesh():
    c = Collaboration()
    try:
        c.add_datacenter("dc0", n_dtns=2)
        c.add_datacenter("dc1", n_dtns=2)
        victim = c.dtns[-1].dtn_id
        c.crash_dtn(victim)
        # replication starts while the DTN is down: its pump is created but
        # must not run until the restart
        c.start_replication(max_age_s=0.02, poll_s=0.005)
        ws = Workspace(c, "alice", "dc0")
        p = _path_owned_by(c, "dc0", "rejoin")
        ws.write(p, b"rejoined")
        ws.flush()
        c.restart_dtn(victim)
        pump = c.dtns[victim].replica_pump
        assert pump is not None and pump._thread is not None and pump._thread.is_alive()
        assert c.quiesce_replication()
        owner = c.owner_dtn(p).dtn_id
        rep = c.dtns[victim].metadata.getattr_replica(path=p, origin=owner)
        assert rep["entry"] is not None and rep["entry"]["path"] == p
        ws.close()
    finally:
        c.close()


def test_async_indexer_not_started_while_down():
    c = Collaboration()
    try:
        c.add_datacenter("dc0", n_dtns=1)
        dtn = c.dtns[0]
        dtn.crash()
        assert dtn.start_async_indexer() is None
        dtn.restart()
    finally:
        c.close()


# -- canned plans --------------------------------------------------------------
def test_canned_plans_registry():
    assert set(CANNED_PLANS) == {
        "drops", "flaky", "crash", "chaos", "quorum", "lease-expiry",
    }
    for name in CANNED_PLANS:
        assert isinstance(canned_plan(name, seed=2), FaultPlan)
    with pytest.raises(ValueError):
        canned_plan("nope")


def test_heal_cancels_timed_restarts_and_resets_cadence():
    """install_faults(None) must leave the collaboration indistinguishable
    from one that never had the plan: pending crash_dtn_at_call timed
    restarts cancelled (the victim restarted NOW, not 30 s later), partitions
    lifted, and all cadence state (rule matched/fired, crash triggers) reset
    — while the lifetime observability totals survive (scripts/fault_matrix.py
    reads plan.stats() after the heal)."""
    c = _replicated()
    try:
        victim = next(d.dtn_id for d in c.dtns if d.dc_id == "dc1")
        plan = (
            FaultPlan(seed=5)
            .duplicate(every=2)
            .crash_dtn_at_call(victim, 3, restart_after_s=30.0)
        )
        c.install_faults(plan)
        ws = _partitioned_reader(c, "alice")
        for _ in range(6):
            try:
                ws.plane.meta_call(victim, "lookup", path="/heal/probe")
            except RpcError:
                pass
        assert plan.crashes == 1 and c.dtns[victim].down
        assert plan.duplicated > 0
        timers = list(plan._timers)
        assert timers  # the 30 s restart is pending
        dup_before = plan.duplicated
        c.install_faults(None)
        # healed: victim back up immediately, timer cancelled, schedule reset
        assert not c.dtns[victim].down
        assert all(t.finished.is_set() for t in timers)
        assert plan._timers == [] and plan._crashed_by_plan == set()
        # schedule restored to the as-built spec: the crash trigger is
        # re-armed (not gone) and no partitions were ever configured
        assert plan._crash_at == {victim: [3, 30.0]} and plan._partitions == set()
        for rule in plan._rules:
            assert rule.matched == 0 and rule.fired == 0
        # lifetime totals preserved: history, not pending behavior
        assert plan.crashes == 1 and plan.duplicated == dup_before
        assert plan.stats()["crashes"] == 1
        # healed ≡ fresh: the very same plan re-installed starts its cadence
        # from zero — the victim crashes again only after 3 fresh calls
        c.install_faults(plan)
        for _ in range(6):
            try:
                ws.plane.meta_call(victim, "lookup", path="/heal/probe2")
            except RpcError:
                pass
        assert plan.crashes == 2 and c.dtns[victim].down
        c.install_faults(None)
        assert not c.dtns[victim].down
        ws.close()
    finally:
        c.close()


def test_resilience_stats_budget_exhaustion_and_dedup_evictions():
    """Satellite 2: retry-budget exhaustion and server-side dedup-window
    evictions are observable through resilience_stats() (plane + workspace)."""
    c = _replicated()
    try:
        # (a) budget exhaustion: a 1-retry budget under a partition — the
        # give-up is charged to the budget, not the per-call attempt cap
        tight = RetryPolicy(max_attempts=4, base_s=0.0005, cap_s=0.002,
                            timeout_s=0.0, deadline_s=1.0, budget=1)
        ws = Workspace(c, "alice", "dc0", retry=tight, failover=False)
        victim = next(d.dtn_id for d in c.dtns if d.dc_id == "dc1")
        c.install_faults(FaultPlan(seed=0).partition("dc0", "dc1"))
        for _ in range(3):
            with pytest.raises(RpcUnavailable):
                ws.plane.meta_call(victim, "lookup", path="/budget/x")
        rs = ws.resilience_stats()
        assert rs["budget_exhausted"] >= 1
        c.install_faults(None)
        # (b) dedup evictions: shrink every server's idempotency window to
        # zero — each cached reply is immediately aged out and counted
        for d in c.dtns:
            d.metadata_server.dedup_window = 0
            d.discovery_server.dedup_window = 0
        ws2 = Workspace(c, "bob", "dc1", retry=FAST)
        for i in range(3):
            ws2.write(f"/budget/evict{i}.dat", b"x")
        ws2.flush()
        rs2 = ws2.resilience_stats()
        assert rs2["dedup_evictions"] > 0
        ws.close()
        ws2.close()
    finally:
        c.close()


def test_breaker_half_open_failed_probe_reopens_via_plane():
    """Satellite 3a: a half-open probe that fails re-opens the breaker for a
    fresh cooldown — observed through the plane's guarded_call path, not the
    CircuitBreaker in isolation."""
    c = _replicated()
    try:
        ws = _partitioned_reader(c, "dave", breaker_threshold=2, breaker_cooldown_s=0.05)
        victim = next(d.dtn_id for d in c.dtns if d.dc_id == "dc1")
        c.crash_dtn(victim)
        for _ in range(2):
            with pytest.raises(RpcUnavailable):
                ws.plane.guarded_call("meta", victim, "lookup", path="/probe/x")
        br = ws.plane.breakers[victim]
        assert br.state == "open" and br.opened == 1
        skips = ws.plane.breaker_skips
        with pytest.raises(RpcUnavailable):
            ws.plane.guarded_call("meta", victim, "lookup", path="/probe/x")
        assert ws.plane.breaker_skips == skips + 1  # refused instantly, no RPC
        time.sleep(0.06)
        assert br.state == "half-open"
        # the single probe goes through, fails (victim still down), re-opens
        with pytest.raises(RpcUnavailable):
            ws.plane.guarded_call("meta", victim, "lookup", path="/probe/x")
        assert br.state == "open" and br.opened == 2
        assert not br.allow()  # backed off for a fresh full cooldown
        c.restart_dtn(victim)
        ws.close()
    finally:
        c.close()


def test_breaker_half_open_successful_probe_closes_via_plane():
    """Satellite 3b: a half-open probe that succeeds fully closes the
    breaker — subsequent calls flow without probe gating."""
    c = _replicated()
    try:
        ws = _partitioned_reader(c, "dave", breaker_threshold=2, breaker_cooldown_s=0.05)
        victim = next(d.dtn_id for d in c.dtns if d.dc_id == "dc1")
        c.crash_dtn(victim)
        for _ in range(2):
            with pytest.raises(RpcUnavailable):
                ws.plane.guarded_call("meta", victim, "lookup", path="/probe/y")
        br = ws.plane.breakers[victim]
        assert br.state == "open"
        c.restart_dtn(victim)
        time.sleep(0.06)
        assert br.state == "half-open"
        assert ws.plane.guarded_call("meta", victim, "lookup", path="/probe/y") is False
        assert br.state == "closed"
        # fully closed: back-to-back calls all admitted (no single-probe gate)
        for _ in range(3):
            ws.plane.guarded_call("meta", victim, "lookup", path="/probe/y")
        assert br.state == "closed"
        ws.close()
    finally:
        c.close()


def test_fault_plan_seed_determinism():
    def fire_pattern(seed):
        plan = FaultPlan(seed).drop(p=0.3)

        class _Srv:  # minimal server stand-in with a site
            site = "dc1"

        srv = _Srv()
        return [plan.on_message("dc0", srv, 100) is not None for _ in range(50)]

    assert fire_pattern(11) == fire_pattern(11)
    assert fire_pattern(11) != fire_pattern(12)
