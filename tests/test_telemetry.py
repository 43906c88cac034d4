"""Telemetry plane: unified metrics registry, cross-DC tracing, profiling.

The contracts under test:

- registry primitives (Counter/Gauge/Histogram) snapshot and fold across
  registries: counters sum, histograms merge, percentiles stay monotone;
- ``Workspace.telemetry()`` is ONE scrape covering every documented counter
  family — rpc / datapath / replication / lease / plane / faults — and the
  legacy ``*_stats()`` shims read the same numbers (the stats drift
  hazard: two hand-merged views of the same counters disagreeing);
- every Workspace entry point roots a trace; the RPC envelope propagates it
  so client spans, server apply spans, and striped-lane spans assemble into
  one parent-linked cross-DC tree (``Collaboration.collect_trace``);
- under chaos (drops + duplicates + retries) an assembled trace shows
  exactly ONE server apply span per rid — retried deliveries hit the dedup
  window and never re-execute, and the trace proves it;
- a fenced write's trace shows the refusal (``rpc.fenced``) with no shard
  apply child — the write never touched a service;
- the acceptance cut (ISSUE 10): a degraded quorum write during a partition
  produces one trace tree spanning >= 3 DTNs — lease fan-out, journal
  intent, coordinator create, quorum pushes — and the heal-time reconcile
  joins the same trace as the final causal step;
- ``trace_enabled=False`` buffers nothing and still scrapes metrics.
"""

import json
import os

import pytest

from repro.core import (
    Collaboration,
    FaultPlan,
    RetryPolicy,
    RpcFenced,
    Workspace,
    canned_plan,
)
from repro.core.telemetry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Telemetry,
    assemble_trace,
    chrome_trace,
    fold_snapshots,
    render_timeline,
)

FAST = RetryPolicy(max_attempts=8, base_s=0.001, cap_s=0.02, timeout_s=0.0, deadline_s=5.0)

#: one key per documented counter family — the regression guard for the
#: "every plane reports through one scrape" claim (module docstring table)
DOCUMENTED_KEYS = [
    "rpc.calls",
    "rpc.ops",
    "rpc.retries",
    "rpc.deduped",
    "rpc.requests",
    "rpc.fenced_rejections",
    "rpc.call_seconds",
    "datapath.transfer_seconds",
    "datapath.cache.hit_bytes",
    "datapath.cache.miss_bytes",
    "replication.records_shipped",
    "lease.granted",
    "plane.degraded_writes",
    "plane.replica_hits",
    "invalidations.published",
]


def _replicated(n_dcs=2):
    c = Collaboration()
    for i in range(n_dcs):
        c.add_datacenter(f"dc{i}", n_dtns=2)
    c.start_replication(max_age_s=0.02, poll_s=0.005)
    return c


def _path_owned_by(collab, dc_id, tag):
    for i in range(500):
        p = f"/shared/{tag}{i}.dat"
        if collab.owner_dtn(p).dc_id == dc_id:
            return p
    raise AssertionError(f"no path hashed to {dc_id}")


def _spans_of(tree):
    """Flatten an assembled trace tree to its span dicts."""
    out = []

    def walk(node):
        out.append(node)
        for ch in node.get("children", ()):
            walk(ch)

    for root in tree["roots"]:
        walk(root)
    return out


# -- registry primitives -------------------------------------------------------
def test_counter_gauge_histogram_snapshot():
    reg = MetricsRegistry()
    reg.counter("rpc.calls").inc()
    reg.counter("rpc.calls").inc(4)
    reg.gauge("replication.window").set(17.0)
    h = reg.histogram("rpc.call_seconds")
    for v in (1e-6, 2e-6, 1e-3):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["rpc.calls"] == 5
    assert snap["replication.window"] == 17.0
    hs = snap["rpc.call_seconds"]
    assert hs["count"] == 3 and hs["min"] <= 1e-6 and hs["max"] >= 1e-3
    # log-bucket percentiles are coarse (factor of 2) but ordered and clamped
    assert hs["min"] <= hs["p50"] <= hs["p99"] <= hs["max"]
    assert isinstance(Counter("x").snapshot(), int)
    assert isinstance(Gauge("x").snapshot(), float)
    assert isinstance(Histogram("x"), Histogram)


def test_fold_snapshots_sums_counters_and_merges_histograms():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("rpc.calls").inc(2)
    b.counter("rpc.calls").inc(3)
    a.histogram("lat").observe(1e-6)
    b.histogram("lat").observe(1e-3)
    fold = fold_snapshots([a.snapshot(), b.snapshot()])
    assert fold["rpc.calls"] == 5
    assert fold["lat"]["count"] == 2
    assert fold["lat"]["min"] <= 1e-6 and fold["lat"]["max"] >= 1e-3


def test_collectors_flatten_nested_stats_dicts():
    reg = MetricsRegistry()
    reg.add_collector("datapath", lambda: {"remote_reads": 7, "cache": {"hits": 3}})
    snap = reg.snapshot()
    assert snap["datapath.remote_reads"] == 7
    assert snap["datapath.cache.hits"] == 3


# -- the unified scrape --------------------------------------------------------
def test_workspace_telemetry_covers_documented_counters_under_faults():
    """The single-scrape acceptance: after a faulted, replicated workload
    every documented counter family is present in ONE ``ws.telemetry()``
    call, and the scrape is JSON-serializable as scraped."""
    c = _replicated()
    try:
        ws = Workspace(c, "alice", "dc0", retry=FAST)
        c.install_faults(canned_plan("chaos", seed=2))
        for i in range(6):
            ws.write(f"/shared/tel{i}.dat", os.urandom(128))
        ws.flush()
        ws.read("/shared/tel0.dat")
        tel = ws.telemetry()
        missing = [k for k in DOCUMENTED_KEYS if k not in tel]
        assert not missing, f"scrape lost documented keys: {missing}"
        # the faults plane reports through the same scrape while a plan is live
        assert tel["faults.dropped"] + tel["faults.duplicated"] > 0
        json.dumps(tel)  # a scrape is wire-ready as scraped
        assert tel["rpc.calls"] > 0 and tel["rpc.requests"] > 0
    finally:
        c.close()


def test_stats_shims_read_the_registry_not_a_second_ledger():
    """The fault-matrix keys come out of the same fold the scrape
    uses — the drift hazard the registry removes (satellite a)."""
    c = _replicated()
    try:
        ws = Workspace(c, "alice", "dc0", retry=FAST)
        c.install_faults(FaultPlan(seed=7).drop(every=5).drop(every=7, replies=True))
        for i in range(6):
            ws.write(f"/shared/shim{i}.dat", b"x" * 64)
        ws.flush()
        tel = ws.telemetry()
        res = ws.plane.resilience_stats()
        assert res["degraded_writes"] == tel["plane.degraded_writes"]
        assert res["fenced_rejections"] == tel["rpc.fenced_rejections"]
        assert res["dedup_evictions"] == tel["rpc.dedup_evictions"]
        assert res["budget_exhausted"] == tel["rpc.budget_exhausted"]
        assert res["leases"]["acquired"] == tel["lease.acquired"]
        rpc = ws.rpc_stats()
        assert rpc["retries"] == tel["rpc.retries"] > 0
        assert rpc["calls"] == tel["rpc.calls"] > 0
        assert tel["rpc.deduped"] > 0  # server side of the same resend story
    finally:
        c.close()


# -- tracing -------------------------------------------------------------------
def test_write_roots_a_cross_site_trace_tree():
    c = _replicated()
    try:
        ws = Workspace(c, "alice", "dc0")
        ws.write("/shared/traced.dat", b"payload")
        tid = ws.plane.telemetry.tracer.last_trace  # the write's root trace
        assert tid is not None
        ws.flush()
        tree = c.collect_trace(tid)
        assert tree is not None and tree["trace_id"] == tid
        spans = _spans_of(tree)
        names = [s["name"] for s in spans]
        assert "ws.write" in names        # the workspace root
        assert any(n.startswith("rpc.") for n in names)    # client side
        assert any(n.startswith("apply.") for n in names)  # server side
        # client and server spans come from different sites, linked by the
        # envelope's [trace_id, span_id] pair
        sites = {s["site"] for s in spans}
        assert any(site.startswith("dtn") for site in sites)
        assert any("/plane" in site for site in sites)
        # parent links resolve: exactly one root (the ws.write span)
        assert len(tree["roots"]) == 1 and tree["roots"][0]["name"] == "ws.write"
        render_timeline(tree)  # smoke: the profiler renders any valid tree
        json.dumps(chrome_trace(spansource(c, tid)))
    finally:
        c.close()


def spansource(collab, trace_id):
    spans = []
    for buf in collab._span_buffers:
        spans.extend(buf.for_trace(trace_id))
    return spans


def test_trace_disabled_buffers_nothing_and_still_scrapes():
    c = Collaboration()
    c.add_datacenter("dc0", n_dtns=2, trace_enabled=False)
    try:
        ws = Workspace(c, "alice", "dc0")
        ws.write("/shared/quiet.dat", b"x")
        ws.flush()
        assert ws.plane.telemetry.tracer.last_trace is None
        assert len(ws.plane.telemetry.spans) == 0
        assert all(len(d.telemetry.spans) == 0 for d in c.dtns)
        tel = ws.telemetry()
        assert tel["rpc.calls"] > 0  # metrics stay on when tracing is off
    finally:
        c.close()


def test_chaos_trace_shows_exactly_one_apply_span_per_rid():
    """Exactly-once, *visible in the trace*: retried deliveries are refused
    by the rid dedup window, so no rid ever gets a second server apply span
    even though the client provably resent (satellite c)."""
    c = _replicated()
    try:
        c.install_faults(canned_plan("chaos", seed=4))
        ws = Workspace(c, "alice", "dc0", retry=FAST)
        tids = []
        for i in range(8):
            ws.write(f"/shared/chaos{i}.dat", os.urandom(64))
            tids.append(ws.plane.telemetry.tracer.last_trace)
        ws.flush()
        tel = ws.telemetry()
        assert tel["rpc.retries"] > 0 and tel["rpc.deduped"] > 0
        seen_rids = {}
        for tid in tids:
            for s in _spans_of(c.collect_trace(tid)):
                rid = (s.get("tags") or {}).get("rid")
                if rid is not None and s["name"].startswith("apply."):
                    seen_rids.setdefault(rid, []).append(s)
        assert seen_rids, "no rid-tagged apply spans collected"
        doubled = {r: len(v) for r, v in seen_rids.items() if len(v) != 1}
        assert not doubled, f"rids with != 1 apply span: {doubled}"
        # ...while the client side DID resend: some client span retried
        statuses = {
            s["status"] for tid in tids for s in _spans_of(c.collect_trace(tid))
        }
        assert "retried" in statuses
    finally:
        c.close()


def test_fenced_write_trace_has_refusal_and_no_apply_child():
    """A stale holder's trace must show ``rpc.fenced`` (the refusal) and NO
    ``apply.*`` child — the fenced mutation never reached a shard."""
    c = _replicated()
    try:
        ws = Workspace(c, "alice", "dc0", retry=FAST)
        p = _path_owned_by(c, "dc1", "fence")
        owner = ws.plane.owner(p)
        c.dtns[owner].leases.admit("/shared", 99)  # a newer lease exists
        tracer = ws.plane.telemetry.tracer
        with tracer.span("test.stale_write"):
            with pytest.raises(RpcFenced):
                ws.plane.fenced_call(
                    "meta", owner, {"prefix": "/shared", "token": 1},
                    "create", path=p, owner="alice", dc_id="dc0",
                    ns_id=0, is_dir=False, sync=True,
                )
            tid = tracer.current()[0]
        spans = _spans_of(c.collect_trace(tid))
        names = [s["name"] for s in spans]
        assert "rpc.fenced" in names
        fenced = next(s for s in spans if s["name"] == "rpc.fenced")
        assert fenced["status"] == "fenced"
        assert fenced["site"].startswith("dtn")  # recorded where it was refused
        assert not any(n.startswith("apply.") for n in names)
        assert ws.telemetry()["rpc.fenced_rejections"] >= 1
    finally:
        c.close()


# -- the ISSUE 10 acceptance cut -----------------------------------------------
def test_degraded_quorum_write_assembles_trace_across_three_dtns():
    """One degraded write during a partition -> ONE trace tree: lease grant
    fan-out, journal intent, coordinator create, quorum pushes — causally
    linked spans on >= 3 DTNs — and the heal-time reconcile joins the same
    trace as the final step."""
    c = _replicated(n_dcs=3)
    try:
        # quorum of 3 so the push fan-out must leave the home DC (2 DTNs)
        ws = Workspace(c, "alice", "dc0", retry=FAST, write_quorum=3)
        p_far = _path_owned_by(c, "dc1", "deg")
        c.install_faults(FaultPlan(seed=3).partition("dc0", "dc1"))
        res = ws.write(p_far, b"partition payload")
        assert res.degraded
        tid = ws.plane.telemetry.tracer.last_trace
        tree = c.collect_trace(tid)
        spans = _spans_of(tree)
        names = [s["name"] for s in spans]
        # the causal chain of the degraded path, all inside one trace
        assert "ws.write" in names
        assert "plane.quorum_create" in names
        assert "lease.acquire" in names
        assert "journal.intent" in names
        qc = next(s for s in spans if s["name"] == "plane.quorum_create")
        assert qc["status"] == "degraded"
        assert qc["tags"]["acks"] >= ws.plane.write_quorum
        # server-side applies landed on >= 3 distinct DTNs across >= 2 DCs
        apply_sites = {
            s["site"] for s in spans
            if s["site"].startswith("dtn") and s["name"].startswith("apply.")
        }
        assert len(apply_sites) >= 3, f"trace only reached {sorted(apply_sites)}"
        dcs = {site.split("@", 1)[1] for site in apply_sites}
        assert len(dcs) >= 2
        assert "dc1" not in dcs  # the partitioned owner DC never applied
        # heal: the reconcile span parents into this same trace (link_trace)
        c.install_faults(None)
        report = c.reconcile("/shared")
        assert report["converged"]
        healed = _spans_of(c.collect_trace(tid))
        rec = [s for s in healed if s["name"] == "reconcile"]
        assert rec and rec[0]["site"] == "cluster"
        assert len(healed) > len(spans)  # the tree grew at heal time
    finally:
        c.close()


# -- assembly / rendering edge cases -------------------------------------------
def test_assemble_trace_adopts_orphans_and_renders():
    """Spans whose parent never reached a buffer (evicted / partitioned
    away) still assemble — as extra roots, not silent drops."""
    t = Telemetry("t")
    tr = t.tracer
    with tr.span("root"):
        ctx = tr.current()
    orphan = tr.start_span("orphan.child", parent=(ctx[0], ctx[1] + 999))
    tr.finish(orphan)
    spans = [s for s in t.spans.for_trace(ctx[0])]
    tree = assemble_trace(spans)
    got = {s["name"] for s in _spans_of(tree)}
    assert got == {"root", "orphan.child"}
    assert len(tree["roots"]) == 2  # orphan promoted to a root
    out = render_timeline(tree)
    assert "root" in out and "orphan.child" in out
