#!/usr/bin/env python
"""CI fault-matrix smoke: replay canned fault plans against one workload.

    PYTHONPATH=src python scripts/fault_matrix.py [--seed N]

Runs a compact collaboration workload (write + tag + search + cross-DC
read-back, two workspaces on opposite DCs) once per canned
:class:`repro.core.faults.FaultPlan` ("drops", "flaky", "crash", "chaos",
"quorum", "lease-expiry") and asserts, for every cell of the matrix:

- the workload **completes** (retries + backoff ride out every injected
  fault, including the mid-workload DTN crash of the "crash" plan);
- every read-back is **byte-identical** to what was written;
- search returns **exactly** the tagged set (nothing lost, nothing doubled);
- the plan actually **fired** (its fault counters are non-zero — a cell that
  injects nothing would be vacuous);
- retried mutations applied **exactly once** wherever a request or reply was
  dropped or duplicated (server-side dedup counters are the witness).

The partition plans ("quorum", "lease-expiry") get a dedicated workload:
writes targeting far-DC owners must come back *degraded* (epoch-fenced
lease + quorum acknowledgement on the reachable side, ``blocked > 0``
proving the link was actually severed), and after ``install_faults(None)``
+ ``Collaboration.reconcile()`` every DTN must agree byte-identically and
every read-back must match what was written into the partition.

Plans are seeded, so a red cell replays deterministically with the printed
seed.  Exit code 0 = all cells green; the failing plan name is in the
traceback otherwise.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

from repro.core import (  # noqa: E402
    Channel,
    Collaboration,
    RetryPolicy,
    Workspace,
    canned_plan,
)
from repro.core.faults import CANNED_PLANS  # noqa: E402

N_FILES = 8
FILE_BYTES = 32 << 10

#: generous attempts: the matrix asserts completion, not goodput
RETRY = RetryPolicy(
    max_attempts=10, base_s=0.001, cap_s=0.02, timeout_s=0.0,
    deadline_s=10.0, budget=100_000,
)

#: short fuse for the partition cells: a severed link should hand the write
#: to the quorum/lease path fast instead of retrying into the void
PARTITION_RETRY = RetryPolicy(
    max_attempts=2, base_s=0.0005, cap_s=0.002, timeout_s=0.0,
    deadline_s=0.5, budget=100_000,
)

#: plans whose headline fault is a severed inter-DC link
PARTITION_PLANS = {"quorum", "lease-expiry"}


def _make_collab() -> Collaboration:
    def channels(a: str, b: str) -> Channel:
        return Channel(name="intra" if a == b else "cross", latency_s=1e-6)

    collab = Collaboration(channel_policy=channels)
    collab.add_datacenter("dc0", n_dtns=2)
    collab.add_datacenter("dc1", n_dtns=2)
    return collab


def _deduped(collab: Collaboration) -> int:
    return sum(
        d.metadata_server.deduped + d.discovery_server.deduped
        for d in collab.dtns
    )


def _owned_paths(collab: Collaboration, dc_id: str, n: int) -> list:
    out = []
    for i in range(2000):
        p = f"/shared/q{i}.dat"
        if collab.owner_dtn(p).dc_id == dc_id:
            out.append(p)
            if len(out) == n:
                return out
    raise RuntimeError(f"could not find {n} {dc_id}-owned paths")


def _assert_scrape(ws: Workspace, name: str) -> None:
    """Every cell must leave a non-empty, JSON-serializable telemetry scrape
    behind — the contract scripts/trace_dump.py --smoke also exercises."""
    tel = ws.telemetry()
    assert tel, f"{name}: empty telemetry scrape"
    assert tel.get("rpc.calls", 0) > 0, f"{name}: scrape missing rpc.calls"
    json.dumps(tel)  # raises on anything a real scraper could not export


def run_partition_cell(name: str, seed: int) -> str:
    """Partition cell: degraded quorum writes, then heal-time convergence."""
    collab = _make_collab()
    collab.start_replication(max_age_s=0.02, poll_s=0.005)
    try:
        alice = Workspace(collab, "alice", "dc0", extraction_mode="none",
                          retry=PARTITION_RETRY)
        bob = Workspace(collab, "bob", "dc1", extraction_mode="none", retry=RETRY)
        paths = _owned_paths(collab, "dc1", N_FILES)
        payloads = {p: os.urandom(FILE_BYTES) for p in paths}

        plan = canned_plan(name, seed=seed)
        collab.install_faults(plan)
        for p, data in payloads.items():
            res = alice.write(p, data)
            assert getattr(res, "degraded", False), (
                f"{name}: write to partitioned owner {p} was not degraded"
            )
            alice.tag(p, "matrix", name)
        stats = alice.plane.resilience_stats()
        assert stats["degraded_writes"] >= N_FILES, f"{name}: {stats}"
        assert stats["leases"]["acquired"] >= 1, f"{name}: {stats}"
        fired = plan.stats()
        assert fired["blocked"] > 0, f"{name}: the partition never fired ({fired})"

        collab.install_faults(None)
        report = collab.reconcile("/shared")
        assert report["converged"], f"{name}: reconcile did not converge ({report})"
        rows = [d.metadata.path_digest("/shared")["rows"] for d in collab.dtns]
        assert all(r == rows[0] for r in rows[1:]), f"{name}: shards diverge post-heal"
        hits = bob.search(f"matrix = {name}")
        assert {r["path"] for r in hits} == set(payloads), (
            f"{name}: search returned {sorted(r['path'] for r in hits)}"
        )
        for p, data in payloads.items():
            assert bob.read(p) == data, f"{name}: corrupt read-back for {p}"
        _assert_scrape(alice, name)
        return (
            f"{sum(fired.values()):3d} faults "
            f"(blocked {fired['blocked']} dup {fired['duplicated']} "
            f"delay {fired['delayed']}), "
            f"{stats['degraded_writes']} degraded writes, "
            f"reconcile replayed {report['records_replayed']}"
            f"+{report['index_records_replayed']}"
        )
    finally:
        collab.stop_replication()


def run_cell(name: str, seed: int) -> str:
    if name in PARTITION_PLANS:
        return run_partition_cell(name, seed)
    collab = _make_collab()
    alice = Workspace(collab, "alice", "dc0", extraction_mode="none", retry=RETRY)
    bob = Workspace(collab, "bob", "dc1", extraction_mode="none", retry=RETRY)

    plan = canned_plan(name, seed=seed)
    if name == "crash":
        # retarget the canned crash at a DTN this workload actually loads
        plan._crash_at.clear()  # noqa: SLF001 — smoke script, not API
        victim = collab.owner_dtn("/shared/m0.dat").dtn_id
        plan.crash_dtn_at_call(victim, 4, restart_after_s=0.02)
    collab.install_faults(plan)

    payloads = {f"/shared/m{i}.dat": os.urandom(FILE_BYTES) for i in range(N_FILES)}
    for p, data in payloads.items():
        alice.write(p, data)
        alice.tag(p, "matrix", name)
    hits = bob.search(f"matrix = {name}")
    assert {r["path"] for r in hits} == set(payloads), (
        f"{name}: search returned {sorted(r['path'] for r in hits)}"
    )
    for p, data in payloads.items():
        assert bob.read(p) == data, f"{name}: corrupt read-back for {p}"

    collab.install_faults(None)
    fired = plan.stats()
    injected = sum(fired.values())
    assert injected > 0, f"{name}: plan never fired ({fired})"
    lossy = fired["dropped"] + fired["dropped_replies"] + fired["duplicated"]
    if lossy:
        assert _deduped(collab) > 0, (
            f"{name}: lossy plan but no server-side dedup — retries may double-apply"
        )
    _assert_scrape(alice, name)
    return (
        f"{injected:3d} faults "
        f"(drop {fired['dropped']}+{fired['dropped_replies']} "
        f"dup {fired['duplicated']} delay {fired['delayed']} "
        f"crash {fired['crashes']}), deduped {_deduped(collab)}"
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    print(f"fault matrix (seed {args.seed}, {N_FILES} files x {len(CANNED_PLANS)} plans):")
    for name in sorted(CANNED_PLANS):
        detail = run_cell(name, args.seed)
        print(f"  ok {name:6s} {detail}")
    print("fault matrix: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
