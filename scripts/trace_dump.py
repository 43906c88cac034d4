#!/usr/bin/env python
"""Per-op timeline profiler: run a small workload, dump its trace trees.

    PYTHONPATH=src python scripts/trace_dump.py                 # text timelines
    PYTHONPATH=src python scripts/trace_dump.py --plan chaos    # under faults
    PYTHONPATH=src python scripts/trace_dump.py --chrome t.json # Perfetto export
    PYTHONPATH=src python scripts/trace_dump.py --smoke         # smoke check

Builds a two-DC collaboration (modeled channels, so spans carry real
modeled wire time), runs a write / flush / cross-DC read / tag / search
sequence — optionally under a canned :class:`repro.core.faults.FaultPlan` —
then reassembles each operation's spans with
``Collaboration.collect_trace`` and prints
:func:`repro.core.telemetry.render_timeline`.  ``--chrome`` additionally
exports every buffered span as Chrome-trace JSON (load in chrome://tracing
or Perfetto: sites are rows, traces are lanes).

``--smoke`` replays the chaos plan,
then assert the unified scrape ``Workspace.telemetry()`` is non-empty and
JSON-serializable and that ``collect_trace`` reassembles a non-empty tree
for the last traced op.  Prints ``trace smoke: OK`` and exits 0 when green.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
)

from repro.core import (  # noqa: E402
    Channel,
    Collaboration,
    RetryPolicy,
    Workspace,
    canned_plan,
    chrome_trace,
    render_timeline,
)

RETRY = RetryPolicy(
    max_attempts=10, base_s=0.001, cap_s=0.02, timeout_s=0.0,
    deadline_s=10.0, budget=100_000,
)


def _make_collab() -> Collaboration:
    # free stores keep the dump quick; the channel latencies still give
    # every cross-DC span real modeled wire time
    def channels(a: str, b: str) -> Channel:
        if a == b:
            return Channel(name="intra", latency_s=5e-6)
        return Channel(name="cross", latency_s=55e-6, gbps=100.0, stream_gbps=5.0)

    collab = Collaboration(channel_policy=channels)
    collab.add_datacenter("dc0", n_dtns=2)
    collab.add_datacenter("dc1", n_dtns=2)
    return collab


def run_workload(plan_name: str) -> tuple:
    """Run the sequence, returning (collab, workspace, [(op, trace_id)...])."""
    collab = _make_collab()
    alice = Workspace(collab, "alice", "dc0", retry=RETRY)
    bob = Workspace(collab, "bob", "dc1", retry=RETRY)
    if plan_name:
        collab.install_faults(canned_plan(plan_name, seed=7))
    traces = []

    def traced(ws: Workspace, op: str, fn) -> None:
        fn()
        traces.append((f"{ws.collaborator}:{op}", ws.plane.telemetry.tracer.last_trace))

    traced(alice, "mkdir /t", lambda: alice.mkdir("/t"))
    traced(alice, "write /t/a.bin", lambda: alice.write("/t/a.bin", b"x" * (600 << 10)))
    traced(alice, "flush", alice.flush)
    traced(alice, "tag", lambda: alice.tag("/t/a.bin", "kind", "dump"))
    traced(bob, "read /t/a.bin", lambda: bob.read("/t/a.bin"))
    traced(bob, "search", lambda: bob.search("kind = dump"))
    # the plan stays installed so the scrape still shows faults.* counters
    return collab, alice, traces


def smoke() -> int:
    collab, ws, traces = run_workload("chaos")
    tel = ws.telemetry()
    assert tel, "smoke: empty telemetry scrape"
    assert tel.get("rpc.calls", 0) > 0, "smoke: scrape missing rpc.calls"
    json.dumps(tel)  # the scrape must stay exportable
    assembled = 0
    for op, tid in traces:
        tree = collab.collect_trace(tid)
        assert tree and tree["n_spans"] >= 1, f"smoke: empty trace for {op}"
        assembled += tree["n_spans"]
    print(f"trace smoke: OK ({len(traces)} ops, {assembled} spans, "
          f"{len(tel)} metrics, faults.injected counters present: "
          f"{any(k.startswith('faults.') for k in tel)})")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--plan", default="", help="canned FaultPlan name ('' = none)")
    ap.add_argument("--chrome", default="", metavar="OUT.json",
                    help="also export all buffered spans as Chrome-trace JSON")
    ap.add_argument("--smoke", action="store_true", help="smoke check")
    args = ap.parse_args(argv)
    if args.smoke:
        return smoke()

    collab, ws, traces = run_workload(args.plan)
    for op, tid in traces:
        tree = collab.collect_trace(tid)
        print(f"== {op} ==")
        print(render_timeline(tree))
        print()

    if args.chrome:
        spans = []
        for buf in collab._span_buffers:  # noqa: SLF001 — export tool
            spans.extend(buf.spans())
        with open(args.chrome, "w") as fh:
            json.dump({"traceEvents": chrome_trace(spans)}, fh)
        print(f"wrote {len(spans)} spans to {args.chrome}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
