"""Benchmark harness: one module per paper table/figure + framework benches.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Paper experiments (ratios/trends are the reproduction target — DESIGN.md §8):
  fig7   block-size sweep          fig8   collaborator scaling
  fig9a  MEU export                fig9b  extraction modes
  tab2   query latency/hit-ratio   fig9c  end-to-end analysis
  fig9d  metadata plane: pipelined five-op writes + scatter-gather query
  fig10  replicated metadata tier: replica reads, convergence, journal replay
  fig11  wire-path acceleration: codec fast path, compacted shipping, pruning
  fig12  data plane: striped multi-lane transfers, chunk cache, read-ahead
  fig13  fault plane: partition failover availability, exactly-once chaos goodput
  fig14  partition-tolerant writes: quorum availability, heal-time convergence
Framework:
  ckpt_stall  LW+MEU vs workspace checkpointing

The multi-pod dry-run is a CPU analysis tool with 512 forced host devices; it
runs in a process of its own (``python -m repro.launch.dryrun``), never as a
child of this one, which has already initialised JAX.
"""

from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (
    ckpt_stall,
    fig7_blocksize,
    fig8_collaborators,
    fig9a_meu,
    fig9b_extraction,
    fig9c_end2end,
    fig9d_plane,
    fig10_replication,
    fig11_wirepath,
    fig12_datapath,
    fig13_faults,
    fig14_quorum,
    tab2_query,
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true", help="reduced sweep sizes")
    args = ap.parse_args(argv)

    benches = [
        ("fig7_blocksize", fig7_blocksize.main),
        ("fig8_collaborators", fig8_collaborators.main),
        ("fig9a_meu", fig9a_meu.main),
        ("fig9b_extraction", fig9b_extraction.main),
        ("tab2_query", tab2_query.main),
        ("fig9c_end2end", fig9c_end2end.main),
        ("fig9d_plane", fig9d_plane.main),
        ("fig10_replication", fig10_replication.main),
        ("fig11_wirepath", fig11_wirepath.main),
        ("fig12_datapath", fig12_datapath.main),
        ("fig13_faults", fig13_faults.main),
        ("fig14_quorum", fig14_quorum.main),
        ("ckpt_stall", ckpt_stall.main),
    ]
    failures = 0
    t0 = time.time()
    for name, fn in benches:
        print(f"\n=== {name} ===")
        try:
            fn(quick=args.quick)
        except Exception as exc:  # noqa: BLE001
            failures += 1
            import traceback

            traceback.print_exc()
            print(f"BENCH FAIL {name}: {exc}")
    print(f"\nbenchmarks done in {time.time()-t0:.0f}s, failures={failures}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
