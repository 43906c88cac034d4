"""Run one cell of the chip benchmark once, in this process, on this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json`` at the root of the checkout.  Its
weights and data come from ``--seed``; set-up warms only the cell's own
shapes (compiles come from the persistent compile cache); the window
measures for ``--seconds``; the check against the plain reference runs after
it.  The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and ``breakdown`` with
``--trace 1``), and the numbers compared, each with its limit, under
``checks``; the same numbers are the last lines of standard error.

Where JAX finds no TPU, or fewer chips than the cell asks for, it exits
non-zero before printing any result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
# libtpu logs to a fixed directory under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.join(ROOT, "src"))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        from chipbench import harness

        line = harness.run_cell(args, t_start=T_START)
    except Exception as exc:  # noqa: BLE001 — any failure: no result line, non-zero exit
        import traceback

        traceback.print_exc()
        print(f"chipbench: no result: {type(exc).__name__}: {exc}", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
