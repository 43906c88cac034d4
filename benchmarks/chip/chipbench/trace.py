"""From a profiler trace to the device's busy time, idle share and breakdown.

:func:`extract` reads the ``.xplane.pb`` the JAX profiler wrote into plain
lists: per device, the ops of its ``XLA Ops`` line as (name, start, end) in
nanoseconds; on the host, the benchmark's ``bench:`` spans.  :func:`reduce`
works on those lists alone, so a small recorded trace can be kept as JSON
and checked without a chip.

- busy: the union of a device's op intervals inside the window span,
  averaged over the devices;
- idle share: 1 − busy / window;
- ``device_ops``: the HLO instructions with the most self time in the window
  (an op's own time, less the ops nested in it, as a loop's body in the loop);
- ``idle_gaps``: the longest stretches with no op on the device, each named
  by the innermost benchmark span that covers its middle.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Tuple

from .spans import PREFIX

WINDOW = "window"
TOP = 10


def extract(log_dir: str) -> Dict:
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    devices: Dict[str, List[Tuple[str, int, int]]] = {}
    host: List[Tuple[str, int, int]] = []
    for path in files:
        for plane in ProfileData.from_file(path).planes:
            name = plane.name
            if name.startswith("/device:TPU:") and name.rsplit(":", 1)[-1].isdigit():
                ops = devices.setdefault(name, [])
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        ops.extend((e.name, int(e.start_ns), int(e.end_ns)) for e in line.events)
            elif name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(PREFIX):
                            host.append((e.name[len(PREFIX):], int(e.start_ns), int(e.end_ns)))
    return {"devices": devices, "host": host}


def _op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` → ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _self_times(ops) -> Dict[str, float]:
    """Seconds per op name, each op less the ops nested inside it."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []  # [name, end, own ns]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            n, _, own = stack.pop()
            out[n] += own / 1e9
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    for n, _, own in stack:
        out[n] += own / 1e9
    return out


def _merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _name_of(t0: int, t1: int, host) -> str:
    """The shortest benchmark span (other than the window) that covers [t0, t1]'s middle."""
    mid = (t0 + t1) / 2
    best, best_len = "no benchmark span", None
    for name, s, e in host:
        if name != WINDOW and s <= mid <= e and (best_len is None or e - s < best_len):
            best, best_len = name, e - s
    return best


def reduce(trace: Dict) -> Dict:
    """busy_s, window_s, idle_share (%), device_ops and idle_gaps of the window."""
    host = [tuple(h) for h in trace["host"]]
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if not windows:
        raise ValueError("the trace holds no window span")
    w0, w1 = windows[0]
    devices = {k: v for k, v in trace["devices"].items() if v}
    if not devices:
        raise ValueError("the trace holds no device op")
    busy, per_op = [], defaultdict(float)
    gaps: List[Tuple[int, int]] = []
    for i, (_, ops) in enumerate(sorted(devices.items())):
        clipped = [(_op_name(n), max(s, w0), min(e, w1)) for n, s, e in ops if e > w0 and s < w1]
        for n, sec in _self_times(clipped).items():
            per_op[n] += sec
        merged = _merge([(s, e) for _, s, e in clipped])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        if i == 0:  # gaps on the first device name what held it back
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            gaps = [(g0, g1) for g0, g1 in zip(edges[::2], edges[1::2]) if g1 > g0]
    n = len(devices)
    window_s = (w1 - w0) / 1e9
    busy_s = sum(busy) / n
    ops = sorted(((k, v / n) for k, v in per_op.items()), key=lambda kv: -kv[1])[:TOP]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 100.0 * (1.0 - busy_s / window_s),
        "device_ops": [[k, v] for k, v in ops],
        "idle_gaps": [[_name_of(g0, g1, host), (g1 - g0) / 1e9] for g0, g1 in longest],
    }
