"""The trace reduction on a small recorded trace."""

import json
from pathlib import Path

import pytest

from chipbench import trace

SMALL = Path(__file__).resolve().parent / "testdata" / "trace_small.json"


def test_busy_is_the_union_of_ops_inside_the_window():
    out = trace.reduce(json.loads(SMALL.read_text()))
    assert out["window_s"] == pytest.approx(1e-3)
    assert out["busy_s"] == pytest.approx(550e-6)  # overlaps counted once, edges clipped
    assert out["idle_share"] == pytest.approx(45.0)


def test_breakdown_names_ops_and_gaps():
    out = trace.reduce(json.loads(SMALL.read_text()))
    # fusion.2 starts inside fusion.1: the overlap counts once, to fusion.2
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(350e-6)]
    assert sum(s for _, s in out["device_ops"]) == pytest.approx(out["busy_s"])
    assert [name for name, _ in out["device_ops"]] == ["fusion.1", "fusion.2", "convolution.3"]
    # each gap is named by the innermost benchmark span over its middle
    assert out["idle_gaps"] == [
        ["trainer.run[step + save]", pytest.approx(250e-6)],
        ["data.batch_at", pytest.approx(200e-6)],
    ]


@pytest.mark.parametrize("drop", ["window", "devices"])
def test_a_trace_without_window_or_device_ops_is_refused(drop):
    t = json.loads(SMALL.read_text())
    if drop == "window":
        t["host"] = [h for h in t["host"] if h[0] != "window"]
    else:
        t["devices"] = {"/device:TPU:0": []}
    with pytest.raises(ValueError):
        trace.reduce(t)


def test_recorded_chip_trace():
    """A trace recorded on a v5e chip: three steps of a jitted matmul, each
    followed by a 2 ms host sleep inside a benchmark span."""
    t = json.loads((SMALL.parent / "trace_chip.json").read_text())
    out = trace.reduce(t)
    assert 0 < out["busy_s"] < out["window_s"]
    assert 0 < out["idle_share"] < 100
    assert [name for name, _ in out["idle_gaps"][:3]] == ["host.sleep"] * 3
    assert all(g >= 0.002 for _, g in out["idle_gaps"][:3])
    names = [name for name, _ in out["device_ops"]]
    assert all(" = " not in n and not n.startswith("%") for n in names)
    assert sum(s for _, s in out["device_ops"]) <= out["busy_s"] * (1 + 1e-9)


def test_nested_ops_count_once():
    t = {"devices": {"/device:TPU:0": [["%while.1 = (...) while(...)", 0, 1000],
                                       ["%fusion.2 = f32[] fusion(...)", 100, 400],
                                       ["%fusion.3 = f32[] fusion(...)", 500, 900]]},
         "host": [["window", 0, 1000]]}
    out = trace.reduce(t)
    assert dict((n, s) for n, s in out["device_ops"]) == pytest.approx(
        {"fusion.3": 400e-9, "fusion.2": 300e-9, "while.1": 300e-9})
