"""The readers of the program's own spans (``chipbench/program_spans.py``):
their arithmetic on hand-built spans, the window they select, and ``None``
where there is nothing to read or the buffer lost spans."""

import time
from types import SimpleNamespace

import pytest

import repro.train
from chipbench import harness
from repro.core.telemetry import Telemetry, now

#: the window on the spans' clock, in seconds
W0, W1 = 100.0, 200.0

READERS = ["train_step.host_s", "train_step.batch_s", "ckpt_save.d2h_s", "ckpt_save.serialize_s",
           "ckpt_save.store_s"]


def ctx():
    """A context whose run's window is [W0, W1] on the spans' clock."""
    offset = time.perf_counter() - now()
    return SimpleNamespace(run=SimpleNamespace(window_start=W0 + offset, window_s=W1 - W0))


def read(name):
    return harness.metric_reader(name)(ctx())


class Spans:
    def __init__(self, tel):
        self.tracer = tel.tracer

    def add(self, name, start, seconds, parent=None, **tags):
        at = None if parent is None else (parent.trace_id, parent.span_id)
        return self.tracer.record(name, parent=at, start=start, end=start + seconds, tags=tags)

    def iteration(self, t, step, batch, save=None):
        """A loop iteration at ``t``: batch, step, then (d2h, save) if given; 0.25 s of its own."""
        parts = [("train.batch", batch), ("train.step", step)]
        if save:
            parts += [("ckpt.d2h", save[0]), ("ckpt.save", save[1])]
        it = self.add("train.iter", t, sum(s for _, s in parts) + 0.25)
        for name, s in parts:
            self.add(name, t, s, it)
            t += s
        return it

    def save(self, t, serialize, store):
        root = self.add("ckpt.save", t, sum(serialize) + sum(store))
        for i, (a, b) in enumerate(zip(serialize, store)):
            w = self.add("ckpt.write", t, a + b, root, shard=i)
            self.add("scidata.serialize", t, a, w)
            self.add("store.write", t + a, b, w)
            t += a + b


@pytest.fixture
def spans(monkeypatch):
    tel = Telemetry("train")
    monkeypatch.setattr(repro.train, "TELEMETRY", tel)
    return Spans(tel)


def test_trainer_loop_readers(spans):
    spans.iteration(110.0, step=0.5, batch=0.25)
    spans.iteration(120.0, step=0.5, batch=0.75, save=(2.0, 40.0))
    # self time is the iteration less its step, d2h and save: the batch stays in
    assert read("train_step.host_s") == pytest.approx((0.25 + 0.25 + 0.25 + 0.75) / 2)
    assert read("train_step.batch_s") == pytest.approx(0.5)
    assert read("ckpt_save.d2h_s") == pytest.approx(2.0)


def test_save_readers_sum_the_shards_of_each_save(spans):
    spans.save(110.0, serialize=[1.0, 2.0], store=[4.0, 8.0])
    spans.save(130.0, serialize=[3.0, 4.0], store=[2.0, 2.0])
    assert read("ckpt_save.serialize_s") == pytest.approx((3.0 + 7.0) / 2)
    assert read("ckpt_save.store_s") == pytest.approx((12.0 + 4.0) / 2)


def test_only_spans_that_start_inside_the_window_count(spans):
    spans.save(50.0, serialize=[10.0], store=[10.0])    # set-up
    spans.save(150.0, serialize=[2.0], store=[1.0])
    spans.save(250.0, serialize=[30.0], store=[30.0])   # after the window
    spans.iteration(90.0, step=5.0, batch=5.0)          # starts before it
    spans.iteration(160.0, step=0.5, batch=0.5)
    assert read("ckpt_save.serialize_s") == pytest.approx(2.0)
    assert read("ckpt_save.store_s") == pytest.approx(1.0)
    assert read("train_step.batch_s") == pytest.approx(0.5)
    assert read("train_step.host_s") == pytest.approx(0.75)


@pytest.mark.parametrize("name", READERS)
def test_none_without_spans_in_the_window(spans, name):
    spans.save(50.0, serialize=[1.0], store=[1.0])
    spans.iteration(250.0, step=0.5, batch=0.5, save=(1.0, 1.0))
    spans.save(250.0, serialize=[1.0], store=[1.0])
    assert read(name) is None


@pytest.mark.parametrize("name", READERS)
def test_none_where_the_buffer_dropped_spans(spans, name):
    spans.iteration(120.0, step=0.5, batch=0.5, save=(1.0, 1.0))
    spans.save(130.0, serialize=[1.0], store=[1.0])
    assert read(name) is not None
    repro.train.TELEMETRY.spans.dropped = 1
    assert read(name) is None


@pytest.mark.parametrize("name", READERS)
def test_none_from_a_program_without_train_telemetry(monkeypatch, name):
    monkeypatch.delattr(repro.train, "TELEMETRY")
    assert read(name) is None
