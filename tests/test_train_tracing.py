"""The train layer's spans (``repro.train.telemetry``).

- a trainer that saves every 2 steps, then a restore at the other DC, give
  the span tree the module documents, with parent links: each save nests
  under the loop iteration that triggered it, and each save and each
  restore is one trace;
- the byte and shard tags equal what the rows and the state say, the
  in-memory store copied none of a shard's bytes (it kept the container
  as built), and every timing in the rows is the duration of its span;
- with the tracer off nothing is buffered, and the rows and the restored
  state are those of a traced run;
- the spans also land in the JAX profiler's trace, inside an enclosing
  annotation, so they share the device trace's clock;
- the span buffer counts what ages out of it.
"""

import glob
import os
from collections import defaultdict

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, smoke_variant
from repro.core.telemetry import TRACE_BUFFER_SPANS, Span, SpanBuffer
from repro.data import ShardedPipeline, SyntheticLM
from repro.launch.mesh import make_mesh
from repro.models.model import Model
from repro.optim import AdamW, AdamWConfig
from repro.train import TELEMETRY, CheckpointManager, Trainer, TrainerConfig

SHARDS = 2
STEPS = 4
EVERY = 2
TIMINGS = ("seconds", "write_s", "publish_s", "total_s")


def _train_and_restore(collab):
    """Train STEPS steps saving every EVERY at dc0, then restore the newest
    step at dc1; the trainer and the restored state."""
    cfg = smoke_variant(ARCHS["codeqwen1.5-7b"])
    pipe = ShardedPipeline(
        SyntheticLM(vocab_size=cfg.vocab_size, seq_len=32, period=8), global_batch=4
    )
    opt = AdamW(AdamWConfig(peak_lr=1e-3, warmup_steps=2, total_steps=50))
    tr = Trainer(
        Model(cfg), opt, make_mesh((1,), ("data",)), pipe,
        TrainerConfig(loss_chunk=16, ckpt_every=EVERY),
        ckpt=CheckpointManager(collab, run="tiny", home_dc="dc0", n_shards=SHARDS),
    )
    tr.run(STEPS)
    other = CheckpointManager(collab, run="tiny", home_dc="dc1", n_shards=SHARDS)
    restored = other.restore(tr._abstract, other.latest_step(), shardings=tr.shardings)
    return tr, restored


@pytest.fixture(scope="module")
def traced():
    """The collaboration, the spans of the train bundle, the trainer and the
    restored state of one traced run."""
    from repro.core import Collaboration

    collab = Collaboration()
    collab.add_datacenter("dc0", n_dtns=2)
    collab.add_datacenter("dc1", n_dtns=2)
    TELEMETRY.spans.clear()
    tr, restored = _train_and_restore(collab)
    yield collab, TELEMETRY.spans.spans(), tr, restored
    collab.close()


def _children(spans):
    out = defaultdict(list)
    for s in sorted(spans, key=lambda s: s.start):
        out[s.parent_id].append(s)
    return out


def _dur(s):
    return s.end - s.start


def test_spans_form_the_documented_tree(traced):
    _, spans, _, restored = traced
    kids = _children(spans)
    names = lambda parent: [s.name for s in kids[parent.span_id]]
    roots = kids[None]
    assert [s.name for s in roots] == ["train.iter"] * STEPS + ["ckpt.discover", "ckpt.restore"]
    saves = []
    for i, it in enumerate(roots[:STEPS]):
        if (i + 1) % EVERY:
            assert names(it) == ["train.batch", "train.step"]
            continue
        assert names(it) == ["train.batch", "train.step", "ckpt.d2h", "ckpt.save"]
        save = kids[it.span_id][-1]
        saves.append(save)
        assert names(save) == ["ckpt.pack"] + ["ckpt.write"] * SHARDS + ["ckpt.publish"]
        writes = kids[save.span_id][1:-1]
        assert [w.tags["shard"] for w in writes] == list(range(SHARDS))
        for w in writes:
            assert names(w) == ["scidata.serialize", "store.write"]
            store = kids[w.span_id][1]
            assert store.tags["bytes"] == w.tags["bytes"] > 0
            # the in-memory store kept the built container: nothing copied
            assert store.tags["copied"] == 0
        assert names(kids[save.span_id][-1]) == ["meu.export", "ckpt.index"]
    restore = roots[-1]
    n_leaves = len(jax.tree.leaves(restored))
    assert names(restore) == (
        ["ckpt.open"] * SHARDS + ["ckpt.read", "ckpt.assemble"] * n_leaves + ["ckpt.place"]
    )
    opens = kids[restore.span_id][:SHARDS]
    assert [o.tags["shard"] for o in opens] == list(range(SHARDS))
    reads = kids[restore.span_id][SHARDS:-1:2]
    for assemble in kids[restore.span_id][SHARDS + 1:-1:2]:
        assert names(assemble) == ["ckpt.put"]
    # one read per leaf, largest first
    assert len({r.tags["leaf"] for r in reads}) == n_leaves
    sizes = [r.tags["bytes"] for r in reads]
    assert sizes == sorted(sizes, reverse=True)
    assert restore.tags["bytes"] == sum(sizes) > 0
    # every piece was copied by the store straight into its leaf
    assert restore.tags["into_bytes"] == restore.tags["bytes"]
    assert restore.tags["shards"] == SHARDS and restore.tags["step"] == STEPS
    # the in-memory channel models no wire time: the restore's time is host work
    assert all(r.wire_s == 0.0 for r in opens + reads)

    # each save and each restore is one trace, each its own
    def descendants(root):
        out, todo = [], [root]
        while todo:
            s = todo.pop()
            out.append(s)
            todo.extend(kids[s.span_id])
        return out

    ids = []
    for root in saves + [restore]:
        tree = descendants(root)
        assert {s.trace_id for s in tree} == {root.trace_id}
        ids.append(root.trace_id)
    assert len(set(ids)) == len(ids) == STEPS // EVERY + 1


def test_collaboration_assembles_a_checkpoint_trace(traced):
    collab, spans, _, restored = traced
    restore = [s for s in spans if s.name == "ckpt.restore"][-1]
    tree = collab.collect_trace(restore.trace_id)
    # the restore, its opens, its place, and a read, a cast and a put per leaf
    assert tree["n_spans"] == 2 + SHARDS + 3 * len(jax.tree.leaves(restored))
    assert [r["name"] for r in tree["roots"]] == ["ckpt.restore"]


def test_tags_match_rows_and_state(traced):
    _, spans, tr, restored = traced
    by_name = lambda name: [s for s in spans if s.name == name]
    rows = [r for r in tr.metrics_log if r.get("event") == "save"]
    assert len(rows) == STEPS // EVERY == len(by_name("ckpt.save"))
    assert len(by_name("train.step")) == STEPS
    writes = by_name("ckpt.write")
    assert sum(w.tags["bytes"] for w in writes) == sum(r["bytes"] for r in rows)
    assert len(writes) == SHARDS * len(rows)
    saved = jax.tree.leaves(tr.state)
    assert [d.tags["bytes"] for d in by_name("ckpt.d2h")] == [sum(x.nbytes for x in saved)] * len(rows)
    restore = by_name("ckpt.restore")[-1]
    leaves = jax.tree.leaves(restored)
    assert len(by_name("ckpt.put")) == len(leaves)
    assert restore.tags["bytes"] == sum(x.nbytes for x in leaves)


def test_row_timings_are_span_durations(traced):
    _, spans, tr, _ = traced
    kids = _children(spans)
    by_name = lambda name: [s for s in sorted(spans, key=lambda s: s.start) if s.name == name]
    steps = [r for r in tr.metrics_log if "loss" in r]
    assert [r["seconds"] for r in steps] == [_dur(s) for s in by_name("train.step")]
    rows = [r for r in tr.metrics_log if r.get("event") == "save"]
    for row, d2h, save in zip(rows, by_name("ckpt.d2h"), by_name("ckpt.save")):
        assert set(row) == {"step", "event", "bytes"} | set(TIMINGS)
        assert row["total_s"] == _dur(save)
        assert row["seconds"] == _dur(d2h) + _dur(save)
        writes = [s for s in kids[save.span_id] if s.name == "ckpt.write"]
        assert row["write_s"] == sum(_dur(w) for w in writes)
        assert row["publish_s"] == _dur(kids[save.span_id][-1])
        assert row["bytes"] == sum(w.tags["bytes"] for w in writes)


def test_tracer_off_records_nothing_and_changes_no_result(traced, collab, monkeypatch):
    _, _, tr_on, restored_on = traced
    monkeypatch.setattr(TELEMETRY.tracer, "enabled", False)
    before = TELEMETRY.spans.spans()
    tr, restored = _train_and_restore(collab)
    assert TELEMETRY.spans.spans() == before

    def untimed(log):
        return [{k: v for k, v in r.items() if k not in TIMINGS} for r in log]

    assert untimed(tr.metrics_log) == untimed(tr_on.metrics_log)
    assert [set(r) for r in tr.metrics_log] == [set(r) for r in tr_on.metrics_log]
    assert all(r["seconds"] > 0 for r in tr.metrics_log)
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(restored_on)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_package_bundle_takes_the_spans(collab):
    mgr = CheckpointManager(collab, run="default", home_dc="dc0", n_shards=SHARDS)
    assert mgr.native.tracer is TELEMETRY.tracer
    mgr.list_steps()
    last = TELEMETRY.spans.spans()[-1]
    assert last.name == "ckpt.discover" and last.trace_id == TELEMETRY.tracer.last_trace
    assert collab.collect_trace(last.trace_id)["roots"][0]["name"] == "ckpt.discover"


def test_span_buffer_counts_what_ages_out():
    buf = SpanBuffer(16)
    for i in range(20):
        buf.add(Span(i, i, None, "x", "s", 0.0))
    assert len(buf) == 16 and buf.dropped == 4
    assert [s.span_id for s in buf.spans()] == list(range(4, 20))
    buf.clear()
    assert buf.dropped == 0


def test_a_step_records_three_spans(traced):
    """Three spans an iteration without a save: the default depth holds a
    window of more than a thousand steps."""
    _, spans, _, _ = traced
    kids = _children(spans)
    plain = [it for i, it in enumerate(kids[None][:STEPS]) if (i + 1) % EVERY]
    assert all(len(kids[it.span_id]) == 2 for it in plain)
    assert len(spans) < TRACE_BUFFER_SPANS
    assert 3 * 1000 < TRACE_BUFFER_SPANS


def test_spans_land_in_the_profiler_trace_inside_the_enclosing_annotation(tmp_path, collab):
    """Run a save and a restore under the profiler inside an outer
    annotation, as the benchmark's window is, and read the trace back."""
    from jax.profiler import ProfileData

    state = {"w": np.arange(64, dtype=np.float32).reshape(8, 8), "b": np.ones(8, np.float32)}
    like = jax.eval_shape(lambda: state)
    shardings = jax.tree.map(lambda _: jax.sharding.SingleDeviceSharding(jax.devices()[0]), state)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench:window"):
            CheckpointManager(collab, run="profiled", home_dc="dc0", n_shards=SHARDS).save(state, 1)
            CheckpointManager(collab, run="profiled", home_dc="dc1", n_shards=SHARDS).restore(
                like, 1, shardings=shardings
            )
    finally:
        jax.profiler.stop_trace()
    events = defaultdict(list)
    for path in glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True):
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        events[e.name].append((int(e.start_ns), int(e.end_ns)))
    (w0, w1), = events["bench:window"]
    assert len(events["ckpt.write"]) == len(events["store.write"]) == SHARDS
    assert len(events["ckpt.put"]) == len(state) and len(events["ckpt.place"]) == 1
    for name in ("ckpt.write", "store.write", "ckpt.put", "ckpt.place"):
        assert all(w0 <= s <= e <= w1 for s, e in events[name]), name
