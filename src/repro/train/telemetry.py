"""The train layer's telemetry: spans of steps, saves and restores.

One :class:`~repro.core.telemetry.Telemetry` bundle, :data:`TELEMETRY`,
takes the spans of every :class:`~repro.train.Trainer` and
:class:`~repro.train.CheckpointManager`, so a save's spans nest under the
loop iteration that triggered it.  Every span also opens a
``jax.profiler.TraceAnnotation`` of its name, which puts it on the
profiler's host timeline, on the same clock as the device ops, whenever a
trace is taken.

Spans (parent > children; tags in brackets):

- ``train.iter`` > ``train.batch`` [bytes], ``train.step`` [MoE models:
  ``moe_routed``, ``moe_kept``, ``moe_slots``], ``ckpt.d2h`` [bytes],
  ``ckpt.save``: one iteration of ``Trainer.run``; ``train.batch`` is
  ``batch_at`` plus ``device_put``, ``train.step`` the dispatch through
  ``block_until_ready`` (its tags the step's MoE counters summed over
  layers: choices bound for the experts held here, those kept within
  capacity, expert slots computed), ``ckpt.d2h`` the state's copy to the host;
- ``ckpt.save`` [step] > ``ckpt.pack``, ``ckpt.write`` [shard, bytes],
  ``ckpt.publish``; ``ckpt.write`` > ``scidata.serialize``, ``store.write``
  [bytes] (native mode); ``ckpt.publish`` > ``meu.export``, ``ckpt.index``;
- ``ckpt.discover``: the SDS query for the run's steps;
- ``ckpt.restore`` [step, shards, bytes, into_bytes] > ``ckpt.open``
  [shard] (one stat and one header read), then per leaf, largest first,
  ``ckpt.read`` [leaf, bytes] (the wait for its pieces, read on the copy
  threads) and ``ckpt.assemble`` (its cast) > ``ckpt.put`` (its
  ``device_put``), then ``ckpt.place`` (the wait for the copies the reads
  did not hide).  ``wire_s`` of ``ckpt.restore`` and ``ckpt.open`` is the
  data path's modeled seconds; ``into_bytes`` counts the bytes read
  straight into their leaf, ``bytes`` when no read handed back an array of
  its own.

With the tracer off nothing is recorded or annotated and no tag is
computed; :func:`span` still times its block, since the trainer's rows and
its straggler balancer take their seconds from it.
"""

from __future__ import annotations

from typing import Any

import jax

from repro.core.telemetry import Span, Telemetry, now

__all__ = ["TELEMETRY", "span"]

#: the train package's bundle
TELEMETRY = Telemetry("train", region=jax.profiler.TraceAnnotation)


class _Unrecorded:
    """A span timed on the session clock and kept nowhere (tracer off)."""

    __slots__ = ("_span",)

    def __init__(self, name: str, tags: dict):
        self._span = Span(0, 0, None, name, "", 0.0, tags or None)

    def __enter__(self) -> Span:
        self._span.start = now()
        return self._span

    def __exit__(self, *exc: Any) -> None:
        self._span.end = now()


def span(name: str, **tags: Any):
    """``with span(name, **tags) as sp:`` — a span of :data:`TELEMETRY`'s
    tracer, or an unrecorded but timed one when the tracer is off."""
    tracer = TELEMETRY.tracer
    if tracer.enabled:
        return tracer.span(name, **tags)
    return _Unrecorded(name, tags)
