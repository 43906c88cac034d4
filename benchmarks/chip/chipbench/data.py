"""Token batches for a training job, from a traffic file's parameters.

A batch is a pure function of (seed, step, row), so the program and the
reference see the same rows, and every row of every step differs.  Each row
is a noisy repetition of a random base pattern of ``period`` tokens drawn
from the first ``vocab_eff`` ids, with ``noise`` of the positions redrawn:
text with structure a model can learn, at the lengths the traffic names.
(The arithmetic is that of the program's ``SyntheticLM``.)
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from . import spans


class TokenBatches:
    """The trainer's data source: ``batch_at(step)`` → tokens and targets."""

    def __init__(self, seed: int, traffic: Dict[str, Any], vocab_size: int):
        self.seed = seed
        self.batch = traffic["batch"]
        self.seq_len = traffic["seq_len"]
        self.period = traffic["period"]
        self.noise = traffic["noise"]
        self.vocab = min(traffic["vocab_eff"], vocab_size)

    def row(self, step: int, row: int) -> np.ndarray:
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, step, row]))
        n = self.seq_len + 1
        base = rng.integers(0, self.vocab, size=self.period)
        seq = np.tile(base, -(-n // self.period))[:n]
        flips = rng.random(n) < self.noise
        return np.where(flips, rng.integers(0, self.vocab, size=n), seq).astype(np.int32)

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        with spans.span("data.batch_at"):
            seqs = np.stack([self.row(step, r) for r in range(self.batch)])
            return {"tokens": seqs[:, :-1], "targets": seqs[:, 1:]}
