"""Faults planted in the program for the benchmark's own tests.

Each is a function of pytest's ``monkeypatch`` that breaks the timed path
underneath the harness, as a faulty change to the program would:

- ``unchanged``  — the train step returns the state it was given (only the
                   step counter moves, or the trainer would never finish);
- ``half_batch`` — half of each batch is left out and the mean taken over the rest;
- ``save_byte``  — one byte of the first array of every saved shard is altered;
- ``read_byte``  — one byte of every dataset read through the workspace is altered.
"""

from __future__ import annotations

import numpy as np


def unchanged(monkeypatch) -> None:
    import repro.train.trainer as trainer_mod

    real = trainer_mod.build_train_step

    def build(*args, **kw):
        step = real(*args, **dict(kw, donate=False))

        def broken(state, batch):
            _, metrics = step(state, batch)
            return dict(state, step=state["step"] + 1), metrics

        return broken

    monkeypatch.setattr(trainer_mod, "build_train_step", build)


def half_batch(monkeypatch) -> None:
    from repro.train.trainer import Trainer

    real = Trainer._device_batch

    def device_batch(self, batch_np):
        half = {k: v[: len(v) // 2] for k, v in batch_np.items()}
        return real(self, half)

    monkeypatch.setattr(Trainer, "_device_batch", device_batch)


def _flip(arr: np.ndarray) -> np.ndarray:
    out = np.array(arr, copy=True)
    out.reshape(-1).view(np.uint8)[0] ^= 0x40
    return out


def save_byte(monkeypatch) -> None:
    from repro.core.workspace import NativeSession

    real = NativeSession.write_scidata

    def write(self, path, arrays, attrs):
        first = sorted(arrays)[0]
        return real(self, path, dict(arrays, **{first: _flip(arrays[first])}), attrs)

    monkeypatch.setattr(NativeSession, "write_scidata", write)


def read_byte(monkeypatch) -> None:
    from repro.core.workspace import Workspace

    real = Workspace.read_dataset

    def read(self, path, name):
        return _flip(real(self, path, name))

    monkeypatch.setattr(Workspace, "read_dataset", read)


FAULTS = {"unchanged": unchanged, "half_batch": half_batch, "save_byte": save_byte, "read_byte": read_byte}
