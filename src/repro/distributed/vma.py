"""Varying-manual-axes (vma) plumbing for partial-manual shard_map.

When the train step runs manual over ``pod`` (hierarchical/compressed
cross-pod modes), jax's vma checker requires every ``lax.scan`` carry to
have consistent "varying over pod" typing.  Model code initializes carries
with ``jnp.zeros`` (unvarying); under the manual region those inits must be
pcast to varying.

Model code stays mode-agnostic by calling :func:`vary` on carry inits — a
no-op unless the surrounding step builder has entered :func:`manual_axes`.
The flag is consulted at **trace time**, so the same function traced under
the auto (plain GSPMD) mode is untouched.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Iterator, Tuple

import jax

__all__ = ["manual_axes", "vary", "current_manual_axes"]

_STATE = threading.local()


def current_manual_axes() -> Tuple[str, ...]:
    return getattr(_STATE, "axes", ())


@contextlib.contextmanager
def manual_axes(*axes: str) -> Iterator[None]:
    prev = current_manual_axes()
    _STATE.axes = tuple(axes)
    try:
        yield
    finally:
        _STATE.axes = prev


def vary(tree: Any) -> Any:
    """Mark a pytree varying over the active manual axes (no-op otherwise)."""
    axes = current_manual_axes()
    if not axes:
        return tree
    return jax.tree.map(lambda x: jax.lax.pcast(x, axes, to="varying"), tree)
