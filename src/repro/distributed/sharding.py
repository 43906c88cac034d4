"""Sharding rules: parameter/batch/cache PartitionSpecs for every arch × mesh.

Strategy:

- **DP** — the batch dimension is sharded over all data-like axes
  (``('pod', 'data')`` on the multi-pod mesh, ``('data',)`` single-pod).
- **TP** — weight matrices shard their "wide" dimension on ``model``:
  attention heads (column-parallel), FFN hidden (column for wi, row for wo),
  vocab for embedding/unembedding tables.
- **EP** — MoE expert stacks shard the expert dimension on ``model``; the
  one-hot dispatch einsums then lower to all-to-alls under GSPMD.
- **CP/SP** — decode shapes with tiny batches (long_500k has B=1) shard the
  KV-cache *sequence* dimension over ``data``; attention reductions over the
  cache lower to psums across the CP group.

Rules are **divisibility-checked best-effort**: each leaf has an ordered
preference list of (dim → axis) assignments; the first one whose dimension
is divisible by the axis size wins, the rest stay replicated.  This is what
keeps one rule set valid for e.g. both nemotron (48 heads / 16-way TP) and
gemma2 (8 heads — falls back to sharding head_dim, then d_ff).

Leaf matching is by parameter *path* (stable names from models/layers.py),
so the rules survive architectural recombination (patterns, stacked units).
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "data_axes",
    "batch_spec",
    "param_spec_for_path",
    "param_shardings",
    "batch_shardings",
    "cache_shardings",
    "path_of",
]


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """All data-parallel mesh axes, pod-major."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def _axis_size(mesh: Mesh, axis) -> int:
    if isinstance(axis, tuple):
        out = 1
        for a in axis:
            out *= mesh.shape[a]
        return out
    return mesh.shape[axis]


def batch_spec(mesh: Mesh) -> P:
    ax = data_axes(mesh)
    return P(ax if len(ax) > 1 else ax[0])


def path_of(keypath) -> str:
    """jax.tree_util key path → 'units/pos0/mixer/wq/w' style string."""
    parts = []
    for k in keypath:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


# ---------------------------------------------------------------------------
# rule table
# ---------------------------------------------------------------------------

# Each entry: (path regex, [(dim, axis), ...] preference list).  dim indexes
# are for the *unstacked* leaf; stacked unit params (leading n_units dim) are
# detected by shape-rank mismatch and the rule shifts right by one.
# fmt: off
_RULES: Sequence[Tuple[str, List[Tuple[int, str]]]] = (
    # embeddings: prefer vocab (row) sharding, fall back to d_model
    (r"(embed|lm_head)/table$",            [(0, "model"), (1, "model")]),
    # attention projections: shard heads, then head_dim, never d_model(in)
    (r"(mixer|self_attn|cross_attn|attn)/wq/w$", [(1, "model"), (2, "model")]),
    (r"(mixer|self_attn|cross_attn|attn)/wk/w$", [(1, "model"), (2, "model")]),
    (r"(mixer|self_attn|cross_attn|attn)/wv/w$", [(1, "model"), (2, "model")]),
    (r"(mixer|self_attn|cross_attn|attn)/w[qkv]/b$", [(0, "model"), (1, "model")]),
    # MLA: the key/value expansion is per head; the latent projection is small
    (r"mixer/wkv_b/w$",                    [(1, "model")]),
    (r"mixer/wkv_a/w$",                    []),
    # attention output: row-parallel (heads are the contraction dim)
    (r"(mixer|self_attn|cross_attn|attn)/wo/w$", [(0, "model"), (1, "model")]),
    # dense MLP: column-parallel in, row-parallel out
    (r"ffn/wi_gate/w$",                    [(1, "model")]),
    (r"ffn/wi_up/w$",                      [(1, "model")]),
    (r"ffn/wo/w$",                         [(0, "model")]),
    (r"ffn/(wi_gate|wi_up|wo)/b$",         []),
    # MoE: expert-parallel stacks + replicated router
    (r"ffn/(w_gate|w_up|w_down)/w$",       [(0, "model")]),
    (r"ffn/router/w$",                     []),
    (r"ffn/shared/wi_gate/w$",             [(1, "model")]),
    (r"ffn/shared/wi_up/w$",               [(1, "model")]),
    (r"ffn/shared/wo/w$",                  [(0, "model")]),
    # Mamba: shard d_inner (column for in_proj, row for out_proj)
    (r"mixer/in_proj/w$",                  [(1, "model")]),
    (r"mixer/x_proj/w$",                   [(0, "model")]),
    (r"mixer/dt_proj/w$",                  [(1, "model")]),
    (r"mixer/dt_proj/b$",                  [(0, "model")]),
    (r"mixer/out_proj/w$",                 [(0, "model")]),
    (r"mixer/(conv_w|conv_b)$",            [(1, "model"), (0, "model")]),
    (r"mixer/(A_log|D|dt_bias)$",          [(0, "model")]),
    # RWKV time-mix / channel-mix: column-parallel square projections
    (r"mixer/w_[rkvg]/w$",                 [(1, "model")]),
    (r"mixer/w_o/w$",                      [(0, "model")]),
    (r"mixer/decay_lora_a/w$",             [(1, "model")]),
    (r"mixer/decay_lora_b/w$",             [(0, "model")]),
    (r"ffn/w_k/w$",                        [(1, "model")]),
    (r"ffn/w_v/w$",                        [(0, "model")]),
    (r"ffn/w_r/w$",                        [(1, "model")]),
    # frontend projection (vlm/audio stubs)
    (r"frontend_proj/w$",                  [(1, "model")]),
    # norms / scalars / small vectors: replicated
    (r"(norm|gn_scale|gn_bias|mu|bonus|decay_base|scale|bias)", []),
)
# fmt: on


def param_spec_for_path(
    path: str, shape: Tuple[int, ...], mesh: Mesh, *, fsdp: bool = False
) -> P:
    """Resolve the PartitionSpec for one parameter leaf.

    ``fsdp=True`` additionally shards each leaf's largest still-unsharded
    dimension over ``data`` (ZeRO/FSDP-style fully-sharded state): GSPMD
    all-gathers weights per layer in the forward, and optimizer state stays
    1/|data| per chip — what lets the 52B/773B archs fit the 16 GB/chip
    budget (``repro.launch.dryrun`` reports per-cell bytes).
    """
    for pattern, prefs in _RULES:
        if re.search(pattern, path):
            return _apply_prefs(path, shape, prefs, mesh, fsdp=fsdp)
    # default: replicate (safe for anything unmatched)
    return _apply_prefs(path, shape, [], mesh, fsdp=fsdp)


def _apply_prefs(
    path: str,
    shape: Tuple[int, ...],
    prefs: List[Tuple[int, str]],
    mesh: Mesh,
    *,
    fsdp: bool = False,
) -> P:
    # stacked unit params carry a leading n_units dim (scan layout) — and
    # enc/dec layer stacks a leading n_layers dim; shift dims right by one
    shift = 1 if re.search(r"(units/pos\d+|enc_layers|dec_layers)/", path) else 0
    spec: List[Optional[Any]] = [None] * len(shape)
    used_axes = set()
    for dim, axis in prefs:
        d = dim + shift
        if d >= len(shape):
            continue
        if axis in used_axes or axis not in mesh.axis_names:
            continue
        if spec[d] is not None:
            continue
        if shape[d] % mesh.shape[axis] == 0 and shape[d] >= mesh.shape[axis]:
            spec[d] = axis
            used_axes.add(axis)
            break  # first satisfiable preference wins; do not over-shard
    if fsdp and "data" in mesh.axis_names and "data" not in used_axes:
        # largest unsharded non-stack dim that divides; scan axis excluded.
        # (Preferring output dims instead was tried and refuted: it trades
        # the input-dim psums for output-activation gathers at +5% wire.  FSDP's in-dim psums are why it is enabled
        # only where capacity requires it — see launch.dryrun.FSDP_ARCHS.)
        dp = mesh.shape["data"]
        cands = [
            d for d in range(shift, len(shape))
            if spec[d] is None and shape[d] % dp == 0 and shape[d] >= dp
        ]
        if cands:
            d = max(cands, key=lambda i: shape[i])
            spec[d] = "data"
    return P(*spec)


def param_shardings(params_abstract, mesh: Mesh, *, fsdp: bool = False):
    """Pytree of NamedShardings matching ``params_abstract`` (shapes only)."""

    def leaf_sharding(keypath, leaf):
        spec = param_spec_for_path(path_of(keypath), tuple(leaf.shape), mesh, fsdp=fsdp)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(leaf_sharding, params_abstract)


def batch_shardings(batch_abstract, mesh: Mesh, *, shard_batch: bool = True):
    """Batch inputs: shard dim 0 over the data axes (replicate if B=1)."""
    ax = data_axes(mesh)
    dp = _axis_size(mesh, ax if len(ax) > 1 else ax[0])

    def leaf_sharding(leaf):
        if shard_batch and leaf.ndim >= 1 and leaf.shape[0] % dp == 0 and leaf.shape[0] >= dp:
            return NamedSharding(mesh, P(ax if len(ax) > 1 else ax[0]))
        return NamedSharding(mesh, P())

    return jax.tree.map(leaf_sharding, batch_abstract)


def cache_shardings(cache_abstract, mesh: Mesh, *, batch: int):
    """Decode caches.

    Batched decode shards the cache batch dim over the data axes (DP
    serving).  When the batch cannot be sharded (long_500k: B=1), the cache
    *sequence* dimension is sharded over ``data`` instead — context
    parallelism; attention over the cache then reduces across the CP group.
    Head/expert-like dims shard on ``model`` when divisible.
    """
    ax = data_axes(mesh)
    dp = _axis_size(mesh, ax if len(ax) > 1 else ax[0])
    cp_axis = "data"  # sequence parallelism always uses the intra-pod axis
    cp = mesh.shape[cp_axis] if cp_axis in mesh.axis_names else 1

    def leaf_sharding(keypath, leaf):
        path = path_of(keypath)
        leaf_name = path.split("/")[-1]
        spec: List[Optional[Any]] = [None] * leaf.ndim
        # layout: [L, B, T, Kv, hd] (attn) / [L, B, K-1, di] (conv) /
        #         [L, B, di, ds] (ssm) / [L, B, H, C, C] (wkv) / [L,B,1,D] /
        #         [L, B, T, latent or rope] (mla: c, k_pe)
        if leaf.ndim >= 2 and batch % dp == 0 and batch >= dp:
            spec[1] = ax if len(ax) > 1 else ax[0]
        elif leaf_name in ("k", "v", "self_k", "self_v", "cross_k", "cross_v", "c", "k_pe"):
            # CP: shard the cache sequence dim (index 2) when batch can't split
            if leaf.ndim >= 3 and leaf.shape[2] % cp == 0 and leaf.shape[2] >= cp:
                spec[2] = cp_axis
        # model-parallel head/channel dims: prefer dim 3 (KV heads — aligns
        # with the wk/wv projection sharding, no resharding at cache write),
        # then the largest remaining dim ≥ 3 (hd / state channels)
        if leaf.ndim >= 4:
            tp = mesh.shape["model"] if "model" in mesh.axis_names else 1
            cands = [3] + sorted(
                range(4, leaf.ndim), key=lambda i: -leaf.shape[i]
            )
            for d in cands:
                if (
                    spec[d] is None
                    and leaf.shape[d] % tp == 0
                    and leaf.shape[d] >= tp
                ):
                    spec[d] = "model"
                    break
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(leaf_sharding, cache_abstract)
