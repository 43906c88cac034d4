"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

A CPU analysis tool: the environment lines below MUST run before jax is
imported — jax locks the platform and device count at first init, and the
production meshes need 512 placeholder host devices (single-pod 16×16 = 256
used as a sub-mesh, multi-pod 2×16×16 = 512).  It never claims a chip.

Per cell this script:
  1. builds the model + abstract state (ShapeDtypeStructs, no allocation),
  2. attaches in/out shardings from :mod:`repro.distributed.sharding`,
  3. ``jit(...).lower(...).compile()`` — sharding mismatches, unsupported
     collectives or compile-time OOM are failures,
  4. prints ``memory_analysis()`` (does it fit 16 GB/chip?) and
     ``cost_analysis()`` (FLOPs / bytes for the roofline),
  5. emits the 3-term roofline row (single-pod mesh only, per the spec).

Usage:
    python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k
    python -m repro.launch.dryrun --all --multi-pod --out dryrun.json
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
).strip()

import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES, applicable_shapes, get_config
from repro.distributed.sharding import (
    batch_shardings,
    cache_shardings,
    param_shardings,
)
from repro.launch.mesh import make_production_mesh
from repro.models.model import Model
from repro.models import transformer as _tf
from repro.optim import AdamW, AdamWConfig
from repro.roofline import model_flops, roofline
from repro.train.step import build_train_step, init_state_abstract, state_shardings

__all__ = ["run_cell", "main"]

#: Per-shape train microbatch defaults (memory-bounded baseline).
TRAIN_MICROBATCHES = 4

#: Archs whose optimizer state needs FSDP sharding to fit 16 GB/chip.
#: fp32 AdamW state = 12 bytes/param over the 16-way model axis: 7B ⇒ 5.3 GB
#: (fits), 15B ⇒ 11.3 GB (fits, tight), 52B/773B ⇒ 39/580 GB (need FSDP).
#: FSDP costs data-axis collectives on the contracted weight dims, so it is
#: enabled only where capacity forces it.
FSDP_ARCHS = {
    "jamba-v0.1-52b",
    "llama4-maverick-400b-a17b",
}


def _sds(abstract, shardings):
    """ShapeDtypeStructs carrying shardings (lower() inputs, no allocation)."""
    return jax.tree.map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        abstract,
        shardings,
    )


def _count_params_abstract(model) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(model.init_abstract()))


def _active_fraction(cfg) -> float:
    """active/total parameter fraction (MoE expert down-weighting)."""
    if cfg.moe is None:
        return 1.0
    # expert stacks dominate; approximate with exact per-leaf accounting
    import numpy as np

    total = 0
    active = 0
    model = Model(cfg)
    flat = jax.tree_util.tree_flatten_with_path(model.init_abstract())[0]
    from repro.distributed.sharding import path_of

    for kp, leaf in flat:
        n = int(np.prod(leaf.shape))
        total += n
        path = path_of(kp)
        if any(s in path for s in ("w_gate/", "w_up/", "w_down/")) and "ffn/" in path:
            active += int(n * cfg.moe.top_k / cfg.moe.n_experts)
        else:
            active += n
    return active / total


def run_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    microbatches: int = TRAIN_MICROBATCHES,
    fsdp: Optional[bool] = None,
    cross_pod: str = "auto",
    mesh=None,
    overrides: Optional[Dict[str, Any]] = None,
    verbose: bool = True,
) -> Dict[str, Any]:
    """Lower+compile one cell; returns its record."""
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return {
            "arch": arch, "shape": shape_name, "mesh": "multi" if multi_pod else "single",
            "status": "SKIP", "reason": "pure full-attention arch",
        }
    if fsdp is None:
        fsdp = arch in FSDP_ARCHS
    mesh = mesh if mesh is not None else make_production_mesh(multi_pod=multi_pod)
    n_devices = mesh.size
    pod_size = n_devices // mesh.shape.get("pod", 1)
    model = Model(cfg)
    t0 = time.time()

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt = AdamW(AdamWConfig())
            n_pods = mesh.shape.get("pod", 0) if cross_pod != "auto" else 0
            state_abs = init_state_abstract(model, opt, n_pods=n_pods)
            st_sh = state_shardings(state_abs, mesh, fsdp=fsdp)
            batch_abs = {
                k: jax.ShapeDtypeStruct(s, d)
                for k, (s, d) in model.input_shapes(shape).items()
            }
            b_sh = batch_shardings(batch_abs, mesh)
            step = build_train_step(
                model, opt, mesh, microbatches=microbatches, loss_chunk=512,
                cross_pod=cross_pod,
            )
            lowered = step.lower(_sds(state_abs, st_sh), _sds(batch_abs, b_sh))
            tokens = shape.global_batch * shape.seq_len
            kind = "train"
        elif shape.kind == "prefill":
            params_abs = model.init_abstract()
            p_sh = param_shardings(params_abs, mesh, fsdp=fsdp)
            batch_abs = {
                k: jax.ShapeDtypeStruct(s, d)
                for k, (s, d) in model.input_shapes(shape).items()
            }
            b_sh = batch_shardings(batch_abs, mesh)
            serve_fn = jax.jit(
                lambda p, b: model.prefill(p, b, max_len=shape.seq_len)
            )
            lowered = serve_fn.lower(_sds(params_abs, p_sh), _sds(batch_abs, b_sh))
            tokens = shape.global_batch * shape.seq_len
            kind = "serve"
        else:  # decode
            params_abs = model.init_abstract()
            p_sh = param_shardings(params_abs, mesh, fsdp=fsdp)
            cache_abs = jax.eval_shape(
                lambda: model.init_decode_cache(shape.global_batch, shape.seq_len)
            )
            c_sh = cache_shardings(cache_abs, mesh, batch=shape.global_batch)
            tok_abs = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
            pos_abs = jax.ShapeDtypeStruct((), jnp.int32)
            serve_fn = jax.jit(model.decode_step, static_argnums=())
            lowered = serve_fn.lower(
                _sds(params_abs, p_sh), _sds(cache_abs, c_sh), tok_abs, pos_abs
            )
            tokens = shape.global_batch  # one new token per row
            kind = "serve"

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()

    n_params = _count_params_abstract(model)
    n_active = int(n_params * _active_fraction(cfg))
    mf = model_flops(n_params, n_active, tokens, kind=("train" if kind == "train" else "serve"))
    from repro.roofline.analytic import cell_bytes, cell_flops

    af = cell_flops(cfg, shape)
    ab = cell_bytes(
        cfg, shape, n_params=n_params, n_devices=n_devices,
        fsdp=fsdp, tp=mesh.shape["model"],
    )
    rep = roofline(
        cost=cost,
        hlo_text=hlo,
        n_devices=n_devices,
        pod_size=pod_size if multi_pod else 0,
        model_flops_total=mf,
        analytic_flops_total=af,
        analytic_bytes_per_chip=ab,
    )
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "OK",
        "fsdp": fsdp,
        "microbatches": microbatches if shape.kind == "train" else 0,
        "n_params": n_params,
        "n_active": n_active,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "mem": {
            "argument_gb": mem.argument_size_in_bytes / 1e9,
            "output_gb": mem.output_size_in_bytes / 1e9,
            "temp_gb": mem.temp_size_in_bytes / 1e9,
            "alias_gb": mem.alias_size_in_bytes / 1e9,
            "peak_est_gb": (
                mem.argument_size_in_bytes
                + mem.output_size_in_bytes
                + mem.temp_size_in_bytes
                - mem.alias_size_in_bytes
            )
            / 1e9,
        },
        **rep,
    }
    if verbose:
        print(
            f"[{record['mesh']}] {arch:26s} {shape_name:12s} "
            f"compile={t_compile:6.1f}s peak={record['mem']['peak_est_gb']:7.2f}GB "
            f"t_comp={rep['t_compute_s']:.3e} t_mem={rep['t_memory_s']:.3e} "
            f"t_coll={rep['t_collective_s']:.3e} -> {rep['bottleneck']}"
        )
        print("  memory_analysis:", mem)
        print(
            "  cost_analysis: flops=%.3e bytes=%.3e"
            % (float(cost.get("flops", 0)), float(cost.get("bytes accessed", 0)))
        )
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="one architecture (default: all)")
    ap.add_argument("--shape", default=None, help="one shape cell (default: all)")
    ap.add_argument("--all", action="store_true", help="run every cell")
    ap.add_argument("--multi-pod", action="store_true", help="2×16×16 mesh")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--microbatches", type=int, default=TRAIN_MICROBATCHES)
    ap.add_argument("--out", default=None, help="write records to this JSON file")
    args = ap.parse_args(argv)

    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    records = []
    failures = 0
    for multi in meshes:
        for a in archs:
            for s in shapes:
                try:
                    rec = run_cell(a, s, multi_pod=multi, microbatches=args.microbatches)
                except Exception as exc:  # noqa: BLE001 — report, keep sweeping
                    traceback.print_exc()
                    rec = {
                        "arch": a, "shape": s,
                        "mesh": "2x16x16" if multi else "16x16",
                        "status": "FAIL", "error": f"{type(exc).__name__}: {exc}",
                    }
                    failures += 1
                records.append(rec)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(records, fh, indent=1)
        print(f"wrote {len(records)} records to {args.out}")
    ok = sum(1 for r in records if r["status"] == "OK")
    skip = sum(1 for r in records if r["status"] == "SKIP")
    print(f"dry-run: {ok} OK, {skip} SKIP, {failures} FAIL / {len(records)} cells")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
