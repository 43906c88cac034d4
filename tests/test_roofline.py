"""Roofline machinery: loop-aware collective parsing, analytic cross-checks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline import parse_collectives, roofline
from repro.roofline.hlo_loops import region_multipliers, split_regions
from tests._multidev import run_multidev


def test_cost_analysis_counts_loops_once():
    """Documents the XLA behaviour the analytic model corrects for."""
    D, N = 64, 8
    ws = jnp.zeros((N, D, D))
    x = jnp.zeros((4, D))

    def scanned(x, ws):
        def body(c, w):
            return c @ w, None
        return jax.lax.scan(body, x, ws)[0]

    def unrolled(x, ws):
        for i in range(N):
            x = x @ ws[i]
        return x

    cs = jax.jit(scanned).lower(x, ws).compile().cost_analysis()
    cu = jax.jit(unrolled).lower(x, ws).compile().cost_analysis()
    assert cu["flops"] >= (N - 1) * cs["flops"]  # scan counted ~once


def test_loop_aware_collective_bytes():
    """A collective inside an N-trip scan is weighted ×N."""
    out = run_multidev(
        """
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.mesh import make_mesh
        from repro.roofline import parse_collectives
        mesh = make_mesh((2, 4), ('data', 'model'))
        N, D = 8, 64
        ws = jax.ShapeDtypeStruct((N, D, D), jnp.float32,
            sharding=NamedSharding(mesh, P(None, None, 'model')))
        x = jax.ShapeDtypeStruct((8, D), jnp.float32,
            sharding=NamedSharding(mesh, P('data', None)))

        def scanned(x, ws):
            def body(c, w):
                return (c @ w) @ w.T, None   # all-reduce over model per step
            return jax.lax.scan(body, x, ws)[0].sum()

        with jax.set_mesh(mesh):
            comp = jax.jit(scanned).lower(x, ws).compile()
        colls = parse_collectives(comp.as_text(), n_devices=8)
        in_loop = [c for c in colls if c.kind == 'all-reduce' and c.wire_bytes_per_chip > 0]
        # the per-step all-reduce moves [8/2, 64] f32 = 1024B payload;
        # ring cost 2*(g-1)/g*payload with g=4 → 1536B, ×8 trips = 12288
        weighted = max(c.wire_bytes_per_chip for c in in_loop)
        assert weighted >= 8 * 1024, (weighted, [ (c.kind, c.wire_bytes_per_chip) for c in colls])
        print('weighted bytes:', weighted)
        """,
        devices=8,
    )
    assert "weighted bytes:" in out


def test_region_split_and_multipliers_smoke():
    hlo = """
HloModule m

%body.1 (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %all-reduce.5 = f32[4]{0} all-reduce(%x), replica_groups={{0,1}}, to_apply=%add
}

%cond.1 (p: (s32[], f32[4])) -> pred[] {
  ROOT %lt = pred[] compare(%a, %b), direction=LT
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %constant.9 = s32[] constant(5)
  %tuple.2 = (s32[], f32[4]) tuple(%constant.9, %x)
  %while.3 = (s32[], f32[4]) while(%tuple.2), condition=%cond.1, body=%body.1
}
"""
    regions = split_regions(hlo)
    assert set(regions) == {"body.1", "cond.1", "main"}
    mult = region_multipliers(hlo)
    assert mult["body.1"] == 5 and mult["main"] == 1
    colls = parse_collectives(hlo, n_devices=2)
    ar = [c for c in colls if c.kind == "all-reduce"]
    assert len(ar) == 1
    # payload 16B, g=2 → ring 16B, ×5 trips
    assert ar[0].wire_bytes_per_chip == pytest.approx(5 * 16.0)


def test_analytic_matches_unrolled_cost():
    """Closed-form FLOPs ≈ cost_analysis on an UNROLLED single-unit model."""
    from repro.configs import ARCHS
    from repro.configs.base import ShapeConfig
    from repro.models.model import Model
    from repro.roofline.analytic import cell_flops

    S, B = 256, 2
    shape = ShapeConfig("t", "train", S, B)
    cfg = ARCHS["codeqwen1.5-7b"].replace(
        n_layers=2, scan_layers=False, remat="none",
        dtype="float32", param_dtype="float32",
        attn_chunk_q=S, attn_chunk_kv=S,
    )
    model = Model(cfg)
    params_abs = model.init_abstract()
    batch_abs = {
        "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
        "targets": jax.ShapeDtypeStruct((B, S), jnp.int32),
    }
    fn = jax.jit(
        lambda p, b: jax.value_and_grad(lambda pp: model.train_loss(pp, b, loss_chunk=S)[0])(p)
    )
    cost = fn.lower(params_abs, batch_abs).compile().cost_analysis()
    analytic = cell_flops(cfg, shape)
    # loss-chunk scan has 1 trip at loss_chunk=S; flash scans have 1 block;
    # unit loop unrolled ⇒ cost_analysis sees everything.
    ratio = cost["flops"] / analytic
    assert 0.7 < ratio < 1.4, (cost["flops"], analytic, ratio)


def test_roofline_terms_and_bottleneck():
    rep = roofline(
        cost={"flops": 1e12, "bytes accessed": 1e9},
        hlo_text="",
        n_devices=256,
        model_flops_total=2e14,
    )
    assert rep["t_compute_s"] == pytest.approx(1e12 / 197e12)
    assert rep["t_memory_s"] == pytest.approx(1e9 / 819e9)
    assert rep["bottleneck"] == "compute"
    assert rep["useful_flops_ratio"] == pytest.approx(2e14 / (1e12 * 256))
