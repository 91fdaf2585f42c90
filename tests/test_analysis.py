"""HLO walker + roofline + sharding rules + cost model unit tests."""
import jax
from jax.sharding import AxisType
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo import analyze
from repro.analysis.roofline import Roofline
from repro.sharding.rules import spec_for


def test_walker_counts_scanned_dot_flops():
    """A scan of L matmuls must report L x the per-iteration FLOPs (XLA's
    own cost_analysis counts the body once — the walker must not)."""
    L, M, K, N = 7, 32, 48, 16
    W = jnp.ones((L, K, N), jnp.float32)

    def f(x):
        def body(x, w):
            return x @ w @ jnp.ones((N, K), jnp.float32), ()
        x, _ = jax.lax.scan(body, x, W)
        return x

    compiled = jax.jit(f).lower(jnp.ones((M, K))).compile()
    a = analyze(compiled.as_text())
    want = L * (2 * M * K * N + 2 * M * N * K)
    assert a.flops == pytest.approx(want, rel=0.05)
    assert any(t == L for t in a.while_trip_counts.values())


def test_walker_counts_collective_bytes():
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >1 device")
    mesh = jax.make_mesh((len(devs),), ("d",),
                         axis_types=(AxisType.Auto,))
    from jax.sharding import NamedSharding, PartitionSpec as P

    def f(x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(None))).sum() + x.sum()

    x_sh = NamedSharding(mesh, P("d"))
    with jax.set_mesh(mesh):
        compiled = jax.jit(f, in_shardings=(x_sh,)).lower(
            jax.ShapeDtypeStruct((len(devs) * 8, 4), jnp.float32)).compile()
    a = analyze(compiled.as_text())
    assert a.total_collective_bytes > 0


def test_roofline_terms_and_bottleneck():
    r = Roofline(chips=256, flops=197e12, hbm_bytes=10e9,
                 attn_tile_bytes=0.0,
                 collective_bytes=100e9, collective_breakdown={},
                 model_flops=197e12 * 256 * 0.5, xla_flops=0, xla_bytes=0)
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_memory == pytest.approx(10e9 / 819e9)
    assert r.t_collective == pytest.approx(2.0)
    assert r.bottleneck == "collective"
    assert r.useful_flops_ratio == pytest.approx(0.5)
    assert 0 < r.mfu < 1


def test_sharding_rules_divisibility_fallback():
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    # divisible: sharded
    assert spec_for(("vocab", None), (512, 16), mesh)[0] == "model"
    # not divisible: replicated
    s = spec_for(("vocab", None), (510, 16), mesh)
    assert len(s) == 0 or s[0] is None
    # combined axes
    s = spec_for(("batch", None), (8, 16), mesh)
    assert s[0] == ("data",) or s[0] == "data"


def test_costmodel_caching_and_feasibility():
    from repro.configs.paper_workloads import resnet18
    from repro.core import CostModel
    from repro.core.cn import identify_cns
    from repro.hw.catalog import mc_hetero
    w = resnet18()
    acc = mc_hetero()
    cm = CostModel(w, acc)
    cns = identify_cns(w, "line")
    c1 = cm.cost(cns[5], 0)
    c2 = cm.cost(cns[5], 0)
    assert c1 is c2  # cached
    # SIMD core cannot run convs
    simd = acc.simd_core_id
    conv_cn = next(c for c in cns if w.layers[c.layer].op == "conv")
    assert cm.cost(conv_cn, simd) is None


def test_zigzag_lite_loma_picks_better_order():
    """C-K dataflows must not pay per-MAC weight reads (order B wins)."""
    from repro.core.zigzag_lite import cn_cost
    from repro.hw.core_model import CoreModel
    core = CoreModel("t", (("C", 32), ("K", 32)), act_mem_bytes=1 << 16,
                     weight_mem_bytes=1 << 17, sram_bw_bits_per_cc=1024)
    c = cn_cost({"K": 64, "C": 64, "OY": 16, "OX": 56, "FY": 3, "FX": 3},
                "conv", core)
    assert c.cycles < c.ideal_cycles * 8  # no catastrophic stall
    assert 0 < c.spatial_util <= 1.0


def test_aimc_flexible_packing():
    from repro.core.zigzag_lite import cn_cost
    from repro.hw.core_model import CoreModel
    core = CoreModel("a", (("C", 128), ("FY", 3), ("FX", 3), ("K", 256)),
                     act_mem_bytes=1 << 14, weight_mem_bytes=1 << 18,
                     core_type="aimc", aimc_cc_per_op=10)
    # 3x3x64 filter = 576 rows <= 1152 -> one activation per output pixel
    c = cn_cost({"K": 64, "C": 64, "OY": 1, "OX": 56, "FY": 3, "FX": 3},
                "conv", core)
    assert c.ideal_cycles == 56 * 10
