"""Roofline machinery: trip-count-aware HLO cost model + term math."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline import analysis as ra
from repro.roofline import hlo_cost, hw


def test_scan_flops_multiplied():
    def one(x, w):
        return x @ w

    def scan10(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    f1 = hlo_cost.analyze(jax.jit(one).lower(x, w).compile().as_text()).flops
    f10 = hlo_cost.analyze(jax.jit(scan10).lower(x, w).compile().as_text()).flops
    assert f1 == pytest.approx(2 * 256 ** 3, rel=0.01)
    assert f10 == pytest.approx(10 * f1, rel=0.02)


def test_nested_scan_multiplied():
    def nested(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            ci, _ = jax.lax.scan(inner, c, None, length=3)
            return ci, None
        y, _ = jax.lax.scan(outer, x, None, length=4)
        return y

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    f = hlo_cost.analyze(jax.jit(nested).lower(x, w).compile().as_text()).flops
    assert f == pytest.approx(12 * 2 * 128 ** 3, rel=0.05)


def test_xla_cost_analysis_undercounts_loops_motivation():
    """Documents WHY hlo_cost exists: XLA counts loop bodies once."""
    def scan10(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    comp = jax.jit(scan10).lower(x, w).compile()
    xla_flops, _ = ra.cost_analysis_terms(comp)
    ours = hlo_cost.analyze(comp.as_text()).flops
    assert ours >= 9 * xla_flops  # XLA missed ~10x


def test_collective_bytes_parse():
    hlo = """
HloModule test
ENTRY %main (p: f32[1024]) -> f32[1024] {
  %p = f32[1024]{0} parameter(0)
  %ar = f32[1024]{0} all-reduce(%p), to_apply=%add
  ROOT %ag = f32[8192]{0} all-gather(%ar), dimensions={0}
}
"""
    out = ra.collective_bytes(hlo)
    assert out["all-reduce"] == 4096
    assert out["all-gather"] == 4096      # operand bytes, not result
    assert out["total"] == 8192


def test_roofline_terms_math():
    v5e = hw.chip(hw.V5E)
    t = ra.roofline(flops=v5e.peak_flops_bf16, hbm_bytes=v5e.hbm_bw / 2,
                    coll_bytes=0, n_chips=4,
                    model_flops_total=v5e.peak_flops_bf16)
    assert t.compute_s == pytest.approx(1.0)
    assert t.memory_s == pytest.approx(0.5)
    assert t.bottleneck == "compute"
    assert t.mfu_bound == pytest.approx(0.25)   # model/(4 chips * peak * 1s)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no hardware constants"):
        hw.chip("TPU v99")


def test_model_flops():
    assert ra.model_flops("train", 10, 100) == 6000
    assert ra.model_flops("prefill", 10, 100) == 2000
    assert ra.model_flops("train", 10, 100, embed_params=4) == 3600


def test_conditional_takes_max_branch():
    def f(x, pred):
        return jax.lax.cond(pred, lambda a: a @ a, lambda a: a + 1.0, x)

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    p = jax.ShapeDtypeStruct((), jnp.bool_)
    c = hlo_cost.analyze(jax.jit(f).lower(x, p).compile().as_text())
    assert c.flops >= 2 * 128 ** 3 * 0.95      # matmul branch counted
