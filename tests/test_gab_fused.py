"""Fused gather→combine→apply kernel (kernels/gab_fused.py, DESIGN.md §14).

Three layers of checks:

  * kernel-level parity: ``gab_fused`` vs the unfused composition (one-hot
    ``segment_reduce`` at the same blocks + the apply/mask tail) over the
    adversarial shapes the engine produces — E=0, an edge block that is
    pure padding, E/V that are not block multiples, Q>1 with sublane
    padding;
  * engine-level bit-identity: all six shipped apps run with
    ``kernel_autotune`` on and must reproduce the unfused one-hot path at
    the autotuner's blocks byte for byte, serial and pipelined;
  * autotuner units: determinism, VMEM feasibility filtering, the static
    (512, 256) never model-beating the pick, stack-size clamping.

A note on float exactness (see DESIGN.md §14): XLA:CPU deletes
``optimization_barrier`` and contextually contracts ``a·x + b·y`` into an
FMA when the apply fuses with the accumulator's producer, so an XLA-traced
affine apply and the in-kernel apply can legitimately differ in the last
ulp for arbitrary coefficients.  Bit-identity is *guaranteed* whenever the
products are exactly representable — min/max applies (no arithmetic) and
power-of-two affine coefficients — so the strict equality tests pin
``damping=0.5``; default-damping runs are asserted at float tolerance.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gab
from repro.kernels.gab_fused import FusedSpec, gab_fused
from repro.roofline import kernel_tune


# ---------------------------------------------------------------------------
# kernel-level parity vs the unfused composition
# ---------------------------------------------------------------------------

def _unfused(spec, src_vals, a, b, dst_local, old, base, num_rows, row_cap,
             blocks):
    """The unfused composition at the same blocks, evaluated eagerly:
    gather expression -> one-hot segment_reduce -> apply -> valid/updated
    mask.  Mirrors core/gab.tile_gather_apply term for term."""
    sv = jnp.asarray(src_vals, jnp.float32)
    contrib = sv
    if a is not None:
        av = jnp.asarray(a, jnp.float32)
        contrib = contrib * (av[:, None] if sv.ndim == 2 else av)
    if b is not None:
        bv = jnp.asarray(b, jnp.float32)
        contrib = contrib + (bv[:, None] if sv.ndim == 2 else bv)
    if spec.add_const is not None:
        contrib = contrib + jnp.float32(spec.add_const)
    accum = gab.segment_reduce(
        contrib, jnp.asarray(dst_local, jnp.int32), row_cap + 1,
        spec.combine, impl="pallas_onehot", blocks=blocks)[:row_cap]
    ov = jnp.asarray(old, jnp.float32)
    if spec.apply == "affine":
        bb = jnp.float32(spec.alpha) * jnp.asarray(base, jnp.float32) \
            if base is not None else jnp.float32(spec.alpha)
        new = bb + jnp.float32(spec.beta) * accum
    elif spec.apply == "min":
        new = jnp.minimum(ov, accum)
    else:
        new = jnp.maximum(ov, accum)
    rows = jnp.arange(row_cap)
    valid = rows < num_rows
    valid = valid[:, None] if new.ndim == 2 else valid
    new = jnp.where(valid, new, ov)
    if spec.update_tol > 0.0:
        upd = jnp.abs(new - ov) > spec.update_tol
    else:
        upd = new != ov
    return np.asarray(new), np.asarray(jnp.logical_and(valid, upd))


def _random_tile(rng, E, row_cap, Q, spec, pad_frac=0.2):
    """Random tile-shaped inputs honoring the sink-row padding convention."""
    shape = (E,) if Q == 1 else (E, Q)
    sv = rng.normal(size=shape).astype(np.float32)
    if spec.combine in ("min", "max"):
        sv = np.abs(sv)         # distances: keep comparable magnitudes
    dst = np.sort(rng.integers(0, row_cap, E)).astype(np.int32)
    npad = int(E * pad_frac)
    if npad:
        dst[E - npad:] = row_cap            # trailing inert padding edges
        sv[E - npad:] = 0.0
    a = (rng.random(E).astype(np.float32) + 0.1) if spec.scale_aux else None
    b = rng.random(E).astype(np.float32) if spec.add_edge else None
    oshape = (row_cap,) if Q == 1 else (row_cap, Q)
    old = np.abs(rng.normal(size=oshape)).astype(np.float32) + 1.0
    base = rng.random(oshape).astype(np.float32) if spec.base_aux else None
    num_rows = max(1, row_cap - 3)
    return sv, a, b, dst, old, base, num_rows


# Power-of-two affine coefficients: products exact in f32, so FMA
# contraction cannot change the rounding — strict equality is well-defined.
SPECS = {
    "sum_affine": FusedSpec(combine="sum", scale_aux="inv", apply="affine",
                            alpha=0.5, beta=0.5, update_tol=1e-8),
    "sum_affine_base": FusedSpec(combine="sum", scale_aux="inv",
                                 apply="affine", alpha=0.25, beta=0.5,
                                 base_aux="seed", update_tol=1e-9),
    "min_edge": FusedSpec(combine="min", add_edge=True, apply="min"),
    "min_const": FusedSpec(combine="min", add_const=1.0, apply="min"),
    "max_plain": FusedSpec(combine="max", apply="max"),
}


@pytest.mark.parametrize("E,row_cap,Q", [
    (777, 130, 3),      # nothing a block multiple
    (513, 257, 5),      # one past a block boundary both axes
    (64, 16, 1),        # far below one block (1-D squeeze path)
    (2000, 300, 8),     # a full sublane of queries
    (5000, 1500, 1),    # many row blocks: most edge blocks are skipped
])
@pytest.mark.parametrize("spec_name", sorted(SPECS))
def test_fused_matches_unfused_composition(E, row_cap, Q, spec_name):
    spec = SPECS[spec_name]
    rng = np.random.default_rng(E * 31 + row_cap + Q)
    sv, a, b, dst, old, base, num_rows = _random_tile(rng, E, row_cap, Q,
                                                      spec)
    blocks = (256, 128)
    new_f, upd_f = gab_fused(
        spec, jnp.asarray(sv), None if a is None else jnp.asarray(a),
        None if b is None else jnp.asarray(b), jnp.asarray(dst),
        jnp.asarray(old), None if base is None else jnp.asarray(base),
        jnp.int32(num_rows), row_cap, block_e=blocks[0], block_r=blocks[1],
        interpret=True)
    new_u, upd_u = _unfused(spec, sv, a, b, dst, old, base, num_rows,
                            row_cap, blocks)
    np.testing.assert_array_equal(np.asarray(new_f), new_u, err_msg=spec_name)
    np.testing.assert_array_equal(np.asarray(upd_f), upd_u, err_msg=spec_name)


@pytest.mark.parametrize("spec_name", ["sum_affine", "min_edge"])
def test_fused_unsorted_dst_matches_unfused(spec_name):
    """The per-row-block edge-block bounds span every edge of the row
    block, so unsorted dst ids (wide bounds) stay exact too."""
    spec = SPECS[spec_name]
    rng = np.random.default_rng(11)
    sv, a, b, dst, old, base, num_rows = _random_tile(rng, 1500, 400, 2,
                                                      spec)
    dst = rng.permutation(dst)
    blocks = (128, 128)
    new_f, upd_f = gab_fused(
        spec, jnp.asarray(sv), None if a is None else jnp.asarray(a),
        None if b is None else jnp.asarray(b), jnp.asarray(dst),
        jnp.asarray(old), None, jnp.int32(num_rows), 400,
        block_e=blocks[0], block_r=blocks[1], interpret=True)
    new_u, upd_u = _unfused(spec, sv, a, b, dst, old, None, num_rows, 400,
                            blocks)
    np.testing.assert_array_equal(np.asarray(new_f), new_u)
    np.testing.assert_array_equal(np.asarray(upd_f), upd_u)


def test_fused_default_damping_within_float_noise():
    """Non-power-of-two affine coefficients: XLA may contract the unfused
    apply into an FMA, so agreement is asserted at float tolerance (the
    accumulation itself is still order-identical)."""
    spec = FusedSpec(combine="sum", scale_aux="inv", apply="affine",
                     alpha=1.0 - 0.85, beta=0.85, update_tol=1e-8)
    rng = np.random.default_rng(3)
    sv, a, b, dst, old, base, num_rows = _random_tile(rng, 900, 200, 4, spec)
    new_f, _ = gab_fused(spec, jnp.asarray(sv), jnp.asarray(a), None,
                         jnp.asarray(dst), jnp.asarray(old), None,
                         jnp.int32(num_rows), 200, interpret=True)
    from repro.kernels.gab_gather import DEFAULT_BLOCK_E, DEFAULT_BLOCK_R
    new_u, _ = _unfused(spec, sv, a, b, dst, old, base, num_rows, 200,
                        (DEFAULT_BLOCK_E, DEFAULT_BLOCK_R))
    np.testing.assert_allclose(np.asarray(new_f), new_u, rtol=1e-6,
                               atol=3e-8)


@pytest.mark.parametrize("spec_name", ["sum_affine", "min_edge", "max_plain"])
def test_fused_empty_edge_list(spec_name):
    """E=0 pads to one all-padding block; every row reduces the identity,
    so affine rows become alpha·base and min/max rows keep old."""
    spec = SPECS[spec_name]
    row_cap, Q = 40, 3
    old = np.abs(np.random.default_rng(0).normal(size=(row_cap, Q))
                 ).astype(np.float32) + 1.0
    a = np.zeros((0,), np.float32) if spec.scale_aux else None
    b = np.zeros((0,), np.float32) if spec.add_edge else None
    new_f, upd_f = gab_fused(
        spec, jnp.zeros((0, Q), jnp.float32),
        None if a is None else jnp.asarray(a),
        None if b is None else jnp.asarray(b),
        jnp.zeros((0,), jnp.int32), jnp.asarray(old), None,
        jnp.int32(row_cap), row_cap, interpret=True)
    if spec.apply in ("min", "max"):
        np.testing.assert_array_equal(np.asarray(new_f), old)
        assert not np.asarray(upd_f).any()
    else:
        want = np.float32(spec.alpha) + np.float32(spec.beta) * np.float32(0)
        np.testing.assert_array_equal(np.asarray(new_f),
                                      np.full_like(old, want))


@pytest.mark.parametrize("spec_name", ["sum_affine_base", "min_const"])
def test_fused_all_padding_edges(spec_name):
    """Every edge routed to the sink row: the accumulator must stay at the
    identity for all real rows (one whole edge block is pure padding)."""
    spec = SPECS[spec_name]
    E, row_cap, Q = 300, 70, 2
    rng = np.random.default_rng(1)
    sv = np.zeros((E, Q), np.float32)
    dst = np.full((E,), row_cap, np.int32)
    old = np.abs(rng.normal(size=(row_cap, Q))).astype(np.float32) + 1.0
    base = rng.random((row_cap, Q)).astype(np.float32)
    a = (rng.random(E).astype(np.float32) if spec.scale_aux else None)
    b = rng.random(E).astype(np.float32) if spec.add_edge else None
    new_f, upd_f = gab_fused(
        spec, jnp.asarray(sv), None if a is None else jnp.asarray(a),
        None if b is None else jnp.asarray(b), jnp.asarray(dst),
        jnp.asarray(old), None if spec.base_aux is None
        else jnp.asarray(base), jnp.int32(row_cap), row_cap, interpret=True)
    new_u, upd_u = _unfused(spec, sv, a, b, dst, old,
                            base if spec.base_aux else None,
                            row_cap, row_cap, (256, 128))
    np.testing.assert_array_equal(np.asarray(new_f), new_u)
    np.testing.assert_array_equal(np.asarray(upd_f), upd_u)


# ---------------------------------------------------------------------------
# engine-level bit-identity with kernel_autotune on
# ---------------------------------------------------------------------------

def _apps():
    from repro.core import apps

    # damping=0.5: affine products exact -> strict equality well-defined
    # (see module docstring); Q spans 1, 3, and a full sublane of 8.
    return [
        ("pagerank", lambda: apps.PageRank(damping=0.5, update_tol=1e-8)),
        ("wcc", lambda: apps.WCC()),
        ("sssp", lambda: apps.SSSP(source=0)),
        ("ppr", lambda: apps.PersonalizedPageRank(
            seeds=(1, 7, 50), damping=0.5)),
        ("msbfs", lambda: apps.MultiSourceBFS(sources=(2, 11, 60))),
        ("landmarks", lambda: apps.LandmarkDistances(
            landmarks=(0, 9, 33, 60, 101, 160, 201, 250))),
    ]


def _run(store, prog, supersteps=10, **cfg_kw):
    from repro.core.engine import EngineConfig, OutOfCoreEngine

    eng = OutOfCoreEngine(store, EngineConfig(num_servers=2, **cfg_kw))
    res = eng.run(prog, max_supersteps=supersteps)
    return np.asarray(res.values), eng


@pytest.mark.parametrize("app_name,mk", _apps())
def test_engine_autotuned_fused_bit_identical(small_store, app_name, mk):
    """kernel_autotune promotes to the fused kernel; the result must be
    byte-for-byte the unfused one-hot path at the autotuner's blocks."""
    store, _, _ = small_store
    v_fused, eng = _run(store, mk(), kernel_autotune=True)
    choice = eng.kernel_choice
    assert choice is not None and choice.block_e >= 128
    v_unfused, _ = _run(store, mk(), seg_impl="pallas_onehot",
                        kernel_blocks=choice.blocks)
    np.testing.assert_array_equal(v_fused, v_unfused, err_msg=app_name)


@pytest.mark.parametrize("app_name,mk", [_apps()[0], _apps()[5]])
def test_engine_autotuned_pipelined_bit_identical(small_store, app_name, mk):
    """Serial and pipelined fused execution agree byte for byte (Q=1 and a
    full Q=8 sublane)."""
    store, _, _ = small_store
    v_serial, _ = _run(store, mk(), kernel_autotune=True,
                       engine_mode="tiled")
    v_pipe, _ = _run(store, mk(), kernel_autotune=True, engine_mode="tiled",
                     pipeline=True)
    np.testing.assert_array_equal(v_serial, v_pipe, err_msg=app_name)


def test_engine_autotuned_default_damping_close(small_store):
    """Default (non-power-of-two) damping: fused vs unfused agree to float
    tolerance — the last-ulp slack is XLA's FMA contraction of the traced
    apply, not an accumulation difference."""
    from repro.core.apps import PersonalizedPageRank

    store, _, _ = small_store
    v_fused, eng = _run(store, PersonalizedPageRank(seeds=(1, 7, 50)),
                        supersteps=20, kernel_autotune=True)
    v_unfused, _ = _run(store, PersonalizedPageRank(seeds=(1, 7, 50)),
                        supersteps=20, seg_impl="pallas_onehot",
                        kernel_blocks=eng.kernel_choice.blocks)
    np.testing.assert_allclose(v_fused, v_unfused, rtol=1e-5, atol=1e-12)


def test_engine_autotune_fallback_without_fused_spec(small_store):
    """A program with no fused form (InDegree) falls back to the one-hot
    kernel under kernel_autotune and still matches the jnp reference."""
    from repro.core.apps import InDegree

    store, _, _ = small_store
    assert InDegree().fused_spec() is None
    v_auto, _ = _run(store, InDegree(), supersteps=3, kernel_autotune=True)
    v_ref, _ = _run(store, InDegree(), supersteps=3)
    np.testing.assert_array_equal(v_auto, v_ref)


def test_engine_explicit_kernel_blocks_override(small_store):
    """cfg.kernel_blocks bypasses the cost model verbatim."""
    from repro.core.apps import PageRank
    from repro.core.engine import EngineConfig, OutOfCoreEngine

    store, _, _ = small_store
    eng = OutOfCoreEngine(store, EngineConfig(
        num_servers=1, kernel_autotune=True, kernel_blocks=(128, 128)))
    impl, blocks, _ = eng.kernel_plan(PageRank())
    assert impl == "pallas_fused" and blocks == (128, 128)
    assert eng.kernel_choice is None          # model never consulted


# ---------------------------------------------------------------------------
# roofline autotuner units
# ---------------------------------------------------------------------------

def test_pick_blocks_deterministic_and_feasible():
    a = kernel_tune.pick_blocks("sum", 1, 4096, 512, bandwidth=100e9)
    b = kernel_tune.pick_blocks("sum", 1, 4096, 512, bandwidth=100e9)
    assert a == b
    assert a.block_e % 128 == 0 and a.block_r % 128 == 0
    assert 1 <= a.stack_size <= 16
    assert a.predicted_s > 0 and a.edges_per_s > 0
    assert a.bound in ("memory", "compute")
    assert kernel_tune.vmem_plan_bytes("sum", 1, a.block_e, a.block_r) \
        <= kernel_tune.vmem_budget()


def test_pick_blocks_never_model_worse_than_static():
    """The static (512, 256) default is always a candidate when feasible,
    so the pick can never predict worse than it."""
    for combine in ("sum", "min"):
        for q in (1, 8, 32):
            for ec, rc in [(4096, 512), (65536, 2048), (512, 128)]:
                pick = kernel_tune.pick_blocks(combine, q, ec, rc,
                                               bandwidth=50e9)
                static = kernel_tune.tile_cost(
                    combine, q, ec, rc, *kernel_tune.STATIC_BLOCKS,
                    bandwidth=50e9)
                feasible = kernel_tune.vmem_plan_bytes(
                    combine, q, *kernel_tune.STATIC_BLOCKS) \
                    <= kernel_tune.vmem_budget()
                if feasible:
                    assert pick.predicted_s <= static.predicted_s, \
                        (combine, q, ec, rc)


def test_pick_blocks_vmem_constrains_minmax_wide_q():
    """min/max plan a [Q, BE, BR] select: wide Q must be pushed to smaller
    edge blocks than the sum monoid at the same shape."""
    s = kernel_tune.pick_blocks("sum", 32, 8192, 1024, bandwidth=100e9)
    m = kernel_tune.pick_blocks("min", 32, 8192, 1024, bandwidth=100e9)
    assert kernel_tune.vmem_plan_bytes("min", 32, m.block_e, m.block_r) \
        <= kernel_tune.vmem_budget()
    assert m.block_e * m.block_r <= s.block_e * s.block_r


def test_pick_blocks_caps_at_tile_shape():
    """Blocks larger than the padded tile only pad — candidates are capped,
    so a tiny tile picks the minimum (128, 128)."""
    c = kernel_tune.pick_blocks("sum", 1, 100, 60, bandwidth=100e9)
    assert c.blocks == (128, 128)


def test_target_chip_unknown_tpu_kind_raises(monkeypatch):
    """The tuner plans with the default device's constants: an unknown TPU
    kind is an error, never the v5e numbers."""
    import jax

    class _Dev:
        platform = "tpu"
        device_kind = "TPU v99"

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Dev()])
    with pytest.raises(KeyError, match="no hardware constants"):
        kernel_tune.target_chip()
    with pytest.raises(KeyError):
        kernel_tune.pick_blocks("sum", 1, 4096, 512, bandwidth=100e9)


def test_stack_size_scales_inverse_with_tile_time():
    assert kernel_tune._stack_size(1e-6) == 16     # tiny tiles: batch hard
    assert kernel_tune._stack_size(1.0) == 1       # huge tiles: no batching


def test_degenerate_vmem_budget_falls_back():
    c = kernel_tune.pick_blocks("min", 64, 4096, 2048, bandwidth=100e9,
                                vmem_bytes=1024)
    assert c.blocks == (128, 128)


# ---------------------------------------------------------------------------
# weighted-edge association regression
# ---------------------------------------------------------------------------

def test_engine_weighted_edges_bit_identical(tmp_path):
    """Regression: on *weighted* graphs the fused path pre-folds the scale
    stream as ``a = inv · ev``, so the unfused gather must group
    ``src · (inv · ev)`` the same way — the historical ``(src · inv) · ev``
    rounds differently whenever ev != 1.0 and broke bit-identity only on
    weighted stores (unweighted ev == 1.0 hides it)."""
    from repro.core import apps
    from repro.graphio import spe, synth
    from repro.graphio.formats import TileStore

    store = TileStore(str(tmp_path / "wstore"))
    spe.preprocess(
        lambda: synth.rmat_edges(600, 4000, seed=3, weighted=True),
        600, store, tile_size=128, weighted=True)
    for mk in (lambda: apps.PageRank(damping=0.5, update_tol=1e-8),
               lambda: apps.SSSP(source=0),
               lambda: apps.PersonalizedPageRank(seeds=(1, 7), damping=0.5)):
        v_fused, eng = _run(store, mk(), kernel_autotune=True)
        v_unfused, _ = _run(store, mk(), seg_impl="pallas_onehot",
                            kernel_blocks=eng.kernel_choice.blocks)
        np.testing.assert_array_equal(v_fused, v_unfused,
                                      err_msg=type(mk()).__name__)
