"""Pallas kernels vs pure-jnp oracles (interpret mode — CPU container)."""
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # deterministic fallback, see _hypothesis_compat
    from _hypothesis_compat import given, settings, strategies as st

from repro.kernels import ops, ref


@pytest.mark.parametrize("E,R", [(64, 16), (1000, 300), (4096, 512),
                                 (777, 1), (128, 1024)])
@pytest.mark.parametrize("combine", ["sum", "min", "max"])
def test_segment_reduce_shapes(E, R, combine):
    rng = np.random.default_rng(E + R)
    c = jnp.asarray(rng.normal(size=E).astype(np.float32))
    d = jnp.asarray(np.sort(rng.integers(0, R, E)).astype(np.int32))
    kfn = getattr(ops, f"segment_{combine}")
    rfn = getattr(ref, f"segment_{combine}")
    got, want = kfn(c, d, R), rfn(c, d, R)
    fin = jnp.isfinite(want)
    assert bool(jnp.all(jnp.isfinite(got) == fin))
    np.testing.assert_allclose(np.asarray(got)[np.asarray(fin)],
                               np.asarray(want)[np.asarray(fin)],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("be,br", [(128, 128), (256, 512), (512, 256)])
def test_segment_sum_block_shapes(be, br):
    rng = np.random.default_rng(be)
    E, R = 2000, 700
    c = jnp.asarray(rng.normal(size=E).astype(np.float32))
    d = jnp.asarray(np.sort(rng.integers(0, R, E)).astype(np.int32))
    got = ops.segment_sum(c, d, R, block_e=be, block_r=br)
    want = ref.segment_sum(c, d, R)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@given(st.integers(1, 2000), st.integers(1, 400), st.integers(0, 10**6))
@settings(max_examples=25, deadline=None)
def test_segment_sum_property(E, R, seed):
    rng = np.random.default_rng(seed)
    c = jnp.asarray(rng.normal(size=E).astype(np.float32))
    d = jnp.asarray(np.sort(rng.integers(0, R, E)).astype(np.int32))
    got = ops.segment_sum(c, d, R)
    want = ref.segment_sum(c, d, R)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    # conservation: total mass preserved
    assert abs(float(jnp.sum(got)) - float(jnp.sum(c))) < 1e-2


def test_segment_sum_unsorted_ids():
    """The one-hot kernel must not require sorted dst ids."""
    rng = np.random.default_rng(0)
    E, R = 1500, 200
    c = jnp.asarray(rng.normal(size=E).astype(np.float32))
    d = jnp.asarray(rng.integers(0, R, E).astype(np.int32))  # unsorted
    np.testing.assert_allclose(np.asarray(ops.segment_sum(c, d, R)),
                               np.asarray(ref.segment_sum(c, d, R)),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# multi-query (contrib [E, Q]) parity — the one-hot matvec becomes a GEMM
# ---------------------------------------------------------------------------

def _per_column_ref(combine, c2, d, R):
    rfn = getattr(ref, f"segment_{combine}")
    return np.stack([np.asarray(rfn(c2[:, q], d, R))
                     for q in range(c2.shape[1])], axis=1)


@pytest.mark.parametrize("E,R,Q", [
    (777, 130, 3),      # nothing a multiple of (BE, BR)
    (1000, 300, 5),
    (64, 16, 2),        # far below one block in both axes
    (513, 257, 4),      # one past the block boundary on both axes
    (3, 1, 7),          # degenerate row count
])
@pytest.mark.parametrize("combine", ["sum", "min", "max"])
def test_segment_reduce_multi_query_parity(E, R, Q, combine):
    """Q>1 parity vs the per-column jnp oracle for every monoid, with
    shapes that are not multiples of the (BE, BR) kernel blocks."""
    rng = np.random.default_rng(E * 7 + R + Q)
    c2 = jnp.asarray(rng.normal(size=(E, Q)).astype(np.float32))
    d = jnp.asarray(np.sort(rng.integers(0, R, E)).astype(np.int32))
    got = np.asarray(getattr(ops, f"segment_{combine}")(c2, d, R))
    want = _per_column_ref(combine, c2, d, R)
    assert got.shape == (R, Q)
    fin = np.isfinite(want)
    assert np.array_equal(np.isfinite(got), fin)
    np.testing.assert_allclose(got[fin], want[fin], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("combine", ["sum", "min", "max"])
@pytest.mark.parametrize("Q", [1, 4])
def test_segment_reduce_all_padding_edge_block(combine, Q):
    """The engine's inert-padding convention: every edge routed to the
    sink (out-of-range) row — one-hot hits no lane, so each output row
    must be the monoid identity.  Exercises an edge block made entirely
    of padding (plus kernel-side padding of the partial block)."""
    from repro.kernels.gab_gather import _IDENTITY

    E, R = 200, 70
    rng = np.random.default_rng(0)
    shape = (E,) if Q == 1 else (E, Q)
    c = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    d = jnp.full((E,), R, dtype=jnp.int32)       # all edges -> sink row R
    got = np.asarray(getattr(ops, f"segment_{combine}")(c, d, R + 1))
    # rows [0, R) saw no edge at all; row R collected everything
    body = got[:R]
    assert np.all(body == np.float32(_IDENTITY[combine])), combine


@pytest.mark.parametrize("combine", ["sum", "min", "max"])
def test_segment_reduce_empty_edge_list(combine):
    """E=0: the kernel pads up to one full block of pure padding; output
    must be all-identity (sum collapses to 0 everywhere)."""
    from repro.kernels.gab_gather import _IDENTITY, segment_reduce_pallas

    c = jnp.zeros((0, 3), dtype=jnp.float32)
    d = jnp.zeros((0,), dtype=jnp.int32)
    got = np.asarray(segment_reduce_pallas(c, d, 40, combine=combine,
                                           interpret=True))
    assert got.shape == (40, 3)
    assert np.all(got == np.float32(_IDENTITY[combine]))


def test_segment_sum_q1_column_matches_1d():
    """A [E, 1] batch must reproduce the 1-D kernel result bit-for-bit —
    the invariant the engine's batched-vs-solo differential relies on."""
    rng = np.random.default_rng(5)
    E, R = 900, 250
    c = jnp.asarray(rng.normal(size=E).astype(np.float32))
    d = jnp.asarray(np.sort(rng.integers(0, R, E)).astype(np.int32))
    one = np.asarray(ops.segment_sum(c, d, R))
    col = np.asarray(ops.segment_sum(c[:, None], d, R))[:, 0]
    np.testing.assert_array_equal(one, col)


def test_gab_engine_with_pallas_segsum(small_store, nx_pagerank):
    """End-to-end: PageRank through the engine using the Pallas kernel path."""
    from repro.core.apps import PageRank
    from repro.core.engine import EngineConfig, OutOfCoreEngine

    store, plan, _ = small_store
    eng = OutOfCoreEngine(store, EngineConfig(
        num_servers=2, seg_impl="pallas_onehot", max_supersteps=60))
    res = eng.run(PageRank(update_tol=1e-8))
    ours = res.values / res.values.sum()
    assert np.abs(ours - nx_pagerank).max() < 1e-5


@pytest.mark.parametrize("combine", ["sum", "min", "max"])
def test_segment_reduce_integer_exact_above_2p24(combine):
    """Regression: wide-integer contributions must keep integer exactness.

    The Pallas path casts to f32, which cannot represent odd integers
    above 2**24 — the gather wrapper now routes >=32-bit integer inputs to
    the exact jnp reference instead of silently rounding."""
    big = 1 << 24
    c = jnp.asarray([big - 1, big, big + 1, big + 3, 1, 2], dtype=jnp.int32)
    d = jnp.asarray([0, 0, 1, 1, 2, 2], dtype=jnp.int32)
    got = np.asarray(getattr(ops, f"segment_{combine}")(c, d, 3))
    want = np.asarray(getattr(ref, f"segment_{combine}")(c, d, 3))
    assert got.dtype == want.dtype and np.issubdtype(got.dtype, np.integer)
    np.testing.assert_array_equal(got, want)
    if combine == "sum":
        # the f32 path would have produced 2**25 + 3 -> rounded
        assert got[1] == 2 * big + 4


def test_segment_sum_int32_many_terms_exact():
    """A sum that only crosses 2**24 through accumulation (every term is
    small) must still be exact — the guard keys on dtype, not magnitude,
    because the kernel cannot know the reduction total in advance."""
    E = 4096
    c = jnp.full((E,), 8193, dtype=jnp.int32)       # total = 8193*4096 > 2^25
    d = jnp.zeros((E,), dtype=jnp.int32)
    got = np.asarray(ops.segment_sum(c, d, 1))
    assert int(got[0]) == 8193 * E


@pytest.mark.parametrize("Q", [1, 3, 5, 8])
@pytest.mark.parametrize("combine", ["sum", "min", "max"])
def test_segment_reduce_sublane_q_padding(Q, combine):
    """Regression: Q is padded to a full sublane multiple inside the
    wrapper (raw q as the BlockSpec sublane dim miscompiles on real TPUs)
    and sliced back on return — results must match the per-column oracle
    for every Q in and at the sublane boundary."""
    rng = np.random.default_rng(Q * 11 + len(combine))
    E, R = 513, 130
    shape = (E,) if Q == 1 else (E, Q)
    c = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    d = jnp.asarray(rng.integers(0, R, E).astype(np.int32))
    got = np.asarray(getattr(ops, f"segment_{combine}")(c, d, R))
    want_2d = _per_column_ref(combine, c if c.ndim == 2 else c[:, None],
                              d, R)
    want = want_2d[:, 0] if Q == 1 else want_2d
    assert got.shape == ((R,) if Q == 1 else (R, Q))
    fin = np.isfinite(want)
    assert np.array_equal(np.isfinite(got), fin)
    np.testing.assert_allclose(got[fin], want[fin], rtol=1e-5, atol=1e-5)
