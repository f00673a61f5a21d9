"""The Pallas kernels compile for a TPU v5e chip, checked without one.

The TPU compiler is installed with jaxlib, and it compiles for a chip that
is described (``v5e:2x2``) rather than attached.  That catches what
interpret mode cannot: Mosaic's layout and tiling rules, and the VMEM
limit.  Shapes are one real tile: 262,144 edges × 65,536 rows.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, so describing it while pytest
collects would let one xdist worker take the library and leave the others
collecting different tests.  All compiles stay in this one file, in the
process that described the topology.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import gab_fused as gf
from repro.kernels import gab_gather as gg
from repro.roofline import hw, kernel_tune

EDGES, ROWS = 262_144, 65_536


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep it out of the cache entirely.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _arr(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


SPECS = {
    "sum_affine": gf.FusedSpec(combine="sum", scale_aux="inv",
                               apply="affine", alpha=0.15, beta=0.85,
                               update_tol=1e-9),
    "min": gf.FusedSpec(combine="min", add_edge=True, apply="min"),
}


def _compile_fused(spec, q, blocks, sharding):
    shape_e = (EDGES,) if q == 1 else (EDGES, q)
    shape_r = (ROWS,) if q == 1 else (ROWS, q)
    f32, i32 = jnp.float32, jnp.int32
    edge = _arr((EDGES,), f32, sharding)
    return gf.gab_fused.lower(
        spec, _arr(shape_e, f32, sharding),
        edge if spec.scale_aux else None,
        edge if spec.add_edge else None,
        _arr((EDGES,), i32, sharding), _arr(shape_r, f32, sharding), None,
        _arr((), i32, sharding), ROWS,
        block_e=blocks[0], block_r=blocks[1], interpret=False).compile()


@pytest.mark.parametrize("combine", ["sum", "min"])
def test_segment_reduce_compiles_for_v5e(one_chip, combine):
    compiled = gg.segment_reduce_pallas.lower(
        _arr((EDGES,), jnp.float32, one_chip),
        _arr((EDGES,), jnp.int32, one_chip), ROWS + 1, combine=combine,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("spec_name", sorted(SPECS))
def test_fused_default_blocks_compile_for_v5e(one_chip, spec_name):
    compiled = _compile_fused(SPECS[spec_name], 1,
                              (gf.DEFAULT_BLOCK_E, gf.DEFAULT_BLOCK_R),
                              one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("spec_name", sorted(SPECS))
def test_fused_autotuned_blocks_compile_for_v5e(one_chip, spec_name):
    """The tuner's pick for Q=8 at this tile compiles within the chip's
    VMEM: the tuner's budget is one the compiler accepts."""
    spec = SPECS[spec_name]
    choice = kernel_tune.pick_blocks(spec.combine, 8, EDGES, ROWS,
                                     bandwidth=hw.chip(hw.V5E).hbm_bw)
    compiled = _compile_fused(spec, 8, choice.blocks, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_min_block_beyond_vmem_is_refused(one_chip):
    """A (4096, 2048) min block selects over [8, 4096, 2048] f32 (256 MiB):
    the compiler refuses it for VMEM, and the tuner never plans it."""
    assert (kernel_tune.vmem_plan_bytes("min", 8, 4096, 2048)
            > kernel_tune.vmem_budget())
    with pytest.raises(Exception, match="(?i)vmem|resource"):
        _compile_fused(SPECS["min"], 8, (4096, 2048), one_chip)


def test_resident_superstep_keeps_the_stacks_in_place(one_chip):
    """A server's resident superstep at graph500-22's shapes (1,899 tiles
    of 220,800 slots, 2,396,020 vertices) compiles as one program named
    for the tile stack, and scans the stacks where they lie: its
    temporaries are a small share of the 5 GB of arguments."""
    from repro.core import gab
    from repro.core.apps import PageRank
    from repro.core.engine import resident_vertex_bytes

    tiles, slots, nv = 1_899, 220_800, 2_396_020
    f32, i32 = jnp.float32, jnp.int32
    stk = {"src": _arr((tiles, slots), i32, one_chip),
           "dst_local": _arr((tiles, slots), i32, one_chip),
           "val": _arr((tiles, slots), f32, one_chip),
           "row_start": _arr((tiles,), i32, one_chip),
           "num_rows": _arr((tiles,), i32, one_chip)}
    compiled = gab._jit_run_tile_stack.lower(
        PageRank(update_tol=1e-9), _arr((nv,), f32, one_chip),
        {"inv_out_degree": _arr((nv,), f32, one_chip)}, stk, ROWS, "jnp",
        None).compile()
    assert "tile_stack" in compiled.as_text().splitlines()[0]
    mem = compiled.memory_analysis()
    stacks = tiles * slots * 12 + tiles * 2 * 4
    assert mem.argument_size_in_bytes > stacks
    assert mem.temp_size_in_bytes < mem.argument_size_in_bytes / 100
    # the fit test's vertex bytes hold what the program adds to the stacks
    values = np.empty(nv, np.float32)
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - stacks)
    assert resident_vertex_bytes(nv, ROWS, slots, values,
                                 {"inv_out_degree": values}) >= held
