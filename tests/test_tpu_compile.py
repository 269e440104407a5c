"""Ahead-of-time compiles for a described TPU v5e chip — no chip needed.

The TPU compiler is installed with jax; it compiles for a chip that is
described (``v5e:2x2``) and not attached, and refuses what the chip
would refuse: block shapes off the (8, 128) tiling, VMEM overruns, and
programs that do not fit the device.  Interpret mode checks none of
that, so these compiles guard the Pallas kernel family at every bucket
width the engine plans, one autotuner candidate per bucket, and the
wedge chunk kernels at the scale-21 shapes of the main path.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports every test file.  A compile that passes here is not a
chip run.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.engine import (
    DEFAULT_WIDTHS,
    chunk_count_kernel,
    chunk_per_node_kernel,
    support_scatter,
)
from repro.core.count import gather_panels_arrays
from repro.core.tuning import candidate_tiles
from repro.kernels.triangle_count.triangle_count import (
    KINDS,
    _pick_tiles,
    _run,
    check_tiles,
)

WEDGE_BUDGET = 1 << 24            # the main path's per-launch budget
WIDTHS = (*DEFAULT_WIDTHS, DEFAULT_WIDTHS[-1] * 4)  # + the first ×4 rung
# Kronecker scale 21, edge factor 16: nodes, directed-edge slots, chunk edges
N_NODES, M_DIR, CHUNK_EDGES, N_STEPS = 1 << 21, 1 << 25, 1 << 20, 11


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    # a compile for a described chip is written to an enabled persistent
    # cache but cannot be read back without the chip
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _compile_kernel(one_chip, kind, rows, width, tiles):
    a = _sds((rows, width), one_chip)
    resolved = check_tiles(tiles, rows, width, width, kind)
    return _run.lower(a, a, kind=kind, tiles=resolved, interpret=False).compile()


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("kind", KINDS)
def test_pallas_kernel_compiles_native(one_chip, kind, width):
    """Every family member at every bucket width, with the static tiles,
    at the row count one budget-sized chunk of that bucket holds."""
    rows = WEDGE_BUDGET // width
    compiled = _compile_kernel(one_chip, kind, rows, width, _pick_tiles(rows, width, width))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("width", WIDTHS)
def test_autotuner_candidate_compiles(one_chip, width):
    """The widest-edge candidate the tuner would time (the pick furthest
    from the heuristic), compiled for the VMEM-heaviest member."""
    rows = WEDGE_BUDGET // width
    cand = max(candidate_tiles(rows, width, width), key=lambda c: (c.block_edges, c.tlv))
    compiled = _compile_kernel(one_chip, "support", rows, width, cand.tiles)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", [chunk_count_kernel, chunk_per_node_kernel],
                         ids=["count", "per_node"])
def test_wedge_chunk_kernel_compiles_at_scale21(one_chip, kernel):
    """The wedge schedule at the scale-21 main-path shapes fits the chip
    with room to spare (a few GB of its 16 GB)."""
    args = (
        _sds((CHUNK_EDGES,), one_chip), _sds((CHUNK_EDGES,), one_chip),
        _sds((N_NODES + 1,), one_chip), _sds((M_DIR,), one_chip),
        _sds((N_NODES,), one_chip),
    )
    compiled = kernel.lower(*args, wedge_budget=WEDGE_BUDGET, n_steps=N_STEPS).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (2 << 30), mem


@pytest.mark.parametrize("width", (256, 1024, 4096))
def test_panel_gather_compiles_at_scale21(one_chip, width):
    """The XLA panel gather feeding the kernels, one budget-sized chunk,
    in its TPU form: whole 128-lane rows, not single elements."""
    rows = WEDGE_BUDGET // width
    lowered = gather_panels_arrays.lower(
        _sds((N_NODES + 1,), one_chip), _sds((M_DIR,), one_chip),
        _sds((N_NODES,), one_chip), _sds((rows,), one_chip),
        _sds((rows,), one_chip), width=width,
    )
    assert "slice_sizes = array<i64: 1, 128>" in lowered.as_text()
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (2 << 30), mem


@pytest.mark.parametrize("width", (16, 64, 256))
def test_support_scatter_compiles_at_kron16(one_chip, width):
    """The per-edge support scatter of one budget-sized chunk into a
    Kronecker-16 graph's 909,785 edges, in its TPU form: the arm and
    closure rows added as whole 128-lane rows, not single elements."""
    rows, n_nodes, m_out = min(WEDGE_BUDGET // width, 1 << 16), 1 << 16, 909_785
    lowered = support_scatter.lower(
        _sds((rows,), one_chip), _sds((rows,), one_chip), _sds((rows,), one_chip),
        _sds((n_nodes + 1,), one_chip), _sds((rows,), one_chip),
        _sds((rows, width), one_chip), _sds((rows, width), one_chip), m_out=m_out,
    )
    assert "update_window_dims = [2]" in lowered.as_text()
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (1 << 30), mem
