"""Pallas triangle-intersection kernel family vs jnp oracles.

Interpret-mode parity for every member — scalar count, per-node
(count + arm) and support (count + arm + closure) — across bucket
widths, including all-padding tiles, empty buckets and explicit tile
overrides (the autotuner's hook).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container image has no hypothesis; use the local stub
    from _hypothesis_stub import given, settings, st

from repro.core import bucketize_edges, count_triangles, gather_panels, preprocess
from repro.core.count import add_elements, add_windows, gather_elements, gather_windows
from repro.kernels.triangle_count import (
    intersect_count_pallas,
    intersect_per_node_pallas,
    intersect_support_pallas,
)
from repro.kernels.triangle_count.ref import (
    intersect_count_ref,
    intersect_per_node_ref,
    intersect_support_ref,
)


def random_panels(rng, b, l, dtype):
    rows = []
    for _ in range(b):
        n = int(rng.integers(0, l + 1))
        vals = np.sort(rng.choice(4 * l + 8, size=n, replace=False))
        rows.append(np.concatenate([vals, -np.ones(l - n)]).astype(dtype))
    return jnp.asarray(np.stack(rows))


def assert_family_matches_ref(a, c):
    """All three kernels agree with their oracles on one panel pair."""
    ref_cnt, ref_arm, ref_clo = intersect_support_ref(a, c)
    np.testing.assert_array_equal(
        np.asarray(intersect_count_pallas(a, c)), np.asarray(ref_cnt)
    )
    cnt, arm = intersect_per_node_pallas(a, c)
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(ref_cnt))
    np.testing.assert_array_equal(np.asarray(arm), np.asarray(ref_arm))
    cnt, arm, clo = intersect_support_pallas(a, c)
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(ref_cnt))
    np.testing.assert_array_equal(np.asarray(arm), np.asarray(ref_arm))
    np.testing.assert_array_equal(np.asarray(clo), np.asarray(ref_clo))


@pytest.mark.parametrize("dtype", [np.int32, np.int16])
@pytest.mark.parametrize(
    "b,lu,lv",
    [(1, 8, 8), (5, 16, 64), (32, 128, 128), (9, 256, 1024), (2, 2048, 128), (64, 64, 32)],
)
def test_kernel_matches_ref(b, lu, lv, dtype, rng):
    a = random_panels(rng, b, lu, dtype)
    c = random_panels(rng, b, lv, dtype)
    ref = intersect_count_ref(a, c)
    got = intersect_count_pallas(a, c)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


@pytest.mark.parametrize(
    "b,lu,lv",
    [(1, 8, 8), (5, 16, 64), (32, 128, 128), (9, 256, 1024), (2, 2048, 128), (64, 64, 32)],
)
def test_attribution_kernels_match_ref(b, lu, lv, rng):
    """Per-node and support kernels: every axis reduction matches the
    oracle across bucket widths (incl. v-tiling past TLv=512)."""
    a = random_panels(rng, b, lu, np.int32)
    c = random_panels(rng, b, lv, np.int32)
    assert_family_matches_ref(a, c)


def test_arm_closure_consistency(rng):
    """count == Σ arm == Σ closure row-wise — the 3-edge billing identity."""
    a = random_panels(rng, 17, 96, np.int32)
    c = random_panels(rng, 17, 160, np.int32)
    cnt, arm, clo = intersect_support_pallas(a, c)
    np.testing.assert_array_equal(
        np.asarray(cnt), np.asarray(arm).sum(axis=1)
    )
    np.testing.assert_array_equal(
        np.asarray(cnt), np.asarray(clo).sum(axis=1)
    )


def test_explicit_tile_override_parity(rng):
    """tiles=(TB, TLv) overrides (the autotuner hook) never change results,
    and a tile the TPU compiler would refuse is refused here, not clamped."""
    a = random_panels(rng, 23, 64, np.int32)
    c = random_panels(rng, 23, 640, np.int32)
    ref_cnt, ref_arm, ref_clo = intersect_support_ref(a, c)
    for tiles in [(128, 128), (128, 256), (256, 512), (256, 4096)]:
        cnt, arm, clo = intersect_support_pallas(a, c, tiles=tiles)
        np.testing.assert_array_equal(np.asarray(cnt), np.asarray(ref_cnt)), tiles
        np.testing.assert_array_equal(np.asarray(arm), np.asarray(ref_arm))
        np.testing.assert_array_equal(np.asarray(clo), np.asarray(ref_clo))
        np.testing.assert_array_equal(
            np.asarray(intersect_count_pallas(a, c, tiles=tiles)),
            np.asarray(ref_cnt),
        )
    for bad in [(1, 128), (8, 256), (64, 512), (128, 200)]:
        with pytest.raises(ValueError, match="tiles="):
            intersect_count_pallas(a, c, tiles=bad)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 17), st.sampled_from([8, 32, 96]), st.sampled_from([8, 48, 128]),
       st.integers(0, 2**31 - 1))
def test_kernel_property(b, lu, lv, seed):
    rng = np.random.default_rng(seed)
    a = random_panels(rng, b, lu, np.int32)
    c = random_panels(rng, b, lv, np.int32)
    assert_family_matches_ref(a, c)


def test_degree_skew_bucketing(small_graphs):
    """Adversarial skew: star + clique mix exercises multiple buckets."""
    import jax.numpy as jnp

    e = small_graphs["kron"]
    csr = preprocess(jnp.asarray(e), n_nodes=int(e.max()) + 1)
    buckets = bucketize_edges(csr)
    assert sum(len(v) for v in buckets.values()) == csr.col.shape[0]
    total = 0
    total_arm = 0
    for width, idx in buckets.items():
        a, b, al, bl = gather_panels(csr, jnp.asarray(idx), width)
        total += int(np.asarray(intersect_count_pallas(a, b)).sum())
        _, arm = intersect_per_node_pallas(a, b)
        total_arm += int(np.asarray(arm).sum())
    assert total == count_triangles(e)
    assert total_arm == total  # each hit has exactly one arm slot


def _slices(col, base, length, width):
    out = np.full((len(base), width), -1, np.int32)
    for i, (s, n) in enumerate(zip(base, length)):
        out[i, :n] = col[s : s + n]
    return out


@pytest.mark.parametrize("width", [16, 64, 256, 1024, 4096, 16384])
@pytest.mark.parametrize("form", [gather_elements, gather_windows],
                         ids=["elements", "windows"])
def test_panel_gather_matches_slices(form, width, rng):
    """Both panel gathers return ``col[base : base + length]``, −1 padded:
    every lane shift 0..127 of a 128-lane row at full and random lengths,
    and every base within 130 slots of a window from ``col``'s end."""
    m = 2 * width + 3 * 128 + 77  # not a multiple of 128
    col = np.sort(rng.integers(0, 1 << 30, m)).astype(np.int32)
    shifted = 128 + np.arange(128)
    tail = np.arange(m - width - 130, m + 1)
    base = np.concatenate([shifted, shifted, tail, [0]])
    room = m - base
    length = np.concatenate([
        np.full(128, width), rng.integers(0, width + 1, 128),
        np.full(tail.size, width), [0],
    ])
    length = np.minimum(length, room)
    got = jax.jit(form, static_argnames="width")(
        jnp.asarray(col), jnp.asarray(base, jnp.int32), jnp.asarray(length, jnp.int32),
        width=width)
    np.testing.assert_array_equal(np.asarray(got), _slices(col, base, length, width))


def test_window_gather_matches_element_gather_on_graph(small_graphs):
    """The TPU's panel gather and the CPU's agree on every bucket of a
    skewed graph's real (base, length) pairs."""
    e = small_graphs["kron"]
    csr = preprocess(jnp.asarray(e), n_nodes=int(e.max()) + 1)
    for width, idx in bucketize_edges(csr).items():
        for ends in (csr.src, csr.col):
            node = ends[jnp.asarray(idx)]
            args = (csr.col, csr.row_offsets[node], csr.out_degree[node])
            np.testing.assert_array_equal(
                np.asarray(gather_windows(*args, width)),
                np.asarray(gather_elements(*args, width)))


def _added(m, base, rows):
    out = np.zeros(m, np.int64)
    for s, row in zip(base, rows):
        n = min(len(row), m - s)
        out[s : s + n] += row[:n]
    return out


@pytest.mark.parametrize("width", [16, 64, 256, 1024])
@pytest.mark.parametrize("form", [add_elements, add_windows], ids=["elements", "windows"])
def test_window_add_matches_slices(form, width, rng):
    """Both support-scatter adds put ``rows[i]`` at ``out[base[i]:]``,
    summing where windows overlap: every lane shift 0..127, repeated
    bases, and windows that run past ``out``'s end with zero lanes
    there."""
    m = 2 * width + 3 * 128 + 77  # not a multiple of 128
    shifted = 128 + np.arange(128)
    tail = np.arange(m - width - 130, m + 1)
    base = np.concatenate([shifted, shifted, tail, [0, 0]])
    rows = rng.integers(0, 1000, (base.size, width)).astype(np.int32)
    room = m - base
    rows[np.arange(width)[None, :] >= room[:, None]] = 0
    start = rng.integers(0, 50, m).astype(np.int32)
    got = jax.jit(form)(jnp.asarray(start), jnp.asarray(base, jnp.int32), jnp.asarray(rows))
    np.testing.assert_array_equal(np.asarray(got), start + _added(m, base, rows))


def test_window_add_matches_element_add_on_graph(small_graphs, rng):
    """The TPU's support-scatter add and the CPU's agree on every bucket
    of a skewed graph's real row offsets, at the real edge count."""
    e = small_graphs["kron"]
    csr = preprocess(jnp.asarray(e), n_nodes=int(e.max()) + 1)
    m = int(csr.col.shape[0])
    for width, idx in bucketize_edges(csr).items():
        for ends in (csr.src, csr.col):
            node = ends[jnp.asarray(idx)]
            lane = np.arange(width)[None, :]
            rows = rng.integers(0, 100, (node.shape[0], width)).astype(np.int32)
            rows[lane >= np.asarray(csr.out_degree[node])[:, None]] = 0
            args = (jnp.zeros((m,), jnp.int32), csr.row_offsets[node], jnp.asarray(rows))
            np.testing.assert_array_equal(
                np.asarray(add_windows(*args)), np.asarray(add_elements(*args)))


def test_empty_rows():
    """All-padding tiles: every kernel sees only −1 and yields zeros."""
    a = jnp.full((4, 16), -1, jnp.int32)
    b = jnp.full((4, 8), -1, jnp.int32)
    assert (np.asarray(intersect_count_pallas(a, b)) == 0).all()
    cnt, arm = intersect_per_node_pallas(a, b)
    assert (np.asarray(cnt) == 0).all() and (np.asarray(arm) == 0).all()
    cnt, arm, clo = intersect_support_pallas(a, b)
    assert (np.asarray(cnt) == 0).all()
    assert (np.asarray(arm) == 0).all() and (np.asarray(clo) == 0).all()
