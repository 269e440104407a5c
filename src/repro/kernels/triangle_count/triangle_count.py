"""Pallas TPU kernel family: bucketed sorted-set intersection.

This is the TPU re-blocking of the paper's ``CountTriangles`` CUDA kernel
(§III-C).  The CUDA version runs one serial two-pointer merge per thread;
here each *lane* of the 8×128 VPU owns one edge, exactly the paper's
one-thread-per-edge mapping.  Panels arrive edge-major from the caller

    a : (B, Lu)   out-neighbors of the u endpoints   (−1 padded)
    b : (B, Lv)   out-neighbors of the v endpoints   (−1 padded)

and are transposed so the edge axis is the lane axis.  A grid step holds
an ``(TLu, TB)`` tile of ``a`` and a ``(TLv, TB)`` tile of ``b`` in VMEM
and walks the u-slots eight at a time: each u-slot row ``(1, TB)`` is
broadcast down the sublanes and compared with the whole ``b`` tile, so
every compare is a full-width VPU op and no 3-D equality cube is ever
materialized.

The family shares that one equality tile and differs only in which
reductions leave the kernel:

``intersect_count_pallas``
    ``Σ_{j,k} eq`` per edge — the scalar per-edge match count.
``intersect_per_node_pallas``
    adds the *arm* attribution ``Σ_k eq`` (one slot per u-neighbor):
    how many triangles each wedge arm ``(u, w)`` closes.
``intersect_support_pallas``
    adds the *closure* attribution ``Σ_j eq`` (one slot per v-neighbor),
    so every hit can be billed to all three directed edges of its
    triangle — the per-edge support scatter k-truss peels on.

Tiling rules (what the v5e compiler accepts, checked by
``tests/test_tpu_compile.py``): ``TB`` — edges per grid step, the lane
axis — is a multiple of 128; ``TLv`` is a multiple of 128 or the whole
(8-padded) v width; ``TLu`` is derived (the whole u width up to
:data:`TLU_MAX`, else :data:`TLU_MAX`-row tiles); and the blocks of one
grid step, double-buffered, fit :data:`VMEM_BUDGET`.  :func:`_pick_tiles`
only produces such tiles, :mod:`repro.core.tuning` only proposes them,
and an explicit ``tiles=`` override that breaks them raises
``ValueError``.  Edge, u and v axes are padded with non-matching
sentinels to whole tiles, so any bucket width the engine plans —
including its ×4 ladder extension past 4096 — compiles.

Grid order is ``(edge block, u tile, v tile)``.  The count block is
revisited across both inner axes and the arm block across the v axis
(consecutive revisits: standard accumulation); the closure output
carries one partial per u tile, summed by XLA after the call.

Off-TPU the kernels run in Pallas' interpret mode — the only backend on
which they do; on a TPU they always compile natively.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "intersect_count_pallas",
    "intersect_per_node_pallas",
    "intersect_support_pallas",
    "check_tiles",
    "KINDS",
    "LANES",
    "TLU_MAX",
    "VMEM_BUDGET",
]

KINDS = ("count", "per_node", "support")

LANES = 128          # VPU lane width: the edge (TB) axis tiles in 128s
SUBLANES = 8         # u-slots are walked one 8-row sublane group at a time
TLU_MAX = 2048       # u-side tile height; wider u panels are tiled
TLV_MAX = 512        # v-side tile height picked by the heuristic
TB_MAX = 2048        # edges per grid step picked by the heuristic
EQ_TILE_MAX = 1 << 16  # TLv·TB elements of one equality tile (64 vregs)
# bytes of double-buffered blocks + scratch per grid step: under the 16 MiB
# scoped-VMEM default of a v5e TensorCore, with room for compiler temps
VMEM_BUDGET = 12 << 20


def _round_up(x: int, m: int) -> int:
    return -(-int(x) // m) * m


def _u_tile(lu: int) -> int:
    """TLu: the whole 8-padded u width, or TLU_MAX-row tiles past it."""
    return min(_round_up(max(lu, 1), SUBLANES), TLU_MAX)


def _vmem_bytes(kind: str, tb: int, tlu: int, tlv: int) -> int:
    """VMEM one grid step holds: double-buffered blocks + the accumulator."""
    elems = 2 * (tlu + tlv) * tb          # a and b tiles
    elems += 2 * SUBLANES * tb            # count row (pads to 8 sublanes)
    if kind != "count":
        elems += 2 * tlu * tb             # arm block
    if kind == "support":
        elems += 2 * tlv * tb             # closure block
    elems += 2 * tlv * tb                 # accumulator scratch + eq temp
    return 4 * elems


def check_tiles(tiles, n_edges: int, lu: int, lv: int, kind: str = "support"):
    """Validate a ``(TB, TLv)`` pick; return the resolved ``(TB, TLu, TLv)``.

    A ``TB`` past the (128-padded) edge count or a ``TLv`` past the
    (8-padded) v width means "the whole axis".  Anything the v5e
    compiler would refuse — ``TB`` not a positive multiple of 128,
    ``TLv`` neither a multiple of 128 nor the whole width, or blocks
    past :data:`VMEM_BUDGET` — raises ``ValueError``; nothing is clamped
    into a tile that cannot compile.
    """
    tb, tlv = int(tiles[0]), int(tiles[1])
    if tb < LANES or tb % LANES:
        raise ValueError(
            f"tiles={tuple(tiles)}: TB={tb} edges per step must be a positive "
            f"multiple of {LANES} (the edge axis is the TPU lane axis)"
        )
    lv_full = _round_up(max(lv, 1), SUBLANES)
    if tlv < 1:
        raise ValueError(f"tiles={tuple(tiles)}: TLv must be positive")
    if tlv >= lv_full:
        tlv = lv_full
    elif tlv % LANES:
        raise ValueError(
            f"tiles={tuple(tiles)}: TLv={tlv} must be a multiple of {LANES} "
            f"or cover the whole v width ({lv})"
        )
    tb = min(tb, _round_up(max(n_edges, 1), LANES))
    tlu = _u_tile(lu)
    need = _vmem_bytes(kind, tb, tlu, tlv)
    if need > VMEM_BUDGET:
        raise ValueError(
            f"tiles={tuple(tiles)} for a ({n_edges}, {lu}×{lv}) panel needs "
            f"{need} bytes of VMEM per grid step, over the {VMEM_BUDGET}-byte "
            "budget"
        )
    return tb, tlu, tlv


def _pick_tiles(n_edges: int, lu: int, lv: int) -> tuple[int, int]:
    """The static ``(TB, TLv)`` heuristic; always passes :func:`check_tiles`.

    ``TLv`` is the whole v width up to :data:`TLV_MAX`; ``TB`` is the
    largest power of two (128 … :data:`TB_MAX`) that the edge count can
    fill, whose equality tile stays within :data:`EQ_TILE_MAX` elements
    and whose support blocks fit :data:`VMEM_BUDGET`.
    :mod:`repro.core.tuning` grid-searches the same space per pow2
    bucket shape and its picks are passed back in via ``tiles=``.
    """
    lv_full = _round_up(max(lv, 1), SUBLANES)
    tlv = lv_full if lv_full <= TLV_MAX else TLV_MAX
    tlu = _u_tile(lu)
    cap = min(TB_MAX, _round_up(max(n_edges, 1), LANES))
    tb = LANES
    while (
        tb * 2 <= cap
        and tb * 2 * tlv <= EQ_TILE_MAX
        and _vmem_bytes("support", tb * 2, tlu, tlv) <= VMEM_BUDGET
    ):
        tb *= 2
    return tb, tlv


def _kernel(a_ref, b_ref, *refs, kind: str):
    """One (edge block, u tile, v tile) grid step.

    ``a_ref``: (TLu, TB) u-panel tile, ``b_ref``: (TLv, TB) v-panel tile,
    both lane = edge.  Sentinels differ (−2 in a, −1 in b), so padding
    never matches.
    """
    count_ref = refs[0]
    arm_ref = refs[1] if kind != "count" else None
    clo_ref = refs[2] if kind == "support" else None
    acc_ref = refs[-1]
    u, v = pl.program_id(1), pl.program_id(2)
    tb = a_ref.shape[1]

    @pl.when((u == 0) & (v == 0))
    def _init_count():
        count_ref[...] = jnp.zeros_like(count_ref)

    if arm_ref is not None:
        @pl.when(v == 0)
        def _init_arm():
            arm_ref[...] = jnp.zeros_like(arm_ref)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    sub = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, tb), 0)

    def group(g, carry):
        s = pl.multiple_of(g * SUBLANES, SUBLANES)
        a8 = a_ref[pl.ds(s, SUBLANES), :]          # (8, TB)
        b = b_ref[...]                             # (TLv, TB)
        tot = None
        arm8 = None
        for r in range(SUBLANES):
            eq = jnp.where(b == a8[r:r + 1, :], 1, 0)   # (TLv, TB) int32
            tot = eq if tot is None else tot + eq
            if arm_ref is not None:
                row = jnp.sum(eq, axis=0, keepdims=True, dtype=jnp.int32)  # (1, TB)
                part = jnp.where(sub == r, row, 0)
                arm8 = part if arm8 is None else arm8 + part
        acc_ref[...] += tot
        if arm_ref is not None:
            arm_ref[pl.ds(s, SUBLANES), :] += arm8
        return carry

    jax.lax.fori_loop(0, a_ref.shape[0] // SUBLANES, group, 0)
    acc = acc_ref[...]
    count_ref[...] += jnp.sum(acc, axis=0, keepdims=True, dtype=jnp.int32)
    if clo_ref is not None:
        clo_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("kind", "tiles", "interpret"))
def _run(a, b, *, kind: str, tiles: tuple[int, int, int], interpret: bool):
    """Pad, transpose to lane = edge, launch, and restore edge-major outputs.

    ``tiles`` is the resolved ``(TB, TLu, TLv)`` from :func:`check_tiles`.
    """
    n, lu = a.shape
    _, lv = b.shape
    tb, tlu, tlv = tiles
    n_p = _round_up(max(n, 1), tb)
    lu_p = _round_up(max(lu, 1), tlu)
    lv_p = _round_up(max(lv, 1), tlv)
    a = a.astype(jnp.int32)
    b = b.astype(jnp.int32)
    a_t = jnp.pad(jnp.where(a >= 0, a, -2), ((0, n_p - n), (0, lu_p - lu)),
                  constant_values=-2).T
    b_t = jnp.pad(jnp.where(b >= 0, b, -1), ((0, n_p - n), (0, lv_p - lv)),
                  constant_values=-1).T
    n_u = lu_p // tlu
    grid = (n_p // tb, n_u, lv_p // tlv)
    out_specs = [pl.BlockSpec((1, tb), lambda i, u, v: (0, i))]
    out_shape = [jax.ShapeDtypeStruct((1, n_p), jnp.int32)]
    if kind != "count":
        out_specs.append(pl.BlockSpec((tlu, tb), lambda i, u, v: (u, i)))
        out_shape.append(jax.ShapeDtypeStruct((lu_p, n_p), jnp.int32))
    if kind == "support":
        out_specs.append(
            pl.BlockSpec((pl.Squeezed(), tlv, tb), lambda i, u, v: (u, v, i))
        )
        out_shape.append(jax.ShapeDtypeStruct((n_u, lv_p, n_p), jnp.int32))
    outs = pl.pallas_call(
        functools.partial(_kernel, kind=kind),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tlu, tb), lambda i, u, v: (u, i)),
            pl.BlockSpec((tlv, tb), lambda i, u, v: (v, i)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((tlv, tb), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name=f"intersect_{kind}",
    )(a_t, b_t)
    count = outs[0][0, :n]
    if kind == "count":
        return count
    arm = outs[1][:lu, :n].T
    if kind == "per_node":
        return count, arm
    closure = jnp.sum(outs[2], axis=0, dtype=jnp.int32)[:lv, :n].T
    return count, arm, closure


def _launch(kind: str, a, b, tiles):
    n, lu = a.shape
    lv = b.shape[1]
    resolved = check_tiles(
        tiles if tiles is not None else _pick_tiles(n, lu, lv), n, lu, lv, kind
    )
    # interpret mode only where no TPU compiler runs the kernel; on a TPU
    # backend the kernels always compile natively
    interpret = jax.default_backend() != "tpu"
    return _run(a, b, kind=kind, tiles=resolved, interpret=interpret)


def intersect_count_pallas(a: jax.Array, b: jax.Array, tiles=None):
    """Count matches between −1-padded sorted rows. a:(B,Lu) b:(B,Lv)→(B,)int32."""
    return _launch("count", a, b, tiles)


def intersect_per_node_pallas(a: jax.Array, b: jax.Array, tiles=None):
    """Per-edge counts + arm attribution.

    Returns ``(count, arm)`` with ``count: (B,) int32`` the per-row match
    total and ``arm: (B, Lu) int32`` the per-u-neighbor match count
    (``count == arm.sum(axis=1)``; padding slots are always 0).
    """
    return _launch("per_node", a, b, tiles)


def intersect_support_pallas(a: jax.Array, b: jax.Array, tiles=None):
    """Per-edge counts + arm + closure attributions.

    Returns ``(count, arm, closure)`` where ``closure: (B, Lv) int32``
    counts matches per v-neighbor slot (``count == closure.sum(axis=1)``).
    Together the three outputs bill every triangle to its three directed
    edges — the per-edge support primitive.
    """
    return _launch("support", a, b, tiles)
