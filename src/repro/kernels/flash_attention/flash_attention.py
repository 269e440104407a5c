"""Pallas TPU flash attention (forward) with GQA head sharing.

Canonical revisited-block schedule: grid ``(B·Hq, n_q_blocks,
n_kv_blocks)`` with running (m, l, acc) softmax state in VMEM scratch,
initialized at the first kv block and finalized at the last.  The kv-block
index maps for K/V divide the head index by the GQA group size, so grouped
queries read the same K/V tiles without materializing repeats.

MXU alignment: q/k/v tiles are (TQ, D) / (TK, D) with TQ=TK=128 by default
and D the head dim (128 for every assigned LM arch) — all contraction dims
are multiples of the 128-lane systolic array.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

_NEG_INF = -1e30


def _kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, causal, sm_scale, tq, tk, nk, sq, skv
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)  # (TQ, D)
    k = k_ref[0].astype(jnp.float32)  # (TK, D)
    v = v_ref[0].astype(jnp.float32)
    # Sanitize block-padding rows past the true kv length: out-of-bounds
    # tile reads are undefined, and 0·garbage must stay 0 in p @ v.
    row_valid = ik * tk + jax.lax.broadcasted_iota(jnp.int32, (tk, 1), 0) < skv
    k = jnp.where(row_valid, k, 0.0)
    v = jnp.where(row_valid, v, 0.0)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale  # (TQ, TK)
    qi = iq * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    kj = ik * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    valid = kj < skv  # mask block padding past the true kv length
    if causal:
        valid &= qi + (skv - sq) >= kj
    s = jnp.where(valid, s, _NEG_INF)
    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    # `where` (not bare exp) so a fully-masked block contributes 0, not e⁰
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ik == nk - 1)
    def _fin():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "sm_scale", "block_q", "block_k", "interpret")
)
def flash_attention_pallas(
    q: jax.Array,  # (B, Hq, Sq, D)
    k: jax.Array,  # (B, Hkv, Skv, D)
    v: jax.Array,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    g = hq // hkv
    if sm_scale is None:
        sm_scale = d ** -0.5
    tq = min(block_q, sq)
    tk = min(block_k, skv)
    nq = pl.cdiv(sq, tq)
    nk = pl.cdiv(skv, tk)
    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hkv, skv, d)
    vf = v.reshape(b * hkv, skv, d)

    kernel = functools.partial(
        _kernel, causal=causal, sm_scale=sm_scale, tq=tq, tk=tk, nk=nk, sq=sq, skv=skv
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, tq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, tk, d), lambda h, i, j, g=g: (h // g, j, 0)),
            pl.BlockSpec((1, tk, d), lambda h, i, j, g=g: (h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, tq, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, hq, sq, d)
