"""Microbenchmarks of the panel-intersection count: the jnp oracle and the
Pallas kernel, plus the jnp flash-attention blocks.

Every row says which mode ran (``mode=jnp``, ``mode=pallas-native`` on a
TPU, ``mode=pallas-interpret`` elsewhere) and on which device; an
interpret-mode time measures the Pallas interpreter, not a chip."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.triangle_count import intersect_count_pallas
from repro.kernels.triangle_count.ref import intersect_count_ref
from repro.models.attention import flash_attention_jnp

from .common import timeit


def run():
    rows = []
    rng = np.random.default_rng(0)
    dev = jax.devices()[0].device_kind
    pallas_mode = "pallas-native" if jax.default_backend() == "tpu" else "pallas-interpret"

    def panels(b, l):
        vals = np.sort(rng.integers(0, 1 << 20, size=(b, l)), axis=1).astype(np.int32)
        return jnp.asarray(vals)

    for b, lu, lv in [(1024, 64, 64), (256, 256, 256), (64, 1024, 1024)]:
        a, c = panels(b, lu), panels(b, lv)
        f = jax.jit(intersect_count_ref)
        us = timeit(lambda: jax.block_until_ready(f(a, c)), warmup=1, iters=3)
        pairs = b * lu * lv
        rows.append((f"kernel/intersect-ref/b{b}xl{lu}x{lv}", us,
                     f"mode=jnp;device={dev};pairs_per_us={pairs/us:.0f}"))
        us = timeit(lambda: jax.block_until_ready(intersect_count_pallas(a, c)),
                    warmup=1, iters=3)
        rows.append((f"kernel/intersect-pallas/b{b}xl{lu}x{lv}", us,
                     f"mode={pallas_mode};device={dev};pairs_per_us={pairs/us:.0f}"))

    q = jnp.asarray(rng.normal(size=(1, 4, 512, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 4, 512, 64)), jnp.float32)
    for bk in (128, 256, 512):
        us = timeit(
            lambda bk=bk: jax.block_until_ready(
                flash_attention_jnp(q, k, k, block_k=bk)
            ),
            warmup=1, iters=3,
        )
        rows.append((f"kernel/flash-jnp/block_k{bk}", us, f"mode=jnp;device={dev}"))
    return rows
