"""Kernel tile autotuner — the paper's §III-D5 grid search, persisted.

The paper tunes its CUDA kernel by sweeping threads-per-edge warp sizes
per graph and keeping the fastest; the TPU analogue of that knob is the
Pallas kernel's ``(block_edges, TLv)`` tile pair (edges per grid step
on the lane axis × v-panel tile height).  :func:`autotune_tiles` reruns
exactly that sweep — time every admissible candidate on synthetic panels
of the *shape* being tuned (shapes, not data, determine kernel runtime)
and keep the argmin —
and :class:`TileCache` persists the winners in a versioned on-disk JSON
so the sweep is paid once per shape per machine, not once per run.

Shapes are keyed pow2-bucketed (``B`` rounded up, ``Lu``/``Lv`` taken
verbatim — the engine's bucket ladder already makes them powers of two),
matching the compile-stability bucketing used everywhere else, so a
handful of cache entries covers every chunk the engine ever launches.

::

    tuner = AutoTuner(cache_path="tiles.json", tune_on_miss=True)
    tc = TriangleCounter(method="pallas", tuner=tuner)
    tc.count(edges)        # cold: sweeps + writes cache; warm: cache hits

The cache file carries a format version and the jax backend it was
measured on; a mismatch on either discards it (stale picks are worse
than the heuristic).  Writes are atomic (tmp + rename).
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import tempfile
import time

import numpy as np

from repro import obs

from .engine import next_pow2

__all__ = [
    "TileConfig",
    "TileCache",
    "AutoTuner",
    "candidate_tiles",
    "autotune_tiles",
    "shape_key",
    "CACHE_VERSION",
]

# 2: tiles are (edges-per-step on the lane axis, TLv); version-1 picks
# (TB in sublanes) are refused by the kernels and must not be served
CACHE_VERSION = 2

_TB_LADDER = (128, 256, 512, 1024, 2048)
_TLV_LADDER = (128, 256, 512)


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """One (block_edges, TLv) tile pick, plus the time that earned it."""

    block_edges: int
    tlv: int
    us: float = 0.0  # measured µs per call (0 when untimed/heuristic)

    @property
    def tiles(self) -> tuple[int, int]:
        """The kwarg form the kernels accept (``tiles=cfg.tiles``)."""
        return (self.block_edges, self.tlv)


def shape_key(n_edges: int, lu: int, lv: int) -> str:
    """Cache key: pow2-bucketed edge count × the exact panel widths."""
    return f"B{next_pow2(max(int(n_edges), 1))}xLu{int(lu)}xLv{int(lv)}"


def candidate_tiles(n_edges: int, lu: int, lv: int) -> list[TileConfig]:
    """The §III-D5 sweep grid for one panel shape.

    Every (TB, TLv) with TB in the lane-multiple ladder (up to the first
    rung covering the edge count) and TLv in the lane-width ladder plus
    the whole v width, that the kernel's own :func:`check_tiles` admits
    for every family member — so no candidate can fail to compile.  The
    static heuristic's pick is always included so tuning can never do
    worse than not tuning.
    """
    from repro.kernels.triangle_count.triangle_count import (
        KINDS,
        _pick_tiles,
        check_tiles,
    )

    n_edges = max(int(n_edges), 1)
    seen: dict[tuple[int, int], None] = {_pick_tiles(n_edges, lu, lv): None}
    for tb in _TB_LADDER:
        for tlv in (*_TLV_LADDER, max(int(lv), 1)):
            try:
                resolved = [check_tiles((tb, tlv), n_edges, lu, lv, k) for k in KINDS]
            except ValueError:
                continue
            tb_r, _, tlv_r = resolved[0]
            seen.setdefault((tb_r, tlv_r), None)
    return [TileConfig(tb, tlv) for tb, tlv in seen]


def _synthetic_panels(rng: np.random.Generator, b: int, l: int) -> np.ndarray:
    """Sorted, −1-padded panels with ~half-full rows (the typical bucket)."""
    out = np.full((b, l), -1, np.int32)
    for i in range(b):
        n = int(rng.integers(l // 2, l + 1)) if l > 1 else 1
        out[i, :n] = np.sort(rng.choice(4 * l + 8, size=n, replace=False))
    return out


def autotune_tiles(
    n_edges: int,
    lu: int,
    lv: int,
    *,
    iters: int = 2,
    warmup: int = 1,
    seed: int = 0,
) -> TileConfig:
    """Grid-search the count kernel's tiles for one pow2 bucket shape.

    Times :func:`repro.kernels.triangle_count.intersect_count_pallas`
    (the cheapest family member — tile behavior is shared) on synthetic
    sorted panels and returns the fastest admissible config.  The
    measured shape uses the pow2-bucketed edge count, so the result is
    valid for every chunk that maps to the same cache key.
    """
    import jax.numpy as jnp

    from repro.kernels.triangle_count import intersect_count_pallas

    b = next_pow2(max(int(n_edges), 1))
    rng = np.random.default_rng(seed)
    a = jnp.asarray(_synthetic_panels(rng, b, lu))
    c = jnp.asarray(_synthetic_panels(rng, b, lv))
    best: TileConfig | None = None
    for cand in candidate_tiles(b, lu, lv):
        for _ in range(warmup):
            intersect_count_pallas(a, c, tiles=cand.tiles).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(iters):
            intersect_count_pallas(a, c, tiles=cand.tiles).block_until_ready()
        us = (time.perf_counter() - t0) / max(iters, 1) * 1e6
        if best is None or us < best.us:
            best = TileConfig(cand.block_edges, cand.tlv, us)
    assert best is not None
    return best


@contextlib.contextmanager
def _cache_write_lock(path: str):
    """Advisory exclusive lock serializing read-merge-write cycles.

    ``fcntl.flock`` on a ``.lock`` sidecar where available (POSIX); on
    platforms without it the merge still runs — the window shrinks to
    the read→replace gap instead of disappearing, and the write itself
    stays atomic either way.
    """
    try:
        import fcntl
    except ImportError:  # non-POSIX: atomic replace only
        yield
        return
    fd = os.open(path + ".lock", os.O_CREAT | os.O_RDWR, 0o644)
    try:
        fcntl.flock(fd, fcntl.LOCK_EX)
        yield
    finally:
        fcntl.flock(fd, fcntl.LOCK_UN)
        os.close(fd)


class TileCache:
    """Versioned on-disk store of per-shape tile picks.

    The JSON payload is ``{"version", "backend", "entries": {key: {...}}}``;
    loading discards the file on a version or jax-backend mismatch so a
    cache tuned on TPU never steers a CPU run (or vice versa).

    Safe for **concurrent use**: two engines tuning different shapes
    into the same cache file cannot lose each other's entries —
    :meth:`save` is an atomic read-merge-write (under an advisory file
    lock where the platform has one) with last-writer-wins per *key*,
    not per file.  The seed wrote the instance's in-memory view over the
    whole file, so whichever engine saved last erased the other's picks.
    """

    def __init__(self, path: str | os.PathLike | None = None):
        self.path = os.fspath(path) if path is not None else None
        self.entries: dict[str, TileConfig] = {}
        self.loaded_from_disk = False
        if self.path is not None and os.path.exists(self.path):
            self.entries = self._read_disk_entries()
            self.loaded_from_disk = bool(self.entries)

    @staticmethod
    def _backend() -> str:
        import jax

        return jax.default_backend()

    def _read_disk_entries(self) -> dict[str, TileConfig]:
        """Current on-disk entries; {} on missing/corrupt/mismatched file."""
        try:
            with open(self.path) as f:
                payload = json.load(f)
        except (OSError, json.JSONDecodeError):
            return {}
        if (
            payload.get("version") != CACHE_VERSION
            or payload.get("backend") != self._backend()
        ):
            return {}
        out: dict[str, TileConfig] = {}
        for key, ent in payload.get("entries", {}).items():
            try:
                out[key] = TileConfig(
                    int(ent["block_edges"]), int(ent["tlv"]), float(ent.get("us", 0.0))
                )
            except (KeyError, TypeError, ValueError):
                continue
        return out

    def get(self, key: str) -> TileConfig | None:
        return self.entries.get(key)

    def put(self, key: str, cfg: TileConfig) -> None:
        self.entries[key] = cfg

    def save(self) -> None:
        """Atomic read-merge-write: disk entries ∪ ours, ours win per key."""
        if self.path is None:
            return
        d = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(d, exist_ok=True)
        with _cache_write_lock(self.path):
            merged = {**self._read_disk_entries(), **self.entries}
            payload = {
                "version": CACHE_VERSION,
                "backend": self._backend(),
                "entries": {
                    k: {"block_edges": c.block_edges, "tlv": c.tlv, "us": c.us}
                    for k, c in sorted(merged.items())
                },
            }
            fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
            try:
                with os.fdopen(fd, "w") as f:
                    json.dump(payload, f, indent=1, sort_keys=True)
                os.replace(tmp, self.path)
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
            self.entries = merged


class AutoTuner:
    """Policy layer the engine's pallas backend consults per panel shape.

    ``tune_on_miss=True`` runs the grid search (and persists it) the
    first time a shape is seen; ``False`` only serves already-cached
    picks and leaves unknown shapes to the kernel's static heuristic —
    the safe default for latency-sensitive callers.
    """

    def __init__(
        self,
        cache_path: str | os.PathLike | None = None,
        *,
        tune_on_miss: bool = False,
        iters: int = 2,
        seed: int = 0,
    ):
        self.cache = TileCache(cache_path)
        self.tune_on_miss = tune_on_miss
        self.iters = iters
        self.seed = seed
        self.n_hits = 0
        self.n_tuned = 0

    def tiles(self, n_edges: int, lu: int, lv: int) -> tuple[int, int] | None:
        """The (block_edges, tlv) pick for a shape, or None → heuristic."""
        key = shape_key(n_edges, lu, lv)
        cfg = self.cache.get(key)
        if cfg is not None:
            self.n_hits += 1
            obs.counter("tiles.cache_hits").add()
            return cfg.tiles
        obs.counter("tiles.cache_misses").add()
        if not self.tune_on_miss:
            return None
        cfg = autotune_tiles(n_edges, lu, lv, iters=self.iters, seed=self.seed)
        self.cache.put(key, cfg)
        self.cache.save()
        self.n_tuned += 1
        obs.counter("tiles.tuned").add()
        return cfg.tiles
