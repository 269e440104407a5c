"""Tile autotuner: candidate grid, cache round-trip, engine integration.

The acceptance smoke: a cold tune writes the versioned cache, a warm
load picks the *identical* tiles without re-measuring, and tuned tiles
never change kernel results (only their speed).
"""
import json
import os

import numpy as np
import pytest

from repro.core.tuning import (
    CACHE_VERSION,
    AutoTuner,
    TileCache,
    TileConfig,
    autotune_tiles,
    candidate_tiles,
    shape_key,
)


def test_candidate_grid_respects_vmem_budget():
    """Every candidate is a tile the TPU compiler accepts: TB a multiple of
    the lane width, TLv lane-aligned or the whole width, and the blocks of
    every family member inside the kernel's VMEM budget."""
    from repro.kernels.triangle_count.triangle_count import (
        KINDS,
        LANES,
        VMEM_BUDGET,
        _vmem_bytes,
        check_tiles,
    )

    for (n, lu, lv) in [(32, 16, 16), (512, 256, 1024), (8, 4096, 4096),
                        (3000, 64, 640), (100, 16384, 16384)]:
        cands = candidate_tiles(n, lu, lv)
        assert cands, (n, lu, lv)
        for c in cands:
            assert c.block_edges % LANES == 0, (c, n, lu, lv)
            assert c.block_edges <= max(-(-n // LANES) * LANES, LANES)
            assert c.tlv % LANES == 0 or c.tlv >= lv, (c, n, lu, lv)
            for kind in KINDS:
                tb, tlu, tlv = check_tiles(c.tiles, n, lu, lv, kind)
                assert _vmem_bytes(kind, tb, tlu, tlv) <= VMEM_BUDGET


def test_shape_key_pow2_buckets():
    assert shape_key(33, 64, 64) == shape_key(64, 64, 64)
    assert shape_key(64, 64, 64) != shape_key(65, 64, 64)
    assert shape_key(1, 16, 32) == "B1xLu16xLv32"


def test_cold_tune_then_warm_load_identical_tiles(tmp_path):
    """The CI acceptance smoke: cold tune → cache write → warm load picks
    the identical tiles (no re-measure, hit counted)."""
    path = tmp_path / "tiles.json"
    tuner = AutoTuner(path, tune_on_miss=True, iters=1)
    tiles_cold = tuner.tiles(24, 16, 16)
    assert tiles_cold is not None
    assert tuner.n_tuned == 1 and tuner.n_hits == 0
    assert path.exists()
    payload = json.loads(path.read_text())
    assert payload["version"] == CACHE_VERSION
    assert shape_key(24, 16, 16) in payload["entries"]
    # warm: a fresh tuner that may NOT tune must serve the same pick
    warm = AutoTuner(path, tune_on_miss=False)
    assert warm.cache.loaded_from_disk
    tiles_warm = warm.tiles(24, 16, 16)
    assert tiles_warm == tiles_cold
    assert warm.n_hits == 1 and warm.n_tuned == 0
    # same pow2 bucket (24 and 17 both round to B32) → same entry, no
    # new tuning even with tune_on_miss enabled
    again = AutoTuner(path, tune_on_miss=True, iters=1)
    assert again.tiles(17, 16, 16) == tiles_cold
    assert again.n_tuned == 0


def test_cache_discards_version_mismatch(tmp_path):
    path = tmp_path / "tiles.json"
    cache = TileCache(path)
    cache.put(shape_key(8, 16, 16), TileConfig(4, 128, 1.0))
    cache.save()
    payload = json.loads(path.read_text())
    payload["version"] = CACHE_VERSION + 1
    path.write_text(json.dumps(payload))
    stale = TileCache(path)
    assert not stale.loaded_from_disk and not stale.entries


def test_cache_discards_backend_mismatch(tmp_path):
    path = tmp_path / "tiles.json"
    cache = TileCache(path)
    cache.put(shape_key(8, 16, 16), TileConfig(4, 128, 1.0))
    cache.save()
    payload = json.loads(path.read_text())
    payload["backend"] = "not-a-backend"
    path.write_text(json.dumps(payload))
    stale = TileCache(path)
    assert not stale.loaded_from_disk and not stale.entries


def test_cache_survives_corrupt_file(tmp_path):
    path = tmp_path / "tiles.json"
    path.write_text("{ this is not json")
    cache = TileCache(path)  # must not raise
    assert not cache.entries
    cache.put("k", TileConfig(8, 128))
    cache.save()
    assert TileCache(path).get("k") == TileConfig(8, 128, 0.0)


def test_autotune_result_is_admissible():
    from repro.kernels.triangle_count.triangle_count import check_tiles

    cfg = autotune_tiles(8, 16, 16, iters=1, warmup=0)
    assert check_tiles(cfg.tiles, 8, 16, 16) == (128, 16, 16)
    assert cfg.us > 0.0


def test_tuned_engine_matches_untuned(tmp_path, small_graphs):
    """A tuner-steered pallas counter is bit-identical to the untuned one
    and actually consults the cache."""
    from repro.core import TriangleCounter

    e = small_graphs["kron"]
    base = TriangleCounter(method="pallas")
    expect = base.count(e)
    pn0 = base.per_node(e)
    tuner = AutoTuner(tmp_path / "tiles.json", tune_on_miss=True, iters=1)
    tc = TriangleCounter(method="pallas", tuner=tuner)
    assert tc.count(e) == expect
    np.testing.assert_array_equal(tc.per_node(e), pn0)
    assert tuner.n_tuned + tuner.n_hits > 0
    # warm run, fresh process-level state: cache hits only
    warm_tuner = AutoTuner(tmp_path / "tiles.json", tune_on_miss=False)
    tc2 = TriangleCounter(method="pallas", tuner=warm_tuner)
    assert tc2.count(e) == expect
    assert warm_tuner.n_hits > 0 and warm_tuner.n_tuned == 0


def test_concurrent_caches_merge_instead_of_clobber(tmp_path):
    """Two engines sharing one cache file must not lose each other's
    entries: save() is read-merge-write, last-writer-wins per *key*.

    The seed wrote the in-memory view over the whole file, so whichever
    instance saved last erased the other's picks — the regression this
    pins down."""
    path = tmp_path / "tiles.json"
    a = TileCache(path)
    b = TileCache(path)
    ka, kb = shape_key(8, 16, 16), shape_key(64, 32, 32)
    a.put(ka, TileConfig(4, 128, 1.0))
    a.save()
    b.put(kb, TileConfig(16, 256, 2.0))
    b.save()  # seed behavior: would erase ka
    merged = TileCache(path)
    assert merged.get(ka) == TileConfig(4, 128, 1.0)
    assert merged.get(kb) == TileConfig(16, 256, 2.0)
    # per-key last-writer-wins: a re-save of ka with a new pick prevails
    a.put(ka, TileConfig(8, 256, 0.5))
    a.save()
    assert TileCache(path).get(ka) == TileConfig(8, 256, 0.5)
    assert TileCache(path).get(kb) == TileConfig(16, 256, 2.0)


def test_contended_saves_union_survives(tmp_path):
    """Many threads interleaving put+save on one file: the union of every
    thread's keys survives (no lost updates under contention)."""
    import threading

    path = tmp_path / "tiles.json"
    n_threads, keys_per = 6, 5
    errs = []

    def writer(tid):
        try:
            cache = TileCache(path)
            for i in range(keys_per):
                cache.put(f"t{tid}k{i}", TileConfig(8, 128, float(tid)))
                cache.save()
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=writer, args=(t,)) for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    final = TileCache(path)
    expect = {f"t{t}k{i}" for t in range(n_threads) for i in range(keys_per)}
    assert expect <= set(final.entries)
