"""trilint tests: seeded-violation fixtures, repo cleanliness, suppression
channels, the CLI, and the REPRO_CHECK runtime sanitizer."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.check import run_checks
from repro.check.base import parse_allowlist
from repro.check.runtime import (
    PARTIAL_HEADROOM,
    RuntimeCheckError,
    check_partial,
    enabled,
)

REPO = Path(__file__).resolve().parents[1]
SRC_REPRO = REPO / "src" / "repro"
FIXTURES = Path(__file__).resolve().parent / "fixtures" / "trilint"
ALLOWLIST = REPO / "trilint.allow"


def codes(findings):
    return {f.code for f in findings}


# ---------------------------------------------------------------------------
# each pass catches its seeded fixture


@pytest.mark.parametrize(
    "passname,fixture,expected_codes",
    [
        ("overflow", "core/bad_overflow.py", {"O1-sum-dtype", "O2-host-fold", "O3-narrow"}),
        ("recompile", "core/bad_recompile.py", {"R1-unbucketed-shape"}),
        (
            "collectives",
            "core/bad_collectives.py",
            {"C1-axis-undeclared", "C2-axis-index-in-core", "C3-shardmap-specs"},
        ),
        (
            "backend_protocol",
            "core/bad_backend_protocol.py",
            {
                "B1-capability-unimplemented",
                "B2-no-capability-table",
                "B3-undeclared-capability",
                "B4-missing-plan",
            },
        ),
        ("stats_lifecycle", "core/bad_stats_lifecycle.py", {"S1-stale-stats"}),
        ("codec", "core/bad_codec.py", {"Z1-unchecked-decode-narrow"}),
        ("obs_discipline", "core/bad_obs_discipline.py", {"D1-unsynced-span"}),
    ],
)
def test_pass_flags_seeded_fixture(passname, fixture, expected_codes):
    findings = run_checks(FIXTURES, select=[passname])
    in_fixture = [f for f in findings if f.path == fixture and not f.suppressed]
    assert expected_codes <= codes(in_fixture), (
        f"{passname} missed codes {expected_codes - codes(in_fixture)}; "
        f"got {[f.render() for f in findings]}"
    )


def test_obs_discipline_synced_and_host_spans_not_flagged():
    findings = run_checks(FIXTURES, select=["obs_discipline"])
    in_fixture = [
        f for f in findings
        if f.path == "core/bad_obs_discipline.py" and not f.suppressed
    ]
    # exactly the one unsynced span: the synced and host-only spans pass
    assert len(in_fixture) == 1, [f.render() for f in in_fixture]
    assert "chunk_count_kernel" in in_fixture[0].message


def test_obs_discipline_dispatch_spans_and_blocks(tmp_path):
    """D1 lets a span over a launch through when its name says it times
    the enqueue (``.dispatch``/``.chunk``, f-strings included) or when it
    blocks; any other name over a launch is flagged."""
    core = tmp_path / "core"
    core.mkdir()
    (core / "mod.py").write_text(
        "import jax\n"
        "def a(obs, k, c):\n"
        "    with obs.span(f'{k}.chunk'):\n"
        "        return c.count_chunk(1)\n"
        "def b(obs, c):\n"
        "    with obs.span('engine.dispatch') as sp:\n"
        "        return [c.count_chunk(i) for i in range(2)]\n"
        "def d(obs, c):\n"
        "    with obs.span('engine.wait'):\n"
        "        return jax.block_until_ready(c.count_chunk(1))\n"
        "def e(obs, c):\n"
        "    with obs.span('engine.dispatched_count'):\n"
        "        return c.count_chunk(1)\n"
        "def f(obs, c):\n"
        "    with obs.span('engine.kernel') as sp:\n"
        "        return sp.sync(c.count_chunk(1))\n"
    )
    flagged = run_checks(tmp_path, select=["obs_discipline"])
    assert sorted(f.line for f in flagged) == [12, 15], [f.render() for f in flagged]


def test_stats_lifecycle_compliant_method_not_flagged():
    findings = run_checks(FIXTURES, select=["stats_lifecycle"])
    flagged = {f.message.split("`")[1] for f in findings}
    assert "LeakyEngine.query" in flagged
    assert "LeakyEngine.count" not in flagged


def test_codec_guarded_narrowing_not_flagged():
    findings = run_checks(FIXTURES, select=["codec"])
    in_fixture = [
        f for f in findings
        if f.path == "core/bad_codec.py" and not f.suppressed
    ]
    # exactly the two unguarded narrows; the ensure_fits_int32 twin passes
    assert len(in_fixture) == 2, [f.render() for f in in_fixture]
    flagged = {f.message.split("`")[1] for f in in_fixture}
    assert flagged == {"unguarded_block_cols", "unguarded_scalar_cast"}


# ---------------------------------------------------------------------------
# the real tree is clean (modulo the reviewed allowlist)


def test_src_repro_clean_modulo_allowlist():
    findings = run_checks(SRC_REPRO, allowlist_path=ALLOWLIST)
    unsuppressed = [f for f in findings if not f.suppressed]
    assert not unsuppressed, "\n".join(f.render() for f in unsuppressed)


# ---------------------------------------------------------------------------
# suppression channels


def test_inline_suppression(tmp_path):
    bad = tmp_path / "core"
    bad.mkdir()
    (bad / "mod.py").write_text(
        "import jax.numpy as jnp\n"
        "def f(x):\n"
        "    return jnp.sum(x)  # trilint: ok[overflow]\n"
        "def g(x):\n"
        "    return jnp.sum(x)\n"
    )
    findings = run_checks(tmp_path, select=["overflow"])
    by_line = {f.line: f for f in findings}
    assert by_line[3].suppressed and by_line[3].suppression == "inline"
    assert not by_line[5].suppressed


def test_allowlist_matching(tmp_path):
    bad = tmp_path / "core"
    bad.mkdir()
    (bad / "mod.py").write_text("import numpy as np\ndef f(x):\n    return int(x.sum())\n")
    allow = tmp_path / "allow.txt"
    allow.write_text("# reviewed\ncore/*.py O2-host-fold *\n")
    findings = run_checks(tmp_path, allowlist_path=allow, select=["overflow"])
    assert findings and all(f.suppressed for f in findings)
    assert findings[0].suppression.startswith("allowlist:")


def test_parse_allowlist_shapes():
    rules = parse_allowlist("# c\ncore/x.py overflow substr\ncore/y.py\n")
    assert len(rules) == 2
    assert rules[0].substring == "substr"
    assert rules[1].rule == "*" and rules[1].substring == "*"


# ---------------------------------------------------------------------------
# CLI


def test_cli_json_clean_on_repo():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "repro.check", "--json"],
        capture_output=True,
        text=True,
        env=env,
        timeout=120,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    report = json.loads(r.stdout)
    assert report["counts"]["unsuppressed"] == 0
    assert set(report["passes"]) == {
        "overflow", "recompile", "collectives", "backend_protocol",
        "stats_lifecycle", "obs_discipline", "codec",
    }


def test_cli_fails_on_fixtures():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [
            sys.executable, "-m", "repro.check",
            "--root", str(FIXTURES), "--no-allowlist", "--json",
        ],
        capture_output=True,
        text=True,
        env=env,
        timeout=120,
    )
    assert r.returncode == 1
    report = json.loads(r.stdout)
    assert report["counts"]["unsuppressed"] >= 5


# ---------------------------------------------------------------------------
# runtime sanitizer


def test_enabled_reads_env(monkeypatch):
    monkeypatch.delenv("REPRO_CHECK", raising=False)
    assert not enabled()
    monkeypatch.setenv("REPRO_CHECK", "1")
    assert enabled()
    monkeypatch.setenv("REPRO_CHECK", "0")
    assert not enabled()


def test_check_partial_accepts_contract():
    check_partial(np.zeros(4, np.int32), kind="count")
    check_partial(jnp.ones(3, jnp.int32), kind="per_node")
    check_partial(np.zeros(0, np.int64), kind="count")  # empty: vacuous


def test_check_partial_rejects_wide_dtype():
    with pytest.raises(RuntimeCheckError, match="int32"):
        check_partial(np.ones(3, np.int64), kind="count")


def test_check_partial_rejects_negative_and_headroom():
    with pytest.raises(RuntimeCheckError, match="negative"):
        check_partial(np.array([-1], np.int32), kind="count")
    with pytest.raises(RuntimeCheckError, match="2\\^30"):
        check_partial(np.array([PARTIAL_HEADROOM], np.int32), kind="support")


def test_run_workload_sanitizer_integration(monkeypatch, small_graphs):
    from repro.core.engine import (
        TriangleCounter,
        WedgeBackend,
        preprocess,
        run_workload,
        workload_from_csr,
    )
    from repro.graphs import canonicalize_edges

    edges = canonicalize_edges(small_graphs["kron"])
    monkeypatch.setenv("REPRO_CHECK", "1")

    # healthy path: identical result with the sanitizer on
    tc = TriangleCounter(method="wedge_bsearch")
    with_check = tc.count(edges)
    monkeypatch.delenv("REPRO_CHECK")
    assert TriangleCounter(method="wedge_bsearch").count(edges) == with_check

    class WideBackend(WedgeBackend):
        """Violates the device contract: emits int64 partials."""

        def count_chunk(self, adj, chunk):
            return np.asarray(super().count_chunk(adj, chunk)).astype(np.int64)

    csr = preprocess(jnp.asarray(edges), int(edges.max()) + 1)
    work = workload_from_csr(csr)
    # without REPRO_CHECK the wide partial folds silently
    run_workload(WideBackend(), "count", work, budget=None)
    monkeypatch.setenv("REPRO_CHECK", "1")
    with pytest.raises(RuntimeCheckError, match="int32"):
        run_workload(WideBackend(), "count", work, budget=None)


def test_incremental_clears_stats_on_entry(small_graphs):
    from repro.core.incremental import IncrementalTriangleCounter

    tc = IncrementalTriangleCounter(small_graphs["triangle"])
    tc.insert(np.array([[0, 9], [9, 1]]))
    assert tc.last_update_stats is not None
    # a batch that raises must not leave the previous batch's stats visible
    with pytest.raises(ValueError):
        tc.insert(np.array([[-5, 2]]))
    assert tc.last_update_stats is None
