"""``BENCHMARK.json`` against the benchmark's contract, and every name in
it against the file the harness will look for."""
import json
import os
import re

import pytest

from bench import graphs, run as harness
from bench.control import kinds
from bench.tests.conftest import ROOT
from bench.tests.small import small

SPEC = harness.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def one_line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and "\n" not in text \
        and "\t" not in text


def test_top_level():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= len(SPEC["command"]) <= 32 and all(one_line(w) for w in SPEC["command"])
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries(section):
    names = [e["name"] for e in SPEC[section]]
    assert len(names) == len(set(names))
    for e in SPEC[section]:
        extra = {"workloads"} if section in ("end_to_end", "per_layer") else set()
        assert KEYS[section] <= set(e) <= KEYS[section] | extra, e
        assert NAME.match(e["name"])
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
        for key in ("why", "layer", "source"):
            if key in e:
                assert one_line(e[key])


def test_configs():
    used = {w["config"] for w in SPEC["workloads"]}
    files = set()
    for c in SPEC["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/") and c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["source"] == c["source"]
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert "assumed" in body and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])


@pytest.mark.parametrize("config", [c["name"] for c in SPEC["configs"]])
def test_config_has_a_small_stand_in(config):
    """Every configuration's ``small`` block, which the self-tests run in
    its place, names only graphs its generators accept, at a small size."""
    with open(os.path.join(ROOT, harness.by_name(SPEC["configs"], config)["file"])) as f:
        stand_in = small(json.load(f))
    for spec in stand_in.get("tenants", [stand_in]):
        edges, n_nodes = graphs.generate(spec)
        assert 0 < edges.shape[0] <= 1 << 20 and 0 < n_nodes <= 1 << 16
        assert edges.max() < n_nodes


def test_workloads_find_their_files():
    pairs = set()
    configs = {c["name"] for c in SPEC["configs"]}
    for w in SPEC["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        with open(os.path.join(ROOT, "bench", "traffic", f"{w['traffic']}.json")) as f:
            traffic = json.load(f)
        for kind in kinds(traffic):
            assert os.path.exists(os.path.join(ROOT, "bench", "answers", f"{kind}.py"))
        assert os.path.exists(os.path.join(ROOT, "bench", "loops", f"{traffic['loop']}.py"))
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(1, len(SPEC["workloads"]) // 2)


def test_metrics():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert m["moves"] in e2e and one_line(m["layer"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics", f"{m['name']}.py"))


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_each_cell_reports_enough(cell):
    e2e = [m["name"] for m in harness.cell_metrics(SPEC, cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = harness.cell_metrics(SPEC, cell, "per_layer")
    assert per_layer and all(m["moves"] in e2e for m in per_layer)


def test_full_check_fits():
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
