"""BENCHMARK.json: its keys, the character rules on names and units, and
that every cell, configuration, traffic mix and metric is found by name."""
import json
import re

import pytest

from bench import harness
from bench_fixtures import REPO, tiny_root

DOC = json.loads((REPO / "BENCHMARK.json").read_text())
NAME_RE = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT_RE = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")


def name_problems(doc: dict) -> list:
    """Names and units that break the character rules of BENCHMARK.json."""
    bad = []

    def name(v, where):
        if not (isinstance(v, str) and NAME_RE.fullmatch(v)):
            bad.append(f"{where}: bad name {v!r}")

    for c in doc.get("configs", []):
        name(c.get("name"), "configs")
        for k in c.get("reduced", []):
            name(k, f"configs.{c.get('name')}.reduced")
    for w in doc.get("workloads", []):
        for key in ("name", "config", "traffic"):
            name(w.get(key), f"workloads.{key}")
    for group in ("end_to_end", "per_layer"):
        for mt in doc.get(group, []):
            name(mt.get("name"), group)
            if not (isinstance(mt.get("unit"), str) and UNIT_RE.fullmatch(mt["unit"])):
                bad.append(f"{group}.{mt.get('name')}: bad unit {mt.get('unit')!r}")
            if mt.get("better") not in ("lower", "higher"):
                bad.append(f"{group}.{mt.get('name')}: better={mt.get('better')!r}")
    return bad


def test_top_level_keys_and_rules():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert name_problems(DOC) == []
    assert 1 <= DOC["run_seconds"] <= 51
    names = [m["name"] for m in DOC["end_to_end"] + DOC["per_layer"]]
    assert len(names) == len(set(names))
    for m in DOC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("bad", ["tokens per second", "μs", "", "x" * 17])
def test_bad_units_are_named(bad):
    doc = json.loads(json.dumps(DOC))
    doc["end_to_end"][0]["unit"] = bad
    assert name_problems(doc)


@pytest.mark.parametrize("bad", ["a b", "a/b", "a,b", ".lead", "x" * 65])
def test_bad_names_are_named(bad):
    doc = json.loads(json.dumps(DOC))
    doc["workloads"][0]["name"] = bad
    assert name_problems(doc)


@pytest.mark.parametrize("cell", [w["name"] for w in DOC["workloads"]])
def test_every_cell_resolves_and_reports(cell):
    c = harness.resolve(cell, 2**31 + 5)
    assert 0 <= c.seed < 2**31
    assert harness.load_driver(c.traffic["driver"]).Driver
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e
        assert callable(harness.load_reader(m["name"]))


def test_configs_are_used_and_name_their_files():
    used = {w["config"] for w in DOC["workloads"]}
    for c in DOC["configs"]:
        assert c["name"] in used
        body = json.loads((REPO / c["file"]).read_text())
        assert body["name"] == c["name"] and body["reduced"] == c["reduced"]


def test_seeds_past_32_bits_do_not_alias():
    assert harness.derive_seed(7) != harness.derive_seed(2**32 + 7)


def test_a_new_cell_is_found_by_name(tmp_path):
    """A cell added as files and entries only: a traffic mix, its limits,
    a per-layer metric and its reader."""
    root = tiny_root(tmp_path)
    b = root / "bench"
    (b / "traffic" / "train-4x64.json").write_text(json.dumps(dict(
        json.loads((b / "traffic" / "train-2x1024.json").read_text()), batch=4)))
    (b / "limits" / "qwen2-0.5b.train-4x64.json").write_text(
        (b / "limits" / "qwen2-0.5b.train-2x1024.json").read_text())
    (b / "metrics" / "steps_in_window.py").write_text(
        "def read(ctx):\n    return ctx['counters'].get('attempted')\n")
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["workloads"].append({"name": "qwen2-0.5b.train-4x64", "config": "qwen2-0.5b",
                             "traffic": "train-4x64", "chips": 1, "why": "test"})
    doc["end_to_end"][0]["workloads"].append("qwen2-0.5b.train-4x64")
    doc["per_layer"].append({"name": "steps_in_window", "unit": "steps",
                             "better": "higher", "source": "host_clock",
                             "layer": "train step", "moves": "train_tokens_per_s",
                             "workloads": ["qwen2-0.5b.train-4x64"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    cell = harness.resolve("qwen2-0.5b.train-4x64", 1, root)
    assert cell.traffic["batch"] == 4
    assert "steps_in_window" in [m["name"] for m in cell.per_layer]
    read = harness.load_reader("steps_in_window", b)
    assert read({"counters": {"attempted": 3}}) == 3
