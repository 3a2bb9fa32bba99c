"""BENCHMARK.json against the benchmark's contract, and the harness's
finding of every configuration, traffic mix and metric by name."""
import json
import re
import shutil
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    return harness.load_spec(ROOT)


def test_top_level_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"][:2] == ["python3", "bench/run.py"]
    assert spec["paths"] == ["bench"]
    assert 1 <= spec["run_seconds"] <= 51
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_names_and_units(spec):
    names = [e["name"] for sec in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in spec[sec]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for w in spec["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        assert w["chips"] in (1, 4)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for c in spec["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("bench/")


def test_every_cell_finds_its_parts(spec):
    for w in spec["workloads"]:
        cell, cfg, traffic = harness.cell_parts(spec, w["name"], ROOT)
        assert cfg["name"] == cell["config"]
        assert hasattr(harness.generator_module(traffic), "Run")
    used = {w["config"] for w in spec["workloads"]}
    assert used == {c["name"] for c in spec["configs"]}
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 2)


def test_every_metric_has_a_reader_and_its_cells_report_its_moves(spec):
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    cells = {w["name"] for w in spec["workloads"]}
    for m in spec["per_layer"]:
        assert callable(harness.metric_reader(m["name"], ROOT))
        assert m["moves"] in e2e
        for w in m.get("workloads", cells):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", cells), (m, w)
    for w in cells:
        reported = harness.cell_metrics(spec, w, "end_to_end")
        assert "setup_s" in {m["name"] for m in reported}
        assert len(reported) >= 2
        assert harness.cell_metrics(spec, w, "per_layer")


def test_a_new_cell_needs_only_new_files_and_entries(spec, tmp_path):
    """Copy the benchmark, add a traffic mix, a cell and a metric as new
    files and entries, and find them all by name without touching code."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    new = dict(spec)
    traffic = json.loads((ROOT / "bench" / "traffic" /
                          f"{spec['workloads'][0]['traffic']}.json").read_text())
    traffic["why"] = "a new mix"
    (tmp_path / "bench" / "traffic" / "new-mix.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench" / "metrics" / "new_metric.py").write_text(
        "def read(view):\n    return None\n")
    w0 = spec["workloads"][0]
    new["workloads"] = spec["workloads"] + [dict(
        w0, name="new.cell", traffic="new-mix", why="a new cell")]
    new["per_layer"] = spec["per_layer"] + [dict(
        spec["per_layer"][0], name="new_metric", workloads=["new.cell"])]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    loaded = harness.load_spec(tmp_path)
    cell, cfg, tr = harness.cell_parts(loaded, "new.cell", tmp_path)
    assert tr["why"] == "a new mix" and cfg["name"] == w0["config"]
    assert harness.metric_reader("new_metric", tmp_path)(None) is None
    names = {m["name"] for m in harness.cell_metrics(loaded, "new.cell",
                                                     "per_layer")}
    assert "new_metric" in names


def test_run_refuses_without_a_chip():
    """On this CPU-only host the command exits nonzero and prints no
    result line."""
    import subprocess
    import sys
    w = harness.load_spec(ROOT)["workloads"][0]["name"]
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"),
                        "--workload", w, "--seed", "1", "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True,
                       env={**__import__("os").environ,
                            "JAX_PLATFORMS": "cpu"}, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
