"""BENCHMARK.json against the rules its harness relies on, and the harness's
resolution of every cell to its files by name."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


@pytest.fixture(scope="module")
def bench(root):
    return harness.load_benchmark(Path(root))


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_names_units_and_keys(bench):
    names = {}
    for section, keys in KEYS.items():
        for entry in bench[section]:
            extra = set(entry) - keys - ({"workloads"} if section in (
                "end_to_end", "per_layer") else set())
            assert not extra and keys <= set(entry), (section, entry)
            assert NAME.match(entry["name"]), entry["name"]
            names.setdefault(section, set()).add(entry["name"])
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
                assert entry["better"] in ("lower", "higher")
            for k in ("why", "layer", "source"):
                if k in entry and isinstance(entry[k], str):
                    assert _line(entry[k]), (k, entry[k])
    for section in KEYS:
        assert len(names[section]) == len(bench[section]), section
    metric_names = names["end_to_end"] | names["per_layer"]
    assert len(metric_names) == len(bench["end_to_end"]) + len(bench["per_layer"])
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    assert all(_line(word) for word in bench["command"])
    assert "setup_s" in names["end_to_end"]


def test_every_cell_finds_its_files_by_name(bench, root):
    pairs = set()
    for w in bench["workloads"]:
        cell = harness.resolve(w["name"], Path(root))
        assert (cell.config_name, cell.traffic_name) not in pairs
        pairs.add((cell.config_name, cell.traffic_name))
        assert cell.config["name"] == w["config"]
        harness.problem(cell.config["problem"], Path(root))
        assert hasattr(harness.driver(cell.traffic["kind"], Path(root)), "run")
        for m in cell.per_layer:
            assert callable(harness.reader(m["name"], Path(root)).read)
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer


def test_each_per_layer_metric_moves_a_metric_its_cells_report(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert harness.applies(e2e[m["moves"]], cell), (m["name"], cell)


def test_bounds(bench):
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert 1 <= bench["run_seconds"] <= 51


def _tree_digest(top):
    out = {}
    for p in sorted(Path(top).rglob("*")):
        if p.is_file() and "__pycache__" not in p.parts:
            out[str(p.relative_to(top))] = p.read_bytes()
    return out


def test_a_new_config_traffic_and_metric_need_only_new_files(tmp_path, root):
    shutil.copytree(Path(root) / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(Path(root) / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = _tree_digest(tmp_path / "bench")

    (tmp_path / "bench/configs/tiny_head.json").write_text(json.dumps(
        {"name": "tiny_head", "problem": "logistic", "d": 8, "m": 4, "mu": 1.0,
         "rows_per_chip": 16, "planted_rank": 2, "label_noise": 0.0}))
    (tmp_path / "bench/traffic/fit_short.json").write_text(json.dumps(
        {"kind": "fit", "schedule": "const:1", "step_size": "default",
         "solver": "rank1", "comm": "dense", "topology": "flat",
         "gap_rel": 0.5, "max_epochs": 3, "limits": {"loss_rel": 1e-4}}))
    (tmp_path / "bench/metrics/jobs_run.py").write_text(
        "def read(ctx):\n    return len(ctx['fits']) or None\n")
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny_head", "source": "a test",
                         "file": "bench/configs/tiny_head.json", "reduced": [],
                         "why": "a test"})
    b["workloads"].append({"name": "tiny_head.short", "config": "tiny_head",
                           "traffic": "fit_short", "chips": 1, "why": "a test"})
    b["per_layer"].append({"name": "jobs_run", "unit": "jobs", "better": "higher",
                           "source": "host_clock", "layer": "driver",
                           "moves": "fit_to_gap_s", "workloads": ["tiny_head.short"]})
    b["end_to_end"][0]["workloads"].append("tiny_head.short")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    cell = harness.resolve("tiny_head.short", tmp_path)
    assert cell.config["d"] == 8 and cell.traffic["max_epochs"] == 3
    assert [m["name"] for m in cell.per_layer] == ["jobs_run"]
    assert harness.read_per_layer(cell, {"fits": [{}, {}]}, tmp_path) == {
        "jobs_run": {"value": 2.0, "unit": "jobs"}}
    after = _tree_digest(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=120)


def test_run_exits_nonzero_without_a_tpu(root):
    p = _run(["--workload", "head_in1k_fit_1chip", "--seed", "1",
              "--seconds", "1", "--trace", "0"], root)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "needs a TPU" in p.stderr


def test_run_exits_nonzero_in_a_checkout_of_the_benchmark_alone(tmp_path, root):
    shutil.copytree(Path(root) / "bench", tmp_path / "bench")
    shutil.copy(Path(root) / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(["--workload", "head_in1k_fit_1chip", "--seed", "1",
              "--seconds", "1", "--trace", "0"], tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
