"""The harness is driven by data; BENCHMARK.json agrees with the files; the
command has no CPU route."""

import json
import os
import re
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from perfbench_tiny import BENCH, REPO, SEED, TINY, tiny_root  # noqa: E402

sys.path.insert(0, REPO)
from perfbench import run  # noqa: E402

_REPO = REPO


def test_driven_by_data_new_files_no_edit(tmp_path):
    """A new configuration, a new cell and a new span metric are three new
    files found by name; no file of perfbench/ is edited (the loaders take
    the directory as an argument)."""
    root = tiny_root(tmp_path)
    before = {p: os.path.getmtime(os.path.join(d, p))
              for d, _s, fs in os.walk(BENCH) for p in fs if "__pycache__" not in d}
    shallow = dict(TINY, name="tiny-shallow", num_hidden_layers=1)
    (root / "configs" / "tiny-shallow.json").write_text(json.dumps(shallow))
    (root / "layer_metrics" / "add_request_p90_ms.json").write_text(json.dumps({
        "layer": "admission, scheduler, cache manager", "unit": "ms",
        "better": "lower", "moves": "ttft_p90_ms",
        "reader": {"source": "span", "span": "add_request", "stat": "p90_ms"}}))
    cell = json.loads((root / "workloads" / "chat.json").read_text())
    cell.update(config="tiny-shallow", per_layer=["add_request_p90_ms", "admit_ms.ttft"])
    (root / "workloads" / "chat-shallow.json").write_text(json.dumps(cell))
    line = run.run_cell(str(root), "chat-shallow", 5, 0.5, True,
                        trace_dir=str(root / "trace"))
    assert line["correct"] and set(line["metrics"]) == {"add_request_p90_ms",
                                                        "admit_ms.ttft"}
    assert line["metrics"]["add_request_p90_ms"]["unit"] == "ms"
    after = {p: os.path.getmtime(os.path.join(d, p))
             for d, _s, fs in os.walk(BENCH) for p in fs if "__pycache__" not in d}
    assert before == after


def test_expression_reader_is_arithmetic_only():
    assert run.evaluate("100 * tokens / (steps * 8)", {"tokens": 16, "steps": 4}) == 50
    for bad in ("__import__('os')", "tokens.real", "a if b else c", "2 ** 8"):
        with pytest.raises((ValueError, KeyError, SyntaxError)):
            run.evaluate(bad, {"tokens": 1, "a": 1, "b": 1, "c": 1})


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_agrees_with_the_files():
    with open(os.path.join(_REPO, "BENCHMARK.json")) as f:
        b = json.load(f)
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "-m", "perfbench.run"]
    assert b["paths"] == ["perfbench", "tests/perfbench"]
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in b["end_to_end"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        with open(os.path.join(_REPO, c["file"])) as f:
            held = json.load(f)
        assert c["file"] == f"perfbench/configs/{c['name']}.json"
        assert held["source"] == c["source"] and held["reduced"] == c["reduced"]
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        assert set(held["published"]) == set(c["reduced"])
    cells = {}
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        cell = run.load_cell(BENCH, w["name"])        # the cell, its config, its metrics exist
        cells[w["name"]] = cell
        assert cell["config"] == w["config"] and w["config"] in configs
        assert cell["chips"] == w["chips"] and cell["why"] == w["why"]
        assert os.path.exists(os.path.join(BENCH, "drivers", cell["driver"] + ".py"))
        assert "setup_s" in cell["end_to_end"] and len(cell["end_to_end"]) >= 2
        for k in cell["end_to_end"]:
            assert cell["units"][k] == e2e[k]["unit"]
            assert "workloads" not in e2e[k] or w["name"] in e2e[k]["workloads"]
    assert {c["name"] for c in b["configs"]} == {c["config"] for c in cells.values()}
    for m in b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        with open(os.path.join(BENCH, "layer_metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        for k in ("layer", "unit", "better", "moves"):
            assert spec[k] == m[k], (m["name"], k)
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        reporting = [n for n, c in cells.items() if m["name"] in c["per_layer"]]
        assert sorted(reporting) == sorted(m.get("workloads", cells))
        for n in reporting:                       # whoever reports it reports what it moves
            assert m["moves"] in cells[n]["end_to_end"]
    for name, m in e2e.items():                   # each end-to-end metric lists its cells
        reporting = [n for n, c in cells.items() if name in c["end_to_end"]]
        assert sorted(reporting) == sorted(m.get("workloads", cells))
    for cell in cells.values():                   # and no cell reports an unlisted metric
        assert set(cell["per_layer"]) <= {m["name"] for m in b["per_layer"]}


def test_the_command_has_no_cpu_route():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload",
         "train-mistral7b-seq4k", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=_REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0 and out.stdout.strip() == "", out.stdout[-300:]
    assert "TPU" in out.stderr
