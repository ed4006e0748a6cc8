"""PR 36's metric files (set-up's layers, and the window's lowering seconds and
framework compiles): new data files only, read by the ACCEPTED counter reader
of `perfbench/run.py` from the accepted serving driver's window delta, through
`run.run_cell` on a temporary root whose tiny cell lists them.  None is in
`BENCHMARK.json` or in a cell file yet (PERF.md section 7)."""

import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from perfbench_tiny import BENCH, REPO, SEED, tiny_root  # noqa: E402

sys.path.insert(0, REPO)
from perfbench import run  # noqa: E402
from perfbench.drivers import _serve  # noqa: E402

WINDOW = ["window_lower_s.serve_tok", "window_framework_compiles.serve_tok"]
SETUP = ["setup_before_import_s", "setup_import_s", "setup_engine_build_s", "setup_optimizer_state_s",
         "setup_trace_s", "setup_lower_s", "setup_compile_s",
         "setup_cache_read_s", "setup_first_use_s", "setup_unaccounted_s"]


def _spec(name):
    return run._load(BENCH, "layer_metrics", name)


def _layers_of_perf_md():
    """The first column of PERF.md's table of layers (section 3)."""
    with open(os.path.join(REPO, "PERF.md")) as f:
        text = f.read()
    table = text[text.index("## 3. Layers"):text.index("## 4. Cells")]
    return {m.group(1).strip() for m in re.finditer(r"^\| ([^|]+?) \|", table, re.M)
            if m.group(1).strip() not in ("layer", "---")}


@pytest.mark.parametrize("name", WINDOW + SETUP)
def test_the_file_is_data_for_the_accepted_reader(name):
    from paddle_tpu import profiler

    spec = _spec(name)
    assert set(spec) == {"layer", "unit", "better", "moves", "what", "reader"}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert spec["moves"] in {m["name"] for m in bench["end_to_end"]}
    assert spec["moves"] == ("setup_s" if name in SETUP else "serve_tok_s")
    assert spec["layer"] in _layers_of_perf_md()
    assert spec["layer"] in ("compile cache", "entry points")
    assert spec["better"] == "lower" and spec["unit"] in ("s", "count")
    # not in the benchmark yet: a benchmark issue appends it (PERF.md 7)
    assert name not in {m["name"] for m in bench["per_layer"]}
    r = spec["reader"]
    assert set(r) == {"source", "fn", "expr"} and r["source"] == "counter"
    # arithmetic only, over keys the program's reader really has
    stats = getattr(profiler, r["fn"])()
    numbers = {k: v for k, v in stats.items() if isinstance(v, (int, float))}
    assert isinstance(run.evaluate(r["expr"], numbers), float)
    assert r["fn"] == ("startup_stats" if name in SETUP else "compile_stats")
    if name in SETUP:
        assert "no accepted driver hands over yet" in spec["what"]
    # an accepted driver's counters hold no such function yet: nothing to
    # read, no error (the line leaves the metric out)
    assert run.read_metric(spec, {"counters": {"decode_stats": {"tokens": 1}}},
                           None, None, {}, None) is None


def _sat_root(tmp_path, names):
    root = tiny_root(tmp_path)
    cell = json.loads((root / "workloads" / "sat.json").read_text())
    cell["per_layer"] = names
    (root / "workloads" / "sat-setup.json").write_text(json.dumps(cell))
    return root


@pytest.fixture(scope="module")
def primed(tmp_path_factory):
    """One run of the tiny saturated cell, so that the reference's eager
    pieces (which the accepted driver counts into the window's delta: PERF.md
    7 (c)) are in this process's caches."""
    root = _sat_root(tmp_path_factory.mktemp("primed"), WINDOW + SETUP)
    return root, run.run_cell(str(root), "sat-setup", SEED, 0.5, True,
                              trace_dir=str(root / "trace"))


def test_a_warm_window_builds_no_framework_program(primed):
    root, first = primed
    assert first["correct"]
    # whatever the reference compiled, the framework's own programs were all
    # built before the window opened
    assert first["metrics"]["window_framework_compiles.serve_tok"] == {
        "value": 0.0, "unit": "count"}
    # ... and so is their lowering (the reference's pieces, lowered inside
    # this first window, are no framework program's)
    assert first["metrics"]["window_lower_s.serve_tok"] == {"value": 0.0,
                                                            "unit": "s"}
    # no accepted driver hands startup_stats over: the set-up metrics are
    # left out of the line, and nothing raises
    assert set(first["metrics"]) == set(WINDOW)
    # again, with the reference's pieces cached: nothing is lowered at all
    again = run.run_cell(str(root), "sat-setup", SEED, 0.5, True,
                         trace_dir=str(root / "trace"))
    assert again["metrics"]["window_framework_compiles.serve_tok"]["value"] == 0
    assert again["metrics"]["window_lower_s.serve_tok"] == {"value": 0.0,
                                                            "unit": "s"}


def test_a_program_built_inside_the_window_is_counted_and_timed(primed, monkeypatch):
    root, _first = primed
    open_window = _serve.Serving.open_window

    def forget_the_macro_step(self):
        t = open_window(self)
        self.engine._step_fns.clear()      # the window's first step rebuilds it
        return t

    monkeypatch.setattr(_serve.Serving, "open_window", forget_the_macro_step)
    line = run.run_cell(str(root), "sat-setup", SEED, 0.5, True,
                        trace_dir=str(root / "trace"))
    assert line["correct"]
    assert line["metrics"]["window_framework_compiles.serve_tok"]["value"] == 1
    assert line["metrics"]["window_lower_s.serve_tok"]["value"] > 0


def test_the_setup_metrics_read_a_snapshot_taken_at_the_windows_opening(
        primed, monkeypatch):
    """The one line a driver needs (PERF.md section 7): `startup_stats()` at
    `open_window`, handed over in `counters`."""
    from paddle_tpu import profiler

    root, _first = primed
    open_window, counters = _serve.Serving.open_window, _serve.Serving.counters
    held = {}

    def snapshot(self):
        t = open_window(self)
        held["at"] = profiler.startup_stats()
        return t

    monkeypatch.setattr(_serve.Serving, "open_window", snapshot)
    monkeypatch.setattr(_serve.Serving, "counters",
                        lambda self: {**counters(self), "startup_stats": held["at"]})
    before = profiler.startup_stats()
    line = run.run_cell(str(root), "sat-setup", SEED, 0.5, True,
                        trace_dir=str(root / "trace"))
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(m) == set(WINDOW + SETUP)
    assert all(line["metrics"][k]["unit"] == "s" for k in SETUP)
    at = held["at"]
    assert m["setup_import_s"] == at["import_seconds"] > 0
    assert m["setup_before_import_s"] == at["before_import_seconds"] > 0
    assert m["setup_optimizer_state_s"] == at["train_optimizer_state_seconds"]
    # this run's engine and first uses are in the snapshot: set-up, not window
    assert m["setup_engine_build_s"] > before["engine_build_seconds"]
    assert m["setup_first_use_s"] > before["program_first_use_seconds"]
    assert at["programs_first_used"] > before["programs_first_used"]
    assert m["setup_lower_s"] > before["lower_seconds"]
    assert m["setup_unaccounted_s"] == pytest.approx(
        at["elapsed_seconds"] - at["accounted_seconds"])
    for k in ("setup_trace_s", "setup_compile_s", "setup_cache_read_s"):
        assert m[k] >= 0


def test_no_accepted_file_lists_the_new_names():
    """Only new files: no cell file, `program_metrics.json` or
    `BENCHMARK.json` names them."""
    names = set(WINDOW + SETUP)
    for d, _s, files in os.walk(os.path.join(BENCH, "workloads")):
        for fn in files:
            with open(os.path.join(d, fn)) as f:
                assert not names & set(json.load(f).get("per_layer", ())), fn
    with open(os.path.join(BENCH, "program_metrics.json")) as f:
        assert not names & {m for ms in json.load(f).values() for m in ms}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        assert not any(n in f.read() for n in names)
