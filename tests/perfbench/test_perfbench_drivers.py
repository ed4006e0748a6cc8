"""Each driver end to end at a tiny size on the CPU (rehearsal 1): keys and
counts of its result line only — a CPU run gives no time, rate or share worth
a name."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from perfbench_tiny import REPO, SEED, real_cell, tiny_root  # noqa: E402

sys.path.insert(0, REPO)
from perfbench import run  # noqa: E402


def _check_line(line, cell, traced):
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    names = set(line["metrics"])
    if traced:
        # on the CPU there is no device plane: trace readers return nothing and
        # their metrics are left out; span and counter readers still report
        assert names <= set(cell["per_layer"]) and names
        assert "busy_s" not in line["device"]
    else:
        assert names == set(cell["end_to_end"]) and "setup_s" in names
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] == m["value"]
    json.dumps(line)


@pytest.mark.parametrize("name,real", [
    ("train", "train-mistral7b-seq4k"), ("sat", "serve-internlm2-decode-sat"),
    ("chat", "serve-internlm2-chat-r80")])
def test_driver_end_to_end_tiny(tmp_path, name, real):
    root, cell = tiny_root(tmp_path), real_cell(real)
    plain = run.run_cell(str(root), name, SEED, 0.5, False)
    _check_line(plain, cell, traced=False)
    traced = run.run_cell(str(root), name, SEED, 0.5, True,
                          trace_dir=str(root / "trace"))
    _check_line(traced, cell, traced=True)
    if name == "chat":
        assert traced["metrics"]["window_compiles.ttft"]["value"] == 0
    if name == "sat":
        assert 0 < traced["metrics"]["batch_occupancy"]["value"] <= 100
        assert traced["metrics"]["window_compiles.serve_tok"]["value"] == 0


def test_closed_loop_window_is_the_same_work_for_every_seed(tmp_path, capsys):
    """The window is iterations, not seconds, and the order of lengths is
    fixed: another seed admits as many requests and emits as many tokens."""
    root = tiny_root(tmp_path)
    seen = []
    for seed in (SEED, SEED + 1):
        line = run.run_cell(str(root), "sat", seed, 0.5, False)
        said = [l for l in capsys.readouterr().out.splitlines() if "samples:" in l]
        seen.append((line["attempted"], said[0].split(";")[0]))
    assert seen[0] == seen[1] and "20 iterations" in seen[0][1]
