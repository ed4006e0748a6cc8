"""perfbench/program_metrics.py and the metric files of PR 25: the two new
reductions on synthetic intervals, the counter reader on missing counters,
every new metric file through the tiny cells, and — on the trace recorded on
the chip in PR 23, whose program has none of the names — the accepted readers
reading what they read before and the new ones reading nothing."""

import gzip
import json
import os
import re
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from perfbench_tiny import BENCH, REPO, SEED, tiny_root  # noqa: E402

sys.path.insert(0, REPO)
from perfbench import program_metrics as pm  # noqa: E402
from perfbench import roofline, run, trace_reduce as tr  # noqa: E402
from perfbench.spans import SPAN_NAMES, Recorder  # noqa: E402

with open(os.path.join(BENCH, "program_metrics.json")) as _f:
    EXTRA = json.load(_f)
NEW = sorted({m for names in EXTRA.values() for m in names})
FLASH = "(^|_)flash_(fwd|bwd_dq|bwd_dkv)_* "


def _cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _spec(name):
    return run._load(BENCH, "layer_metrics", name)


# ------------------------------------------------------- the two reductions

def test_idle_inside_named_spans():
    busy = [(0, 2), (3, 4), (9, 10)]                 # idle: 2-3, 4-9
    spans = [("serving.admit.prefill", 1, 3.5), ("serving.admit.pour", 3.5, 6),
             ("serving.admit.prefill", 8, 12)]
    # 2-3 and 8-9 fall inside prefill spans; 4-6 inside the pour
    assert pm.idle_inside(busy, spans, {"serving.admit.prefill"}, 0, 10) == pytest.approx(2)
    assert pm.idle_inside(busy, spans, {"serving.admit.pour"}, 0, 10) == pytest.approx(2)
    assert pm.idle_inside(busy, spans, {"absent"}, 0, 10) == 0
    # with busy_inside it makes up the spans' cover of the window
    for names in ({"serving.admit.prefill"}, {"serving.admit.pour"}):
        cover = tr.total(tr.clip(tr.union((s, e) for n, s, e in spans if n in names), 0, 10))
        assert (pm.idle_inside(busy, spans, names, 0, 10)
                + tr.busy_inside(busy, spans, names, 0, 10)) == pytest.approx(cover)


def _trace(ops, host):
    return tr.Trace({0: ops}, host, [])


def test_idle_in_span_metric_on_a_synthetic_trace():
    spec = {"reader": {"source": "trace", "reduce": "idle_in_span",
                       "span": "serving.admit.prefill"}}
    ops = [("fusion", 0.0, 2.0), ("fusion", 3.0, 4.0), ("copy", 9.0, 10.0)]
    host = [("add_request", 0.0, 4.0), ("serving.admit.prefill", 1.0, 3.5),
            ("engine.step", 4.0, 10.0)]
    assert pm.read_metric(spec, {}, None, _trace(ops, host), {}, None) == pytest.approx(10.0)
    # a program without the span (the parent): nothing to read, no error
    assert pm.read_metric(spec, {}, None, _trace(ops, host[::2]), {}, None) is None
    assert pm.read_metric(spec, {}, None, tr.Trace({}, host, []), {}, None) is None
    assert pm.read_metric(spec, {}, None, None, {}, None) is None


def test_op_match_roofline_on_a_synthetic_trace():
    cfg = _cfg("mistral-7b")
    facts = {"batch": 1, "seq": 4096}
    least = pm.train_attention_min_s(cfg, facts, "TPU v5 lite")
    spec = {"reader": {"source": "roofline", "fn": "train_attention_min_s",
                       "over": {"op_match": FLASH, "per_span": "train.step"}}}
    t = 10 * least   # the three kernels take ten times the least time per step
    ops = []
    for k in range(2):                                # two steps
        at = k * 1.0
        ops += [("jvp_flash_fwd_ custom-call -> (bf16[1,32,4096,128], f32[1,32,4096,128])", at, at + 0.3 * t),
                ("jvp_rms_norm_fwd_ custom-call -> bf16[4096,4096]", at + 0.3 * t, at + 0.31 * t),
                ("transpose_jvp_flash_bwd_dq__ custom-call -> bf16[1,32,4096,128]", at + 0.4 * t, at + 0.7 * t),
                ("transpose_jvp_flash_bwd_dkv__ custom-call -> (bf16[1,32,4096,128], bf16[1,32,4096,128])", at + 0.7 * t, at + 1.1 * t),
                ("fusion kOutput -> bf16[4096,4096]", at + 1.1 * t, at + 0.9)]
    host = [("train.step", 0.0, 0.01), ("train.step", 1.0, 1.01), ("train.loss_read", 1.01, 2.0)]
    out = {"config": cfg}
    assert pm.read_metric(spec, out, None, _trace(ops, host), facts, "TPU v5 lite") == pytest.approx(10.0)
    # the serving path's names, outside a jvp, match too; the norm and swiglu never
    pat = re.compile(FLASH)
    assert pat.search("flash_fwd custom-call -> bf16[1,16,128,128]")
    assert not pat.search("jvp_swiglu_fwd_ custom-call -> bf16[4096,14336]")
    assert not pat.search("jvp__ custom-call -> bf16[4096,4096]")   # PR 23's names
    # nothing matches (the parent's trace), no chip known: nothing to read
    parent_ops = [("jvp__ custom-call -> bf16[4096,4096]", 0.0, 0.5)]
    assert pm.read_metric(spec, out, None, _trace(parent_ops, host), facts, "TPU v5 lite") is None
    assert pm.read_metric(spec, out, None, _trace(ops, host), facts, None) is None
    assert pm.read_metric(spec, out, None, _trace(ops, host[2:]), facts, "TPU v5 lite") is None


@pytest.mark.parametrize("name,seq", [("mistral-7b", 4096), ("mistral-7b", 512),
                                      ("internlm2-1.8b", 2048)])
def test_attention_term_agrees_with_the_whole_steps_flops(name, seq):
    cfg = _cfg(name)
    assert (6.0 * roofline.matmul_params(cfg) + pm.train_attention_flops_per_token(cfg, seq)
            == pytest.approx(roofline.train_flops_per_token(cfg, seq), rel=1e-12))


def test_attention_of_one_mistral_step_is_the_issues_hand_sum():
    cfg = _cfg("mistral-7b")
    # 2 layers x 12 x 4096 x 2048.5 a token x 4096 tokens = 8.25e11; 4.19 ms at 197 TFLOP/s
    assert 4096 * pm.train_attention_flops_per_token(cfg, 4096) == 2 * 12 * 4096 * 2048.5 * 4096
    assert pm.train_attention_min_s(cfg, {"batch": 1, "seq": 4096}, "TPU v5 lite") * 1e3 \
        == pytest.approx(4.19, abs=0.005)
    with pytest.raises(KeyError, match="no published peaks"):
        pm.train_attention_min_s(cfg, {"batch": 1, "seq": 4096}, "TPU v9")


# --------------------------------------------------------- the counter reader

@pytest.mark.parametrize("counters,expected", [
    ({"admit_prefill_seconds": 3.0, "admissions": 2}, 1500.0),
    ({"admit_prefill_seconds": 0.0, "admissions": 0}, None),   # nothing admitted
    ({"tokens": 5}, None),                                     # the parent: no such counter
    ({}, None)])
def test_counter_metric_with_a_missing_counter_or_no_denominator_is_left_out(counters, expected):
    spec = _spec("admit_prefill_ms.serve_tok")
    out = {"counters": {"decode_stats": counters}}
    assert pm.read_metric(spec, out, None, None, {}, None) == expected


def test_accepted_metrics_go_through_unchanged():
    spec = _spec("batch_occupancy")
    out = {"counters": {"decode_stats": {"tokens": 128, "macro_steps": 2, "last_chunk": 8}}}
    facts = {"max_batch": 16}
    assert pm.read_metric(spec, out, None, None, facts, None) \
        == run.read_metric(spec, out, None, None, facts, None) == 50.0
    rec = Recorder(False)
    rec.add("engine.step", 1.0, 1.5)
    spec = _spec("engine_step_ms")
    assert pm.read_metric(spec, {"window": (0, 2)}, rec, None, {}, None) == 500.0


# ------------------------------------------------------------ the new files

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("name", NEW)
def test_new_metric_file_is_ready_for_benchmark_json(name):
    """What `test_benchmark_json_agrees_with_the_files` will ask of it once a
    `benchmark` issue lists it: its keys, its names, a layer the benchmark
    already names, and cells that report the end-to-end metric it moves."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        b = json.load(f)
    spec = _spec(name)
    assert set(spec) == {"layer", "unit", "better", "moves", "what", "reader"}
    assert NAME.match(name) and UNIT.match(spec["unit"])
    assert spec["better"] in ("lower", "higher")
    assert spec["layer"] in {m["layer"] for m in b["per_layer"]}
    for cell_name, names in EXTRA.items():
        if name in names:
            cell = run.load_cell(BENCH, cell_name)
            assert spec["moves"] in cell["end_to_end"] and spec["moves"] != "setup_s"
            assert name not in cell["per_layer"]
    suffix = {"serve_tok_s": ".serve_tok", "ttft_p90_ms": ".ttft"}.get(spec["moves"])
    assert suffix is None or name.endswith(suffix)
    r = spec["reader"]
    if r["source"] == "counter":
        assert r["fn"] in ("decode_stats", "compile_stats")
    elif r["source"] == "trace":
        from paddle_tpu import profiler

        assert r["span"] in profiler.SPAN_NAMES
    else:
        assert r["fn"] in pm.FUNCTIONS and re.compile(r["over"]["op_match"])


def test_wanted_spans_are_what_the_files_name():
    specs = {m: _spec(m) for m in EXTRA["serve-internlm2-decode-sat"]
             + EXTRA["train-mistral7b-seq4k"] + ["decode_roofline_share", "prefill_share"]}
    assert pm.wanted_spans(specs) == {"serving.admit.prefill", "train.step",
                                      "engine.step", "add_request"}


# ------------------------------------------------- through the tiny cells

def _tiny(tmp_path, name, real, seed=SEED):
    root = tiny_root(tmp_path)
    (root / "program_metrics.json").write_text(json.dumps({name: EXTRA[real]}))
    accepted = run.load_cell(str(root), name)["per_layer"]
    line = pm.run_cell(str(root), name, seed, 0.5, True, trace_dir=str(root / "trace"))
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) <= set(accepted) | set(EXTRA[real])
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] == m["value"]
    json.dumps(line)
    assert run.finish.__module__ == "perfbench.run"     # the accepted one is back
    return line["metrics"]


def test_decode_sat_reports_the_admission_split(tmp_path):
    got = _tiny(tmp_path / "a", "sat", "serve-internlm2-decode-sat")
    # the accepted counter and span metrics are still there
    assert {"engine_step_ms", "admit_ms.serve_tok", "batch_occupancy",
            "window_compiles.serve_tok"} <= set(got)
    for k in ("admit_prefill_ms.serve_tok", "admit_first_token_ms.serve_tok",
              "admit_pour_ms.serve_tok"):
        assert got[k]["unit"] == "ms" and got[k]["value"] > 0
    # seconds beside window_compiles' count: jax also re-traces what is called
    # through a new Python closure without compiling anything
    assert got["window_compile_s.serve_tok"]["unit"] == "s"
    assert 0 <= got["window_compile_s.serve_tok"]["value"] < 60
    assert "idle_in_prefill_share.serve_tok" not in got    # the CPU has no device plane
    # a count: whole, and the same in a second run of the same seed
    ops = got["admit_eager_ops.serve_tok"]["value"]
    assert ops > 0 and ops == int(ops)
    again = _tiny(tmp_path / "b", "sat", "serve-internlm2-decode-sat")
    assert again["admit_eager_ops.serve_tok"]["value"] == ops


def test_chat_reports_the_prefill_and_the_schedulers_queue_wait(tmp_path):
    got = _tiny(tmp_path, "chat", "serve-internlm2-chat-r80")
    assert got["admit_prefill_ms.ttft"]["value"] > 0
    assert 0 <= got["window_compile_s.ttft"]["value"] < 60
    # 20 requests/s on four slots: where a request waited, the scheduler timed
    # it; where none did, the metric is left out (no denominator)
    if "queue_wait_ms.ttft" in got:
        assert got["queue_wait_ms.ttft"]["value"] > 0


def test_train_on_the_cpu_has_no_kernels_to_read(tmp_path):
    got = _tiny(tmp_path, "train", "train-mistral7b-seq4k")
    assert "train_dispatch_ms" in got and "flash_roofline_share" not in got


def test_the_command_has_no_cpu_route():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "perfbench.program_metrics", "--workload",
         "train-mistral7b-seq4k", "--seed", str(SEED), "--seconds", "1",
         "--trace", "1"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0 and out.stdout.strip() == "", out.stdout[-300:]
    assert "TPU" in out.stderr


# --------------------------------- the trace recorded on the chip in PR 23

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("fixture") / "fixture.xplane.pb"
    with gzip.open(os.path.join(BENCH, "fixtures",
                                "train-mistral7b-seq4k.v5e.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    return str(path)


def test_accepted_readers_read_on_the_recorded_trace_what_they_read_on_the_parent(recorded):
    """Pinned with the parent's perfbench (ceb9887), which this PR does not
    edit: any later change to an accepted reduction shows here."""
    trace = tr.read_xplane(recorded, keep_host=set(SPAN_NAMES))
    red = tr.reduce_trace(trace, SPAN_NAMES)
    assert red["busy_s"] == pytest.approx(0.6115228450000236, rel=1e-12)
    assert red["window_s"] == pytest.approx(0.6260687030000001, rel=1e-12)
    assert red["device_ops"][0] == [
        "fusion kOutput -> (bf16[4096,28672], f32[4096,28672], f32[4096,28672], f32[4096,28672])",
        pytest.approx(0.08989831299999995, rel=1e-9)]
    assert red["idle_gaps"] == [["train.loss_read", pytest.approx(0.008112855999976742, rel=1e-9)],
                                ["train.step", pytest.approx(0.00643300199999966, rel=1e-9)]]
    cell = run.load_cell(BENCH, "train-mistral7b-seq4k")
    out = {"config": cell["config_file"], "window": (0, 0), "counters": {}}
    facts = {"batch": 1, "seq": 4096}
    want = {"train_dispatch_ms": None,      # a host-clock span: not in a trace
            "train_device_mfu": 49.28565865119232,
            "device_idle_share.train_tok": 2.3233645014158144}
    assert list(cell["metric_files"]) == list(want)
    for name, spec in cell["metric_files"].items():
        for reader in (run.read_metric, pm.read_metric):
            got = reader(spec, out, Recorder(False), trace, facts, "TPU v5 lite")
            assert got == (want[name] if want[name] is None
                           else pytest.approx(want[name], rel=1e-12)), name


def test_new_readers_find_nothing_on_the_parents_trace_and_do_not_raise(recorded):
    """PR 23's program named no kernel (`jvp__`, `transpose_jvp___`) and had
    no span of its own: the driver's traced run of the parent must get a
    line without the new metrics, not an error."""
    specs = {m: _spec(m) for m in NEW}
    trace = tr.read_xplane(recorded, keep_host=set(SPAN_NAMES) | pm.wanted_spans(specs))
    names = {n for n, _s, _e in trace.device_ops[0]}
    assert any(n.startswith("jvp__ custom-call") for n in names)
    cell = run.load_cell(BENCH, "train-mistral7b-seq4k")
    out = {"config": cell["config_file"], "window": (0, 0),
           "counters": {"compile_stats": {"compiles": 0}, "decode_stats": {"tokens": 1}}}
    for name, spec in specs.items():
        assert pm.read_metric(spec, out, Recorder(False), trace,
                              {"batch": 1, "seq": 4096}, "TPU v5 lite") is None, name
