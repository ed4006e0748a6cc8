"""The compile ledger (`_core/compile_cache.py`: every program jax builds,
timed by name through trace, lowering, compile or cache read) and start-up's
account of itself (`profiler/startup.py`: `startup_stats()`, the spans
`serving.engine.build*` and `program.first_use`)."""

import json
import os
import subprocess
import sys

import jax
import jax.monitoring as monitoring
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler, serving
from paddle_tpu._core import compile_cache as cc
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.profiler.statistics import compile_cache_line, startup_line

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROW_KEYS = {"traces", "trace_seconds", "lowerings", "lower_seconds",
            "compiles", "compile_seconds", "cache_hits", "cache_misses",
            "cache_read_seconds", "first_seen_s"}


def _delta(a, b):
    return {k: b[k] - a[k] for k in b
            if isinstance(b[k], (int, float)) and not isinstance(b[k], bool)}


# ----------------------------------------------- (a) the ledger, from jax itself

def test_a_fresh_jitted_function_is_one_lowering_under_its_own_name():
    @jax.jit
    def ledger_probe_one(x):
        return jnp.tanh(x) * 3 + 1

    x = jnp.arange(7, dtype=jnp.float32)
    jax.block_until_ready(x)
    c0 = profiler.compile_stats()
    ledger_probe_one(x)
    c1 = profiler.compile_stats()
    d = _delta(c0, c1)
    assert d["lowerings"] == 1 and d["lower_seconds"] > 0
    assert d["compiles"] == 1 and d["traces"] >= 1
    row = c1["by_program"]["jit_ledger_probe_one"]   # ONE form: the device trace's
    assert set(row) == ROW_KEYS
    assert (row["traces"], row["lowerings"], row["compiles"]) == (1, 1, 1)
    assert 0 < row["lower_seconds"] == pytest.approx(d["lower_seconds"])
    assert row["first_seen_s"] > 0
    assert "ledger_probe_one" not in c1["by_program"]
    assert "jit(ledger_probe_one)" not in c1["by_program"]
    # a second call builds nothing
    ledger_probe_one(x)
    c2 = profiler.compile_stats()
    assert not any(_delta(c1, c2).values())
    assert c2["by_program"]["jit_ledger_probe_one"] == row
    # a test's own function is no framework program
    assert d["framework_compiles"] == 0 and d["framework_lower_seconds"] == 0


def test_a_helper_traced_inside_a_program_counts_once_in_the_wall():
    @jax.jit
    def ledger_helper(x):
        return jnp.sin(x) + jnp.cos(x)

    @jax.jit
    def ledger_outer(x):
        return ledger_helper(x) * ledger_helper(x + 1)

    x = jnp.arange(5, dtype=jnp.float32)
    jax.block_until_ready(x)
    c0, w0 = profiler.compile_stats(), cc.ledger_wall_seconds()
    ledger_outer(x)
    c1, w1 = profiler.compile_stats(), cc.ledger_wall_seconds()
    d = _delta(c0, c1)
    rows = c1["by_program"]
    # the helper's trace ran inside the outer one's: its seconds are in both
    # rows, `nested_seconds` says so, and the wall counts them once
    assert rows["jit_ledger_helper"]["traces"] >= 1
    assert rows["jit_ledger_helper"]["lowerings"] == 0   # lowered as a call
    assert (rows["jit_ledger_outer"]["trace_seconds"]
            >= rows["jit_ledger_helper"]["trace_seconds"] > 0)
    assert d["nested_seconds"] >= rows["jit_ledger_helper"]["trace_seconds"]
    wall = (d["trace_seconds"] + d["lower_seconds"] - d["nested_seconds"]
            + d["compile_miss_seconds"] + d["cache_read_seconds"])
    assert w1 - w0 == pytest.approx(wall) and wall > 0
    assert wall < d["trace_seconds"] + d["lower_seconds"] + d["compile_seconds"]


_CACHE_DRIVE = """
import json, jax, jax.numpy as jnp
from paddle_tpu._core import compile_cache as cc
from paddle_tpu import profiler
print("DIR", cc.enable())

@jax.jit
def cached_f(x):
    return jnp.tanh(x @ x.T).sum()

@jax.jit
def cached_g(x):
    return jnp.exp(x).mean(axis=0)

x = jnp.ones((8, 8))
jax.block_until_ready(x)
c0 = profiler.compile_stats()
cached_f(x); cached_g(x)
c1 = profiler.compile_stats()
jax.clear_caches()            # what a second process would find: disk only
cached_f(x)
c2 = profiler.compile_stats()
keep = lambda c: {k: v for k, v in c.items() if k != "by_program"}
print("STATS", json.dumps({"c0": keep(c0), "c1": keep(c1), "c2": keep(c2),
      "rows1": {n: c1["by_program"][n] for n in ("jit_cached_f", "jit_cached_g")},
      "rows2": c2["by_program"]}))
"""


@pytest.fixture(scope="module")
def cache_drive(tmp_path_factory):
    """Two programs compiled into an EMPTY persistent cache, then one of
    them built again from disk alone, in a process of its own (the cache
    directory is the environment's, by the one rule)."""
    cache = tmp_path_factory.mktemp("jax_cache")
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_DRIVE], capture_output=True, text=True,
        cwd=REPO, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
             "JAX_COMPILATION_CACHE_DIR": str(cache)})
    lines = dict(l.split(" ", 1) for l in out.stdout.splitlines()
                 if l.startswith(("DIR ", "STATS ")))
    assert "STATS" in lines, out.stderr[-3000:]
    assert lines["DIR"] == str(cache)
    return json.loads(lines["STATS"])


def test_a_cold_build_is_a_miss_whose_seconds_are_compile_seconds(cache_drive):
    d = _delta(cache_drive["c0"], cache_drive["c1"])
    assert d["persistent_cache_misses"] == 2 and d["persistent_cache_hits"] == 0
    assert d["cache_read_seconds"] == 0
    assert 0 < d["compile_miss_seconds"] == pytest.approx(d["compile_seconds"])
    for name in ("jit_cached_f", "jit_cached_g"):
        row = cache_drive["rows1"][name]
        assert (row["cache_misses"], row["cache_hits"]) == (1, 0)
        assert row["cache_read_seconds"] == 0 and row["compile_seconds"] > 0


def test_a_build_from_disk_is_a_hit_read_and_no_miss_on_its_own_row(cache_drive):
    """jax emits the hit and the retrieval time INSIDE the program's
    backend_compile event and with no name: held here, not assumed."""
    d = _delta(cache_drive["c1"], cache_drive["c2"])
    assert d["persistent_cache_hits"] == 1 and d["persistent_cache_misses"] == 0
    assert d["cache_read_seconds"] > 0 and d["compile_miss_seconds"] == 0
    assert d["lowerings"] == 1 and d["lower_seconds"] > 0   # no cache holds it
    # the read is inside backend_compile, which encloses it
    assert d["compile_seconds"] >= d["cache_read_seconds"]
    f, g = (cache_drive["rows2"][n] for n in ("jit_cached_f", "jit_cached_g"))
    assert (f["cache_hits"], f["cache_misses"], f["lowerings"]) == (1, 1, 2)
    assert f["cache_read_seconds"] == pytest.approx(d["cache_read_seconds"])
    assert (g["cache_hits"], g["cache_misses"], g["lowerings"]) == (0, 1, 1)
    assert g["cache_read_seconds"] == 0
    # nothing landed on a row that enclosed nothing
    rows = cache_drive["rows2"]
    assert sum(r["cache_hits"] for r in rows.values()) == 1
    assert sum(r["cache_read_seconds"] for r in rows.values()) \
        == pytest.approx(d["cache_read_seconds"])


# --------------------------- (a) the ledger, event by event (jax's own emitters)

TRACE, LOWER, COMPILE = cc._TRACE_EVENT, cc._LOWER_EVENT, cc._COMPILE_EVENT


def _stage(event, name, seconds, inside=()):
    """One stage event as jax emits it: a scalar when it opens, whatever
    happens inside, its duration when it closes."""
    monitoring.record_scalar(event, 0.0, fun_name=name)
    for emit in inside:
        emit()
    monitoring.record_event_duration_secs(event, seconds, fun_name=name)


@pytest.fixture
def clean_ledger():
    """An empty table for synthetic events; afterwards the process's own
    ledger is as it was (start-up's account must not hold made-up seconds)."""
    saved = (dict(cc._stats), dict(cc._carried),
             {n: dict(r) for n, r in cc._by_program.items()})
    profiler.compile_stats(reset=True)
    yield
    cc._stats.update(saved[0])
    cc._carried.update(saved[1])
    cc._by_program.clear()
    cc._by_program.update(saved[2])


def test_hit_miss_and_retrieval_land_on_the_program_that_encloses_them(clean_ledger):
    hit = lambda: monitoring.record_event(cc._HIT_EVENT)           # noqa: E731
    miss = lambda: monitoring.record_event(cc._MISS_EVENT)         # noqa: E731
    read = lambda: monitoring.record_event_duration_secs(cc._READ_EVENT, 0.25)  # noqa: E731
    _stage(TRACE, "prog_a", 0.5)
    _stage(LOWER, "jit(prog_a)", 1.5)
    _stage(COMPILE, "jit(prog_a)", 0.3, inside=(hit, read))
    _stage(COMPILE, "jit(prog_b)", 2.0, inside=(miss,))
    _stage(COMPILE, "jit(prog_c)", 4.0)        # no cache in use: XLA compiled
    c = profiler.compile_stats()
    a, b, cc_row = (c["by_program"][n] for n in ("jit_prog_a", "jit_prog_b",
                                                 "jit_prog_c"))
    assert (a["cache_hits"], a["cache_misses"], a["cache_read_seconds"]) == (1, 0, 0.25)
    assert (a["trace_seconds"], a["lower_seconds"], a["compile_seconds"]) == (0.5, 1.5, 0.3)
    assert (b["cache_hits"], b["cache_misses"], b["cache_read_seconds"]) == (0, 1, 0.0)
    assert (cc_row["cache_hits"], cc_row["cache_misses"]) == (0, 0)
    assert c["persistent_cache_hits"] == 1 and c["persistent_cache_misses"] == 1
    assert c["cache_read_seconds"] == 0.25
    assert c["compile_miss_seconds"] == 6.0 and c["compile_seconds"] == 6.3
    assert c["nested_seconds"] == 0
    assert cc.ledger_wall_seconds() - _carried_wall() == pytest.approx(
        0.5 + 1.5 + 6.0 + 0.25)


def _carried_wall():
    c = cc._carried
    return (c["trace_seconds"] + c["lower_seconds"] - c["nested_seconds"]
            + c["compile_miss_seconds"] + c["cache_read_seconds"])


def test_an_event_inside_another_is_nested_and_the_wall_counts_it_once(clean_ledger):
    inner = lambda: _stage(TRACE, "helper", 0.2)                       # noqa: E731
    eager = lambda: _stage(COMPILE, "jit(convert_element_type)", 0.1)  # noqa: E731
    _stage(TRACE, "prog", 1.0, inside=(inner, inner, eager))
    c = profiler.compile_stats()
    assert c["traces"] == 3 and c["trace_seconds"] == pytest.approx(1.4)
    assert c["nested_seconds"] == pytest.approx(0.5)
    # the program's trace second, with the eager compile in it counted there
    assert cc.ledger_wall_seconds() - _carried_wall() == pytest.approx(1.0)
    assert c["by_program"]["jit_helper"]["traces"] == 2
    assert cc._open.depth == 0


def test_this_jax_says_when_a_stage_opens():
    """`nested_seconds` rests on the scalar jax records as a stage OPENS: a
    jax that stopped emitting it would read every event as outermost, and
    `accounted_seconds` could pass `elapsed_seconds`.  Held here from jax's
    own emitter, not a synthetic one."""
    opened, closed = [], []

    def on_scalar(event, value, **kw):
        if event in cc._STAGES:
            opened.append((event, cc._open.depth))

    def on_duration(event, duration, **kw):
        if event in cc._STAGES:
            closed.append(event)

    monitoring.register_scalar_listener(on_scalar)
    monitoring.register_event_duration_secs_listener(on_duration)
    try:
        @jax.jit
        def ledger_opens(x):
            return x * 5 - 2

        jax.block_until_ready(ledger_opens(jnp.ones(3)))
    finally:
        monitoring.unregister_scalar_listener(on_scalar)
        monitoring.unregister_event_duration_listener(on_duration)
    # every stage that closed had opened, and ours (registered first) had
    # counted the opening before this listener saw it
    assert sorted(e for e, _d in opened) == sorted(closed)
    assert {TRACE, LOWER, COMPILE} <= set(closed)
    assert all(depth >= 1 for _e, depth in opened)
    assert cc._open.depth == 0


def test_the_table_is_bounded_and_framework_programs_keep_their_rows(clean_ledger):
    for i in range(cc.MAX_PROGRAM_ROWS + 7):
        _stage(LOWER, f"jit(crumb_{i})", 1.0)
    _stage(LOWER, "jit(decode_macro_step)", 2.0)
    _stage(COMPILE, "jit(decode_macro_step)", 3.0)
    _stage(COMPILE, "jit(logit_rows)", 5.0)      # a check's program: not the hot path's
    c = profiler.compile_stats()
    rows = c["by_program"]
    assert len(rows) == cc.MAX_PROGRAM_ROWS + 2          # (other), the macro-step
    assert rows["(other)"]["lowerings"] == 7 and rows["(other)"]["compiles"] == 1
    assert rows["jit_decode_macro_step"]["lower_seconds"] == 2.0
    assert c["lowerings"] == cc.MAX_PROGRAM_ROWS + 8
    assert (c["framework_compiles"], c["framework_lower_seconds"],
            c["framework_compile_seconds"]) == (1, 2.0, 3.0)
    assert "jit_logit_rows" not in profiler.PROGRAM_NAMES
    # the lower layer lists no program: the exemption is what profiler registered
    assert cc._kept_rows >= set(profiler.PROGRAM_NAMES)
    assert not hasattr(cc, "PROGRAM_NAMES")
    assert "framework_compiles" not in cc.compile_stats()


@pytest.mark.parametrize("jax_says,kept", [
    ("decode_macro_step", "jit_decode_macro_step"),        # the trace event
    ("jit(decode_macro_step)", "jit_decode_macro_step"),   # lowering, compile
    ("pmap(step)", "pmap_step"),
    ("<lambda>", "jit_<lambda>"),
    (None, "(unnamed)"),
])
def test_one_form_of_a_programs_name(jax_says, kept):
    assert cc._program_name(jax_says) == kept


def test_a_reset_does_not_turn_startups_account_back(clean_ledger):
    _stage(LOWER, "jit(prog)", 1.0)
    w, s = cc.ledger_wall_seconds(), profiler.startup_stats()
    profiler.compile_stats(reset=True)
    assert profiler.compile_stats()["lower_seconds"] == 0
    assert not profiler.compile_stats()["by_program"]
    assert cc.ledger_wall_seconds() == pytest.approx(w)
    assert profiler.startup_stats()["lower_seconds"] == pytest.approx(s["lower_seconds"])


# ------------------------------------------------------- (b) start-up's account

def _engine():
    paddle.seed(0)
    m = LlamaForCausalLM(llama_tiny(dtype="float32"))
    m.eval()
    return m, lambda: serving.GenerationEngine(m, max_batch=2, block_size=8,
                                               num_blocks=16)


def _wave(eng, tag):
    eng.add_request(tag + "a", np.arange(11, dtype=np.int32)[None] % 7,
                    max_new_tokens=10)
    eng.add_request(tag + "b", np.arange(5, dtype=np.int32)[None] % 7,
                    max_new_tokens=4)
    while eng.has_work():
        eng.step()


def s2_is_monotonic(a, b):
    return all(b[k] >= a[k] for k in a if k != "unaccounted_seconds")


def _startup_spans(p):
    return [s for s in p._buffer.spans
            if s.name.startswith(("serving.engine.build", "program.first_use"))]


def test_an_engines_construction_and_first_wave_fill_the_account():
    _m, make = _engine()

    @jax.jit
    def the_tests_own(x):
        return x * 2 + 1

    s0, c0 = profiler.startup_stats(), profiler.compile_stats()
    with profiler.Profiler(timer_only=True) as p:
        eng = make()
        _wave(eng, "1")
        the_tests_own(jnp.ones(3))
    s1, c1 = profiler.startup_stats(), profiler.compile_stats()
    d = _delta(s0, s1)
    spans = _startup_spans(p)
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    # construction: one build span, its parts inside it, counters at the
    # same boundaries
    (build,) = by_name["serving.engine.build"]
    parts = by_name["serving.engine.build.pools"] + by_name["serving.engine.build.state"]
    assert all(s.parent == "serving.engine.build" for s in parts)
    assert d["engine_build_seconds"] == pytest.approx(
        (build.end_ns - build.start_ns) / 1e9, rel=0.2, abs=2e-3)
    assert 0 < d["engine_pool_alloc_seconds"] < d["engine_build_seconds"]
    assert 0 < d["engine_state_alloc_seconds"] < d["engine_build_seconds"]
    # first uses: two prefill buckets and the macro-step (a pour shape has
    # no span: the ledger's row times it, below)
    firsts = by_name["program.first_use"]
    used = sorted((s.args["program"], s.args["key"]) for s in firsts)
    assert used == [("jit_decode_macro_step", "8"),
                    ("jit_prefill_program", "(16, 0)"),
                    ("jit_prefill_program", "(8, 0)")]
    assert d["programs_first_used"] == 3
    assert d["program_first_use_seconds"] == pytest.approx(
        sum(s.end_ns - s.start_ns for s in firsts) / 1e9, rel=0.2, abs=5e-3)
    # the framework's compiles are the engine's programs, not the test's own
    dc = _delta(c0, c1)
    # (the prefill programs and the macro-step are this engine's closures;
    # the pour is one jitted function a process, and an earlier engine of
    # this geometry in the same process may have compiled its shapes)
    assert 3 <= dc["framework_compiles"] <= 5 and dc["framework_lower_seconds"] > 0
    assert c1["by_program"]["jit_the_tests_own"]["compiles"] >= 1
    assert dc["compiles"] > dc["framework_compiles"]
    # one dict accounts for the process
    assert s1["accounted_seconds"] == pytest.approx(
        s1["elapsed_seconds"] - s1["unaccounted_seconds"])
    assert 0 < s1["accounted_seconds"] <= s1["elapsed_seconds"] + 1e-6
    assert s1["import_seconds"] >= s1["import_jax_seconds"] >= 0
    assert s1["import_seconds"] > 0.1
    # this process imported jax and pytest before the package: /proc says so
    assert s1["before_import_seconds"] > 0.1
    assert s2_is_monotonic(s0, s1)
    assert d["lower_seconds"] > 0 and d["trace_seconds"] > 0

    # a second wave of the same shapes, and a second engine's steady state,
    # add no first use and no span
    with profiler.Profiler(timer_only=True) as p2:
        _wave(eng, "2")
    assert not _startup_spans(p2)
    s2 = profiler.startup_stats()
    assert s2["programs_first_used"] == s1["programs_first_used"]
    assert s2["program_first_use_seconds"] == s1["program_first_use_seconds"]
    assert s2["engine_build_seconds"] == s1["engine_build_seconds"]
    assert eng._step_fns[8].__name__ == "decode_macro_step"   # the jitted fn itself


def test_a_train_steps_build_is_one_part_with_its_first_use_inside():
    import paddle_tpu.optimizer as opt
    from paddle_tpu.jit import TrainStep

    paddle.seed(1)
    m = LlamaForCausalLM(llama_tiny(dtype="float32"))
    step = TrainStep(m, opt.AdamW(1e-3, parameters=m.parameters()),
                     lambda mm, i, l: mm(i, l)[0])
    ids = paddle.randint(0, 1024, [2, 16])
    s0 = profiler.startup_stats()
    with profiler.Profiler(timer_only=True) as p:
        step(ids, ids)
    s1 = profiler.startup_stats()
    step(ids, ids)
    s2 = profiler.startup_stats()
    d = _delta(s0, s1)
    assert d["programs_first_used"] == 1
    (first,) = [s for s in p._buffer.spans if s.name == "program.first_use"]
    assert first.args == {"program": "jit_train_step", "key": "[(2, 16), (2, 16)]"}
    assert first.parent == "jit.train_step.build"
    # the parts lie inside the build, and the first use (which waits for the
    # loss) encloses the first call
    assert d["train_build_seconds"] >= (d["train_optimizer_state_seconds"]
                                        + d["program_first_use_seconds"]) > 0
    assert d["program_first_use_seconds"] >= d["train_build_trace_seconds"] > 0
    # ... so the build is accounted ONCE, not its parts again
    assert d["accounted_seconds"] <= d["elapsed_seconds"] + 1e-6
    assert d["accounted_seconds"] >= d["train_build_seconds"]
    # a built step adds nothing
    for k in ("train_build_seconds", "train_optimizer_state_seconds",
              "train_build_trace_seconds", "programs_first_used"):
        assert s2[k] == s1[k]


# ------------------------------------------------------ (c) the operator's lines

def test_the_compile_line_has_the_lowering_and_the_measured_read():
    line = compile_cache_line({
        "traces": 9, "trace_seconds": 1.5, "lowerings": 4, "lower_seconds": 7.25,
        "compiles": 4, "compile_seconds": 3.0, "compile_miss_seconds": 0.5,
        "persistent_cache_hits": 3, "persistent_cache_misses": 1,
        "cache_read_seconds": 2.25, "cache_dir": "/c"})
    assert "lowerings=4 (7.25s)" in line and "read=2.25s" in line
    assert "0.50s of it XLA compiling" in line and "saved" not in line
    assert compile_cache_line({"traces": 0, "compiles": 0}) == ""


def test_the_startup_line_names_every_phase_and_the_slowest_programs():
    stats = profiler.startup_stats()
    rows = {f"jit_p{i}": {"trace_seconds": 0.0, "lower_seconds": float(i),
                          "compile_seconds": 0.5, "lowerings": i}
            for i in range(5)}
    line = startup_line(stats, rows)
    assert "\n" not in line and line.startswith("Start-up: ")
    for word in ("import", "engine build", "train build", "first use of",
                 "unaccounted", "lowering", "cache read"):
        assert word in line
    assert "jit_p4 4.5s" in line and "jit_p2 2.5s" in line
    assert "jit_p1" not in line                 # three rows, slowest first
    assert "slowest" not in startup_line(stats)
    assert set(stats) >= {
        "before_import_seconds", "elapsed_seconds", "import_seconds", "import_jax_seconds",
        "engine_build_seconds", "engine_pool_alloc_seconds",
        "engine_state_alloc_seconds", "train_build_seconds",
        "train_optimizer_state_seconds", "train_build_trace_seconds",
        "program_first_use_seconds", "programs_first_used", "trace_seconds",
        "lower_seconds", "compile_miss_seconds", "cache_read_seconds",
        "compile_outside_seconds", "accounted_seconds", "unaccounted_seconds"}
    assert all(isinstance(v, (int, float)) for v in stats.values())


def test_the_summary_prints_both_lines(capsys):
    jax.jit(lambda x: x - 1)(jnp.ones(2))
    with profiler.Profiler(timer_only=True) as p:
        pass
    out = p.summary()
    capsys.readouterr()
    assert "XLA compile: traces=" in out and "Start-up: " in out


@pytest.mark.parametrize("name", ["export_protobuf", "load_profiler_result",
                                  "_last_profiler"])
def test_what_nothing_read_is_gone(name):
    assert not hasattr(profiler, name) and name not in profiler.__all__
    assert "compile_seconds_saved" not in profiler.compile_stats()


@pytest.mark.parametrize("name", ["serving.engine.build",
                                  "serving.engine.build.pools",
                                  "serving.engine.build.state",
                                  "program.first_use"])
def test_the_new_span_names_are_listed(name):
    assert name in profiler.SPAN_NAMES
    assert len(set(profiler.SPAN_NAMES)) == len(profiler.SPAN_NAMES)
    assert len(profiler.PROGRAM_NAMES) == 8
