"""The program's own spans and admission counters (paddle_tpu.profiler
RecordEvent / SPAN_NAMES, serving.decode_stats): what a span records, where
the sites are, and that the counters add up.  CPU: names, nesting and counts
only — never a time worth a name."""

import glob
import json
import threading
import time

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler, serving
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.profiler.statistics import decode_line


def _program_spans(prof):
    return [s for s in prof._buffer.spans if not s.name.startswith("op::")]


# ------------------------------------------------------------ the primitive

def test_span_records_name_start_end_parent_and_rid():
    with profiler.Profiler(timer_only=True) as p:
        with profiler.RecordEvent("outer", rid="req-7", blocks=3):
            with profiler.RecordEvent("inner"):
                time.sleep(0.001)
        with profiler.RecordEvent("sibling"):
            pass
    by = {s.name: s for s in p._buffer.spans}
    assert by["outer"].parent is None and by["sibling"].parent is None
    assert by["inner"].parent == "outer"
    assert by["outer"].args == {"rid": "req-7", "blocks": 3}
    assert by["outer"].start_ns <= by["inner"].start_ns < by["inner"].end_ns <= by["outer"].end_ns
    assert by["inner"].end_ns - by["inner"].start_ns >= 1_000_000


def test_spans_nest_per_thread():
    """A span's parent is the one open on ITS thread, whatever other
    threads have open."""
    seen = {}
    gate = threading.Barrier(2, timeout=30)

    def work(tag):
        with profiler.RecordEvent(f"outer.{tag}"):
            gate.wait()            # both outers are open now
            ev = profiler.RecordEvent(f"inner.{tag}")
            with ev:
                seen[tag] = ev.parent
            gate.wait()

    threads = [threading.Thread(target=work, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    assert seen == {"a": "outer.a", "b": "outer.b"}


def test_begin_end_out_of_order_keeps_the_stack_sound():
    a, b = profiler.RecordEvent("a").begin(), profiler.RecordEvent("b").begin()
    a.end()
    a.end()                        # twice: a no-op
    c = profiler.RecordEvent("c").begin()
    assert c.parent == "b"
    c.end()
    b.end()
    d = profiler.RecordEvent("d").begin()
    assert d.parent is None
    d.end()


def test_span_with_no_session_open_costs_next_to_nothing():
    """No Profiler, no trace: a span is two clock reads and a TraceMe that
    records nothing.  Measured 1-2 us; the bound leaves a busy CI host room
    and still fails a span that formats, allocates a buffer or takes a lock
    per call (tens of microseconds)."""
    assert profiler._active_profiler is None
    n = 20_000
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(n):
            with profiler.RecordEvent("serving.step", rid=1):
                pass
        best = min(best, (time.perf_counter() - t) / n)
    assert best < 25e-6, f"{best * 1e6:.1f} us per span"


def test_span_lands_in_a_trace_the_caller_opened(tmp_path):
    """The benchmark opens its own jax.profiler session, no Profiler: the
    span is on that trace's clock, its rid among the event's stats, its
    child inside it on the same thread line."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with profiler.RecordEvent("serving.admit", rid="req-9", prompt_len=4):
            with profiler.RecordEvent("serving.admit.prefill"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))[0]
    found = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serving.admit"):
                    found[ev.name] = (line.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns,
                                      dict(ev.stats))
    outer, inner = found["serving.admit"], found["serving.admit.prefill"]
    assert outer[3]["rid"] == "req-9" and outer[3]["prompt_len"] == 4
    assert outer[0] == inner[0]
    assert outer[1] <= inner[1] < inner[2] <= outer[2]


def test_chrome_export_carries_parent_and_rid(tmp_path):
    with profiler.Profiler(timer_only=True) as p:
        with profiler.RecordEvent("serving.admit", rid=("tenant", 3)):
            with profiler.RecordEvent("serving.admit.pour"):
                pass
    path = str(tmp_path / "t.json")
    profiler.export_chrome_tracing(p, path)
    ev = {e["name"]: e for e in json.load(open(path))["traceEvents"]}
    assert ev["serving.admit.pour"]["args"] == {"parent": "serving.admit"}
    assert ev["serving.admit"]["args"]["parent"] is None
    assert ev["serving.admit"]["args"]["rid"] in (["tenant", 3], "('tenant', 3)")


# ------------------------------------------------------------- the Profiler

def test_profiler_keeps_its_device_trace_where_a_reader_can_find_it(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    with profiler.Profiler() as p:
        paddle.ones([4]) + 1
    assert p.trace_dir and glob.glob(p.trace_dir + "/plugins/profile/*/*.xplane.pb")
    assert not (tmp_path / "profiler_log").exists()     # nothing cwd-relative
    handler = profiler.export_chrome_tracing(str(tmp_path / "out"))
    with profiler.Profiler(on_trace_ready=handler) as q:
        with profiler.RecordEvent("mine"):
            pass
    assert q.trace_dir == str(tmp_path / "out")
    assert glob.glob(str(tmp_path / "out" / "plugins" / "profile" / "*" / "*.xplane.pb"))
    spans = json.load(open(tmp_path / "out" / "host_spans.json"))["traceEvents"]
    assert [e["name"] for e in spans] == ["mine"]
    assert profiler.Profiler(timer_only=True).start().trace_dir is None
    profiler._active_profiler.stop()


def test_profiler_start_raises_when_the_trace_cannot_open(tmp_path):
    jax.profiler.start_trace(str(tmp_path))     # someone else's session
    try:
        with pytest.raises(Exception):
            profiler.Profiler().start()
        assert profiler._active_profiler is None
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------- the sites in the program

@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    model = LlamaForCausalLM(llama_tiny(dtype="float32"))
    model.eval()
    return model


def _engine(model, **kw):
    kw = dict(dict(max_batch=2, block_size=16, num_blocks=16), **kw)
    return serving.GenerationEngine(model, **kw)


def _prompt(i, n=20):
    return np.random.default_rng(i).integers(0, 1000, n)


def test_every_name_the_program_emits_is_listed(tiny_model):
    """A tiny train step and a tiny engine run, under a Profiler: every span
    that is not an `op::` is in SPAN_NAMES, and every listed name shows up."""
    import paddle_tpu.optimizer as opt
    from paddle_tpu.jit import TrainStep

    paddle.seed(1)
    train_model = LlamaForCausalLM(llama_tiny(dtype="float32"))
    step = TrainStep(train_model, opt.AdamW(1e-3, parameters=train_model.parameters()),
                     lambda m, i, l: m(i, l)[0])
    ids = paddle.randint(0, 1024, [2, 16])
    with profiler.Profiler(timer_only=True) as p:
        eng = _engine(tiny_model)
        for _ in range(2):
            step(ids, ids)
        for i in range(3):                      # the third one queues
            eng.add_request(f"r{i}", _prompt(i), max_new_tokens=10)
        while eng.has_work():
            eng.step()
    spans = _program_spans(p)
    names = {s.name for s in spans}
    assert names == set(profiler.SPAN_NAMES), names ^ set(profiler.SPAN_NAMES)
    # parents follow the names' own tree, but for an admission from the queue,
    # which the scheduler causes
    # ... and for a program's first use, which lies where the program is
    # first called (a train step's encloses the build's own first call)
    for s in spans:
        if s.name in ("jit.train_step", "serving.step", "serving.engine.build"):
            assert s.parent is None
        elif s.name == "serving.admit":
            assert s.parent in (None, "serving.step.schedule")
        elif s.name == "program.first_use":
            assert s.parent == {
                "jit_train_step": "jit.train_step.build",
                "jit_prefill_program": "serving.admit.prefill",
                "jit__pour_new_blocks": "serving.admit.pour",
                "jit_decode_macro_step": "serving.step.dispatch",
            }[s.args["program"]], (s.args, s.parent)
        elif s.name == "jit.train_step.build.trace":
            assert s.parent == "program.first_use"
        else:
            assert s.parent == s.name.rsplit(".", 1)[0], (s.name, s.parent)
    admits = [s for s in spans if s.name == "serving.admit"]
    assert {s.args["rid"] for s in admits} == {"r0", "r1", "r2"}
    assert all(s.args["prompt_len"] == 20 and s.args["blocks"] == 2 for s in admits)
    # the build happens once; its first call traces instead of dispatching
    assert sum(s.name == "jit.train_step.build" for s in spans) == 1
    assert sum(s.name == "jit.train_step.dispatch" for s in spans) == 1


def test_jitted_programs_are_named_for_what_they_are(tiny_model):
    import paddle_tpu.optimizer as opt
    from paddle_tpu.jit import TrainStep

    m = LlamaForCausalLM(llama_tiny(dtype="float32"))
    step = TrainStep(m, opt.AdamW(1e-3, parameters=m.parameters()),
                     lambda mm, i, l: mm(i, l)[0])
    step._ensure_built()
    assert step._compiled.__name__ == "train_step"
    eng = _engine(tiny_model)
    assert eng._build_step(8).__name__ == "decode_macro_step"
    assert eng._prefill_program(16, 0).__name__ == "prefill_program"


# ------------------------------------------------------ the admission counters

def test_admission_counters_add_up(tiny_model):
    serving.reset_decode_stats()
    eng = _engine(tiny_model)
    assert eng.add_request("a", _prompt(0), max_new_tokens=10) is not None
    assert eng.add_request("b", _prompt(1), max_new_tokens=10) is not None
    st = serving.decode_stats()
    assert st["admissions"] == 2 == st["admitted_normal"]
    assert st["queued_admissions"] == 0 and st["queue_wait_seconds"] == 0.0
    phases = sum(st[f"admit_{k}_seconds"]
                 for k in ("match", "prefill", "first_token", "pour"))
    assert 0 < phases <= st["admit_seconds"]
    assert all(st[f"admit_{k}_seconds"] > 0
               for k in ("match", "prefill", "first_token", "pour"))
    # the same prompt length costs the same eager ops, admission after admission
    assert st["admit_eager_ops"] > 0 and st["admit_eager_ops"] % 2 == 0
    per = st["admit_eager_ops"] // 2

    # both slots are taken: a third request queues, and each attempt while it
    # waits is backed out and adds NOTHING
    assert eng.add_request("c", _prompt(2), max_new_tokens=10) is None
    before = serving.decode_stats()
    eng.step()
    mid = serving.decode_stats()
    assert all(mid[k] == before[k] for k in before
               if k.startswith(("admi", "queue")))
    t_queued = time.perf_counter()
    while eng.has_work():
        eng.step()
    st = serving.decode_stats()
    assert st["admissions"] == 3 and st["queued_admissions"] == 1
    assert 0 < st["queue_wait_seconds"] <= time.perf_counter() - t_queued + 60
    assert st["admit_eager_ops"] == 3 * per
    line = decode_line(st)
    assert "Admission split: 3 admitted" in line and "1 waited" in line
    assert f"({per} eager ops)" in line

    serving.reset_decode_stats()                 # they reset with the rest
    st = serving.decode_stats()
    assert st["admissions"] == 0 and st["admit_seconds"] == 0.0
    assert "Admission split" not in decode_line(st)


def test_pool_exhaustion_backs_out_and_counts_nothing(tiny_model):
    """A slot is free but the pool is not: the attempt opens its span, backs
    out in the match phase, and the split stays as it was."""
    serving.reset_decode_stats()
    eng = _engine(tiny_model, num_blocks=3)      # one request of 2 blocks fits, not two
    assert eng.add_request("a", _prompt(0), max_new_tokens=10) is not None
    one = serving.decode_stats()
    with profiler.Profiler(timer_only=True) as p:
        assert eng.add_request("b", _prompt(1), max_new_tokens=10) is None
    assert [s.name for s in _program_spans(p)] == ["serving.admit.match",
                                                   "serving.admit"]
    assert serving.decode_stats()["admissions"] == one["admissions"] == 1
    assert serving.decode_stats()["admit_seconds"] == one["admit_seconds"]
    while eng.has_work():
        eng.step()
    st = serving.decode_stats()
    assert st["admissions"] == 2 and st["queued_admissions"] == 1
