"""Cost-model-driven Pallas schedule search (ROADMAP item 2, the CINN
auto-scheduler role; docs/SCHEDULE_SEARCH.md).

Reference: paddle/cinn/auto_schedule/auto_tuner.h (measured-cost schedule
search) rebuilt TVM/Ansor-style (PAPERS.md 1802.04799) over DISCOVERED
reduction-/matmul-rooted subgraphs — the fusion-miss classes of
"Operator Fusion in XLA" (2301.13062).  Measurement is injected through
schedule_search's measure hooks so every decision here is deterministic on
CPU; the real OpCostModel.measure path belongs to the bench on a chip.
"""

import json
import os

import numpy as np
import pytest
import jax

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.ops import autotune as at
from paddle_tpu.static import schedule_search as ss
from paddle_tpu.static.program import Program, program_guard
from paddle_tpu.static.rewrite import (PallasFusionPass, ProgramGraph,
                                       ScheduleSearchPass)
from paddle_tpu.static.verify import ProgramVerifier, differential_check


@pytest.fixture()
def tmp_cache(tmp_path):
    """Fresh autotune cache under a tmp dir + zeroed search counters."""
    paddle.set_flags({"FLAGS_autotune_cache_dir": str(tmp_path)})
    at._CACHES.clear()
    ss.reset_schedule_search_stats()
    yield tmp_path
    paddle.set_flags({"FLAGS_autotune_cache_dir": ""})
    at._CACHES.clear()
    ss.reset_schedule_search_stats()


def _feed(prog, name, shape, dtype=np.float32):
    return prog.add_feed(prog.new_var(jax.ShapeDtypeStruct(shape, dtype), name))


def _capture_matmul_chain(M=32, K=16, N=64):
    """matmul→bias-add→relu→mean tail: no named pattern matches it (the
    bias add between matmul and act defeats MatmulEpiloguePattern)."""
    prog = Program()
    with program_guard(prog):
        x = _feed(prog, "x", (M, K))
        w = _feed(prog, "w", (K, N))
        b = _feed(prog, "b", (N,))
        h = paddle.matmul(x, w)
        h = h + b
        h = F.relu(h)
        out = paddle.mean(h, axis=-1, keepdim=True)
    return prog, out


def _capture_softmax_chain(B=4, S=8, H=32):
    """Manual (decomposed) softmax: reduction-rooted DAG — exp feeds both
    the sum and the divide; FlashAttentionPattern never sees it."""
    prog = Program()
    with program_guard(prog):
        x = _feed(prog, "x", (B, S, H))
        m = paddle.max(x, axis=-1, keepdim=True)
        t = paddle.exp(x - m)
        s = paddle.sum(t, axis=-1, keepdim=True)
        out = t / s
    return prog, out


def _win_measure(fn, args, *, label, config):
    """Deterministic: every Pallas candidate wins vs XLA; larger row blocks
    slightly preferred so the chosen config is stable."""
    if config is None:
        return 1.0
    return 0.5 - 1e-4 * config["block_rows"]


def _lose_measure(fn, args, *, label, config):
    return 1.0 if config is None else 5.0


def _optypes(prog):
    return [op.type for op in prog.global_block().ops]


# ---------------------------------------------------------------- discovery


def test_discovery_matmul_rooted_chain_missed_by_named_patterns(tmp_cache):
    prog, out = _capture_matmul_chain()
    assert PallasFusionPass([out._vid]).apply(prog.clone()) == 0
    graph = ProgramGraph(prog, (out._vid,))
    specs = [s for s in (ss.match_subgraph(op, graph)
                         for op in prog.global_block().ops) if s]
    assert len(specs) == 1  # anchored ONCE, at the downstream end
    spec = specs[0]
    assert spec.kind == "matmul"
    assert [type(o).__name__ for o in spec.ops] and len(spec.ops) == 4
    assert spec.has_reduce and not spec.col_tilable
    assert sorted(e.role for e in spec.ext) == ["bcast", "weight", "xrow"]
    assert spec.out_shape == (32, 1) and spec.rows == 32 and spec.cols == 64


def test_discovery_softmax_dag(tmp_cache):
    prog, out = _capture_softmax_chain()
    graph = ProgramGraph(prog, (out._vid,))
    specs = [s for s in (ss.match_subgraph(op, graph)
                         for op in prog.global_block().ops) if s]
    assert len(specs) == 1
    spec = specs[0]
    assert spec.kind == "reduce" and len(spec.ops) == 5  # max,sub,exp,sum,div
    assert spec.rows == 32 and spec.cols == 32
    assert len(spec.ext) == 1 and spec.ext[0].role == "row"


def test_discovery_refuses_side_effect_and_collective(tmp_cache):
    # dropout (RNG side effect) interrupts the chain: ops downstream of it
    # may fuse, the dropout itself and anything upstream never join
    prog = Program()
    with program_guard(prog):
        x = _feed(prog, "x", (16, 32))
        h = paddle.exp(x)
        h = F.dropout(h, p=0.5)
        out = paddle.sum(h * h, axis=-1, keepdim=True)
    graph = ProgramGraph(prog, (out._vid,))
    for op in prog.global_block().ops:
        spec = ss.match_subgraph(op, graph)
        if spec is None:
            continue
        assert all("dropout" not in o.type and o.type != "exp"
                   for o in spec.ops)

    # a collective op (side_effect_op_types) is never crossed either
    prog2 = Program()
    with program_guard(prog2):
        x = _feed(prog2, "x2", (16, 32))
        h = paddle.tanh(x)
        red = prog2.record("all_reduce", lambda v: v, (h,), {})
        out2 = paddle.sum(red * red, axis=-1, keepdim=True)
    graph2 = ProgramGraph(prog2, (out2._vid,))
    for op in prog2.global_block().ops:
        spec = ss.match_subgraph(op, graph2)
        if spec is None:
            continue
        assert all(o.type != "all_reduce" and o.type != "tanh"
                   for o in spec.ops)


def test_square_k_matmul_chain_fuses_with_untiled_cols(tmp_cache):
    """Regression: with K == N the matmul activation's cols equal the
    output cols, so col-tiled candidates used to slice the CONTRACTION dim
    (every build failed) and a small measure budget then persisted the
    subgraph as disabled despite valid untiled winners.  The xrow role
    keeps the activation untiled and build failures no longer burn budget
    slots."""
    prog = Program()
    with program_guard(prog):
        x = _feed(prog, "x", (64, 512))
        w = _feed(prog, "w", (512, 512))
        h = paddle.matmul(x, w)
        out = F.relu(h + 1.0)
    reference = prog.clone()
    n = ScheduleSearchPass(
        [out._vid],
        searcher=ss.ScheduleSearcher(measure=_win_measure, budget=2)).apply(prog)
    assert n == 1, ss.schedule_search_stats()
    assert ss.schedule_search_stats()["disabled"] == 0
    assert differential_check(reference, prog, [out._vid],
                              raise_on_error=False) == []


def test_non_last_axis_reduction_on_square_dims_never_fuses(tmp_cache):
    """Regression: with square dims (S == C) an axis=1 reduction's output
    shape coincides with a last-axis reduction's — shape checks alone would
    fuse it and the kernel would replay the baked axis on the collapsed
    2-D block, reducing the WRONG dimension (max abs err ~30 observed).
    Discovery must probe the baked axis and refuse."""
    prog = Program()
    with program_guard(prog):
        x = _feed(prog, "x", (2, 16, 16))
        out = paddle.sum(paddle.exp(x), axis=1)
    graph = ProgramGraph(prog, (out._vid,))
    assert all(ss.match_subgraph(op, graph) is None
               for op in prog.global_block().ops)
    reference = prog.clone()
    n = ScheduleSearchPass(
        [out._vid],
        searcher=ss.ScheduleSearcher(measure=_win_measure, budget=2)).apply(prog)
    assert n == 0
    assert differential_check(reference, prog, [out._vid],
                              raise_on_error=False) == []
    # the keepdim last-axis twin of the same shape still fuses fine
    prog2 = Program()
    with program_guard(prog2):
        x2 = _feed(prog2, "x2", (2, 16, 16))
        out2 = paddle.sum(paddle.exp(x2), axis=-1, keepdim=True)
    reference2 = prog2.clone()
    n2 = ScheduleSearchPass(
        [out2._vid],
        searcher=ss.ScheduleSearcher(measure=_win_measure, budget=2)).apply(prog2)
    assert n2 == 1
    assert differential_check(reference2, prog2, [out2._vid],
                              raise_on_error=False) == []


def test_fetch_frontier_interior_vid_refused_via_rollback(tmp_cache):
    """A subgraph spanning a fetched interior value must be rolled back by
    the PR-4 use-def machinery and counted in `.refused`."""
    prog, out = _capture_softmax_chain()
    graph = ProgramGraph(prog, ())
    # fetch the interior exp output alongside the final output
    exp_op = next(op for op in prog.global_block().ops if op.type == "exp")
    interior_vid = exp_op.out_vids[0]
    from paddle_tpu.static.verify import verify_stats

    before = verify_stats()["rewrites_refused"]
    pass_ = ScheduleSearchPass(
        [out._vid, interior_vid],
        searcher=ss.ScheduleSearcher(measure=_win_measure, budget=2))
    n = pass_.apply(prog)
    assert n == 0
    assert pass_.refused >= 1
    assert verify_stats()["rewrites_refused"] == before + pass_.refused
    # program untouched and still valid
    assert "sched_chain_5" not in _optypes(prog)
    assert not ProgramVerifier().verify(prog, [out._vid, interior_vid])


# ------------------------------------------------- candidates and pruning


def test_candidate_space_and_pruning_order(tmp_cache):
    prog, out = _capture_matmul_chain(M=64, K=16, N=32)
    graph = ProgramGraph(prog, (out._vid,))
    spec = next(s for s in (ss.match_subgraph(op, graph)
                            for op in prog.global_block().ops) if s)
    cands = ss.enumerate_candidates(spec)
    assert len(cands) >= 3
    assert all(spec.rows % c["block_rows"] == 0 for c in cands)
    # reduce tail present → the reduced axis is never tiled
    assert all(c["block_cols"] == spec.cols for c in cands)

    # VMEM prune: a huge working set is rejected by the generalized check
    assert at.validate_tile(ss.candidate_vmem_bytes(spec, cands[0])) is None
    assert at.validate_tile(64 << 20) is not None

    # budget caps what gets measured (FLAGS_schedule_search_budget role)
    measured = []

    def counting(fn, args, *, label, config):
        if config is not None:
            measured.append(config)
        return _win_measure(fn, args, label=label, config=config)

    searcher = ss.ScheduleSearcher(measure=counting, budget=2)
    decision = searcher.search(spec)
    assert decision.accepted and len(measured) <= 2
    stats = ss.schedule_search_stats()
    assert stats["measured"] == len(measured)
    assert stats["candidates"] == len(cands)


def test_dimension_order_changes_roofline_traffic(tmp_cache):
    """On a 2-D grid the dimension order decides which operand re-streams
    from HBM — the roofline prune must see different traffic."""
    prog = Program()
    with program_guard(prog):
        x = _feed(prog, "x", (32, 16))
        w = _feed(prog, "w", (16, 256))
        b = _feed(prog, "b", (256,))
        out = F.relu(paddle.matmul(x, w) + b)
    graph = ProgramGraph(prog, (out._vid,))
    spec = next(s for s in (ss.match_subgraph(op, graph)
                            for op in prog.global_block().ops) if s)
    assert spec.col_tilable
    cands = ss.enumerate_candidates(spec)
    assert {c["grid_order"] for c in cands} == {"rows_first", "cols_first"}
    cfg = {"block_rows": 8, "block_cols": 128}
    a = ss.candidate_roofline_ms(spec, dict(cfg, grid_order="rows_first"))
    b_ = ss.candidate_roofline_ms(spec, dict(cfg, grid_order="cols_first"))
    assert a != b_
    # and every candidate kernel is numerically exact vs the XLA twin
    rng = np.random.default_rng(0)
    vals = [jax.numpy.asarray(rng.standard_normal(e.shape), e.dtype)
            for e in spec.ext]
    ref = np.asarray(ss.build_reference(spec)(*vals))
    for c in cands:
        got = np.asarray(ss.build_kernel(spec, c)(*vals))
        np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)


# ------------------------------------------------ gate + cache + substitution


def test_accepted_schedule_substitutes_and_matches_numerics(tmp_cache):
    prog, out = _capture_matmul_chain()
    reference = prog.clone()
    pass_ = ScheduleSearchPass(
        [out._vid], searcher=ss.ScheduleSearcher(measure=_win_measure, budget=3))
    assert pass_.apply(prog) == 1
    assert _optypes(prog) == ["sched_chain_4"]
    assert not ProgramVerifier().verify(prog, [out._vid])
    assert differential_check(reference, prog, [out._vid],
                              raise_on_error=False) == []
    stats = ss.schedule_search_stats()
    assert stats["subgraphs_found"] == 1 and stats["accepted"] == 1
    # the winner persisted under the schedule/* namespace with its win meta
    raw = json.load(open(os.path.join(
        str(tmp_cache), at.device_kind_slug() + ".json")))
    (entry,) = raw["schedule/matmul"].values()
    assert entry["meta"]["win"] > 1.0 and "block_rows" in entry["config"]


def test_losing_schedule_disabled_persisted_never_refired(tmp_cache):
    prog, out = _capture_softmax_chain()
    calls = []

    def measure(fn, args, *, label, config):
        calls.append(config)
        return _lose_measure(fn, args, label=label, config=config)

    n = ScheduleSearchPass(
        [out._vid],
        searcher=ss.ScheduleSearcher(measure=measure, budget=2)).apply(prog)
    assert n == 0 and len(calls) > 0
    assert "sched_chain_5" not in _optypes(prog)
    stats = ss.schedule_search_stats()
    assert stats["disabled"] == 1 and stats["accepted"] == 0
    raw = json.load(open(os.path.join(
        str(tmp_cache), at.device_kind_slug() + ".json")))
    (entry,) = raw["schedule/reduce"].values()
    assert entry["config"] == {"disabled": True}
    assert entry["meta"]["win"] < 1.0

    # cold reload: fresh cache objects + fresh pass — the disabled entry
    # must stop the search before ANY measurement
    at._CACHES.clear()
    calls.clear()
    prog2, out2 = _capture_softmax_chain()
    n2 = ScheduleSearchPass(
        [out2._vid],
        searcher=ss.ScheduleSearcher(measure=measure, budget=2)).apply(prog2)
    assert n2 == 0 and calls == []
    assert ss.schedule_search_stats()["disabled_hits"] >= 1


def test_accepted_schedule_served_from_cache_without_remeasure(tmp_cache):
    prog, out = _capture_matmul_chain()
    ScheduleSearchPass(
        [out._vid],
        searcher=ss.ScheduleSearcher(measure=_win_measure, budget=2)).apply(prog)
    at._CACHES.clear()
    calls = []

    def measure(fn, args, *, label, config):
        calls.append(config)
        return 1.0

    prog2, out2 = _capture_matmul_chain()
    reference = prog2.clone()
    n = ScheduleSearchPass(
        [out2._vid],
        searcher=ss.ScheduleSearcher(measure=measure, budget=2)).apply(prog2)
    assert n == 1 and calls == []  # config reloaded, zero re-measurement
    assert ss.schedule_search_stats()["cache_hits"] >= 1
    assert differential_check(reference, prog2, [out2._vid],
                              raise_on_error=False) == []


# ------------------------------------------------------ K-tiling (phase 2)


def _capture_epilogue_chain(M, K, N):
    """matmul→bias-add→relu (col-tilable, no reduce tail): the class whose
    large-K shapes used to be auto-disabled when no whole-K candidate fit
    VMEM."""
    prog = Program()
    with program_guard(prog):
        x = _feed(prog, "x", (M, K))
        w = _feed(prog, "w", (K, N))
        b = _feed(prog, "b", (N,))
        out = F.relu(paddle.matmul(x, w) + b)
    return prog, out


def _spec_of(prog, out):
    graph = ProgramGraph(prog, (out._vid,))
    return next(s for s in (ss.match_subgraph(op, graph)
                            for op in prog.global_block().ops) if s)


@pytest.mark.parametrize("M,K,N", [
    (32, 256, 64),    # non-square M/N/K
    (32, 256, 256),   # K == N: the xrow-aliasing twin (PR-8 class)
    (256, 256, 64),   # K == M: the weight-shape-aliasing twin
])
def test_ktiled_all_candidates_numerics_sweep(tmp_cache, M, K, N):
    """Every enumerated candidate — K-tiled ones included — must match
    the XLA twin numerically, across non-square M/N/K and both PR-8
    square-dim aliasing twins."""
    prog, out = _capture_epilogue_chain(M, K, N)
    spec = _spec_of(prog, out)
    assert spec.k_tilable
    cands = ss.enumerate_candidates(spec)
    ktiled = [c for c in cands if c.get("block_k", K) < K]
    assert ktiled, "large K must enumerate contraction splits"
    assert all(K % c["block_k"] == 0 for c in ktiled)
    # K-tiled candidates pin the contraction innermost: one outer order
    assert all(c["grid_order"] == "rows_first" for c in ktiled)
    rng = np.random.default_rng(0)
    vals = [jax.numpy.asarray(rng.standard_normal(e.shape), e.dtype)
            for e in spec.ext]
    ref = np.asarray(ss.build_reference(spec)(*vals))
    for c in cands:
        got = np.asarray(ss.build_kernel(spec, c)(*vals))
        np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3,
                                   err_msg=str(c))


def test_ktiled_reduce_tail_chain_numerics(tmp_cache):
    """The matmul→bias→act→reduce class K-tiles too: the accumulator
    finishes before the epilogue's reduction replays."""
    prog, out = _capture_matmul_chain(M=32, K=256, N=64)
    spec = _spec_of(prog, out)
    assert spec.k_tilable and spec.has_reduce and not spec.col_tilable
    cands = [c for c in ss.enumerate_candidates(spec)
             if c.get("block_k", 256) < 256]
    assert cands
    rng = np.random.default_rng(0)
    vals = [jax.numpy.asarray(rng.standard_normal(e.shape), e.dtype)
            for e in spec.ext]
    ref = np.asarray(ss.build_reference(spec)(*vals))
    for c in cands:
        got = np.asarray(ss.build_kernel(spec, c)(*vals))
        np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3,
                                   err_msg=str(c))


def test_ktile_rescues_vmem_bound_chain(tmp_cache):
    """A contraction dim too large for any whole-K candidate used to
    auto-disable the chain (every candidate VMEM-pruned).  With block_k
    in the space the search accepts a schedule — and the roofline still
    ranks the split honestly (re-streaming both operands costs more
    traffic than a whole-K candidate of the same block shape)."""
    prog, out = _capture_epilogue_chain(8, 16384, 128)
    spec = _spec_of(prog, out)
    cands = ss.enumerate_candidates(spec)
    whole_k = [c for c in cands if c.get("block_k", 0) == 16384]
    ktiled = [c for c in cands if c.get("block_k", 16384) < 16384]
    assert whole_k and ktiled
    # the whole-K working set busts the budget; the split fits
    assert all(at.validate_tile(ss.candidate_vmem_bytes(spec, c))
               is not None for c in whole_k)
    assert any(at.validate_tile(ss.candidate_vmem_bytes(spec, c)) is None
               for c in ktiled)
    reference = prog.clone()
    n = ScheduleSearchPass(
        [out._vid],
        searcher=ss.ScheduleSearcher(measure=_win_measure, budget=2)
    ).apply(prog)
    assert n == 1, ss.schedule_search_stats()
    assert differential_check(reference, prog, [out._vid],
                              raise_on_error=False) == []
    # the accepted (and persisted) config is a genuine contraction split
    raw = json.load(open(os.path.join(
        str(tmp_cache), at.device_kind_slug() + ".json")))
    entry = next(v for k, v in raw["schedule/matmul"].items()
                 if "k=16384" in k)
    assert 0 < entry["config"]["block_k"] < 16384


def test_ktiled_roofline_costs_restreaming(tmp_cache):
    """K-order honesty: at identical block shape a K-tiled candidate
    models MORE traffic (activation re-streams per column block, weight
    per row block, plus the accumulator write) — the split only ranks
    ahead when VMEM or overhead says so, never for free."""
    prog, out = _capture_epilogue_chain(64, 512, 256)
    spec = _spec_of(prog, out)
    base = {"block_rows": 32, "block_cols": 128, "grid_order": "rows_first"}
    untiled = dict(base, block_k=512)
    split = dict(base, block_k=128)
    assert (ss.candidate_roofline_ms(spec, split)
            > ss.candidate_roofline_ms(spec, untiled))
    # and the split's working set is genuinely smaller
    assert (ss.candidate_vmem_bytes(spec, split)
            < ss.candidate_vmem_bytes(spec, untiled))


def test_ktile_never_offered_when_mm_operand_feeds_elem(tmp_cache):
    """K == N aliasing twin where the matmul ACTIVATION also feeds an
    elementwise op: slicing the contraction dim would hand that op a
    (br, bk) block where it needs (br, K) — discovery must refuse the
    split (and col tiling, per PR 8)."""
    prog = Program()
    with program_guard(prog):
        x = _feed(prog, "x", (32, 256))
        w = _feed(prog, "w", (256, 256))
        h = paddle.matmul(x, w)
        out = F.relu(h + x)  # x re-enters the chain at row shape
    spec = _spec_of(prog, out)
    assert not spec.k_tilable and not spec.col_tilable
    assert all(c.get("block_k") is None
               for c in ss.enumerate_candidates(spec))


# --------------------------------------------------------- e2e + telemetry


def test_executor_flag_e2e_with_verify(tmp_cache):
    """FLAGS_schedule_search end-to-end through Executor.run: discovered,
    searched, substituted, and differentially verified on the live feed."""
    import paddle_tpu.static as static

    rng = np.random.default_rng(0)
    feed = {
        "x": rng.normal(size=(32, 16)).astype(np.float32),
        "w": rng.normal(size=(16, 64)).astype(np.float32),
        "b": rng.normal(size=(64,)).astype(np.float32),
    }
    prog_off, out_off = _capture_matmul_chain()
    ref = static.Executor().run(prog_off, feed=feed, fetch_list=[out_off])
    assert "sched_chain_4" not in _optypes(prog_off)

    from paddle_tpu.profiler import verify_stats

    before = verify_stats()
    paddle.set_flags({"FLAGS_schedule_search": True,
                      "FLAGS_verify_programs": True,
                      "FLAGS_schedule_search_budget": 2})
    try:
        with ss.measure_override(_win_measure):
            prog_on, out_on = _capture_matmul_chain()
            got = static.Executor().run(prog_on, feed=feed, fetch_list=[out_on])
        assert "sched_chain_4" in _optypes(prog_on)
        np.testing.assert_allclose(got[0], ref[0], rtol=2e-3, atol=2e-3)
        after = verify_stats()
        # the substitution WAS differentially replayed, and cleanly
        assert after["differential_checks"] > before["differential_checks"]
        assert after["differential_failures"] == before["differential_failures"]
    finally:
        paddle.set_flags({"FLAGS_schedule_search": False,
                          "FLAGS_verify_programs": False,
                          "FLAGS_schedule_search_budget": 6})


def test_profiler_summary_footer(tmp_cache):
    prog, out = _capture_matmul_chain()
    ScheduleSearchPass(
        [out._vid],
        searcher=ss.ScheduleSearcher(measure=_win_measure, budget=2)).apply(prog)
    from paddle_tpu import profiler

    stats = profiler.schedule_search_stats()
    assert stats["subgraphs_found"] == 1
    p = profiler.Profiler(timer_only=True)
    p.start()
    p.stop()
    text = p.summary()
    assert "Schedule search:" in text
    assert "pruned_roofline" in text and "disabled" in text


def test_lint_sweep_zero_violations(tmp_cache):
    """Programs rewritten with the new pass verify clean (the lint_ir bar)."""
    programs = []
    for cap in (_capture_matmul_chain, _capture_softmax_chain):
        prog, out = cap()
        ScheduleSearchPass(
            [out._vid],
            searcher=ss.ScheduleSearcher(measure=_win_measure,
                                         budget=2)).apply(prog)
        programs.append((prog, [out._vid]))
    v = ProgramVerifier()
    assert all(not v.verify(p, f) for p, f in programs)
