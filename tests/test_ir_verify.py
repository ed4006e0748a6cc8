"""IR verifier + pass-differential checker (static/verify.py).

Each structural violation class gets a minimal failing Program and a
passing twin; the differential harness is proven on a resurrected
transpose-blind MatmulEpilogue fusion (the PR-2 bug, caught mechanically
here instead of by review); the PatternRewritePass use-def guard refuses
rewrites that consume values the fetch frontier still needs; and the
side-effect-aware DCE keeps RNG ops alive."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.static as static
from paddle_tpu.static.program import Operator, Program, program_guard
from paddle_tpu.static.rewrite import (
    MatmulEpiloguePattern,
    PallasFusionPass,
    PatternRewritePass,
    ProgramGraph,
    RewritePattern,
    _make_op,
)
from paddle_tpu.static.verify import (
    DifferentialError,
    ProgramVerifier,
    VerificationError,
    differential_check,
    track_programs,
    verify_program,
    verify_stats,
)

_SINGLE = jax.tree_util.tree_structure(0)


def _codes(violations):
    return {v.code for v in violations}


def _feed(prog, name, shape, dtype=np.float32):
    v = prog.new_var(jax.ShapeDtypeStruct(tuple(shape), dtype), name)
    prog.add_feed(v)
    return v


# --------------------------------------------------------------- unit tests


def test_clean_program_verifies():
    prog = Program()
    with program_guard(prog):
        x = _feed(prog, "x", (2, 3))
        y = paddle.sum(paddle.tanh(x) * 2.0)
    assert ProgramVerifier().verify(prog, [y._vid]) == []


def test_dangling_vid_detected():
    prog = Program()
    with program_guard(prog):
        x = _feed(prog, "x", (2,))
        y = paddle.exp(x)
    # twin passes
    assert verify_program(prog, [y._vid]) == []
    # rewire the op to read a vid nothing defines
    ghost = prog.new_var(jax.ShapeDtypeStruct((2,), np.float32), "ghost")
    op = prog.global_block().ops[0]
    op.arg_spec[0] = ("var", ghost._vid)
    bad = ProgramVerifier().verify(prog, [y._vid])
    assert "dangling-vid" in _codes(bad)


def test_dangling_fetch_detected():
    prog = Program()
    with program_guard(prog):
        x = _feed(prog, "x", (2,))
        y = paddle.exp(x)
    orphan = prog.new_var(jax.ShapeDtypeStruct((2,), np.float32), "orphan")
    bad = ProgramVerifier().verify(prog, [y._vid, orphan._vid])
    assert "dangling-fetch" in _codes(bad)
    with pytest.raises(VerificationError, match="dangling-fetch"):
        verify_program(prog, [orphan._vid])


def test_unknown_op_type_detected():
    prog = Program()
    with program_guard(prog):
        x = _feed(prog, "x", (2,))
        y = paddle.exp(x)
    prog.global_block().ops[0].type = "definitely_not_registered"
    bad = ProgramVerifier().verify(prog, [y._vid])
    assert "unknown-op-type" in _codes(bad)
    # namespaced spellings of REAL ops resolve (pass-rewritten programs)
    prog.global_block().ops[0].type = "wq::fp16::exp"
    assert ProgramVerifier().verify(prog, [y._vid]) == []


def test_missing_required_kwargs_detected():
    prog = Program()
    with program_guard(prog):
        x = _feed(prog, "x", (4, 4))
        w = _feed(prog, "w", (4, 4))
        y = paddle.matmul(x, w, transpose_y=True)
    assert verify_program(prog, [y._vid]) == []  # twin: kwargs recorded
    mm = prog.global_block().ops[0]
    mm.kwargs.pop("transpose_y")
    bad = ProgramVerifier().verify(prog, [y._vid])
    assert "missing-kwargs" in _codes(bad)


def test_shape_and_dtype_mismatch_detected():
    prog = Program()
    with program_guard(prog):
        x = _feed(prog, "x", (2, 3))
        y = paddle.tanh(x)
    op = prog.global_block().ops[0]
    op.fn = lambda v: jnp.zeros((5, 5), jnp.float32)  # rewrite changed shape
    bad = ProgramVerifier().verify(prog, [y._vid])
    assert "shape-mismatch" in _codes(bad)
    op.fn = lambda v: jnp.zeros((2, 3), jnp.int32)  # rewrite changed dtype
    bad = ProgramVerifier().verify(prog, [y._vid])
    assert "dtype-mismatch" in _codes(bad)
    op.fn = lambda v: (v, v)  # rewrite changed arity
    bad = ProgramVerifier().verify(prog, [y._vid])
    assert "arity-mismatch" in _codes(bad)


def _two_producer_program():
    """op1 produces (t, aux); share_loss-style alias re-binds t from u.
    With aux fetched, op1 cannot be pruned — so whether the program is
    legal depends on whether anything reads op1's t before the re-bind
    (the PR-2 executor-prune invariant, hand-built)."""
    prog = Program()
    x = _feed(prog, "x", (3,))
    t = prog.new_var(jax.ShapeDtypeStruct((3,), np.float32), "t")
    aux = prog.new_var(jax.ShapeDtypeStruct((3,), np.float32), "aux")
    u = prog.new_var(jax.ShapeDtypeStruct((3,), np.float32), "u")
    pair = jax.tree_util.tree_structure((0, 0))
    prog.global_block().ops.append(Operator(
        "grad", lambda v: (jnp.tanh(v), jnp.exp(v)), [("var", x._vid)], {},
        [t._vid, aux._vid], pair))
    prog.global_block().ops.append(Operator(
        "exp", jnp.exp, [("var", x._vid)], {}, [u._vid], _SINGLE))
    prog.global_block().ops.append(Operator(
        "share_loss", lambda v: v, [("var", u._vid)], {}, [t._vid], _SINGLE))
    prog.version += 1
    return prog, t, aux


def test_duplicate_live_producer_detected():
    """Two live producers of one vid reaching the fetch frontier — the
    executor-prune invariant PR 2 fixed, now checked mechanically."""
    prog, t, aux = _two_producer_program()
    bad = ProgramVerifier(abstract_eval=False).verify(prog, [t._vid, aux._vid])
    assert "duplicate-producer" in _codes(bad)

    # passing twin: a reader of op1's t BEFORE the re-bind makes the
    # earlier definition live-by-read (read-then-rebind is legal)
    prog2, t2, aux2 = _two_producer_program()
    r = prog2.new_var(jax.ShapeDtypeStruct((), np.float32), "r")
    reader = Operator("sum", jnp.sum, [("var", t2._vid)], {}, [r._vid], _SINGLE)
    prog2.global_block().ops.insert(1, reader)
    prog2.version += 1
    assert ProgramVerifier(abstract_eval=False).verify(
        prog2, [t2._vid, aux2._vid, r._vid]) == []


def test_bad_write_detected():
    prog = Program()
    with program_guard(prog):
        x = _feed(prog, "x", (2,))
        y = paddle.exp(x)
    prog.writes[y._vid] = 987654  # source vid never defined
    bad = ProgramVerifier().verify(prog, [y._vid])
    assert "bad-write" in _codes(bad)


# ------------------------------------------------------ differential checker


def _gelu_matmul_program(transpose_y):
    prog = Program()
    with program_guard(prog):
        x = _feed(prog, "x", (4, 4))
        w = _feed(prog, "w", (4, 4))
        # tanh-GELU: the exact-erf form is never epilogue-fused
        y = F.gelu(paddle.matmul(x, w, transpose_y=transpose_y),
                   approximate=True)
    return prog, y


def test_differential_catches_transpose_blind_epilogue_fusion():
    """Re-introduce the PR-2 MatmulEpilogue bug (fusing x @ w.T as x @ w —
    square weight, so no shape check can catch it) as a fixture pattern:
    the verifier's abstract eval passes, the differential checker fails."""
    prog, y = _gelu_matmul_program(transpose_y=True)
    ref = prog.clone()
    graph = ProgramGraph(prog, [y._vid])
    root = next(op for op in prog.global_block().ops if op.type == "gelu")
    mm = graph.def_op(root.arg_spec[0][1])
    x_vid, w_vid = mm.arg_spec[0][1], mm.arg_spec[1][1]

    def blind(xv, wv):  # the old pattern's kernel: transpose dropped
        return jax.nn.gelu(xv @ wv, approximate=True)

    graph.replace_op(root, _make_op("matmul_epilogue", blind, [x_vid, w_vid], root))

    # structurally valid — shapes/dtypes/arity all agree (square weight)
    assert ProgramVerifier().verify(prog, [y._vid]) == []
    # ... but numerically wrong: only the differential replay catches it
    bad = differential_check(ref, prog, [y._vid], raise_on_error=False)
    assert bad and _codes(bad) == {"differential-mismatch"}
    with pytest.raises(DifferentialError):
        differential_check(ref, prog, [y._vid])


def test_current_epilogue_pattern_refuses_transpose_and_passes_differential():
    prog, y = _gelu_matmul_program(transpose_y=True)
    ref = prog.clone()
    n = PatternRewritePass([MatmulEpiloguePattern()], [y._vid]).apply(prog)
    assert n == 0  # bails on the recorded transpose kwarg
    assert differential_check(ref, prog, [y._vid], raise_on_error=False) == []

    # and the untransposed twin both fuses AND stays numerically identical
    prog2, y2 = _gelu_matmul_program(transpose_y=False)
    ref2 = prog2.clone()
    n = PatternRewritePass([MatmulEpiloguePattern()], [y2._vid]).apply(prog2)
    assert n == 1
    assert differential_check(ref2, prog2, [y2._vid], raise_on_error=False) == []


def test_differential_catches_crashing_rewrite():
    prog = Program()
    with program_guard(prog):
        x = _feed(prog, "x", (2, 2))
        y = paddle.tanh(x)
    ref = prog.clone()

    def broken(v):
        raise RuntimeError("broken kernel")

    old = prog.global_block().ops[0]
    prog.global_block().ops[0] = Operator("tanh", broken, list(old.arg_spec),
                                          {}, list(old.out_vids), old.out_tree)
    prog.version += 1
    bad = differential_check(ref, prog, [y._vid], raise_on_error=False)
    assert "differential-crash" in _codes(bad)


# ------------------------------------------- interior-consumer fusion guard


def _attention_program(B=1, N=2, S=32, D=8):
    prog = Program()
    with program_guard(prog):
        q = _feed(prog, "q", (B, N, S, D))
        k = _feed(prog, "k", (B, N, S, D))
        v = _feed(prog, "v", (B, N, S, D))
        probs = F.softmax(paddle.matmul(q, k, transpose_y=True) / (D ** 0.5),
                          axis=-1)
        attn = paddle.matmul(probs, v)
    return prog, probs, attn


def test_stock_patterns_refuse_when_intermediate_is_fetched():
    """An interior matched var in the fetch list blocks fusion (satellite
    regression: intermediate also fetched)."""
    prog, probs, attn = _attention_program()
    n = PallasFusionPass([attn._vid, probs._vid]).apply(prog)
    assert n == 0
    assert "flash_attention" not in [op.type for op in prog.global_block().ops]

    # twin: without the intermediate fetch the same program fuses
    prog2, probs2, attn2 = _attention_program()
    n = PallasFusionPass([attn2._vid]).apply(prog2)
    assert n == 1
    assert "flash_attention" in [op.type for op in prog2.global_block().ops]


class _EatsInterior(RewritePattern):
    """Adversarial pattern: consumes the softmax producer outright — what a
    buggy/aggressive pattern could do.  The driver's use-def guard must
    roll it back whenever the eaten var is still needed."""

    name = "eats_interior"
    root_type = "matmul"

    def match_and_rewrite(self, op, graph):
        if len(op.arg_spec) != 2 or any(s[0] != "var" for s in op.arg_spec):
            return False
        sm = graph.def_op(op.arg_spec[0][1], "softmax")
        if sm is None:
            return False
        scores_vid = sm.arg_spec[0][1]

        def fused(scores, v):
            return jax.nn.softmax(scores, axis=-1) @ v

        graph.replace_op(op, _make_op(
            "flash_attention", fused, [scores_vid, op.arg_spec[1][1]], op))
        graph.block.ops.remove(sm)  # removes the probs producer
        graph.program.version += 1
        return True


def test_driver_rolls_back_rewrite_that_eats_a_fetched_interior():
    prog, probs, attn = _attention_program()
    before = [op.type for op in prog.global_block().ops]
    drv = PatternRewritePass([_EatsInterior()], [attn._vid, probs._vid])
    assert drv.apply(prog) == 0
    assert drv.refused >= 1
    assert [op.type for op in prog.global_block().ops] == before  # rolled back

    # twin: interior NOT fetched → the same rewrite is accepted
    prog2, probs2, attn2 = _attention_program()
    drv2 = PatternRewritePass([_EatsInterior()], [attn2._vid])
    assert drv2.apply(prog2) == 1
    assert drv2.refused == 0
    types = [op.type for op in prog2.global_block().ops]
    assert "flash_attention" in types and "softmax" not in types


def test_generic_elementwise_fusion_respects_fetch_frontier():
    """A fetched interior value must survive chain fusion — the invariant
    the export path relies on by forwarding its fetch set to the fusion
    passes (static/io.py)."""
    from paddle_tpu.static.rewrite import GenericElementwiseFusionPass

    def build():
        prog = Program()
        with program_guard(prog):
            x = _feed(prog, "x", (8,))
            mid = paddle.tanh(paddle.exp(x) * 2.0)   # interior of the chain
            out = paddle.sqrt(paddle.abs(mid) + 1.0)
        return prog, mid, out

    prog, mid, out = build()
    GenericElementwiseFusionPass([out._vid, mid._vid], min_chain=2).apply(prog)
    assert verify_program(prog, [out._vid, mid._vid]) == []
    defined = set(prog.param_inits) | {v._vid for v in prog.feed_vars}
    for op in prog.global_block().ops:
        defined.update(op.out_vids)
    assert mid._vid in defined  # the fetched intermediate kept a producer

    # twin: with only the final fetch the whole chain fuses into one kernel
    prog2, mid2, out2 = build()
    n = GenericElementwiseFusionPass([out2._vid], min_chain=2).apply(prog2)
    assert n >= 1
    assert any(op.type.startswith("vpu_chain_")
               for op in prog2.global_block().ops)


# --------------------------------------------------- side-effect-aware DCE


def test_dce_keeps_side_effect_ops():
    from paddle_tpu.static.passes import DeadCodeEliminationPass

    prog = Program()
    with program_guard(prog):
        x = _feed(prog, "x", (8,))
        dropped = F.dropout(x, 0.5, training=True)  # RNG op, never fetched
        dead = x + 100.0                            # pure op, never fetched
        y = paddle.sum(x * 2.0)
    types_before = [op.type for op in prog.global_block().ops]
    assert "dropout" in types_before
    removed = DeadCodeEliminationPass([y._vid]).apply(prog)
    types = [op.type for op in prog.global_block().ops]
    # the pure dead chain goes; the RNG op stays (eliminating it would
    # shift every later op's key sequence — the old code path pruned it)
    assert removed >= 1
    assert "dropout" in types
    assert "add" not in [t for t in types]  # dead = x + 100 pruned


def test_dce_still_prunes_pure_ops():
    from paddle_tpu.static.passes import dead_code_elimination

    prog = Program()
    with program_guard(prog):
        x = _feed(prog, "x", (4,))
        dead1 = x + 100.0
        dead2 = dead1 * dead1
        y = paddle.sum(x)
    assert dead_code_elimination(prog, [y]) >= 2


# ----------------------------------------------------- verify-mode wiring


def _flag(name, value):
    paddle.set_flags({name: value})


def test_executor_verify_mode_runs_differential_on_live_feed():
    rng = np.random.default_rng(0)
    _flag("FLAGS_verify_programs", True)
    try:
        base = verify_stats()
        prog, probs, attn = _attention_program()
        exe = static.Executor()
        feed = {n: rng.normal(size=(1, 2, 32, 8)).astype(np.float32)
                for n in ("q", "k", "v")}
        (out,) = exe.run(prog, feed=feed, fetch_list=[attn])
        q, k, v = feed["q"], feed["k"], feed["v"]
        scores = (q @ np.swapaxes(k, -1, -2)) / np.sqrt(8.0)
        ref = jax.nn.softmax(scores, axis=-1) @ v
        np.testing.assert_allclose(out, np.asarray(ref), rtol=2e-3, atol=2e-3)
        stats = verify_stats()
        assert stats["differential_checks"] > base["differential_checks"]
        assert stats["differential_failures"] == base["differential_failures"]
        assert stats["programs_failed"] == base["programs_failed"]
    finally:
        _flag("FLAGS_verify_programs", False)


def test_pass_manager_verifies_between_passes():
    from paddle_tpu.static.passes import ProgramPass, ProgramPassManager

    class _Corruptor(ProgramPass):
        name = "corruptor"

        def apply(self, program):
            op = program.global_block().ops[0]
            op.arg_spec[0] = ("var", 424242)  # dangling read
            program.version += 1
            return 1

    prog = Program()
    with program_guard(prog):
        x = _feed(prog, "x", (2,))
        y = paddle.exp(x)
    _flag("FLAGS_verify_programs", True)
    try:
        with pytest.raises(VerificationError, match="corruptor"):
            ProgramPassManager([_Corruptor()], fetch_vids=[y._vid]).run(prog)
    finally:
        _flag("FLAGS_verify_programs", False)


def test_verify_flag_off_keeps_pass_manager_silent():
    from paddle_tpu.static.passes import ProgramPassManager

    prog = Program()
    with program_guard(prog):
        x = _feed(prog, "x", (2,))
        y = paddle.exp(x)
    assert ProgramPassManager([], fetch_vids=[y._vid]).run(prog) == 0


# ------------------------------------------------------- tier-1 property


def test_every_traced_program_verifies_with_fusion_on():
    """Property: every Program the canonical static paths build — capture,
    training step, control flow, executor-fused attention — passes
    verification with the fusion pipeline on."""
    paddle.seed(0)
    rng = np.random.default_rng(0)
    verifier = ProgramVerifier()
    with track_programs() as programs:
        # capture + run
        main = static.Program()
        with program_guard(main):
            x = static.data("px", [2, 3], "float32")
            y = paddle.sum(paddle.add(x, x) * 2.0)
        static.Executor().run(main, feed={"px": np.ones((2, 3), np.float32)},
                              fetch_list=[y])

        # training step
        layer = nn.Linear(4, 2)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=layer.parameters())
        train = static.Program()
        with program_guard(train):
            xt = static.data("tx", [8, 4], "float32")
            yt = static.data("ty", [8, 2], "float32")
            loss = paddle.mean((layer(xt) - yt) ** 2)
            opt.minimize(loss)
        exe = static.Executor()
        with static.scope_guard(static.Scope()):
            exe.run(train,
                    feed={"tx": rng.normal(size=(8, 4)).astype(np.float32),
                          "ty": rng.normal(size=(8, 2)).astype(np.float32)},
                    fetch_list=[loss])

        # fused attention through the executor pipeline (fusion flag is on
        # by default)
        att, probs, attn = _attention_program()
        static.Executor().run(
            att,
            feed={n: rng.normal(size=(1, 2, 32, 8)).astype(np.float32)
                  for n in ("q", "k", "v")},
            fetch_list=[attn])
        assert "flash_attention" in [op.type for op in att.global_block().ops]

    assert len(programs) >= 3
    for prog in programs:
        violations = verifier.verify(prog)
        assert violations == [], (
            f"program with ops {[op.type for op in prog.global_block().ops]} "
            f"failed verification: {[str(v) for v in violations]}")
