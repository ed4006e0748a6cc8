"""Mesh lint (static/mesh_lint.py, docs/MESH_LINT.md).

Every violation class gets a minimal failing fixture AND a passing twin
(the PR-4 verifier discipline, extended to the mesh): mismatched
collective axis, axis-size mismatch, conditional collective, bad
ppermute/axis_index_groups participation, bad/duplicate/indivisible
placements, replicated-giant, use-after-donation, over-budget memory.
Everything is abstract — no fixture ever launches a device collective,
so this suite cannot trip the 8-device SIGSEGV class it guards against.

The wiring tier checks FLAGS_verify_sharding raises with a named site at
every entry (Executor compile path, pass boundaries, ShardedTrainStep
build, GenerationEngine construction) and that the canonical GREEN
distributed/serving paths lint clean under the flag.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.static as static
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import ProcessMesh
from paddle_tpu.distributed.auto_parallel.placement import Replicate, Shard
from paddle_tpu.distributed.shard_map_compat import shard_map
from paddle_tpu.static.mesh_lint import (
    MeshLinter,
    MeshLintError,
    lint_engine,
    lint_program,
    lint_train_step,
    mesh_lint_stats,
    reset_mesh_lint_stats,
)


def _codes(violations):
    return {v.code for v in violations}


def _dp8():
    return Mesh(np.array(jax.devices()[:8]).reshape(8), ("dp",))


def _dpmp():
    return Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("dp", "mp"))


_AVAL = jax.ShapeDtypeStruct((8, 4), jnp.float32)


def _train_program(seed=0, din=4, dout=4, opt_cls=None):
    """Captured train-step program: forward + grad + optimizer_update with
    state writes (the donated-buffer shape every real step has)."""
    paddle.seed(seed)
    layer = nn.Linear(din, dout)
    opt_cls = opt_cls or paddle.optimizer.SGD
    opt = opt_cls(learning_rate=0.1, parameters=layer.parameters())
    prog = static.Program()
    with static.program_guard(prog):
        x = static.data("x", [4, din], "float32")
        y = static.data("y", [4, dout], "float32")
        loss = paddle.mean((layer(x) - y) ** 2)
        opt.minimize(loss)
    return prog, loss


# ------------------------------------------- family 2: collective congruence
def test_collective_axis_clean_and_unknown():
    linter = MeshLinter(mesh=_dp8())
    assert linter.lint_callable(lambda x: lax.psum(x, "dp"), _AVAL) == []
    bad = linter.lint_callable(lambda x: lax.psum(x, "qq"), _AVAL)
    assert _codes(bad) == {"unknown-axis"}
    assert "qq" in str(bad[0])


def test_shard_map_wrong_axis_and_size_mismatch():
    linter = MeshLinter(mesh=_dp8())
    # twin: a shard_map binding dp at the session size is clean
    ok = shard_map(lambda v: lax.psum(v, "dp"), mesh=_dp8(),
                   in_specs=P("dp"), out_specs=P())
    assert linter.lint_callable(ok, _AVAL) == []
    # an 'mp' shard_map on a dp-only session mesh: the collective would
    # never line up with the session topology
    mp2 = Mesh(np.array(jax.devices()[:2]), ("mp",))
    wrong = shard_map(lambda v: lax.psum(v, "mp"), mesh=mp2,
                      in_specs=P("mp"), out_specs=P())
    assert "unknown-axis" in _codes(linter.lint_callable(wrong, _AVAL))
    # same NAME, different size: built for another topology
    dp2 = Mesh(np.array(jax.devices()[:2]), ("dp",))
    small = shard_map(lambda v: lax.psum(v, "dp"), mesh=dp2,
                      in_specs=P("dp"), out_specs=P())
    assert "axis-size-mismatch" in _codes(linter.lint_callable(small, _AVAL))


def test_conditional_collective_flagged_and_twins():
    linter = MeshLinter(mesh=_dp8())

    def cond_body(v):
        return lax.cond(v.sum() > 0, lambda t: lax.psum(t, "dp"),
                        lambda t: t, v)

    conditional = shard_map(cond_body, mesh=_dp8(), in_specs=P("dp"),
                            out_specs=P("dp"))
    bad = linter.lint_callable(conditional, _AVAL)
    assert "conditional-collective" in _codes(bad)

    # the same branch typed so that shard_map's varying-axes check lets it
    # trace (the psum binds as psum_invariant), and with the check off
    # (a plain psum): the walker itself must see both
    def typed_body(v):
        return lax.cond(
            v.sum() > 0,
            lambda t: lax.pcast(lax.psum(t, "dp"), "dp", to="varying"),
            lambda t: t, v)

    for body, kw in ((typed_body, {}), (cond_body, {"check_vma": False})):
        walked = shard_map(body, mesh=_dp8(), in_specs=P("dp"),
                           out_specs=P("dp"), **kw)
        assert "conditional-collective" in _codes(
            linter.lint_callable(walked, _AVAL))

    # twin 1: the unconditional collective is clean
    flat = shard_map(lambda v: lax.psum(v, "dp"), mesh=_dp8(),
                     in_specs=P("dp"), out_specs=P())
    assert linter.lint_callable(flat, _AVAL) == []

    # twin 2: a collective inside lax.scan is NOT conditional (static trip
    # count — every device runs every iteration)
    def scan_body(v):
        def one(c, x):
            return c + lax.psum(x, "dp"), None

        out, _ = lax.scan(one, jnp.zeros_like(v[0]), v)
        return out[None]

    scanned = shard_map(scan_body, mesh=_dp8(), in_specs=P("dp"),
                        out_specs=P("dp"))
    assert linter.lint_callable(
        scanned, jax.ShapeDtypeStruct((8, 3, 4), jnp.float32)) == []

    # while_loop bodies ARE data-dependent (plain axis-env form: jax
    # 0.4.37's shard_map cannot even trace while+collective — real code
    # reaches this shape through pass super-ops running under a mesh)
    def while_body(v):
        return lax.while_loop(lambda s: s.sum() < 100.0,
                              lambda s: lax.psum(s, "dp"), v)

    assert "conditional-collective" in _codes(
        linter.lint_callable(while_body, _AVAL))


def test_ppermute_participation():
    linter = MeshLinter(mesh=_dp8())

    def sm(perm):
        return shard_map(lambda v: lax.ppermute(v, "dp", perm),
                         mesh=_dp8(), in_specs=P("dp"), out_specs=P("dp"))

    # twin: the ring rotation every pipeline stage uses is clean
    ring = [(i, (i + 1) % 8) for i in range(8)]
    assert linter.lint_callable(sm(ring), _AVAL) == []
    # duplicate source / duplicate destination / out-of-range rank: jax
    # traces all three happily — only the lint catches them
    assert "bad-permutation" in _codes(
        linter.lint_callable(sm([(0, 1), (0, 2)]), _AVAL))
    assert "bad-permutation" in _codes(
        linter.lint_callable(sm([(0, 1), (2, 1)]), _AVAL))
    assert "bad-permutation" in _codes(
        linter.lint_callable(sm([(0, 9)]), _AVAL))


def test_axis_index_groups_participation():
    # plain axis-env form: jax 0.4.37's shard_map rejects
    # axis_index_groups outright, but pmap-style/compat paths still carry
    # them — the lint validates the partition wherever it appears
    linter = MeshLinter(mesh=_dp8())

    def gfn(groups):
        return lambda v: lax.psum(v, "dp", axis_index_groups=groups)

    # twin: halves partition the axis uniformly
    assert linter.lint_callable(
        gfn([[0, 1, 2, 3], [4, 5, 6, 7]]), _AVAL) == []
    # non-uniform group sizes
    assert "bad-groups" in _codes(linter.lint_callable(
        gfn([[0, 1, 2], [3, 4, 5, 6, 7]]), _AVAL))
    # not a partition (rank 7 never rendezvouses)
    assert "bad-groups" in _codes(linter.lint_callable(
        gfn([[0, 1, 2, 3], [4, 5, 6, 6]]), _AVAL))


# ------------------------------------------------ family 1: placements
def test_placement_unknown_axis_and_twin():
    linter = MeshLinter(mesh=_dpmp())
    aval = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    assert "unknown-axis" in _codes(
        linter.lint_placements([("w", aval, P("dp", "qq"))]))
    assert linter.lint_placements([("w", aval, P("dp", "mp"))]) == []


def test_placement_bad_shard_dim_and_twin():
    linter = MeshLinter(mesh=_dpmp())
    aval = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    assert "bad-shard-dim" in _codes(linter.lint_placements(
        [("w", aval, [Shard(5), Replicate()])]))
    assert "bad-shard-dim" in _codes(linter.lint_placements(
        [("w", aval, P("dp", "mp", None))]))  # 3 entries, rank 2
    assert linter.lint_placements(
        [("w", aval, [Shard(0), Replicate()])]) == []


def test_duplicate_axis_and_indivisible_shard():
    linter = MeshLinter(mesh=_dpmp())
    aval = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    assert "duplicate-axis" in _codes(
        linter.lint_placements([("w", aval, P("dp", "dp"))]))
    odd = jax.ShapeDtypeStruct((6, 16), jnp.float32)  # 6 % dp(4) != 0
    assert "indivisible-shard" in _codes(
        linter.lint_placements([("w", odd, P("dp", None))]))
    assert linter.lint_placements([("w", aval, P("dp", "mp"))]) == []


def test_replicated_giant_and_twins():
    linter = MeshLinter(mesh=_dp8(), replicated_bytes=2 ** 20)
    big = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)  # 4 MiB
    bad = linter.lint_placements([("embedding", big, None)])
    assert _codes(bad) == {"replicated-giant"}
    assert "per device" in str(bad[0])
    # twin 1: the same tensor sharded is clean
    assert linter.lint_placements([("embedding", big, P("dp", None))]) == []
    # twin 2: small tensors replicate freely (biases, norms)
    small = jax.ShapeDtypeStruct((1024,), jnp.float32)
    assert linter.lint_placements([("bias", small, None)]) == []
    # twin 3: no mesh, no flag — single-device replication is meaningless
    assert MeshLinter(mesh=None, replicated_bytes=2 ** 20).lint_placements(
        [("embedding", big, None)]) == []


# ----------------------------------------- family 4: per-device memory
def test_memory_estimate_and_budget():
    big = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)  # 4 MiB
    groups = {"params": [("w", big, P("dp", None))],
              "optimizer": [("m", big, P("dp", None))]}
    # twin: budget off (0) never flags
    ok, est = MeshLinter(mesh=_dp8(),
                         budget_bytes=0).estimate_device_bytes(groups)
    assert ok == [] and est["params"] == est["optimizer"] == 2 ** 19
    assert est["total"] == 2 ** 20
    # sharding divides the estimate: replicated would be 4 MiB each
    bad, est2 = MeshLinter(mesh=_dp8(),
                           budget_bytes=2 ** 19).estimate_device_bytes(groups)
    assert _codes(bad) == {"over-budget"}
    assert est2 == est
    # twin: a budget above the estimate is clean
    ok2, _ = MeshLinter(mesh=_dp8(),
                        budget_bytes=2 ** 21).estimate_device_bytes(groups)
    assert ok2 == []


# ------------------------------------------------ family 3: donation
def test_use_after_donation_fetch_and_twin():
    prog, loss = _train_program()
    donated = next(iter(prog.writes))  # a written state var (param)
    bad = lint_program(prog, [loss._vid, donated], mesh=_dp8())
    assert "use-after-donation" in _codes(bad)
    assert "PRE-update" in next(str(v) for v in bad
                                if v.code == "use-after-donation")
    # twin: fetching the UPDATED value (the write source) is the contract
    updated = prog.writes[donated]
    assert lint_program(prog, [loss._vid, updated], mesh=_dp8()) == []


def test_duplicate_donation_in_train_step():
    class Shared(nn.Layer):
        def __init__(self):
            super().__init__()
            self.a = self.create_parameter([4, 4])
            self.b = self.create_parameter([4, 4])
            self.b._bind(self.a._value)  # two params, ONE buffer

        def forward(self, x):
            return x @ self.a + x @ self.b

    paddle.seed(0)
    model = Shared()
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    step = paddle.jit.TrainStep(model, opt,
                                lambda m, x: paddle.mean(m(x) ** 2))
    bad, _est = lint_train_step(
        step, jax.ShapeDtypeStruct((2, 4), jnp.float32))
    assert "use-after-donation" in _codes(bad)
    assert "donates it twice" in next(
        str(v) for v in bad if v.code == "use-after-donation")

    # twin: independent buffers lint clean
    paddle.seed(0)
    model2 = nn.Linear(4, 4)
    opt2 = paddle.optimizer.SGD(learning_rate=0.1,
                                parameters=model2.parameters())
    step2 = paddle.jit.TrainStep(model2, opt2,
                                 lambda m, x: paddle.mean(m(x) ** 2))
    ok, _ = lint_train_step(step2, jax.ShapeDtypeStruct((2, 4), jnp.float32))
    assert ok == []


# ----------------------------------------------------------- wiring tier
def _set_flags(**kv):
    prev = {k: paddle.get_flags(k)[k] for k in kv}
    paddle.set_flags(kv)
    return prev


def test_executor_compile_path_raises_under_flag():
    prog, loss = _train_program(seed=1)
    donated = next(iter(prog.writes))
    feed = {"x": np.zeros((4, 4), np.float32),
            "y": np.zeros((4, 4), np.float32)}
    prev = _set_flags(FLAGS_verify_sharding=True)
    try:
        exe = static.Executor()
        loss_var = prog._var_by_vid[loss._vid]
        donated_var = prog._var_by_vid[donated]
        with pytest.raises(MeshLintError, match="use-after-donation"):
            exe.run(prog, feed=feed, fetch_list=[loss_var, donated_var])
        # twin: the clean fetch set compiles and runs under the flag
        out = exe.run(prog, feed=feed, fetch_list=[loss_var])
        assert np.isfinite(out[0]).all()
    finally:
        paddle.set_flags(prev)


def test_pass_boundary_names_failing_stage():
    from paddle_tpu.static.passes import ProgramPassManager

    prog, loss = _train_program(seed=2)
    donated = next(iter(prog.writes))
    prev = _set_flags(FLAGS_verify_sharding=True)
    try:
        pm = ProgramPassManager([], fetch_vids=[loss._vid, donated])
        with pytest.raises(MeshLintError, match="BEFORE pass pipeline"):
            pm.run(prog)
    finally:
        paddle.set_flags(prev)


def test_sharded_train_step_lint_abstract_raise():
    """A big fully-replicated param on an 8-device mesh is flagged at
    BUILD time — abstractly, before any sharded dispatch could hang."""
    mesh = ProcessMesh(np.arange(8).reshape(8), ["dp"])
    paddle.seed(3)
    model = nn.Linear(512, 600)  # ~1.2 MiB weight, replicated
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    step = dist.ShardedTrainStep(
        model, opt, lambda m, x, y: paddle.mean((m(x) - y) ** 2), mesh,
        zero_stage=0)
    bx = jax.ShapeDtypeStruct((8, 512), jnp.float32)
    by = jax.ShapeDtypeStruct((8, 600), jnp.float32)
    with pytest.raises(MeshLintError, match="replicated-giant"):
        lint_train_step(step, bx, by, replicated_bytes=2 ** 20,
                        raise_on_error=True)
    # twin: the default threshold (8 MiB) tolerates this size
    ok, est = lint_train_step(step, bx, by)
    assert ok == []
    assert est["total"] > 0


def test_engine_wiring_raises_on_replicated_pools():
    """num_key_value_heads % mp != 0 falls back to REPLICATED pools (the
    PR-6 warning path) — under FLAGS_verify_sharding with a tight
    replicated threshold, engine construction fails loudly instead."""
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import GenerationEngine

    paddle.seed(4)
    cfg = LlamaConfig(vocab_size=64, hidden_size=48, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=6,
                      num_key_value_heads=3, max_position_embeddings=128)
    mesh = ProcessMesh(np.arange(2).reshape(2), ["mp"])
    prev = _set_flags(FLAGS_verify_sharding=True,
                      FLAGS_mesh_lint_replicated_mb=0.001)
    try:
        with pytest.warns(UserWarning, match="KV pool replicated"):
            with pytest.raises(MeshLintError, match="replicated-giant"):
                GenerationEngine(LlamaForCausalLM(cfg), num_blocks=16,
                                 mesh=mesh)
    finally:
        paddle.set_flags(prev)
    # twin: divisible KV heads shard the pools — constructs clean under
    # the same flags
    paddle.seed(4)
    cfg2 = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=2, max_position_embeddings=128)
    prev = _set_flags(FLAGS_verify_sharding=True)
    try:
        eng = GenerationEngine(LlamaForCausalLM(cfg2), num_blocks=16,
                               mesh=mesh)
        violations, est = lint_engine(eng)
        assert violations == []
        assert est["kv_pools"] > 0
    finally:
        paddle.set_flags(prev)


def test_single_device_objects_ignore_session_mesh():
    """A plain TrainStep / mesh=None engine is single-device BY CONTRACT:
    an active multi-device session mesh must not reclassify its
    (correctly) replicated state as replication blowups."""
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import GenerationEngine

    mesh = ProcessMesh(np.arange(8).reshape(8), ["dp"])
    dist.set_mesh(mesh)
    prev = _set_flags(FLAGS_mesh_lint_replicated_mb=0.001)
    try:
        paddle.seed(9)
        model = nn.Linear(64, 64)  # 16 KiB weight > the tiny threshold
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        step = paddle.jit.TrainStep(model, opt,
                                    lambda m, x: paddle.mean(m(x) ** 2))
        ok, _ = lint_train_step(
            step, jax.ShapeDtypeStruct((2, 64), jnp.float32))
        assert ok == []

        cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                          intermediate_size=64, num_hidden_layers=1,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=64)
        eng = GenerationEngine(LlamaForCausalLM(cfg), num_blocks=8)
        ok, _ = lint_engine(eng)
        assert ok == []
    finally:
        paddle.set_flags(prev)
        dist.set_mesh(None)


def test_stats_and_summary_footer(capsys):
    reset_mesh_lint_stats()
    linter = MeshLinter(mesh=_dp8())
    linter.lint_callable(lambda x: lax.psum(x, "dp"), _AVAL)
    prog, loss = _train_program(seed=5)
    lint_program(prog, [loss._vid], mesh=_dp8())
    stats = mesh_lint_stats()
    assert stats["entries_linted"] == 1
    assert stats["collectives_checked"] >= 1
    assert stats["violations"] == 0

    from paddle_tpu import profiler

    assert profiler.mesh_lint_stats() == stats
    prof = profiler.Profiler(timer_only=True)
    prof.start()
    prof.stop()
    out = prof.summary()
    assert "Mesh lint:" in out
    assert "violations=0" in out
    capsys.readouterr()


# ------------------------------------------------- green tier-1 sweep
def test_green_distributed_serving_paths_zero_violations():
    """The canonical green paths — ZeRO-rewritten captured program through
    the Executor, dp x mp ShardedTrainStep, TP-sharded GenerationEngine —
    produce ZERO violations under FLAGS_verify_sharding=1 (the tier-1
    acceptance sweep; tools/lint_mesh.py battery is the standalone twin)."""
    reset_mesh_lint_stats()
    prev = _set_flags(FLAGS_verify_sharding=True)
    try:
        # executor path with the ZeRO rewrite
        from paddle_tpu.static.passes import apply_pass

        prog, loss = _train_program(seed=6, din=16, dout=8)
        apply_pass(prog, "auto_parallel_sharding", mesh=_dp8(), stage=2)
        exe = static.Executor()
        rng = np.random.default_rng(0)
        out = exe.run(prog, feed={"x": rng.normal(size=(4, 16)).astype(np.float32),
                                  "y": rng.normal(size=(4, 8)).astype(np.float32)},
                      fetch_list=[prog._var_by_vid[loss._vid]])
        assert np.isfinite(out[0]).all()

        # ShardedTrainStep build + lint (abstract: no sharded dispatch)
        mesh = ProcessMesh(np.arange(8).reshape(4, 2), ["dp", "mp"])
        paddle.seed(7)
        model = nn.Linear(16, 16)
        opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=model.parameters())
        step = dist.ShardedTrainStep(
            model, opt, lambda m, x, y: paddle.mean((m(x) - y) ** 2), mesh,
            batch_spec=P("dp"))
        violations, _ = lint_train_step(
            step, jax.ShapeDtypeStruct((8, 16), jnp.float32),
            jax.ShapeDtypeStruct((8, 16), jnp.float32))
        assert violations == []

        # serving engine (wired lint ran at construction)
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.serving import GenerationEngine

        paddle.seed(8)
        cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                          intermediate_size=64, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=128)
        GenerationEngine(LlamaForCausalLM(cfg), num_blocks=16)

        stats = mesh_lint_stats()
        assert stats["entries_linted"] >= 4
        assert stats["entries_failed"] == 0
        assert stats["violations"] == 0
    finally:
        paddle.set_flags(prev)


def test_engine_adapter_pack_covered_with_twin():
    """Multi-tenant LoRA satellite: an adapter-pack engine's per-device
    estimate includes the pack bytes (via the params-style placements
    path), a tight HBM budget flags them (failing fixture), and the same
    engine constructs clean under FLAGS_verify_sharding at the default
    budget (passing twin)."""
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import GenerationEngine

    paddle.seed(11)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=4, max_position_embeddings=64,
                      dtype="float32")
    prev = _set_flags(FLAGS_verify_sharding=True)
    try:
        # passing twin: adapter engine lints clean at construction and
        # the estimate carries the pack's exact bytes as its own group
        eng = GenerationEngine(LlamaForCausalLM(cfg), num_blocks=8,
                               adapters={"rank": 4, "max_adapters": 2})
        violations, est = lint_engine(eng)
        assert violations == []
        assert est["adapter_pack"] == eng._pack.nbytes > 0
        # the pack-less twin has no adapter_pack group at all
        eng2 = GenerationEngine(LlamaForCausalLM(cfg), num_blocks=8)
        _ok, est2 = lint_engine(eng2)
        assert "adapter_pack" not in est2
    finally:
        paddle.set_flags(prev)

    # failing fixture: a budget below the pack-inclusive estimate names
    # the over-budget site at engine construction
    prev = _set_flags(FLAGS_verify_sharding=True,
                      FLAGS_mesh_lint_hbm_budget_gb=1e-6)
    try:
        with pytest.raises(MeshLintError, match="over-budget"):
            GenerationEngine(LlamaForCausalLM(cfg), num_blocks=8,
                             adapters={"rank": 4, "max_adapters": 2})
    finally:
        paddle.set_flags(prev)


def test_sharded_engine_budget_uses_per_device_estimate():
    """Sharded-serving satellite: FLAGS_mesh_lint_hbm_budget_gb is a
    PER-DEVICE budget, judged against the sharding-divided estimate.
    Passing twin: a budget between the sharded per-device estimate and
    the single-device estimate constructs CLEAN on a 2-device mesh —
    the same engine on one device blows the identical budget (the pool
    'fits' only because the mesh divides it).  Failing fixture: a budget
    below even the per-device estimate flags the sharded engine at
    construction, with the sharded (divided) bytes in the message."""
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import GenerationEngine

    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=128,
                      dtype="float32")
    mesh = ProcessMesh(np.arange(2).reshape(2), ["mp"])

    def build(mesh_arg):
        paddle.seed(4)
        return GenerationEngine(LlamaForCausalLM(cfg), num_blocks=16,
                                kv_cache_dtype="int8", mesh=mesh_arg)

    _ok, est_single = lint_engine(build(None))
    eng = build(mesh)
    violations, est_tp = lint_engine(eng)
    assert violations == []
    # the estimate really is per-device: pools AND int8 scales divided
    assert est_tp["kv_pools"] * 2 == est_single["kv_pools"]
    assert est_tp["kv_scales"] * 2 == est_single["kv_scales"]
    assert est_tp["total"] < est_single["total"]

    mid_gb = (est_tp["total"] + est_single["total"]) / 2 / 2 ** 30
    prev = _set_flags(FLAGS_verify_sharding=True,
                      FLAGS_mesh_lint_hbm_budget_gb=mid_gb)
    try:
        build(mesh)  # passing twin: per-device fits the budget
        with pytest.raises(MeshLintError, match="over-budget"):
            build(None)  # one device holds everything: same budget blows
    finally:
        paddle.set_flags(prev)

    # failing fixture: below the per-device estimate, the SHARDED engine
    # is flagged too — and with the divided estimate, not the global one
    low_gb = est_tp["total"] / 2 / 2 ** 30
    prev = _set_flags(FLAGS_verify_sharding=True,
                      FLAGS_mesh_lint_hbm_budget_gb=low_gb)
    try:
        with pytest.raises(MeshLintError, match="over-budget") as ei:
            build(mesh)
    finally:
        paddle.set_flags(prev)
    assert "per device" in str(ei.value)
