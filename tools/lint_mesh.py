#!/usr/bin/env python
"""Standalone mesh lint: sweep sharded computations through the MeshLinter.

Two modes (docs/MESH_LINT.md), mirroring tools/lint_ir.py:

  python tools/lint_mesh.py
      Battery mode — builds the canonical distributed scenarios on the
      8-device CPU mesh (ZeRO-rewritten captured train step, dp x mp
      ShardedTrainStep, paged-KV GenerationEngine with TP pool sharding)
      and requires ZERO violations; then builds one seeded fixture per
      violation class (mismatched collective axis, axis-size mismatch,
      conditional collective, bad ppermute participation, use-after-
      donation, replicated-giant, over-budget) and requires each to be
      FLAGGED.  Everything is abstract — no device collective launches,
      so the battery cannot trip the 8-device XLA:CPU SIGSEGV class it
      guards against.

  python tools/lint_mesh.py --pytest tests/test_auto_parallel.py [more...]
      Sweep mode — runs pytest in-process with the program-creation hook
      installed (static.verify.track_programs) and mesh-lints EVERY
      Program those tests trace.

Exit status 0 = all scenarios behaved; 1 = a clean scenario violated or a
seeded fixture went unflagged (report on stdout).
"""

from __future__ import annotations

import sys

from _lint_common import (pytest_failures, report as _report, run_cli,
                          setup_env, tracked_pytest)

setup_env(host_devices=8)


def _battery() -> int:
    import numpy as np
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")

    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.static as static
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import ProcessMesh
    from paddle_tpu.distributed.shard_map_compat import shard_map
    from paddle_tpu.static.mesh_lint import (MeshLinter, lint_engine,
                                             lint_program, lint_train_step,
                                             mesh_lint_stats)
    from paddle_tpu.static.passes import apply_pass

    failures = 0
    rng = np.random.default_rng(0)
    devs = jax.devices()
    dp8 = Mesh(np.array(devs[:8]).reshape(8), ("dp",))
    dpmp = ProcessMesh(np.arange(8).reshape(4, 2), ["dp", "mp"])

    # ---------------------------------------------------- clean scenarios
    # 1. captured train step + ZeRO sharding rewrite, linted at the same
    # boundary the Executor uses
    paddle.seed(0)
    layer = nn.Linear(16, 8)
    opt = paddle.optimizer.Adam(learning_rate=0.1,
                                parameters=layer.parameters())
    prog = static.Program()
    with static.program_guard(prog):
        x = static.data("x", [8, 16], "float32")
        yt = static.data("yt", [8, 8], "float32")
        loss = paddle.mean((layer(x) - yt) ** 2)
        opt.minimize(loss)
    apply_pass(prog, "auto_parallel_sharding", mesh=dp8, stage=2)
    failures += _report(
        "zero-sharded-program",
        lint_program(prog, [loss._vid], mesh=dp8))

    # 2. dp x mp ShardedTrainStep — abstract build only (journaled
    # accumulator materialization + jaxpr trace; nothing dispatches)
    paddle.seed(1)
    model = nn.Linear(16, 16)
    opt2 = paddle.optimizer.AdamW(learning_rate=1e-2,
                                  parameters=model.parameters())
    step = dist.ShardedTrainStep(
        model, opt2, lambda m, bx, by: paddle.mean((m(bx) - by) ** 2),
        dpmp, batch_spec=P("dp"))
    bx = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    by = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    violations, est = lint_train_step(step, bx, by)
    failures += _report("sharded-train-step", violations)
    print(f"     per-device estimate: "
          f"{ {k: int(v) for k, v in est.items()} }")

    # 3. GenerationEngine with TP-sharded paged pools
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import GenerationEngine

    paddle.seed(2)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=128)
    mp2 = ProcessMesh(np.arange(2).reshape(2), ["mp"])
    eng = GenerationEngine(LlamaForCausalLM(cfg), num_blocks=16, mesh=mp2)
    violations, est = lint_engine(eng)
    failures += _report("tp-sharded-engine", violations)
    print(f"     per-device estimate: "
          f"{ {k: int(v) for k, v in est.items()} }")

    # ------------------------------------------------- seeded violations
    aval = jax.ShapeDtypeStruct((8, 4), jnp.float32)
    linter = MeshLinter(mesh=dp8)

    # mismatched collective axis: a shard_map built for an 'mp' mesh on a
    # session mesh that only has 'dp'
    mp_mesh = Mesh(np.array(devs[:2]), ("mp",))
    wrong_axis = shard_map(lambda v: lax.psum(v, "mp"), mesh=mp_mesh,
                           in_specs=P("mp"), out_specs=P())
    failures += _report("mismatched-collective-axis",
                        linter.lint_callable(wrong_axis, aval),
                        expect_codes={"unknown-axis"})

    # axis-size mismatch: shard_map binds dp=2 against the dp=8 session
    dp2 = Mesh(np.array(devs[:2]), ("dp",))
    small_world = shard_map(lambda v: lax.psum(v, "dp"), mesh=dp2,
                            in_specs=P("dp"), out_specs=P())
    failures += _report("axis-size-mismatch",
                        linter.lint_callable(small_world, aval),
                        expect_codes={"axis-size-mismatch"})

    # conditional collective: psum reachable only under a data-dependent
    # predicate — the deadlock/SIGSEGV class
    def cond_body(v):
        return lax.cond(v.sum() > 0, lambda t: lax.psum(t, "dp"),
                        lambda t: t, v)

    conditional = shard_map(cond_body, mesh=dp8, in_specs=P("dp"),
                            out_specs=P("dp"))
    failures += _report("conditional-collective",
                        linter.lint_callable(conditional, aval),
                        expect_codes={"conditional-collective"})

    # bad ppermute: duplicate source — jax traces it happily, runtime
    # participation is non-uniform
    bad_perm = shard_map(
        lambda v: lax.ppermute(v, "dp", [(0, 1), (0, 2)]), mesh=dp8,
        in_specs=P("dp"), out_specs=P("dp"))
    failures += _report("bad-ppermute-participation",
                        linter.lint_callable(bad_perm, aval),
                        expect_codes={"bad-permutation"})

    # use-after-donation: fetch the PRE-update buffer of a donated,
    # in-place-written state var
    paddle.seed(3)
    layer2 = nn.Linear(4, 4)
    opt3 = paddle.optimizer.SGD(learning_rate=0.1,
                                parameters=layer2.parameters())
    prog2 = static.Program()
    with static.program_guard(prog2):
        x2 = static.data("x2", [4, 4], "float32")
        y2 = static.data("y2", [4, 4], "float32")
        loss2 = paddle.mean((layer2(x2) - y2) ** 2)
        opt3.minimize(loss2)
    donated_vid = next(iter(prog2.writes))
    failures += _report(
        "use-after-donation",
        lint_program(prog2, [loss2._vid, donated_vid], mesh=dp8),
        expect_codes={"use-after-donation"})

    # replicated-giant: a >threshold tensor fully replicated on the mesh
    big = jax.ShapeDtypeStruct((2048, 2048), jnp.float32)  # 16 MiB
    failures += _report(
        "replicated-giant",
        linter.lint_placements([("big_param", big, None)]),
        expect_codes={"replicated-giant"})

    # over-budget: per-device estimate above a deliberately tiny budget
    tight = MeshLinter(mesh=dp8, budget_bytes=1024)
    viol, est = tight.estimate_device_bytes(
        {"params": [("w", big, P("dp", None))]})
    failures += _report("over-budget-memory", viol,
                        expect_codes={"over-budget"})
    print(f"     per-device estimate: "
          f"{ {k: int(v) for k, v in est.items()} }")

    print()
    print("mesh lint counters:", mesh_lint_stats())
    del rng
    return failures


def _pytest_sweep(node_ids) -> int:
    from paddle_tpu.static.mesh_lint import lint_program, mesh_lint_stats

    rc, programs = tracked_pytest(node_ids)
    print(f"\npytest exit={rc}; {len(programs)} Program(s) traced — "
          "mesh-linting")
    failures = 0
    for i, prog in enumerate(programs):
        violations = lint_program(prog)
        failures += _report(f"program#{i} "
                            f"({len(prog.global_block().ops)} ops)",
                            violations)
    print()
    print("mesh lint counters:", mesh_lint_stats())
    return failures + pytest_failures(rc)


def main(argv=None):
    return run_cli(
        "lint_mesh", _battery, _pytest_sweep, argv, doc=__doc__,
        ok_msg="all scenarios behaved (clean paths clean, seeded "
               "violations flagged)",
        fail_msg="{n} scenario(s) misbehaved",
        forward_extras=True,
        pytest_help="run these pytest node ids and mesh-lint every "
                    "Program they trace; unrecognized args (e.g. "
                    "-m 'not slow', -k expr) are forwarded to pytest")


if __name__ == "__main__":
    sys.exit(main())
