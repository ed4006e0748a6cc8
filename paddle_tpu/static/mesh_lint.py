"""Mesh lint: static SPMD sharding/collective/donation analyzer.

Reference role: the reference's auto_parallel layer validates SPMD rules
*before* execution (paddle/phi/infermeta/spmd_rules, the semi-auto
InferSpmd -> Reshard pipeline) — a mis-axised collective or an impossible
placement is a compile-time error there, never a hang.  Our distributed
tier had no equivalent: a psum over a dead mesh axis, a ppermute whose
permutation double-writes a rank, or a collective reachable only under a
data-dependent predicate surfaces at runtime — worst case as the
in-process 8-device XLA:CPU SIGSEGV class that keeps pushing real
coverage into `slow` (ROADMAP item 5).  This module is the PR-4
ProgramVerifier philosophy (docs/VERIFIER.md: mechanical checks + seeded
violation fixtures catch whole bug classes) extended from single-device
Program semantics to the mesh.

Everything here is ABSTRACT: computations are interpreted via
``jax.make_jaxpr`` / ``jax.eval_shape`` — no device collective is ever
launched, so the analysis itself cannot trip the crash class it hunts.

Four check families (docs/MESH_LINT.md):

1. **Sharding propagation** — every placement/PartitionSpec names a live
   mesh axis, shard dims exist on the tensor, no mesh axis shards two
   dims, sharded dims divide by the axis size; large tensors that end up
   fully replicated on a multi-device mesh are flagged with their
   per-device byte cost (the silent-replication blowup).
2. **Collective congruence** — every collective primitive reachable from
   an entry point (psum/ppermute/all_gather/all_to_all/..., including
   shard_map-internal forms) names axes that exist with consistent sizes,
   ppermute permutations are valid partial permutations (jax does NOT
   check this at trace time — a duplicate destination deadlocks or
   corrupts at run time), axis_index_groups partition the axis uniformly,
   and collectives reachable only under ``lax.cond`` branches or
   ``lax.while_loop`` bodies are flagged as the data-dependent
   deadlock/SIGSEGV class.
3. **Donation / aliasing** — fetching the stale value of a donated,
   in-place-updated state buffer (Program fetch of a `writes` target) and
   double-donating one buffer (the same jax.Array appearing twice in a
   donated state/pool list) are reported as use-after-donation.
4. **Per-device memory estimate** — sharding-divided HBM bytes per device
   for params + optimizer state + KV pools (+ QuantPool scales), linted
   against ``FLAGS_mesh_lint_hbm_budget_gb``.  Persistent state only:
   activation peaks are XLA's to schedule and are deliberately out of
   scope (an abstract liveness bound would be wrong under GSPMD
   repartitioning).

Entry points: ``lint_program`` (Program IR, wired into the Executor and
ProgramPassManager), ``lint_train_step`` (TrainStep / ShardedTrainStep),
``lint_engine`` (serving.GenerationEngine) — all gated in-tree on
``FLAGS_verify_sharding`` (same contract as ``FLAGS_verify_programs``:
pass-boundary checks, named failing site, counters via
``paddle_tpu.profiler.mesh_lint_stats()`` + a Profiler.summary footer).
``tools/lint_mesh.py`` sweeps both battery fixtures and pytest runs.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import jax

__all__ = [
    "MeshViolation",
    "MeshLintError",
    "MeshLinter",
    "lint_program",
    "lint_train_step",
    "lint_engine",
    "mesh_lint_stats",
    "reset_mesh_lint_stats",
]


_COUNTERS = {
    "entries_linted": 0,      # programs + train steps + engines linted
    "entries_failed": 0,
    "violations": 0,
    "collectives_checked": 0,
    "constraints_checked": 0,  # sharding_constraint placements validated
    "placements_checked": 0,   # named tensors through the placement tier
    "donation_checks": 0,
    "memory_estimates": 0,
    "trace_skips": 0,          # op fns that could not be abstractly traced
}


def mesh_lint_stats(reset: bool = False) -> dict:
    out = dict(_COUNTERS)
    if reset:
        reset_mesh_lint_stats()
    return out


def reset_mesh_lint_stats():
    for k in _COUNTERS:
        _COUNTERS[k] = 0


@dataclass
class MeshViolation:
    code: str        # unknown-axis | axis-size-mismatch | bad-permutation |
                     # bad-groups | conditional-collective | bad-shard-dim |
                     # duplicate-axis | indivisible-shard | replicated-giant |
                     # use-after-donation | over-budget
    message: str
    site: str = ""   # entry point / op / tensor the violation anchors to

    def __str__(self):
        loc = f" [{self.site}]" if self.site else ""
        return f"{self.code}{loc}: {self.message}"


class MeshLintError(RuntimeError):
    def __init__(self, violations, header="Mesh lint failed"):
        self.violations = list(violations)
        lines = [f"{header} ({len(self.violations)} violation(s)):"]
        lines += [f"  - {v}" for v in self.violations]
        super().__init__("\n".join(lines))


# Collective primitives whose participation must be congruent across the
# mesh.  Under shard_map's varying-axes typing (check_vma, jax's default)
# psum binds as psum_invariant and all_gather as all_gather_invariant /
# all_gather_reduced; pvary is the typing's marker and axis_index reads a
# coordinate — NOT collectives (no cross-device rendezvous), so they are
# deliberately absent: flagging them under a cond would false-positive
# every data-dependent branch.
_COLLECTIVE_PRIMS = frozenset({
    "psum", "psum_invariant", "unreduced_psum", "pmax", "pmin", "pmean",
    "ppermute", "pshuffle", "pbroadcast", "all_gather",
    "all_gather_invariant", "all_gather_reduced", "all_to_all",
    "ragged_all_to_all", "reduce_scatter", "unreduced_reduce_scatter",
    "psum_scatter", "pgather",
})

# Sub-jaxprs under these eqn param keys execute under a DATA-DEPENDENT
# predicate: a collective inside is only joined by devices whose predicate
# agrees — the deadlock/SIGSEGV class.  (lax.scan has a static trip count
# and pjit/remat are unconditional, so their bodies stay at the same
# conditional depth.)
_CONDITIONAL_PARAM_KEYS = {"branches", "cond_jaxpr", "body_jaxpr"}


def _axis_sizes(mesh) -> dict:
    """name -> size for a ProcessMesh / jax Mesh / {name: size} / None."""
    if mesh is None:
        return {}
    if isinstance(mesh, dict):
        return {str(k): int(v) for k, v in mesh.items()}
    shape = getattr(mesh, "shape", None)
    names = getattr(mesh, "dim_names", None)
    if names is not None and shape is not None:  # ProcessMesh
        return dict(zip(names, shape))
    jm = getattr(mesh, "axis_names", None)
    if jm is not None:  # jax.sharding.Mesh
        return {n: int(mesh.shape[n]) for n in mesh.axis_names}
    raise TypeError(f"cannot read mesh axes from {type(mesh)}")


def _default_mesh():
    from paddle_tpu.distributed.auto_parallel.process_mesh import get_mesh

    return get_mesh()


def _spec_entries(spec):
    """Flatten a PartitionSpec into per-dim tuples of axis names."""
    out = []
    for e in tuple(spec):
        if e is None:
            out.append(())
        elif isinstance(e, (tuple, list)):
            out.append(tuple(e))
        else:
            out.append((e,))
    return out


def _nbytes(aval) -> int:
    return int(np.prod(aval.shape, dtype=np.int64)) * np.dtype(aval.dtype).itemsize


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024.0 or unit == "GiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024.0
    return f"{n:.1f} GiB"


class MeshLinter:
    """Static analyzer over abstract sharded computations.

    mesh: ProcessMesh / jax Mesh / {axis: size} — defaults to the session
    mesh (paddle_tpu.distributed.get_mesh()).  Axis-existence checks are
    skipped when no mesh is known ANYWHERE (no session mesh, no shard_map
    binding in scope); shard_map-bound axes always validate their own
    interiors.  replicated_bytes / budget_bytes default to the
    FLAGS_mesh_lint_replicated_mb / FLAGS_mesh_lint_hbm_budget_gb knobs.
    """

    def __init__(self, mesh=None, replicated_bytes=None, budget_bytes=None):
        from paddle_tpu._core import flags

        self.mesh = mesh if mesh is not None else _default_mesh()
        self.axes = _axis_sizes(self.mesh)
        if replicated_bytes is None:
            replicated_bytes = int(
                float(flags.flag("FLAGS_mesh_lint_replicated_mb")) * 2 ** 20)
        if budget_bytes is None:
            gb = float(flags.flag("FLAGS_mesh_lint_hbm_budget_gb"))
            budget_bytes = int(gb * 2 ** 30) if gb > 0 else 0
        self.replicated_bytes = replicated_bytes
        self.budget_bytes = budget_bytes  # 0 = budget check off

    # ------------------------------------------------- family 2: collectives
    def lint_callable(self, fn, *in_avals, site=""):
        """Abstractly trace `fn` (jax.make_jaxpr under the mesh's axis env)
        and walk the jaxpr for collective congruence.  Never executes.

        The global RNG state is restored after the trace: an op fn that
        draws keys (dropout, sampling) must not shift the live training
        stream just because the lint looked at it (same contract as the
        verifier's differential _replay).  The trace also SUSPENDS static
        capture — linting a funnel-routed callable while a program_guard
        is active must not record the traced ops (or their tracers) into
        the program under capture (same rule as Program.record's op
        bodies and the pipeline's shape probes)."""
        from paddle_tpu._core import random as _rnd

        from .program import suspend_capture

        axis_env = [(n, s) for n, s in self.axes.items()]
        rng_state = _rnd.get_rng_state()
        try:
            with suspend_capture():
                closed = jax.make_jaxpr(fn, axis_env=axis_env)(*in_avals)
        except NameError as e:
            # make_jaxpr raises 'unbound axis name: X' for a collective
            # whose axis neither the mesh nor any shard_map binds — that
            # failure IS the mismatched-collective-axis violation.
            _COUNTERS["collectives_checked"] += 1
            return [MeshViolation(
                "unknown-axis",
                f"collective references an axis no mesh binds: {e} "
                f"(live mesh axes: {sorted(self.axes) or 'none'})", site)]
        except ValueError as e:
            if "axis_index_groups" in str(e):
                # jax validates the partition property itself at trace
                # time; surface it as the named violation instead of a
                # silent skip
                _COUNTERS["collectives_checked"] += 1
                return [MeshViolation(
                    "bad-groups",
                    f"collective axis_index_groups rejected at abstract "
                    f"trace: {e}", site)]
            _COUNTERS["trace_skips"] += 1
            return []
        except TypeError as e:
            if "varying manual axes" in str(e):
                # shard_map's varying-axes typing refuses a cond / while
                # whose paths disagree on which mesh axes the result
                # varies over: one path reduces over an axis (a
                # collective) that the other never runs.  No jaxpr exists
                # to walk, so jax's refusal IS the finding, not a skip
                _COUNTERS["collectives_checked"] += 1
                return [MeshViolation(
                    "conditional-collective",
                    "the paths of a data-dependent conditional disagree "
                    "on the mesh axes their results vary over — a "
                    "collective is reachable only under the predicate "
                    "(the distributed deadlock/SIGSEGV class); jax "
                    f"refused the trace: {e}", site)]
            _COUNTERS["trace_skips"] += 1
            return []
        except Exception:
            # host-only op / data-dependent capture: nothing to walk
            _COUNTERS["trace_skips"] += 1
            return []
        finally:
            _rnd.set_rng_state(rng_state)
        return self._walk_jaxpr(closed.jaxpr, dict(self.axes), site, 0)

    def _walk_jaxpr(self, jaxpr, bound, site, cond_depth):
        v = []
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim == "shard_map":
                v += self._check_shard_map_mesh(eqn, site)
                inner_bound = dict(bound)
                inner_bound.update(_axis_sizes(eqn.params["mesh"]))
                inner = eqn.params["jaxpr"]
                inner = getattr(inner, "jaxpr", inner)
                v += self._walk_jaxpr(inner, inner_bound, site, cond_depth)
                continue
            if prim in _COLLECTIVE_PRIMS:
                v += self._check_collective(eqn, bound, site, cond_depth)
            elif prim == "sharding_constraint":
                v += self._check_constraint(eqn, site)
            # generic recursion into sub-jaxprs (cond branches, while
            # cond/body, scan/pjit/remat bodies, custom_* rules)
            for key, val in eqn.params.items():
                depth = cond_depth + (1 if key in _CONDITIONAL_PARAM_KEYS else 0)
                for sub in (val if isinstance(val, (list, tuple)) else (val,)):
                    sub_jaxpr = getattr(sub, "jaxpr", sub)
                    if hasattr(sub_jaxpr, "eqns"):
                        v += self._walk_jaxpr(sub_jaxpr, bound,
                                              f"{site}/{prim}" if site else prim,
                                              depth)
        return v

    def _eqn_axes(self, eqn):
        names = eqn.params.get("axes", eqn.params.get("axis_name", ()))
        if not isinstance(names, (tuple, list, frozenset, set)):
            names = (names,)
        return [n for n in names if isinstance(n, str)]

    def _check_collective(self, eqn, bound, site, cond_depth):
        v = []
        prim = eqn.primitive.name
        _COUNTERS["collectives_checked"] += 1
        axes = self._eqn_axes(eqn)
        for name in axes:
            if name not in bound:
                v.append(MeshViolation(
                    "unknown-axis",
                    f"{prim} over axis {name!r}, but the live mesh axes are "
                    f"{sorted(bound) or 'none'} — the collective would never "
                    "rendezvous", site))
        if cond_depth > 0:
            v.append(MeshViolation(
                "conditional-collective",
                f"{prim} over {axes or '?'} is reachable only under a "
                "data-dependent predicate (lax.cond branch / while body): "
                "devices whose predicate disagrees skip the rendezvous — "
                "the distributed deadlock/SIGSEGV class.  Hoist the "
                "collective out of the branch or make the predicate "
                "mesh-uniform", site))
        if prim == "ppermute" and axes and axes[0] in bound:
            v += self._check_perm(eqn.params.get("perm", ()),
                                  axes[0], bound[axes[0]], site)
        groups = eqn.params.get("axis_index_groups")
        if groups and axes and axes[0] in bound:
            v += self._check_groups(groups, axes[0], bound[axes[0]], prim, site)
        return v

    @staticmethod
    def _check_perm(perm, axis, size, site):
        """jax traces any perm; a duplicate src/dst or out-of-range index
        is a silent runtime corruption/deadlock.  Require a valid partial
        permutation: unique sources, unique destinations, all in range."""
        v = []
        srcs = [p[0] for p in perm]
        dsts = [p[1] for p in perm]
        bad = [p for p in perm
               if not (0 <= p[0] < size and 0 <= p[1] < size)]
        if bad:
            v.append(MeshViolation(
                "bad-permutation",
                f"ppermute over {axis!r} (size {size}) has out-of-range "
                f"pairs {bad} — ranks beyond the axis never participate",
                site))
        if len(set(srcs)) != len(srcs) or len(set(dsts)) != len(dsts):
            dup_s = sorted({s for s in srcs if srcs.count(s) > 1})
            dup_d = sorted({d for d in dsts if dsts.count(d) > 1})
            v.append(MeshViolation(
                "bad-permutation",
                f"ppermute over {axis!r} is not a partial permutation "
                f"(duplicate sources {dup_s}, duplicate destinations "
                f"{dup_d}) — participation is non-uniform and the result "
                "rank-dependent", site))
        return v

    @staticmethod
    def _check_groups(groups, axis, size, prim, site):
        v = []
        flat = [i for g in groups for i in g]
        sizes = {len(g) for g in groups}
        if len(sizes) > 1:
            v.append(MeshViolation(
                "bad-groups",
                f"{prim} axis_index_groups over {axis!r} have non-uniform "
                f"sizes {sorted(sizes)} — participation differs per group",
                site))
        if sorted(flat) != list(range(size)):
            v.append(MeshViolation(
                "bad-groups",
                f"{prim} axis_index_groups over {axis!r} do not partition "
                f"range({size}): {groups} — some ranks never rendezvous",
                site))
        return v

    def _check_shard_map_mesh(self, eqn, site):
        """A shard_map binds its own mesh; axis names that collide with the
        session mesh at a DIFFERENT size mean the op was built for another
        topology (participation would be non-uniform)."""
        v = []
        for name, size in _axis_sizes(eqn.params["mesh"]).items():
            if self.axes and name in self.axes and self.axes[name] != size:
                v.append(MeshViolation(
                    "axis-size-mismatch",
                    f"shard_map binds axis {name!r} with size {size}, but "
                    f"the session mesh has {name!r} size "
                    f"{self.axes[name]} — the op was built for a different "
                    "topology", site))
            elif self.axes and name not in self.axes:
                v.append(MeshViolation(
                    "unknown-axis",
                    f"shard_map binds axis {name!r} which the session mesh "
                    f"does not have (mesh axes: {sorted(self.axes)}) — "
                    "collectives over it will not line up with the "
                    "session topology", site))
        return v

    def _check_constraint(self, eqn, site):
        _COUNTERS["constraints_checked"] += 1
        sharding = eqn.params.get("sharding")
        spec = getattr(sharding, "spec", None)
        if spec is None or not self.axes:
            return []
        v = []
        for names in _spec_entries(spec):
            for name in names:
                if name not in self.axes:
                    v.append(MeshViolation(
                        "unknown-axis",
                        f"sharding_constraint places over axis {name!r}, "
                        f"not a live mesh axis ({sorted(self.axes)})", site))
        return v

    # -------------------------------------------- family 1: placements
    def lint_placements(self, named, site=""):
        """`named`: iterable of (name, aval-or-array, placement) where
        placement is a NamedSharding, PartitionSpec, placements list
        (Shard/Replicate/Partial), or None (treated as replicated)."""
        from paddle_tpu.distributed.auto_parallel.placement import (
            Placement, Shard)

        v = []
        mesh_size = int(np.prod(list(self.axes.values()))) if self.axes else 1
        for name, aval, placement in named:
            _COUNTERS["placements_checked"] += 1
            here = f"{site}:{name}" if site else name
            ndim = len(aval.shape)
            entries = None  # per-tensor-dim tuple of mesh axis names
            if placement is not None and isinstance(placement, (list, tuple)) \
                    and placement and isinstance(placement[0], Placement):
                # reference placements: one entry per MESH dim
                entries = [()] * ndim
                for mesh_dim, p in enumerate(placement):
                    if mesh_dim >= len(self.axes):
                        v.append(MeshViolation(
                            "bad-shard-dim",
                            f"{len(placement)} placements for a "
                            f"{len(self.axes)}-dim mesh", here))
                        continue
                    if isinstance(p, Shard):
                        axis_name = list(self.axes)[mesh_dim]
                        if p.dim >= ndim or p.dim < -ndim:
                            v.append(MeshViolation(
                                "bad-shard-dim",
                                f"Shard(dim={p.dim}) on a rank-{ndim} "
                                f"tensor of shape {tuple(aval.shape)}", here))
                        else:
                            entries[p.dim % ndim] += (axis_name,)
            else:
                spec = getattr(placement, "spec", placement)
                if spec is not None and not hasattr(spec, "__iter__"):
                    spec = None
                if spec is not None:
                    ents = _spec_entries(spec)
                    if len(ents) > ndim:
                        v.append(MeshViolation(
                            "bad-shard-dim",
                            f"PartitionSpec{tuple(spec)} has "
                            f"{len(ents)} entries for a rank-{ndim} tensor "
                            f"of shape {tuple(aval.shape)}", here))
                        ents = ents[:ndim]
                    entries = ents + [()] * (ndim - len(ents))
                else:
                    entries = [()] * ndim

            used: dict = {}
            for dim, names in enumerate(entries):
                for axis_name in names:
                    if self.axes and axis_name not in self.axes:
                        v.append(MeshViolation(
                            "unknown-axis",
                            f"placed over axis {axis_name!r}, not a live "
                            f"mesh axis ({sorted(self.axes)})", here))
                        continue
                    if axis_name in used:
                        v.append(MeshViolation(
                            "duplicate-axis",
                            f"mesh axis {axis_name!r} shards both dim "
                            f"{used[axis_name]} and dim {dim}", here))
                    used[axis_name] = dim
                    axsz = self.axes.get(axis_name, 1)
                    if axsz > 1 and aval.shape[dim] % axsz != 0:
                        v.append(MeshViolation(
                            "indivisible-shard",
                            f"dim {dim} (size {aval.shape[dim]}) is not "
                            f"divisible by axis {axis_name!r} (size "
                            f"{axsz}) — GSPMD pads and the pad is computed "
                            "and re-synced on every use", here))
            if (mesh_size > 1 and not used
                    and _nbytes(aval) >= self.replicated_bytes > 0):
                nb = _nbytes(aval)
                v.append(MeshViolation(
                    "replicated-giant",
                    f"{_fmt_bytes(nb)} tensor of shape "
                    f"{tuple(aval.shape)} is fully replicated on a "
                    f"{mesh_size}-device mesh — {_fmt_bytes(nb)} of HBM "
                    f"per device, {_fmt_bytes(nb * mesh_size)} total; "
                    "shard it or raise FLAGS_mesh_lint_replicated_mb if "
                    "intentional", here))
        return v

    # -------------------------------------- family 4: per-device memory
    def shard_factor(self, aval, placement) -> int:
        """How many ways `placement` divides the tensor across the mesh."""
        factor = 1
        spec = getattr(placement, "spec", placement)
        if spec is None or not hasattr(spec, "__iter__"):
            return 1
        for names in _spec_entries(spec):
            for name in names:
                factor *= self.axes.get(name, 1)
        return max(1, factor)

    def estimate_device_bytes(self, groups, site=""):
        """groups: {group_name: [(name, aval, placement), ...]} — returns
        (violations, {group: per-device bytes, "total": ...}).  The budget
        check fires on the total when FLAGS_mesh_lint_hbm_budget_gb > 0."""
        _COUNTERS["memory_estimates"] += 1
        est = {}
        for group, named in groups.items():
            total = 0
            for _name, aval, placement in named:
                total += _nbytes(aval) // self.shard_factor(aval, placement)
            est[group] = total
        est["total"] = sum(est.values())
        v = []
        if self.budget_bytes and est["total"] > self.budget_bytes:
            parts = ", ".join(f"{g}={_fmt_bytes(b)}" for g, b in est.items()
                              if g != "total")
            v.append(MeshViolation(
                "over-budget",
                f"estimated {_fmt_bytes(est['total'])} of HBM per device "
                f"({parts}) exceeds the "
                f"FLAGS_mesh_lint_hbm_budget_gb budget of "
                f"{_fmt_bytes(self.budget_bytes)}", site))
        return v, est

    # ------------------------------------------- family 3 + program IR
    def lint_program(self, program, fetch_vids=()):
        """Collective congruence per recorded op + use-after-donation on
        the fetch set (the Executor donates state buffers whenever the
        program carries writes; fetching a write target returns the
        donated input's stale alias)."""
        v = []
        _COUNTERS["donation_checks"] += 1
        redefined = {vid for op in program.global_block().ops
                     for vid in op.out_vids}
        if program.writes:
            for vid in fetch_vids:
                if (vid in program.writes and vid in program.param_inits
                        and vid not in redefined):
                    var = program._var_by_vid.get(vid)
                    name = var.name if var is not None else vid
                    v.append(MeshViolation(
                        "use-after-donation",
                        f"fetch of state var '{name}' (vid {vid}) returns "
                        "the PRE-update buffer of a donated, in-place-"
                        "written state input — the alias is dead the "
                        "moment the dispatch commits.  Fetch the updated "
                        f"value (vid {program.writes[vid]}) instead", name))
        for i, op in enumerate(program.global_block().ops):
            in_avals = []
            ok = True
            for spec in op.arg_spec:
                if spec[0] != "var":
                    continue
                var = program._var_by_vid.get(spec[1])
                if var is None:
                    ok = False  # structural breakage: ProgramVerifier's job
                    break
                in_avals.append(jax.ShapeDtypeStruct(var._value.shape,
                                                     var._value.dtype))
            if ok:
                v += self.lint_callable(op.fn, *in_avals,
                                        site=f"op#{i} {op.type}")
        return v

    # -------------------------------------------------- entry: train step
    def _named_state(self, step):
        """(name, aval, placement) triples for a TrainStep's state, using
        the SAME sharding resolution the step will apply — the lint is
        predictive, not post-hoc."""
        names = {}
        model_sd = step.model.state_dict()
        for n, t in model_sd.items():
            names[id(t)] = n
        out = []
        sharded = hasattr(step, "_param_sharding")
        for t in (step._state or []):
            name = names.get(id(t), getattr(t, "name", "") or "opt_state")
            val = t._value
            if sharded:
                sh = getattr(val, "sharding", None)
                from jax.sharding import NamedSharding

                if not isinstance(sh, NamedSharding):
                    sh = step._param_sharding(t) if id(t) in names else None
                    if sh is None and val.ndim > 0:
                        # optimizer accumulator: resolve like _place_state
                        sh = step._acc_sharding(
                            val, step._param_sharding(t))
                out.append((name, val, sh))
            else:
                out.append((name, val, getattr(val, "sharding", None)
                            if hasattr(val, "sharding") else None))
        return out

    def lint_train_step(self, step, *batch):
        """Families 1-4 over a (Sharded)TrainStep: state placements, the
        step jaxpr's collectives/constraints, the donation contract, and
        the per-device memory estimate.  `batch`: example values or
        ShapeDtypeStructs (nothing is executed)."""
        from paddle_tpu._core import random as rng_mod  # noqa: F401
        from paddle_tpu._core.tensor import Tensor

        step._ensure_built()
        v = []
        named = self._named_state(step)
        v += self.lint_placements(named, site="train_step.state")

        # donation contract: state buffers are donated (donate_argnums=0);
        # one buffer donated twice is UB, and a batch leaf aliasing a
        # donated buffer is read-after-donation by construction
        _COUNTERS["donation_checks"] += 1
        seen: dict = {}
        for name, val, _sh in named:
            key = id(val)
            if key in seen and seen[key] != name:
                v.append(MeshViolation(
                    "use-after-donation",
                    f"state entries '{seen[key]}' and '{name}' share ONE "
                    "buffer — the compiled step donates it twice "
                    "(undefined behavior; alias the Tensors, not the "
                    "buffer)", f"train_step.state:{name}"))
            seen[key] = name
        batch_leaves = jax.tree_util.tree_leaves(
            [b._value if isinstance(b, Tensor) else b for b in batch])
        for i, b in enumerate(batch_leaves):
            if id(b) in seen:
                v.append(MeshViolation(
                    "use-after-donation",
                    f"batch leaf #{i} aliases donated state buffer "
                    f"'{seen[id(b)]}' — the batch input is dead after the "
                    "dispatch donates it", "train_step.batch"))

        # collective congruence of the whole step jaxpr
        def aval(x):
            val = x._value if isinstance(x, Tensor) else x
            if isinstance(val, jax.ShapeDtypeStruct):
                return val
            import jax.numpy as jnp

            val = jnp.asarray(val)
            return jax.ShapeDtypeStruct(val.shape, val.dtype)

        state_avals = [jax.ShapeDtypeStruct(val.shape, val.dtype)
                       for _n, val, _s in named]
        batch_avals = jax.tree_util.tree_map(
            aval, batch, is_leaf=lambda x: isinstance(x, Tensor))
        key_aval = jax.eval_shape(
            lambda: jax.random.fold_in(jax.random.key(0), 0))
        v += self.lint_callable(step._compiled, state_avals,
                                list(batch_avals), key_aval,
                                site="train_step.step_fn")

        # per-device memory: params vs optimizer moments
        model_names = set(step.model.state_dict())
        params = [e for e in named if e[0] in model_names]
        opt = [e for e in named if e[0] not in model_names]
        groups = {"params": params, "optimizer": opt}
        mv, est = self.estimate_device_bytes(groups, site="train_step")
        v += mv
        return v, est

    # ----------------------------------------------------- entry: engine
    def lint_engine(self, engine):
        """Families 1/3/4 over a serving.GenerationEngine: model state and
        KV-pool placements, pool donation aliasing, per-device pool bytes.
        Nothing is dispatched."""
        v = []
        named = []
        for n, t in engine.model.state_dict().items():
            val = t._value
            named.append((n, val, getattr(val, "sharding", None)))
        from paddle_tpu.ops.paged_attention import pool_parts

        d_sharding = getattr(engine, "_d_pool_sharding", None)
        d_pools = getattr(engine, "_d_pools", None) or [[], []]
        pool_lists = [
            ("k", engine._pools[0], engine._pool_sharding),
            ("v", engine._pools[1], engine._pool_sharding),
            ("draft_k", d_pools[0], d_sharding),
            ("draft_v", d_pools[1], d_sharding),
        ]
        pool_named, scale_named = [], []
        for tag, pools, sharding in pool_lists:
            for i, pool in enumerate(pools):
                for part, arr in pool_parts(pool):
                    dest = pool_named if part == "payload" else scale_named
                    dest.append((f"{tag}pool[{i}].{part}", arr, sharding))
        # multi-tenant LoRA: the adapter pack's slot-stacked A/B + scaling
        # arrays are engine state too — placements and per-device bytes go
        # through the same path as params (nn/lora.py AdapterPack.parts)
        pack = getattr(engine, "_pack", None)
        pack_named = []
        if pack is not None:
            pack_named = [(name, arr, getattr(arr, "sharding", None))
                          for name, arr in pack.parts()]

        v += self.lint_placements(named, site="engine.params")
        v += self.lint_placements(pool_named, site="engine.pools")
        if pack_named:
            v += self.lint_placements(pack_named, site="engine.adapter_pack")

        _COUNTERS["donation_checks"] += 1
        seen: dict = {}
        for name, data, _sh in pool_named:
            if id(data) in seen:
                v.append(MeshViolation(
                    "use-after-donation",
                    f"pools '{seen[id(data)]}' and '{name}' share one "
                    "buffer — the decode step donates both pool lists "
                    "(donate_argnums=(1, 2)); a shared buffer is donated "
                    "twice per dispatch", f"engine.pools:{name}"))
            seen[id(data)] = name

        groups = {"params": named, "kv_pools": pool_named}
        if scale_named:  # QuantPool scales ride alongside the int8 payload
            groups["kv_scales"] = scale_named
        if pack_named:  # adapter bytes count against the HBM budget too
            groups["adapter_pack"] = pack_named
        mv, est = self.estimate_device_bytes(groups, site="engine")
        v += mv
        return v, est


# --------------------------------------------------------------------------
# one-shot conveniences (the Executor / TrainStep / engine wiring points)


def _finish(violations, header, raise_on_error):
    _COUNTERS["entries_linted"] += 1
    if violations:
        _COUNTERS["entries_failed"] += 1
        _COUNTERS["violations"] += len(violations)
        if raise_on_error:
            raise MeshLintError(violations, header=header)
    return violations


def lint_program(program, fetch_vids=(), mesh=None, raise_on_error=False,
                 **kwargs):
    linter = MeshLinter(mesh=mesh, **kwargs)
    return _finish(linter.lint_program(program, fetch_vids),
                   "Mesh lint failed (Program)", raise_on_error)


def lint_train_step(step, *batch, mesh=None, raise_on_error=False, **kwargs):
    # the step's OWN mesh is the authority: a plain TrainStep (mesh-less,
    # deliberately single-device) built while a multi-device session mesh
    # happens to be active must NOT be judged against that session mesh —
    # its replicated params are correct, not replication blowups
    if mesh is None:
        mesh = getattr(step, "mesh", None) or {}
    linter = MeshLinter(mesh=mesh, **kwargs)
    violations, est = linter.lint_train_step(step, *batch)
    _finish(violations, "Mesh lint failed (TrainStep)", raise_on_error)
    return violations, est


def lint_engine(engine, mesh=None, raise_on_error=False, **kwargs):
    # same authority rule as lint_train_step: an engine constructed with
    # mesh=None is single-device BY CONTRACT regardless of session state
    if mesh is None:
        mesh = getattr(engine, "mesh", None) or {}
    linter = MeshLinter(mesh=mesh, **kwargs)
    violations, est = linter.lint_engine(engine)
    _finish(violations, "Mesh lint failed (GenerationEngine)", raise_on_error)
    return violations, est
