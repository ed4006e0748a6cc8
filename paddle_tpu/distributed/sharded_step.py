"""Distributed whole-step training via GSPMD.

Reference counterpart: the fleet hybrid-parallel step (SURVEY.md §3.5 —
python/paddle/distributed/fleet/meta_parallel/*, reducer.cc, sharding
stage-1/2 optimizers) and the semi-auto dist codegen branch
(paddle/phi/api/yaml/generator/dist_api_gen.py: InferSpmd → Reshard → local
kernel).

TPU-native design: instead of per-op SPMD rules + NCCL process groups, the
ENTIRE imperative train step (forward, loss.backward(), optimizer.step()) is
traced into one XLA program over a jax.sharding.Mesh:

- DP: batch arguments sharded over the 'dp' mesh axis — gradient allreduce is
  whatever GSPMD inserts (reduce-scatter/all-reduce on ICI), replacing the
  bucketed EagerReducer.
- TP/MP: parameters carry NamedShardings (via shard_tensor placements or a
  model shard_fn); XLA propagates and places the Megatron collectives.
- ZeRO (sharding stage 1/2): optimizer accumulators are additionally sharded
  over 'dp'; XLA materializes the reduce-scatter(grads)/all-gather(params)
  dataflow of DygraphShardingOptimizer.
- State is donated, so parameter/optimizer-state updates are in-place in HBM
  like the reference's in-place optimizer kernels.
"""

from __future__ import annotations

import contextlib
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from paddle_tpu._core import random as rng_mod
from paddle_tpu._core.tensor import Tensor
from paddle_tpu.jit import TrainStep

from .auto_parallel import ProcessMesh

__all__ = ["ShardedTrainStep", "shard_batch"]


def _as_process_mesh(mesh) -> ProcessMesh:
    if isinstance(mesh, ProcessMesh):
        return mesh
    if isinstance(mesh, Mesh):
        return ProcessMesh(mesh)
    raise TypeError(f"mesh must be ProcessMesh or jax Mesh, got {type(mesh)}")


def shard_batch(mesh: ProcessMesh, batch_vals, spec):
    """Place batch arrays with `spec` (a PartitionSpec or one per leaf)."""
    leaves, tree = jax.tree_util.tree_flatten(batch_vals)
    # On jax<0.6 PartitionSpec subclasses tuple: a single spec must not be
    # mistaken for a per-leaf list (its entries would be char-splatted).
    if (isinstance(spec, (list, tuple)) and not isinstance(spec, PartitionSpec)
            and len(spec) == len(leaves)):
        specs = list(spec)
    else:
        specs = [spec] * len(leaves)
    placed = []
    for v, s in zip(leaves, specs):
        if not isinstance(s, PartitionSpec):
            s = PartitionSpec(s) if isinstance(s, str) else PartitionSpec(*s)
        # drop spec entries beyond the array rank
        entries = list(s)[: getattr(v, "ndim", 0)]
        placed.append(jax.device_put(v, NamedSharding(mesh.jax_mesh, PartitionSpec(*entries))))
    return jax.tree_util.tree_unflatten(tree, placed)


class ShardedTrainStep(TrainStep):
    """TrainStep over a device mesh.

    Usage:
        mesh = ProcessMesh(np.arange(8).reshape(2, 4), ["dp", "mp"])
        shard_llama(model, mesh)                      # params get placements
        step = ShardedTrainStep(model, opt, loss_fn, mesh,
                                batch_spec=PartitionSpec("dp"))
        loss = step(input_ids, labels)

    zero_stage: 0 = replicated optimizer state (over dp); 1/2 = accumulators
    sharded over 'dp' on their largest divisible dim (stage 2's grad sharding
    is implicit — XLA is free to reduce-scatter into the sharded update).

    comm_overlap=True decomposes each replicated parameter's dp grad sync
    from GSPMD's single fused all-reduce into reduce-scatter + an explicit
    ring all-gather of (dp-1) collective-permute hops
    (fleet.meta_parallel.schedules.overlap_grad_sync): every hop is an
    independent async collective XLA's latency-hiding scheduler can overlap
    with the optimizer math of already-arrived chunks — and, under a ZB-H1
    pipeline stack, with the W-pass ticks it does not depend on.  Values
    are bit-identical (a gather of shards reassociates nothing); the chain
    is statically checked by the mesh lint like every other collective
    (docs/PIPELINE.md, docs/MESH_LINT.md).
    """

    def __init__(
        self,
        model,
        optimizer,
        loss_fn,
        mesh,
        batch_spec=PartitionSpec("dp"),
        zero_stage: int = 1,
        dp_axis: str = "dp",
        scaler=None,
        comm_overlap: bool = False,
    ):
        super().__init__(model, optimizer, loss_fn, scaler=scaler)
        self.mesh = _as_process_mesh(mesh)
        self.batch_spec = batch_spec
        # group_sharded_parallel records its level on the optimizer
        self.zero_stage = getattr(optimizer, "_zero_stage", zero_stage)
        self.dp_axis = dp_axis if dp_axis in self.mesh.dim_names else None
        self.comm_overlap = comm_overlap

    # ---------------------------------------------------------------- state
    def _param_sharding(self, t: Tensor) -> NamedSharding:
        from .auto_parallel.api import placements_to_spec

        if getattr(t, "process_mesh", None) is not None and t.placements is not None:
            return NamedSharding(t.process_mesh.jax_mesh, placements_to_spec(t.process_mesh, t.placements))
        sh = getattr(t._value, "sharding", None)
        if isinstance(sh, NamedSharding):
            return sh
        return NamedSharding(self.mesh.jax_mesh, PartitionSpec())

    def _acc_sharding(self, acc_val, param_sharding: NamedSharding) -> NamedSharding:
        """Accumulator sharding = like its parameter, plus (stage>=1) sharded
        over dp on the largest dim not already sharded and divisible by dp."""
        spec = list(param_sharding.spec)
        spec += [None] * (acc_val.ndim - len(spec))
        used = {ax for e in spec if e is not None for ax in (e if isinstance(e, tuple) else (e,))}
        if self.zero_stage >= 1 and self.dp_axis is not None and self.dp_axis not in used and acc_val.ndim > 0:
            dp = self.mesh.get_dim_size(self.dp_axis)
            cands = sorted(range(acc_val.ndim), key=lambda d: -acc_val.shape[d])
            for d in cands:
                if spec[d] is None and acc_val.shape[d] % dp == 0 and acc_val.shape[d] >= dp:
                    spec[d] = self.dp_axis
                    break
        return NamedSharding(self.mesh.jax_mesh, PartitionSpec(*spec))

    def _place_state(self):
        """After eager warmup: pin every state tensor to its mesh sharding."""
        model_state = list(self.model.state_dict().values())
        for t in model_state:
            t._bind(jax.device_put(t._value, self._param_sharding(t)))
        param_sh = {}
        for p in self.optimizer._parameter_list:
            param_sh[id(p)] = self._param_sharding(p)
        for (name, pid), acc in self.optimizer._accumulators.items():
            psh = param_sh.get(pid, NamedSharding(self.mesh.jax_mesh, PartitionSpec()))
            if acc._value.ndim == 0 or acc._value.shape == ():
                sh = NamedSharding(self.mesh.jax_mesh, PartitionSpec())
            else:
                sh = self._acc_sharding(acc._value, psh)
            acc._bind(jax.device_put(acc._value, sh))
        # whatever else rides in the state (the learning-rate scalar, a
        # GradScaler's counters) is replicated: left on one device it came
        # back from the first compiled step as a mesh array, and the second
        # call compiled the whole step again for that one new input sharding
        replicated = NamedSharding(self.mesh.jax_mesh, PartitionSpec())
        for t in self._state:
            if not isinstance(t._value.sharding, NamedSharding):
                t._bind(jax.device_put(t._value, replicated))
        # what _pin_state holds the traced step's outputs to
        self._placed = [t._value.sharding for t in self._state]

    def _pin_state(self, vals):
        """Every state output keeps the sharding `_place_state` gave its
        input.  Left to propagation, a parameter came out of the step with
        its fp32 master weight's ZeRO sharding (dp x mp instead of mp): the
        second call then saw new input shardings and compiled again, the
        parameter buffers were no longer donated in place, and every later
        step re-gathered each weight before using it."""
        return [jax.lax.with_sharding_constraint(v, sh)
                for v, sh in zip(vals, self._placed)]

    # ------------------------------------------------- comm/compute overlap
    def _post_backward(self):
        """Traced between backward and optimizer.step: rewrite each
        replicated parameter's gradient through the overlap chain.
        TP-sharded parameters keep GSPMD's own layout (their grads are
        already partial-sharded; re-ringing them over dp would just churn
        layouts), as do sparse SelectedRows grads."""
        if not self.comm_overlap or self.dp_axis is None:
            return
        from paddle_tpu.distributed.fleet.meta_parallel.schedules import (
            overlap_grad_sync,
        )

        for p in self.optimizer._parameter_list:
            g = getattr(p, "grad", None)
            if g is None or not hasattr(g, "_value"):
                continue
            sh = self._param_sharding(p)
            if any(e is not None for e in sh.spec):
                continue
            synced = overlap_grad_sync(g._value, self.mesh.jax_mesh,
                                       self.dp_axis)
            p.grad = Tensor(synced, stop_gradient=True)

    # ----------------------------------------------------------------- call
    def _shard_batch_tensors(self, batch):
        out = []
        for b in batch:
            if isinstance(b, Tensor):
                out.append(Tensor(shard_batch(self.mesh, b._value, self.batch_spec), stop_gradient=b.stop_gradient))
            else:
                out.append(shard_batch(self.mesh, b, self.batch_spec))
        return tuple(out)

    def _mesh_scope(self):
        """The legacy `with mesh:` resolves bare PartitionSpecs; jax.set_mesh
        makes the mesh readable (jax.sharding.get_abstract_mesh) to code
        traced under it — ops.use_pallas() keeps Mosaic kernels, which GSPMD
        cannot partition, out of the sharded program."""
        stack = contextlib.ExitStack()
        stack.enter_context(self.mesh.jax_mesh)
        stack.enter_context(jax.set_mesh(self.mesh.jax_mesh))
        return stack

    def __call__(self, *batch):
        batch = self._shard_batch_tensors(batch)
        if self._compiled is None:
            with self._mesh_scope():
                loss = self._eager_step(*batch)
                self._state = self._collect_state()
                self._place_state()
                self._build()
                # mesh lint BEFORE the first sharded dispatch: placements,
                # collective congruence, donation, per-device HBM estimate
                # — all abstract, so a dead-axis collective is a named
                # error here, never an 8-device rendezvous hang
                self._maybe_mesh_lint(batch)
            return loss
        with self._mesh_scope():
            return super().__call__(*batch)
