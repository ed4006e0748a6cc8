"""Searchable fused decode hot chain: paged gather → dequant → sdpa core →
(running-max) quant-write as ONE Pallas dispatch per layer per token.

Schedule search, phase 2 (ROADMAP item 4; docs/SCHEDULE_SEARCH.md).  The
decode macro-step's per-token chain runs today as separate XLA ops inside
the jitted scan body — exactly the memory-bound fusion-miss class
"Operator Fusion in XLA" (arXiv 2301.13062) catalogs.  This module makes
that chain a SEARCHABLE subgraph for static/schedule_search.py's
ScheduleSearcher: `DecodeChainSpec` describes the chain at one engine
geometry and implements the same searcher protocol Program subgraphs use
(enumerate → roofline → VMEM → parity → measure → measured-win gate), so
winners and losers persist per device kind under the `schedule/decode_*`
AutotuneCache namespaces and the engine's compiled macro-step consumes an
accepted config with zero re-measurement (serving._resolve_decode_chain).

Semantics are NEVER trusted to the gate: every candidate must pass a
numerics parity check against the XLA twin BEFORE it may be measured
(`check_parity`), with the same contract the engine's stream tests
enforce — full-precision ('bf16') pools bit-exact, int8 pools bit-exact
on the quantized payload/scales with the attention output inside the
PR-6 drift budget.  That is why the default `batch` layout replays the
EXACT unfused ops (paged_write / paged_gather / gathered_attention — one
definition each, imported from ops.paged_attention) inside one
pallas_call: fusion changes the number of HBM round trips, never the
math.  The int8-only `rows` layout grids over batch rows (smaller VMEM
working set, whole-pool re-staging per row in the traffic model) and is
tolerance-gated on the attention output.

Mixed-dtype roofline honesty: a QuantPool chain moves int8 payload bytes
AND float32 scale bytes — `traffic_bytes` costs every pool leaf at its
OWN itemsize instead of assuming one dtype for the whole subgraph (the
bf16-pool chain at identical geometry models ~2x the gather traffic,
which is the int8 capacity story told by the cost model).

CPU/on-chip honesty: kernels run in Pallas interpret mode off-TPU, where
XLA usually wins and the gate (correctly) disables — tests and the bench
--smoke twin decide through schedule_search.measure_override.  On TPU the
whole-pool VMEM residency of these layouts is validated by
ops.autotune.validate_tile, so geometries whose pools exceed the budget
are pruned honestly rather than faked; a DMA-pipelined variant can join
the candidate space later without changing the search contract.

Mesh-sharded chains (schedule search over the mesh; ROADMAP item 3): a
spec built with ``mesh=`` describes the SAME chain on a TP-sharded
engine.  ``build`` then wraps the single-device kernel in ``shard_map``
over the engine's pool layout — pools P(None, mp) on the KV-head dim,
q/k_new/v_new P(None, mp, None) on the head dim, tables/lens replicated —
with the per-device kernel geometry taken from
``NamedSharding.shard_shape`` (the same source the serving telemetry's
``pool_device_nbytes`` uses).  GQA head contiguity makes every candidate
layout head-local: device d's query-head shard [d·n/mp, (d+1)·n/mp)
attends exactly its own kv-head shard (``gathered_attention`` contracts
query heads in contiguous groups per kv head), so the fused chain runs ZERO
in-kernel collectives and the mesh adds NO drift — parity re-gates bit-exactly
against the sharded XLA twin (synthetic args committed to the engine's
NamedShardings, reference jitted under GSPMD), the PR-11 contract.  The
roofline costs PER-DEVICE traffic plus ``collective_bytes`` — the psum an
attention epilogue would need if a kv group ever split across devices (0
for every current layout; o_proj's row-parallel psum lives OUTSIDE the
chain, in GSPMD's hands).  Cache verdicts are keyed by (device kind,
mesh shape): the AutotuneCache file is per device kind and ``key()``
gains a ``mesh`` entry only when a mesh is set, so single-device and
sharded verdicts never collide (tested by the cache-pollution
regression).  ``static.mesh_lint.lint_decode_chain`` statically checks
the built kernel's collectives before an engine adopts it.

``PrefillChainSpec`` extends the same searcher protocol to the OTHER
serving hot path: the chunked-prefill attention core (q chunk against
the growing cache, bottom-right aligned).  Candidates tile query rows
(bit-exact — softmax is per row) and stage K/V in chunks (pure data
movement), so long-prompt pours stop being a pure XLA chain once a
config wins; models/llama adopts through ``fused_prefill_attention``
under ``prefill_chain_scope``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

__all__ = [
    "DecodeChainSpec",
    "PrefillChainSpec",
    "spec_from_arrays",
    "ensure_decision",
    "fused_decode_step",
    "fused_prefill_attention",
]

# per-copy-step turnaround for the analytic ranking (the scale of one DMA
# issue): breaks ties between gather granularities whose traffic is
# identical, the same role schedule_search._GRID_STEP_OVERHEAD_S plays
# for 1-D grids
_COPY_STEP_OVERHEAD_S = 1e-7


@dataclass
class DecodeChainSpec:
    """One engine geometry's decode hot chain, ready to schedule.

    kv: 'bf16' (full-precision pools in `dtype`) | 'int8' (QuantPool —
    int8 payload + per-block-per-head f32 scales, running-max writes).
    num_blocks counts the WHOLE pool incl. scratch pages; max_blocks is
    the per-sequence block-table width.

    mesh: None for the single-device chain, or the engine's ProcessMesh —
    the spec then describes the TP-sharded chain (pools on the KV-head
    dim over `mp_axis`, the serving layout) and builds inside shard_map;
    the mesh handle itself never enters `key()` (only its shape string
    does), so cache entries stay (device kind, mesh shape)-keyed and
    host-portable."""

    batch: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    block_size: int
    max_blocks: int
    num_blocks: int
    kv: str = "bf16"
    dtype: object = np.float32
    mesh: object = None
    mp_axis: str = "mp"

    check_parity = True  # searcher protocol: candidates numerics-gate

    def __post_init__(self):
        if self.kv not in ("bf16", "int8"):
            raise ValueError(f"kv must be 'bf16' or 'int8', got {self.kv!r}")

    # ------------------------------------------------------------ identity
    @property
    def seq(self) -> int:
        return self.max_blocks * self.block_size

    def kernel_name(self) -> str:
        return f"schedule/decode_{self.kv}"

    def key(self) -> dict:
        k = {
            "b": self.batch,
            "n": self.num_heads,
            "nkv": self.num_kv_heads,
            "h": self.head_dim,
            "bs": self.block_size,
            "w": self.max_blocks,
            "nb": self.num_blocks,
            "dtype": np.dtype(self.dtype).name,
        }
        # (device kind, mesh shape) verdict keying: the AutotuneCache file
        # is already per device kind; the mesh-shape entry — ONLY when a
        # mesh is set, so existing single-device key strings stay stable —
        # keeps single-device and sharded verdicts from ever colliding
        if self.mesh is not None:
            k["mesh"] = self.mesh_desc()
        return k

    # ---------------------------------------------------------- mesh view
    def mesh_desc(self) -> str:
        """'mp2'-style mesh shape string (the serving telemetry format)."""
        if self.mesh is None:
            return ""
        return "x".join(f"{n}{s}" for n, s in zip(self.mesh.dim_names,
                                                  self.mesh.shape))

    def _mp(self) -> int:
        return int(dict(zip(self.mesh.dim_names,
                            self.mesh.shape))[self.mp_axis])

    def _shardings(self):
        """(pool, heads, replicated) NamedShardings of the serving layout:
        pools shard the KV-head dim (axis 1 of every pool leaf — payload
        AND scales), q/k_new/v_new shard the head dim, tables/lens ride
        replicated."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        jm = self.mesh.jax_mesh
        return (NamedSharding(jm, P(None, self.mp_axis)),
                NamedSharding(jm, P(None, self.mp_axis, None)),
                NamedSharding(jm, P()))

    def device_spec(self) -> "DecodeChainSpec":
        """The PER-DEVICE replica of this geometry: head counts come from
        ``NamedSharding.shard_shape`` over the committed pool/head layouts
        — the same source ops.paged_attention.pool_device_nbytes uses for
        the telemetry's per-device bytes — never from ad-hoc division."""
        import dataclasses

        pool_s, head_s, _rep = self._shardings()
        pool_shape = (self.num_blocks, self.num_kv_heads, self.block_size,
                      self.head_dim)
        _nb, nkv_local, _bs, _h = pool_s.shard_shape(pool_shape)
        _b, n_local, _h2 = head_s.shard_shape(
            (self.batch, self.num_heads, self.head_dim))
        return dataclasses.replace(self, mesh=None,
                                   num_heads=int(n_local),
                                   num_kv_heads=int(nkv_local))

    def label(self) -> str:
        from paddle_tpu.ops.autotune import _key_str

        return f"{self.kernel_name()}|{_key_str(self.key())}"

    def config_label(self, config) -> str:
        lbl = f"#{config.get('layout', 'batch')}-{config.get('gather', 'take')}"
        if config.get("gather") == "loop":
            lbl += f"u{config.get('unroll', 1)}"
        return lbl

    # ------------------------------------------------------ candidate space
    def enumerate_configs(self):
        """Schedule space: `layout` — 'batch' replays the whole batch in
        one grid step (bit-exact by construction; the only layout a
        'bf16' chain may use), 'rows' (int8 only) grids over batch rows;
        `gather` — 'take' stages pages in one bulk gather, 'loop' copies
        `unroll` pages per step (the DMA granularity knob; values are
        bit-identical either way — gathering is pure data movement)."""
        unrolls = [u for u in (1, 2, 4)
                   if u <= self.max_blocks and self.max_blocks % u == 0]
        layouts = ["batch"] + (["rows"] if self.kv == "int8" else [])
        out = []
        for layout in layouts:
            out.append({"layout": layout, "gather": "take"})
            for u in unrolls:
                out.append({"layout": layout, "gather": "loop", "unroll": u})
        return out

    # ------------------------------------------------------------ cost model
    def _leaf_bytes(self):
        """[(name, nbytes)] per pool LEAF at its OWN dtype — one pool's
        int8 payload and f32 scales are costed separately (the mixed-dtype
        fix: a QuantPool chain is not 'one dtype' to the roofline)."""
        nb, nkv, bs, h = (self.num_blocks, self.num_kv_heads,
                          self.block_size, self.head_dim)
        if self.kv == "int8":
            return [("payload", nb * nkv * bs * h * 1),
                    ("scale", nb * nkv * 4)]
        return [("payload", nb * nkv * bs * h
                 * np.dtype(self.dtype).itemsize)]

    def _write_bytes(self):
        """HBM bytes the chain's write phase touches, per pool: bf16
        writes one token slot per row; int8 rewrites each touched block
        (running-max rescale) plus its f32 scales."""
        b, nkv, bs, h = (self.batch, self.num_kv_heads, self.block_size,
                         self.head_dim)
        if self.kv == "int8":
            return b * nkv * bs * h * 1 + b * nkv * 4
        return b * nkv * h * np.dtype(self.dtype).itemsize

    def collective_bytes(self, config) -> int:
        """ICI bytes of the psum the attention epilogue needs, per device.
        Every current layout is head-local — P(None, mp) keeps each query
        head's whole GQA kv group on its own device (contiguous groups in
        gathered_attention), so the chain runs zero in-kernel collectives
        and this is 0; o_proj's row-parallel psum stays OUTSIDE the chain
        (GSPMD's epilogue, costed by the step program, not the kernel).
        A future layout that splits a kv group across devices must cost
        its partial-output psum here: one [b, n_local, h] f32 reduction."""
        if self.mesh is None:
            return 0
        mp = self._mp()
        if self.num_heads % mp == 0 and self.num_kv_heads % mp == 0:
            return 0  # head-local: no epilogue reduction
        # non-divisible heads can't ride shard_shape (uneven split):
        # cost the ceil-divided local head count directly — build()
        # refuses these geometries anyway, this is the honest estimate
        n_local = -(-self.num_heads // mp)
        return self.batch * n_local * self.head_dim * 4

    def traffic_bytes(self, config) -> int:
        """Modeled HBM traffic: every pool leaf read at its own itemsize
        (once for the 'batch' layout; re-staged per row — x batch — for
        'rows'), the write phase's touched bytes, and the q/k/v/token
        tensors + output once.  A mesh spec reports the PER-DEVICE number
        — the device_spec's traffic (shard_shape-divided pools/heads)
        plus the epilogue's collective bytes — because per-device time is
        what the roofline ranks against the sharded XLA twin."""
        if self.mesh is not None:
            return (self.device_spec().traffic_bytes(config)
                    + self.collective_bytes(config))
        it = np.dtype(self.dtype).itemsize
        b, n, nkv, h = (self.batch, self.num_heads, self.num_kv_heads,
                        self.head_dim)
        read_factor = b if config.get("layout") == "rows" else 1
        pool_reads = 2 * sum(sz for _name, sz in self._leaf_bytes())
        traffic = pool_reads * read_factor
        traffic += 2 * self._write_bytes()
        traffic += b * n * h * it            # q
        traffic += 2 * b * nkv * h * it      # k_new, v_new
        traffic += b * self.max_blocks * 4 + b * 4  # tables, lens
        traffic += b * n * h * it            # attention output
        return int(traffic)

    def flops(self) -> float:
        if self.mesh is not None:  # per-device: heads divide over the mesh
            return self.device_spec().flops()
        b, n, h, s = self.batch, self.num_heads, self.head_dim, self.seq
        return 4.0 * b * n * s * h + 5.0 * b * n * s

    def roofline_ms(self, config, cost_model=None) -> float:
        """Analytic rank: per-device flops over per-device traffic (which
        already includes the epilogue's collective bytes on mesh specs),
        plus the copy-granularity tie-breaker and — when a layout needs
        an epilogue psum at all — one collective-launch turnaround."""
        if cost_model is None:
            from paddle_tpu.cost_model import OpCostModel

            cost_model = OpCostModel()
        if config.get("gather") == "loop":
            u = int(config.get("unroll", 1) or 1)
            # one copy per page group per row per pool
            copies = 2 * self.batch * (self.max_blocks // u)
        else:
            copies = 2  # one bulk gather per pool
        if self.collective_bytes(config):
            copies += 1  # the psum launch rides the same turnaround scale
        return (cost_model.flops_time(self.flops(),
                                      self.traffic_bytes(config))
                + copies * _COPY_STEP_OVERHEAD_S) * 1e3

    def vmem_bytes(self, config) -> int:
        """f32-staged working set per grid step (double-buffered, the
        validate_tile convention): the resident pool leaves plus the
        per-step gathered views, logits tile, and token blocks.  The
        'rows' layout holds one row's views; both layouts keep the whole
        pool resident — on-chip geometries whose pools exceed VMEM are
        pruned honestly here.  A mesh spec reports its device_spec's
        working set: VMEM is a per-chip budget."""
        if self.mesh is not None:
            return self.device_spec().vmem_bytes(config)
        it = np.dtype(self.dtype).itemsize
        rows = 1 if config.get("layout") == "rows" else self.batch
        n, nkv, h, s = (self.num_heads, self.num_kv_heads, self.head_dim,
                        self.seq)
        total = 2 * sum(sz for _name, sz in self._leaf_bytes())  # pools
        total += 2 * rows * nkv * s * h * 4        # gathered k/v (f32)
        total += rows * n * s * 4                  # logits tile
        total += rows * (n + 2 * nkv) * h * it     # q, k_new, v_new
        total += rows * n * h * it                 # output block
        return int(total) * 2

    # ------------------------------------------------------------- numerics
    def reference(self):
        """The XLA twin: EXACTLY the unfused macro-step sequence
        (models/llama._decode_layer_paged lines write→write→attend)."""
        from paddle_tpu.ops import paged_attention as pa

        def ref(kc, vc, q, kn, vn, tables, lens):
            pos = lens - 1
            kc = pa.paged_write(kc, kn, tables, pos)
            vc = pa.paged_write(vc, vn, tables, pos)
            o = pa.paged_decode_attention(q, kc, vc, tables, lens)
            return o, kc, vc

        return ref

    def synthetic_args(self):
        """Deterministic engine-shaped args: every row owns DISJOINT
        pool blocks (the engine's allocator invariant the 'rows' layout
        relies on) poured with random content, lengths spread over the
        table span."""
        import jax.numpy as jnp

        from paddle_tpu.ops import paged_attention as pa

        b, n, nkv, h = (self.batch, self.num_heads, self.num_kv_heads,
                        self.head_dim)
        bs, w = self.block_size, self.max_blocks
        rng = np.random.default_rng(0)
        dt = jnp.dtype(self.dtype)
        kc, vc = pa.alloc_paged_cache(
            self.num_blocks, nkv, bs, h,
            jnp.int8 if self.kv == "int8" else dt)
        ids = np.arange(b * w, dtype=np.int32).reshape(b, w)
        kv = jnp.asarray(rng.standard_normal((b * w, nkv, bs, h)),
                         jnp.float32)
        vv = jnp.asarray(rng.standard_normal((b * w, nkv, bs, h)),
                         jnp.float32)
        kc = pa.paged_pour_blocks(kc, kv, ids.reshape(-1))
        vc = pa.paged_pour_blocks(vc, vv, ids.reshape(-1))
        s = self.seq
        lens = np.clip(np.linspace(2, s, b).astype(np.int32), 2, s)
        args = (kc, vc,
                jnp.asarray(rng.standard_normal((b, n, h)), dt),
                jnp.asarray(rng.standard_normal((b, nkv, h)), dt),
                jnp.asarray(rng.standard_normal((b, nkv, h)), dt),
                jnp.asarray(ids), jnp.asarray(lens))
        if self.mesh is None:
            return args
        # commit the args to the engine's committed layout, so jitting
        # reference() over them IS the sharded XLA twin (GSPMD partitions
        # the unfused ops exactly as the serving step does) and the parity
        # gate proves the mesh adds NO drift — the PR-11 contract
        import jax

        pool_s, head_s, rep = self._shardings()
        kc, vc, q, kn, vn, tables, lens = args
        return (jax.device_put(kc, pool_s), jax.device_put(vc, pool_s),
                jax.device_put(q, head_s), jax.device_put(kn, head_s),
                jax.device_put(vn, head_s),
                jax.device_put(tables, rep), jax.device_put(lens, rep))

    def parity_ok(self, fn, args, reference_out) -> bool:
        """The parity gate: pools must match the twin BIT-EXACTLY for
        both kv kinds (quantized writes are deterministic integer math);
        the attention output must be bit-exact for 'bf16' and inside the
        documented PR-6 drift budget for 'int8' (the 'rows' layout
        re-associates the per-row einsum)."""
        import jax

        got = fn(*args)  # a kernel that cannot build or run RAISES
        r_leaves = jax.tree_util.tree_leaves(reference_out)
        g_leaves = jax.tree_util.tree_leaves(got)
        if len(r_leaves) != len(g_leaves):
            return False
        for i, (r, g) in enumerate(zip(r_leaves, g_leaves)):
            if r.shape != g.shape or r.dtype != g.dtype:
                return False
            if i == 0 and self.kv == "int8":  # attention output leaf
                if not np.allclose(np.asarray(r, np.float32),
                                   np.asarray(g, np.float32),
                                   rtol=1e-3, atol=1e-4):
                    return False
            elif not bool((r == g).all()):
                return False
        return True

    # --------------------------------------------------------------- build
    def build(self, config):
        if config.get("layout") == "rows" and self.kv != "int8":
            raise ValueError(
                "the per-row layout re-associates the attention "
                "einsum: bf16 chains are bit-exact-only ('batch')")
        if self.mesh is not None:
            return _build_sharded(self, config)
        if config.get("layout") == "rows":
            return _build_rows(self, config)
        return _build_batch(self, config)


def _loop_gather(pool, tables, unroll):
    """paged_gather's values, one page group at a time: a lax.fori_loop
    copies `unroll` pages per step into the assembly buffer — pure data
    movement, so the result is BIT-IDENTICAL to the bulk take; only the
    copy granularity (the knob a DMA pipeline tunes) differs."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import paged_attention as pa

    quant = isinstance(pool, pa.QuantPool)
    data = pool.data if quant else pool
    b, w = tables.shape
    _nb, nkv, bs, h = data.shape
    buf = jnp.zeros((b, w, nkv, bs, h),
                    jnp.float32 if quant else data.dtype)

    def step(i, buf):
        for t in range(unroll):
            wi = i * unroll + t
            for bi in range(b):
                idx = tables[bi, wi]
                blk = jax.lax.dynamic_index_in_dim(data, idx, 0,
                                                   keepdims=False)
                if quant:
                    sc = jax.lax.dynamic_index_in_dim(pool.scale, idx, 0,
                                                      keepdims=False)
                    blk = blk.astype(jnp.float32) * sc[:, None, None]
                buf = jax.lax.dynamic_update_slice(
                    buf, blk[None, None], (bi, wi, 0, 0, 0))
        return buf

    buf = jax.lax.fori_loop(0, w // unroll, step, buf)
    return jnp.moveaxis(buf, 2, 1).reshape(b, nkv, w * bs, h)


def _pool_specs(spec, whole):
    """(in_specs head, out_specs tail, out_shapes tail, n_leaves) for the
    k/v pool leaves — payload(+scales) per pool, whole-array blocks."""
    import jax
    import jax.numpy as jnp

    pool_shape = (spec.num_blocks, spec.num_kv_heads, spec.block_size,
                  spec.head_dim)
    pool_dt = jnp.int8 if spec.kv == "int8" else jnp.dtype(spec.dtype)
    if spec.kv == "int8":
        scale_shape = (spec.num_blocks, spec.num_kv_heads)
        per_pool = [(pool_shape, pool_dt), (scale_shape, jnp.float32)]
    else:
        per_pool = [(pool_shape, pool_dt)]
    leaves = per_pool + per_pool  # k then v
    in_specs = [whole(shape) for shape, _dt in leaves]
    out_specs = [whole(shape) for shape, _dt in leaves]
    out_shapes = [jax.ShapeDtypeStruct(shape, dt) for shape, dt in leaves]
    return in_specs, out_specs, out_shapes, len(per_pool)


def _build_batch(spec, config):
    """The whole-batch layout: ONE grid step replays the exact unfused op
    sequence (paged_write x2 → paged_gather/loop-gather →
    gathered_attention) over VMEM-resident pools — bit-exact vs the twin
    by construction, fused into a single HBM round trip."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from paddle_tpu.ops import paged_attention as pa
    from paddle_tpu.ops._pl_utils import imap

    int8 = spec.kv == "int8"
    gather = config.get("gather", "take")
    unroll = int(config.get("unroll", 1) or 1)
    b, n, nkv, h = (spec.batch, spec.num_heads, spec.num_kv_heads,
                    spec.head_dim)
    w = spec.max_blocks
    dt = jnp.dtype(spec.dtype)
    n_pool_in = 4 if int8 else 2

    def whole(shape):
        nd = len(shape)
        return pl.BlockSpec(shape, imap(lambda i: (0,) * nd))

    def kernel(*refs):
        pool_ins = refs[:n_pool_in]
        q_r, kn_r, vn_r, tbl_r, ln_r = refs[n_pool_in:n_pool_in + 5]
        o_r = refs[n_pool_in + 5]
        pool_outs = refs[n_pool_in + 6:]
        tables = tbl_r[...]
        lens = ln_r[...]
        pos = lens - 1
        if int8:
            kpool = pa.QuantPool(pool_ins[0][...], pool_ins[1][...])
            vpool = pa.QuantPool(pool_ins[2][...], pool_ins[3][...])
        else:
            kpool, vpool = pool_ins[0][...], pool_ins[1][...]
        kpool = pa.paged_write(kpool, kn_r[...], tables, pos)
        vpool = pa.paged_write(vpool, vn_r[...], tables, pos)
        if int8:
            pool_outs[0][...] = kpool.data
            pool_outs[1][...] = kpool.scale
            pool_outs[2][...] = vpool.data
            pool_outs[3][...] = vpool.scale
        else:
            pool_outs[0][...] = kpool
            pool_outs[1][...] = vpool
        if gather == "take":
            keys = pa.paged_gather(kpool, tables)
            vals = pa.paged_gather(vpool, tables)
        else:
            keys = _loop_gather(kpool, tables, unroll)
            vals = _loop_gather(vpool, tables, unroll)
        o = pa.gathered_attention(q_r[...][:, None], keys, vals, lens)
        o_r[...] = o[:, 0].astype(o_r.dtype)

    pool_in_specs, pool_out_specs, pool_out_shapes, _ = _pool_specs(
        spec, whole)
    in_specs = pool_in_specs + [
        whole((b, n, h)), whole((b, nkv, h)), whole((b, nkv, h)),
        whole((b, w)), whole((b,))]
    out_specs = [whole((b, n, h))] + pool_out_specs
    out_shape = [jax.ShapeDtypeStruct((b, n, h), dt)] + pool_out_shapes
    aliases = {i: i + 1 for i in range(n_pool_in)}  # pools donate in place

    return _wrap_call(spec, kernel, (1,), in_specs, out_specs, out_shape,
                      aliases)


def _build_rows(spec, config):
    """The per-row layout (int8 only): grid over batch rows, each step
    writing its row's token into its OWN pool block (the engine's
    disjoint-ownership invariant) and gathering just that row's pages.
    Pools stay bit-exact (the running-max rescale replays
    _quant_write_chunk's math per row); the attention output re-associates
    the einsum and rides the int8 drift budget."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from paddle_tpu.ops import paged_attention as pa
    from paddle_tpu.ops._pl_utils import imap

    gather = config.get("gather", "take")
    unroll = int(config.get("unroll", 1) or 1)
    b, n, nkv, h = (spec.batch, spec.num_heads, spec.num_kv_heads,
                    spec.head_dim)
    bs, w = spec.block_size, spec.max_blocks
    dt = jnp.dtype(spec.dtype)
    qmax, eps = 127.0, 1e-12

    def whole(shape):
        nd = len(shape)
        return pl.BlockSpec(shape, imap(lambda i: (0,) * nd))

    def row(shape):
        nd = len(shape)
        return pl.BlockSpec((1,) + shape[1:],
                            imap(lambda i: (i,) + (0,) * (nd - 1)))

    def kernel(*refs):
        kd, ks, vd, vs = refs[:4]
        q_r, kn_r, vn_r, tbl_r, ln_r, o_r = refs[4:10]
        okd, oks, ovd, ovs = refs[10:]
        ln = ln_r[0]
        pos = ln - 1
        bidx = tbl_r[0, pos // bs]
        slot = pos % bs

        def write(d_ref, s_ref, od_ref, os_ref, new):
            # _quant_write_chunk's math for ONE row's token: running-max
            # scale growth + in-place rescale of the touched block
            af = new.astype(jnp.float32)                    # [1, Nkv, H]
            tok = jnp.max(jnp.abs(af), axis=-1) / qmax      # [1, Nkv]
            old_s = s_ref[pl.ds(bidx, 1)]                   # [1, Nkv]
            new_s = jnp.maximum(old_s, tok)
            safe = jnp.maximum(new_s, eps)
            old_b = d_ref[pl.ds(bidx, 1)].astype(jnp.float32)
            ratio = jnp.where(new_s > old_s, old_s / safe, 1.0)
            resc = jnp.clip(jnp.round(old_b * ratio[..., None, None]),
                            -qmax, qmax).astype(jnp.int8)
            qv = jnp.clip(jnp.round(af / safe[..., None]),
                          -qmax, qmax).astype(jnp.int8)
            resc = jax.lax.dynamic_update_slice(
                resc, qv[:, :, None, :], (0, 0, slot, 0))
            od_ref[pl.ds(bidx, 1)] = resc
            os_ref[pl.ds(bidx, 1)] = new_s

        write(kd, ks, okd, oks, kn_r[...])
        write(vd, vs, ovd, ovs, vn_r[...])

        def gather_row(od_ref, os_ref):
            # this row's pages out of the WRITTEN pool; take and loop are
            # pure data movement over the same values (one definition of
            # the loop path: _loop_gather)
            pool = pa.QuantPool(od_ref[...], os_ref[...])
            if gather == "take":
                return pa.paged_gather(pool, tbl_r[...])
            return _loop_gather(pool, tbl_r[...], unroll)

        keys = gather_row(okd, oks)
        vals = gather_row(ovd, ovs)
        o = pa.gathered_attention(q_r[...][:, None], keys, vals, ln_r[...])
        o_r[...] = o[:, 0].astype(o_r.dtype)

    pool_in_specs, pool_out_specs, pool_out_shapes, _ = _pool_specs(
        spec, whole)
    in_specs = pool_in_specs + [
        row((b, n, h)), row((b, nkv, h)), row((b, nkv, h)),
        row((b, w)), row((b,))]
    out_specs = [row((b, n, h))] + pool_out_specs
    out_shape = [jax.ShapeDtypeStruct((b, n, h), dt)] + pool_out_shapes
    aliases = {i: i + 1 for i in range(4)}

    return _wrap_call(spec, kernel, (b,), in_specs, out_specs, out_shape,
                      aliases)


def _wrap_call(spec, kernel, grid, in_specs, out_specs, out_shape, aliases):
    """pallas_call wrapper taking the canonical (kc, vc, q, kn, vn,
    tables, lens) signature and returning (o, kc', vc') with QuantPools
    re-assembled leaf-wise."""
    import jax
    from jax.experimental import pallas as pl

    from paddle_tpu.ops import _pl_utils
    from paddle_tpu.ops import paged_attention as pa

    int8 = spec.kv == "int8"

    def fused(kc, vc, q, kn, vn, tables, lens):
        if int8:
            pool_leaves = (kc.data, kc.scale, vc.data, vc.scale)
        else:
            pool_leaves = (kc, vc)
        outs = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            input_output_aliases=aliases,
            interpret=_pl_utils.interpret(),
            name="decode_chain",
        )(*pool_leaves, q, kn, vn, tables, lens)
        if int8:
            o, kd, ks, vd, vs = outs
            return o, pa.QuantPool(kd, ks), pa.QuantPool(vd, vs)
        o, kd, vd = outs
        return o, kd, vd

    return fused


def _build_sharded(spec, config):
    """The mesh chain: the SINGLE-DEVICE kernel at the device_spec's
    shard_shape geometry, wrapped in shard_map over the engine's
    committed layout.  GQA head contiguity makes every candidate layout
    head-local — device d's query-head shard attends exactly its own
    kv-head shard — so the body runs ZERO collectives and each device
    replays the bit-exact single-device math on its slice; the donation
    aliases ride through (pool shards update in place per device)."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed.shard_map_compat import shard_map

    mp = spec._mp()
    if spec.num_heads % mp != 0 or spec.num_kv_heads % mp != 0:
        # a split kv group would need the epilogue psum collective_bytes
        # costs — no candidate implements it, and serving never gets here
        # (ineligible engines keep the counted mesh skip)
        raise ValueError(
            f"sharded decode chain needs head counts divisible by "
            f"{spec.mp_axis}={mp} (got n={spec.num_heads}, "
            f"nkv={spec.num_kv_heads}): a split GQA group requires an "
            "epilogue psum no layout implements")
    inner = spec.device_spec().build(config)
    pool_p, head_p = P(None, spec.mp_axis), P(None, spec.mp_axis, None)
    return shard_map(
        inner, mesh=spec.mesh.jax_mesh,
        in_specs=(pool_p, pool_p, head_p, head_p, head_p, P(), P()),
        out_specs=(head_p, pool_p, pool_p),
        check_vma=False)


# ---------------------------------------------------------------------------
# the prefill-attention chain: the OTHER serving hot path joins the search


@dataclass
class PrefillChainSpec:
    """One chunked-prefill attention call, ready to schedule: a query
    chunk of `seq` tokens against `kv_len` cached-plus-chunk positions
    (bottom-right aligned — chunk token i attends the cache and chunk
    positions <= i), heads POST-GQA-repeat, the exact geometry
    models/llama's LlamaAttention prefill branch hands
    F.scaled_dot_product_attention.

    Candidates keep the query grid at ONE tile (`block_q == seq`: the
    in-kernel attention call has EXACTLY the twin's shapes, so XLA
    compiles the same reduction order at every live kv length — a
    sub-tile's differently-shaped call may re-fuse and drift ~1e-7) and
    schedule the K/V staging granularity (`kchunk` pieces — pure data
    movement, the DMA knob), so the parity gate demands BIT-EXACT
    equality with the XLA twin, no tolerance tier."""

    seq: int
    kv_len: int
    num_heads: int
    head_dim: int
    dtype: object = np.float32

    check_parity = True

    # ------------------------------------------------------------ identity
    def kernel_name(self) -> str:
        return "schedule/prefill"

    def key(self) -> dict:
        return {
            "s": self.seq,
            "t": self.kv_len,
            "n": self.num_heads,
            "h": self.head_dim,
            "dtype": np.dtype(self.dtype).name,
        }

    def label(self) -> str:
        from paddle_tpu.ops.autotune import _key_str

        return f"{self.kernel_name()}|{_key_str(self.key())}"

    def config_label(self, config) -> str:
        lbl = f"#q{config.get('block_q', self.seq)}-{config.get('stage', 'take')}"
        if config.get("stage") == "loop":
            lbl += f"k{config.get('kchunk', 1)}"
        return lbl

    # ------------------------------------------------------ candidate space
    def enumerate_configs(self):
        """`block_q` — query tile height, pinned to the WHOLE chunk: a
        sub-tile's attention call has different shapes than the twin's,
        and XLA may re-fuse its reduction (~1e-7 drift, shape-dependent
        — a candidate could even pass parity at this spec's geometry yet
        drift at another live kv length, which the gate can't see).  One
        full-chunk tile keeps the in-kernel call shape-identical to the
        reference at EVERY kv length.  `stage` — 'take' hands the whole
        K/V block to the core, 'loop' assembles it from `kchunk` staged
        copies first (the K-tiled DMA granularity; values bit-identical
        either way).  seq >= 2 required: jax.nn.dot_product_attention
        special-cases single-row queries (decode shape) with a
        re-associated reduction."""
        if self.seq < 2:
            return []
        kchunks = [c for c in (2, 4)
                   if c <= self.kv_len and self.kv_len % c == 0]
        out = [{"block_q": self.seq, "stage": "take"}]
        for c in kchunks:
            out.append({"block_q": self.seq, "stage": "loop", "kchunk": c})
        return out

    # ------------------------------------------------------------ cost model
    def flops(self) -> float:
        s, t, n, h = self.seq, self.kv_len, self.num_heads, self.head_dim
        return 4.0 * n * s * t * h + 5.0 * n * s * t

    def traffic_bytes(self, config) -> int:
        """q/output once; K/V re-fetched once per query tile when the
        grid revisits them (the candidate_roofline_ms convention for a
        block whose index map is constant across the grid is fetch-once —
        but whole-block K/V here is re-staged per step off-chip unless
        the grid is a single step)."""
        it = np.dtype(self.dtype).itemsize
        s, t, n, h = self.seq, self.kv_len, self.num_heads, self.head_dim
        gq = s // int(config.get("block_q", s))
        traffic = 2 * s * n * h * it          # q in, output out
        traffic += 2 * t * n * h * it * gq    # k, v per query tile
        return int(traffic)

    def roofline_ms(self, config, cost_model=None) -> float:
        if cost_model is None:
            from paddle_tpu.cost_model import OpCostModel

            cost_model = OpCostModel()
        gq = self.seq // int(config.get("block_q", self.seq))
        copies = gq
        if config.get("stage") == "loop":
            copies += 2 * gq * int(config.get("kchunk", 1))
        return (cost_model.flops_time(self.flops(),
                                      self.traffic_bytes(config))
                + copies * _COPY_STEP_OVERHEAD_S) * 1e3

    def vmem_bytes(self, config) -> int:
        """Per grid step: the q tile, whole K/V (+ the staged copy for
        'loop'), the f32 logits tile, and the output tile — x2 for the
        double-buffer convention."""
        it = np.dtype(self.dtype).itemsize
        bq = int(config.get("block_q", self.seq))
        t, n, h = self.kv_len, self.num_heads, self.head_dim
        total = bq * n * h * it                  # q tile
        total += 2 * t * n * h * it              # k, v
        if config.get("stage") == "loop":
            total += 2 * t * n * h * it          # assembly buffers
        total += n * bq * t * 4                  # logits tile (f32)
        total += bq * n * h * it                 # output tile
        return int(total) * 2

    # ------------------------------------------------------------- numerics
    def reference(self):
        """The XLA twin: EXACTLY the nn.functional.attention._core math
        the model otherwise runs — jax.nn.dot_product_attention, causal
        top-left for the square first chunk, the explicit bottom-right
        tri mask for a chunk on a longer cache."""
        import jax
        import jax.numpy as jnp

        def ref(q, k, v):
            sq, sk = q.shape[1], k.shape[1]
            if sq != sk:
                tri = jnp.tril(jnp.ones((sq, sk), bool),
                               k=sk - sq)[None, None]
                return jax.nn.dot_product_attention(q, k, v, mask=tri,
                                                    is_causal=False)
            return jax.nn.dot_product_attention(q, k, v, is_causal=True)

        return ref

    def synthetic_args(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(0)
        dt = jnp.dtype(self.dtype)
        s, t, n, h = self.seq, self.kv_len, self.num_heads, self.head_dim
        return (jnp.asarray(rng.standard_normal((1, s, n, h)), dt),
                jnp.asarray(rng.standard_normal((1, t, n, h)), dt),
                jnp.asarray(rng.standard_normal((1, t, n, h)), dt))

    def parity_ok(self, fn, args, reference_out) -> bool:
        """Bit-exact, no tolerance tier: the full-chunk tile keeps the
        in-kernel attention call shape-identical to the twin (same XLA
        reduction order) and staging is pure data movement."""
        got = fn(*args)  # a kernel that cannot build or run RAISES
        return (got.shape == reference_out.shape
                and got.dtype == reference_out.dtype
                and bool((got == reference_out).all()))

    # --------------------------------------------------------------- build
    def build(self, config):
        return _build_prefill(self, config)


def _stage_chunks(src, kchunk):
    """K/V assembly in `kchunk` pieces: a fori_loop copies each chunk of
    the kv axis into the buffer — pure data movement (bit-identical to
    using `src` directly), only the copy granularity differs."""
    import jax
    import jax.numpy as jnp

    t = src.shape[1]
    step_len = t // kchunk
    buf = jnp.zeros_like(src)

    def step(j, buf):
        sl = jax.lax.dynamic_slice_in_dim(src, j * step_len, step_len,
                                          axis=1)
        return jax.lax.dynamic_update_slice_in_dim(buf, sl, j * step_len,
                                                   axis=1)

    return jax.lax.fori_loop(0, kchunk, step, buf)


def _build_prefill(spec, config):
    """Grid over query-row tiles, whole K/V resident per step: each step
    replays the EXACT reference call (jax.nn.dot_product_attention with
    this tile's bottom-right mask rows) on its rows — bit-exact vs the
    twin by construction, the decode-chain philosophy at prefill
    shapes."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from paddle_tpu.ops import _pl_utils
    from paddle_tpu.ops._pl_utils import imap

    s, t, n, h = spec.seq, spec.kv_len, spec.num_heads, spec.head_dim
    bq = int(config.get("block_q", s))
    stage = config.get("stage", "take")
    kchunk = int(config.get("kchunk", 1) or 1)
    dt = jnp.dtype(spec.dtype)
    gq = s // bq

    def kernel(q_r, k_r, v_r, o_r):
        i = pl.program_id(0)
        k = k_r[...]
        v = v_r[...]
        if stage == "loop":
            k = _stage_chunks(k, kchunk)
            v = _stage_chunks(v, kchunk)
        rows = i * bq + jnp.arange(bq)
        # this tile's rows of tril(ones((s, t)), k=t-s): bottom-right
        # aligned — identical to the causal path for the square chunk
        mask = (jnp.arange(t)[None, :]
                <= rows[:, None] + (t - s))[None, None]
        o = jax.nn.dot_product_attention(q_r[...], k, v, mask=mask,
                                         is_causal=False)
        o_r[...] = o.astype(o_r.dtype)

    def qtile(shape):
        return pl.BlockSpec((1, bq) + shape[2:],
                            imap(lambda i: (0, i, 0, 0)))

    def whole(shape):
        nd = len(shape)
        return pl.BlockSpec(shape, imap(lambda i: (0,) * nd))

    def fused(q, k, v):
        return pl.pallas_call(
            kernel,
            grid=(gq,),
            in_specs=[qtile((1, s, n, h)), whole((1, t, n, h)),
                      whole((1, t, n, h))],
            out_specs=qtile((1, s, n, h)),
            out_shape=jax.ShapeDtypeStruct((1, s, n, h), dt),
            interpret=_pl_utils.interpret(),
            name="prefill_chain",
        )(q, k, v)

    return fused


# ---------------------------------------------------------------------------
# engine-facing plumbing


def spec_from_arrays(kc, q, tables, mesh=None, mp_axis="mp"):
    """Geometry spec for the chain the traced step is about to run —
    derived from the live pool/query/table shapes, so the fused kernel
    and the arrays it consumes can never disagree."""
    from paddle_tpu.ops import paged_attention as pa

    quant = isinstance(kc, pa.QuantPool)
    data = kc.data if quant else kc
    nb, nkv, bs, h = data.shape
    b, n, _h = q.shape
    return DecodeChainSpec(
        batch=int(b), num_heads=int(n), num_kv_heads=int(nkv),
        head_dim=int(h), block_size=int(bs),
        max_blocks=int(tables.shape[1]), num_blocks=int(nb),
        kv="int8" if quant else "bf16",
        dtype=np.dtype(q.dtype), mesh=mesh, mp_axis=mp_axis)


def ensure_decision(spec, searcher=None):
    """Search-or-serve for one decode-chain geometry: cache verdicts are
    final (accepted configs serve with ZERO re-measurement; disabled
    geometries never re-fire), fresh geometries run the full
    enumerate→prune→parity→measure→gate loop and persist.  A
    cache-served config is parity-gated once per consumer anyway — a
    cache file is trusted about SPEED, never about numerics."""
    import jax

    from paddle_tpu.static.schedule_search import (
        Decision, ScheduleSearcher, build_error_decision)

    if searcher is None:
        searcher = ScheduleSearcher()
    decision = searcher.search(spec)
    if decision.status == "cache":
        args = spec.synthetic_args()
        ref_out = jax.jit(spec.reference())(*args)
        try:
            ok = spec.parity_ok(jax.jit(spec.build(decision.config)),
                                args, ref_out)
        except Exception as e:  # noqa: BLE001 — the engine must keep serving
            # a cached winner that no longer builds (a Mosaic compile error
            # included) is NOT a measured loss: its own status, with the cause
            return build_error_decision(e)
        if not ok:
            return Decision("disabled")
    return decision


def fused_decode_step(kc, vc, q, kn, vn, tables, lens, *, config):
    """The macro-step scan body's fused seam: one accepted-config Pallas
    dispatch replacing the write→write→attend op sequence of
    models/llama._decode_layer_paged.  Returns (o, kc', vc').

    A TP-sharded engine injects its mesh handle as the non-persisted
    '_mesh'/'_mp_axis' config entries (serving._resolve_decode_chain) —
    popped here before build, so the cache stores the pure schedule and
    the live mesh object never leaks into a verdict file."""
    config = dict(config)
    mesh = config.pop("_mesh", None)
    mp_axis = config.pop("_mp_axis", "mp")
    spec = spec_from_arrays(kc, q, tables, mesh=mesh, mp_axis=mp_axis)
    return spec.build(config)(kc, vc, q, kn, vn, tables, lens)


def fused_prefill_attention(q, k, v, *, block_q, stage="take", kchunk=1):
    """The prefill branch's fused seam (LlamaAttention.forward under
    models/llama.prefill_chain_scope): one accepted-config Pallas
    dispatch replacing the F.scaled_dot_product_attention core for a
    [1, S, n, h] chunk against [1, T, n, h] post-repeat K/V.  Callers
    gate on divisibility (S % block_q, T % kchunk) — a chunk the config
    doesn't tile keeps the XLA path."""
    _b, s, n, h = q.shape
    spec = PrefillChainSpec(seq=int(s), kv_len=int(k.shape[1]),
                            num_heads=int(n), head_dim=int(h),
                            dtype=np.dtype(q.dtype))
    cfg = {"block_q": int(block_q), "stage": stage, "kchunk": int(kchunk)}
    return spec.build(cfg)(q, k, v)
