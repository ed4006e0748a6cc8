"""Cost-model-driven Pallas schedule search over discovered subgraphs.

Reference: the CINN auto-scheduler role (paddle/cinn/auto_schedule/
auto_tuner.h — measured-cost search over schedule configs) rebuilt in the
TVM/Ansor shape (PAPERS.md: TVM, arXiv 1802.04799): instead of hand-picked
tile sizes per named kernel, DISCOVERED reduction- and matmul-rooted
subgraphs (static/rewrite.py ScheduleSearchPattern) get a searched Pallas
schedule.  The fusion-miss classes hunted here are the ones XLA is known to
leave on the table (PAPERS.md: "Operator Fusion in XLA", arXiv 2301.13062):
matmul→bias→act→reduce tails and softmax-adjacent reduction chains that no
named pattern matches.

Pipeline per subgraph (pruning order is part of the contract, see
docs/SCHEDULE_SEARCH.md):

1. **enumerate** candidate tilings — block shapes (block_rows × block_cols),
   grid layouts and dimension orders (rows-inner vs cols-inner sweep);
2. **roofline prune** (cost_model.device_peaks / flops_time): per-candidate
   HBM traffic is modeled from the grid geometry (a weight tile re-fetched
   per row-block vs an activation tile re-fetched per col-block depends on
   the dimension order), candidates worse than `roofline_margin` × the best
   analytic candidate are dropped;
3. **VMEM prune** (ops.autotune.validate_tile): candidates whose working
   set exceeds the per-core VMEM budget are dropped;
4. **measure** the top-K survivors (K = FLAGS_schedule_search_budget)
   on-device via cost_model.OpCostModel.measure;
5. **measured-win gate**: the best candidate races an XLA-only twin of the
   same subgraph; only a win by ≥ FLAGS_schedule_search_min_win is accepted.
   Winners AND losers persist through the per-device AutotuneCache
   (`schedule/*` kernel namespace in ops/tuned/<slug>.json) — a losing
   subgraph is recorded as *disabled* and never measured again on that
   device kind.

Semantics are guarded independently of the gate: under
FLAGS_verify_programs every accepted substitution is differentially
replayed against the unrewritten program (static/verify.py).

CPU/CI caveat: off a TPU, kernels run in Pallas interpret mode where
XLA-only almost always wins — the gate then (correctly) disables fusions.
Tests and the bench's --smoke twin inject a deterministic `measure`
callback instead (see `measure_override`), keeping the decision logic
falsifiable offline; the real measure path has not run on a chip.
"""

from __future__ import annotations

import contextlib
import hashlib
from dataclasses import dataclass, field

import numpy as np

__all__ = [
    "ExtInput",
    "SubgraphSpec",
    "match_subgraph",
    "enumerate_candidates",
    "candidate_vmem_bytes",
    "candidate_roofline_ms",
    "build_kernel",
    "build_reference",
    "Decision",
    "ScheduleSearcher",
    "measure_override",
    "schedule_search_stats",
    "reset_schedule_search_stats",
]

from ..framework.op_registry import base_op_type as _base_type

# ---------------------------------------------------------------------------
# counters (module-owned, surfaced via profiler.schedule_search_stats())

_COUNTERS = {
    "subgraphs_found": 0,     # fresh searches only (cache service counted
                              # separately in cache_hits / disabled_hits)
    "candidates": 0,          # tilings enumerated across all searches
    "pruned_roofline": 0,     # dropped by the analytic roofline ranking
    "pruned_vmem": 0,         # dropped by the VMEM working-set budget
    "measured": 0,            # candidates actually timed on device
    "accepted": 0,            # subgraphs whose best schedule beat XLA
    "disabled": 0,            # subgraphs recorded as losing
    "build_errors": 0,        # candidates (or cached winners) whose kernel
                              # raised at build/compile/run — a Mosaic compile
                              # error lands HERE, never under "disabled"
    "cache_hits": 0,          # accepted schedules served from the cache
    "disabled_hits": 0,       # disabled subgraphs skipped via the cache
}


def schedule_search_stats(reset: bool = False) -> dict:
    out = dict(_COUNTERS)
    if reset:
        reset_schedule_search_stats()
    return out


def reset_schedule_search_stats():
    for k in _COUNTERS:
        _COUNTERS[k] = 0


# ---------------------------------------------------------------------------
# op-class sets for the discovery tier

_REDUCE_OPS = {
    "sum", "nansum", "mean", "nanmean", "prod", "max", "min", "amax",
    "amin", "logsumexp",
}
# shape-preserving but last-axis-coupled (internal reduction): fusible as a
# row op, forbids tiling the reduced axis
_ROWWISE_OPS = {"softmax", "log_softmax"}
_MATMUL_OPS = {"matmul", "linear"}


@dataclass
class ExtInput:
    """One external input of a discovered subgraph.

    role: 'row'    — leading dims match the row shape; 2-D view (rows, cols)
          'xrow'   — a matmul's activation input: row-shaped leading dims
                     but its last dim is the CONTRACTION dim, so it is
                     never col-tiled (on square K == N shapes it is
                     indistinguishable from 'row' by cols alone)
          'bcast'  — all-leading-1 broadcast (e.g. a bias); view (1, cols)
          'weight' — a matmul's 2-D weight, resident per grid step
    """

    vid: int
    shape: tuple
    dtype: object
    cols: int
    role: str


@dataclass
class SubgraphSpec:
    """A discovered reduction-/matmul-rooted subgraph, ready to schedule."""

    kind: str               # 'matmul' | 'reduce'
    root: object            # downstream-end Operator (keeps its out vid)
    ops: list               # chain Operators in execution order
    ext: list               # ExtInput per external input, in first-use order
    out_vid: int
    out_shape: tuple
    out_cols: int           # last dim of the kernel's 2-D output (cols or 1)
    out_dtype: object
    rows: int
    cols: int
    k_dims: tuple           # matmul inner dims, in chain order
    has_reduce: bool
    col_tilable: bool       # the reduced axis may be tiled (no reduce/rowwise)
    k_tilable: bool = False  # the contraction dim may be tiled (single
                             # matmul whose x/w feed no other chain op)
    sig: str = ""

    def __post_init__(self):
        if not self.sig:
            parts = [
                ",".join(_base_type(op.type) for op in self.ops),
                ";".join(f"{e.role}{e.cols}" for e in self.ext),
                repr(self.out_shape),
            ]
            self.sig = hashlib.sha1("|".join(parts).encode()).hexdigest()[:10]

    def kernel_name(self) -> str:
        return f"schedule/{self.kind}"

    def key(self) -> dict:
        return {
            "rows": self.rows,
            "cols": self.cols,
            "k": "x".join(str(k) for k in self.k_dims) or "0",
            "sig": self.sig,
            "dtype": np.dtype(self.out_dtype).name,
        }

    def label(self) -> str:
        from paddle_tpu.ops.autotune import _key_str

        return f"{self.kernel_name()}|{_key_str(self.key())}"

    # ---- what the ScheduleSearcher asks of a spec ----------------------
    def enumerate_configs(self):
        return enumerate_candidates(self)

    def roofline_ms(self, config, cost_model=None):
        return candidate_roofline_ms(self, config, cost_model)

    def vmem_bytes(self, config):
        return candidate_vmem_bytes(self, config)

    def build(self, config):
        return build_kernel(self, config)

    def reference(self):
        return build_reference(self)

    def synthetic_args(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(0)
        return tuple(
            jnp.asarray(rng.standard_normal(e.shape), e.dtype)
            for e in self.ext)

    def config_label(self, config):
        lbl = (f"#{config['block_rows']}x{config['block_cols']}"
               f"@{config['grid_order']}")
        bk = config.get("block_k")
        if bk and self.k_dims and bk < self.k_dims[0]:
            lbl += f"k{bk}"
        return lbl


# ---------------------------------------------------------------------------
# discovery


def _entry_shape(graph, entry):
    if entry[0] == "var":
        return graph.shape(entry[1])
    try:
        return tuple(np.shape(entry[1]))
    except Exception:
        return None


def _const_ok(value, cols):
    """Consts are baked inside recorded op fns: only scalars and rank<=2
    last-dim broadcasts replay correctly on 2-D row blocks."""
    try:
        arr = np.asarray(value)
    except Exception:
        return False
    if arr.size == 1:
        return True
    if arr.ndim > 2:
        return False
    return all(d == 1 for d in arr.shape[:-1]) and arr.shape[-1] in (1, cols)


def _wide_const(value, cols):
    try:
        arr = np.asarray(value)
    except Exception:
        return False
    return arr.size > 1 and arr.ndim >= 1 and arr.shape[-1] == cols


def _reduces_last_axis(op, row_shape, keepdim_only):
    """True iff the op's BAKED reduction axis is the last one.  Shapes alone
    cannot tell: on square dims (S == C) an axis=1 reduction's output shape
    coincides with a last-axis reduction's — fusing it would replay the
    baked axis on the collapsed 2-D block and reduce the wrong dimension.
    Recorded reduce ops take exactly one tensor and close over no other
    shaped values, so probing the fn at an all-distinct-dims aval is safe:
    only a last-axis reduction maps probe -> probe[:-1] (+ keepdim 1)."""
    import jax

    probe = tuple(range(2, 2 + len(row_shape) - 1)) + (2 + len(row_shape),)
    try:
        out = jax.eval_shape(
            op.fn, jax.ShapeDtypeStruct(probe, np.float32))
        flat = jax.tree_util.tree_leaves(out)
    except Exception:
        return False
    if len(flat) != 1:
        return False
    shape = tuple(flat[0].shape)
    if shape == probe[:-1] + (1,):
        return True
    return not keepdim_only and shape == probe[:-1]


def _classify(op, graph, row_shape, root=None):
    """-> 'elem' | 'rowwise' | 'reduce' | 'matmul' | None (not fusible)."""
    from ..framework.op_registry import side_effect_op_types

    from .rewrite import _ELEMENTWISE

    b = _base_type(op.type)
    if b in side_effect_op_types():
        return None  # dropout/RNG/print/collectives: never cross
    if not op.out_vids or len(op.out_vids) != 1:
        return None
    o = graph.shape(op.out_vids[0])
    if o is None:
        return None
    reduced = row_shape[:-1] + (1,)
    cols = row_shape[-1]
    if b in _MATMUL_OPS:
        if op.kwargs.get("transpose_x") or op.kwargs.get("transpose_y"):
            return None
        if o != row_shape or len(op.arg_spec) not in (2, 3):
            return None
        x_e, w_e = op.arg_spec[0], op.arg_spec[1]
        if x_e[0] != "var":
            return None
        xs = graph.shape(x_e[1])
        if xs is None or len(xs) < 2 or xs[:-1] != row_shape[:-1]:
            return None
        ws = _entry_shape(graph, w_e)
        if not ws or len(ws) != 2 or ws != (xs[-1], cols):
            return None
        if len(op.arg_spec) == 3 and _entry_shape(graph, op.arg_spec[2]) != (cols,):
            return None
        return "matmul"
    if b in _REDUCE_OPS:
        ins = [s for s in op.arg_spec if s[0] == "var"]
        if len(ins) != 1 or len(op.arg_spec) != 1:
            return None
        if graph.shape(ins[0][1]) != row_shape:
            return None
        if o != reduced and not (op is root and o == row_shape[:-1]):
            return None  # non-keepdim only at the root (reshaped at the end)
        if not _reduces_last_axis(op, row_shape, keepdim_only=(o == reduced)):
            return None  # baked axis is not the last one (square-dims trap)
        return "reduce"
    if b in _ROWWISE_OPS:
        ax = op.kwargs.get("axis", -1)
        if ax not in (-1, len(row_shape) - 1):
            return None
        ins = [s for s in op.arg_spec if s[0] == "var"]
        if len(ins) != 1 or graph.shape(ins[0][1]) != row_shape or o != row_shape:
            return None
        return "rowwise"
    if b in _ELEMENTWISE:
        if o not in (row_shape, reduced):
            return None
        oc = o[-1]
        for s in op.arg_spec:
            if s[0] == "var":
                vs = graph.shape(s[1])
                if vs is None:
                    return None
                bcast = (len(vs) >= 1 and all(d == 1 for d in vs[:-1])
                         and vs[-1] in (1, oc))
                if vs not in (row_shape, reduced) and not bcast:
                    return None
            elif not _const_ok(s[1], cols):
                return None
        return "elem"
    return None


def _extends(consumer, graph, row_shape):
    """Would `consumer` continue this chain?  Used to anchor discovery at
    the downstream END only — interior ops stand down so the maximal
    subgraph is searched once, not every suffix of it."""
    from .rewrite import _ELEMENTWISE

    b = _base_type(consumer.type)
    if not consumer.out_vids or len(consumer.out_vids) != 1:
        return False
    o = graph.shape(consumer.out_vids[0])
    if b in _ELEMENTWISE or b in _ROWWISE_OPS:
        return o == row_shape
    if b in _REDUCE_OPS:
        return o in (row_shape[:-1] + (1,), row_shape[:-1])
    return False


def match_subgraph(root, graph, min_ops=2):
    """Anchor at `root` (downstream end) and collect the maximal fusible
    reduction-/matmul-rooted subgraph feeding it; None when `root` is not a
    viable anchor.

    Interior links require every consumer of a value to sit inside the
    chain (DAG discovery — manual softmax's exp feeds both the sum and the
    divide).  Fetch-frontier/write-visible interior values are deliberately
    NOT checked here: the PatternRewritePass use-def rollback (PR 4) is the
    authoritative refusal path and counts them in `.refused`."""
    import jax.numpy as jnp

    from .rewrite import _ELEMENTWISE

    base = _base_type(root.type)
    if not root.out_vids or len(root.out_vids) != 1:
        return None
    out_shape = graph.shape(root.out_vids[0])
    if out_shape is None:
        return None

    if base in _REDUCE_OPS:
        ins = [s for s in root.arg_spec if s[0] == "var"]
        if len(ins) != 1:
            return None
        row_shape = graph.shape(ins[0][1])
        if row_shape is None or len(row_shape) < 2:
            return None
        if out_shape not in (row_shape[:-1], row_shape[:-1] + (1,)):
            return None
    elif base in _ELEMENTWISE or base in _ROWWISE_OPS:
        row_shape = out_shape
        if len(row_shape) < 2 or row_shape[-1] < 2:
            return None
    else:
        return None

    root_kind = _classify(root, graph, row_shape, root=root)
    if root_kind is None:
        return None
    # downstream-END anchor: if every consumer would extend the chain, some
    # later op is the true root — stand down here
    cons = graph.consumers.get(root.out_vids[0], [])
    if cons and all(_extends(c, graph, row_shape) for c in cons):
        return None

    chain = {id(root): root}
    kinds = {id(root): root_kind}
    changed = True
    while changed:
        changed = False
        for op in list(chain.values()):
            if kinds[id(op)] == "matmul":
                continue  # matmul is an origin: its x input stays external
            for s in op.arg_spec:
                if s[0] != "var":
                    continue
                vid = s[1]
                prod = graph.producer.get(vid)
                if prod is None or id(prod) in chain:
                    continue
                vcons = graph.consumers.get(vid, [])
                if not all(id(c) in chain for c in vcons):
                    continue
                k = _classify(prod, graph, row_shape, root=root)
                if k is None:
                    continue
                chain[id(prod)] = prod
                kinds[id(prod)] = k
                changed = True

    ordered = [op for op in graph.block.ops if id(op) in chain]
    if len(ordered) < min_ops:
        return None
    n_mm = sum(1 for op in ordered if kinds[id(op)] == "matmul")
    n_red = sum(1 for op in ordered if kinds[id(op)] == "reduce")
    n_row = sum(1 for op in ordered if kinds[id(op)] == "rowwise")
    if n_mm + n_red + n_row == 0:
        return None  # plain elementwise chain: GenericElementwiseFusionPass's job
    if n_mm and len(ordered) == n_mm:
        return None  # a bare matmul is XLA's bread and butter

    rows = int(np.prod(row_shape[:-1]))
    cols = int(row_shape[-1])
    out_var = graph.program._var_by_vid.get(root.out_vids[0])
    if out_var is None or not jnp.issubdtype(out_var._value.dtype, jnp.inexact):
        return None

    produced = {vid for op in ordered for vid in op.out_vids}
    mm_slots = {}  # vid -> role hint from matmul operand positions
    for op in ordered:
        if kinds[id(op)] == "matmul":
            specs = op.arg_spec
            mm_slots[specs[0][1]] = "xrow"
            if specs[1][0] == "var":
                mm_slots[specs[1][1]] = "weight"
            if len(specs) == 3 and specs[2][0] == "var":
                mm_slots[specs[2][1]] = "bcast"
    reduced_shape = row_shape[:-1] + (1,)
    ext, seen = [], set()
    k_dims = []
    for op in ordered:
        if kinds[id(op)] == "matmul":
            k_dims.append(int(graph.shape(op.arg_spec[0][1])[-1]))
        for s in op.arg_spec:
            if s[0] != "var" or s[1] in produced or s[1] in seen:
                continue
            vid = s[1]
            vs = graph.shape(vid)
            var = graph.program._var_by_vid.get(vid)
            if var is None or vs is None:
                return None
            dt = var._value.dtype
            if not jnp.issubdtype(dt, jnp.inexact):
                return None
            role = mm_slots.get(vid)
            if role is None:
                if vs in (row_shape, reduced_shape):
                    role = "row"
                elif all(d == 1 for d in vs[:-1]):
                    role = "bcast"
                else:
                    return None
            ext.append(ExtInput(vid, vs, dt, int(vs[-1]), role))
            seen.add(vid)
    if not ext:
        return None

    wide_consts = any(
        s[0] == "const" and _wide_const(s[1], cols)
        for op in ordered for s in op.arg_spec)
    # an xrow consumed by a NON-matmul chain op (possible only on square
    # K == N shapes) would mix an untiled (br, K) block with tiled (br, bc)
    # blocks inside the kernel — forbid col tiling then
    xrow_vids = {e.vid for e in ext if mm_slots.get(e.vid) == "xrow"}
    xrow_in_elem = any(
        s[0] == "var" and s[1] in xrow_vids
        for op in ordered if kinds[id(op)] != "matmul"
        for s in op.arg_spec)
    col_tilable = (n_mm > 0 and n_red == 0 and n_row == 0 and not wide_consts
                   and not xrow_in_elem
                   and all(e.role != "weight" or e.cols == cols for e in ext))
    # K-loop tiling (phase 2): a SINGLE matmul whose x AND w are chain-
    # external vars feeding nothing but the matmul itself — the kernel
    # then carries an f32 accumulator across contraction grid steps and
    # replays the epilogue on the last one.  On K == M / K == N aliasing
    # shapes a weight or activation consumed by an elementwise op would
    # mix k-sliced blocks with row blocks, so those chains stay untiled.
    mm_vids = xrow_vids | {e.vid for e in ext
                           if mm_slots.get(e.vid) == "weight"}
    mm_ext_in_elem = any(
        s[0] == "var" and s[1] in mm_vids
        for op in ordered if kinds[id(op)] != "matmul"
        for s in op.arg_spec)
    k_tilable = (n_mm == 1 and not mm_ext_in_elem
                 and any(e.role == "weight" for e in ext))

    out_cols = cols if out_shape == row_shape else 1
    return SubgraphSpec(
        kind="matmul" if n_mm else "reduce",
        root=root,
        ops=ordered,
        ext=ext,
        out_vid=root.out_vids[0],
        out_shape=tuple(out_shape),
        out_cols=out_cols,
        out_dtype=out_var._value.dtype,
        rows=rows,
        cols=cols,
        k_dims=tuple(k_dims),
        has_reduce=n_red > 0 or n_row > 0,
        col_tilable=col_tilable,
        k_tilable=k_tilable,
    )


# ---------------------------------------------------------------------------
# schedule space


def enumerate_candidates(spec: SubgraphSpec):
    """Candidate tilings: block shapes, grid layouts, dimension orders —
    and, for K-tilable matmul chains, contraction-dim splits.

    Row blocks are multiples of 8 (f32 sublane).  The reduced axis is tiled
    only for reduction-free matmul chains (a per-block partial reduction
    would be wrong; a rowwise op needs its whole row).  Dimension order
    (which grid axis sweeps innermost) matters whenever the grid is 2-D:
    it decides whether weight tiles or activation tiles get re-fetched.
    K-tiled candidates carry ``block_k`` and always place the contraction
    axis INNERMOST (the f32 accumulator block then stays VMEM-resident
    across its revisits; with K innermost both operands re-stream the same
    under either outer order, so only one order is enumerated)."""
    rows, cols = spec.rows, spec.cols
    brs = [b for b in (8, 16, 32, 64, 128, 256, 512)
           if b <= rows and rows % b == 0] or [rows]
    if spec.col_tilable:
        bcs = [b for b in (128, 256, 512) if b < cols and cols % b == 0]
        bcs.append(cols)
    else:
        bcs = [cols]
    K = spec.k_dims[0] if spec.k_dims else 0
    if spec.k_tilable and K:
        bks = [b for b in (128, 256, 512) if b < K and K % b == 0]
        bks.append(K)
    else:
        bks = [None]
    out = []
    for br in brs:
        for bc in bcs:
            for bk in bks:
                orders = ["rows_first"]
                split = bk is not None and bk < K  # K innermost: no 2nd order
                if not split and bc != cols and rows // br > 1:
                    orders.append("cols_first")
                for od in orders:
                    cfg = {"block_rows": br, "block_cols": bc,
                           "grid_order": od}
                    if bk is not None:
                        cfg["block_k"] = bk
                    out.append(cfg)
    return out


def _grid_dims(spec, config):
    br, bc = int(config["block_rows"]), int(config["block_cols"])
    return br, bc, spec.rows // br, spec.cols // bc


def _k_split(spec, config):
    """(block_k, grid_k) — (K, 1) when the candidate keeps the contraction
    resident (incl. legacy cached configs with no block_k entry)."""
    K = spec.k_dims[0] if spec.k_dims else 0
    bk = int(config.get("block_k") or 0)
    if spec.k_tilable and K and bk and bk < K:
        return bk, K // bk
    return K, 1


def candidate_vmem_bytes(spec: SubgraphSpec, config: dict) -> int:
    """f32 working-set estimate for one grid step (double-buffered): all
    input blocks + the output block + one block-sized temp per chain op.
    A K-tiled candidate holds (br, bk) activation and (bk, bc) weight
    slices plus the f32 accumulator block instead of whole-K operands —
    the split that lets large-K matmul chains fit the budget at all."""
    br, bc, _, _ = _grid_dims(spec, config)
    bk, gk = _k_split(spec, config)
    tiled = bc != spec.cols
    elems = br * (bc if (tiled and spec.out_cols == spec.cols) else spec.out_cols)
    widest = spec.out_cols
    for e in spec.ext:
        ec = bc if (tiled and e.cols == spec.cols
                    and e.role != "xrow") else e.cols
        if e.role == "xrow":
            elems += br * (bk if gk > 1 else ec)
        elif e.role == "row":
            elems += br * ec
        elif e.role == "bcast":
            elems += ec
        else:  # weight: whole-K resident per step unless K is tiled
            elems += (bk if gk > 1 else e.shape[0]) * ec
        widest = max(widest, ec)
    if gk > 1:
        elems += br * (bc if tiled else spec.cols)  # f32 accumulator block
    elems += len(spec.ops) * br * max(widest, bc if tiled else spec.cols)
    return int(elems) * 4 * 2


# Per-grid-step pipeline/dispatch overhead for the analytic ranking
# (~100ns: the scale of one Mosaic grid-step turnaround).  Matters for
# 1-D grids, where traffic and flops are block_rows-independent and would
# otherwise tie every candidate — the stable sort would then measure only
# the smallest blocks and the budget cutoff could skip the large-block
# schedules that actually feed the MXU/VPU well.
_GRID_STEP_OVERHEAD_S = 1e-7


def candidate_roofline_ms(spec: SubgraphSpec, config: dict,
                          cost_model=None) -> float:
    """Roofline estimate (cost_model.flops_time over device_peaks) with
    per-candidate HBM traffic from the grid geometry: a block whose index
    map is constant across the INNER grid axis is fetched once per outer
    step; one that changes every inner step is re-fetched each time.
    A small per-grid-step overhead term breaks ties between candidates
    whose traffic is identical (1-D grids)."""
    if cost_model is None:
        from paddle_tpu.cost_model import OpCostModel

        cost_model = OpCostModel()
    br, bc, gm, gn = _grid_dims(spec, config)
    bk, gk = _k_split(spec, config)
    rows, cols = spec.rows, spec.cols
    order = config.get("grid_order", "rows_first")
    tiled = gn > 1

    flops = 0.0
    for k in spec.k_dims:
        flops += 2.0 * rows * k * cols
    flops += (len(spec.ops) - len(spec.k_dims)) * rows * cols

    traffic = float(np.prod(spec.out_shape)) * np.dtype(spec.out_dtype).itemsize
    if gk > 1:
        # the f32 accumulator rides an extra HBM-backed output (written
        # once per (i, j) tile) — K-tiling is not free and must rank so
        traffic += float(rows * cols) * 4
    for e in spec.ext:
        sz = float(np.prod(e.shape)) * np.dtype(e.dtype).itemsize
        if gk > 1 and e.role == "xrow":
            # with K innermost the activation's (i, k) slices re-stream
            # once per column block — the whole-K residency that made x
            # fetch-once is exactly what the split gives up
            traffic += sz * gn
            continue
        if gk > 1 and e.role == "weight":
            traffic += sz * gm  # (k, j) slices re-stream per row block
            continue
        j_indexed = tiled and e.cols == cols and e.role in ("bcast", "weight")
        i_only = (e.role == "xrow"
                  or (e.role == "row" and not (tiled and e.cols == cols)))
        if j_indexed:
            traffic += sz * (gm if order == "rows_first" else 1)
        elif i_only:
            traffic += sz * (gn if order == "cols_first" else 1)
        else:
            traffic += sz  # each block visited exactly once
    return (cost_model.flops_time(flops, traffic)
            + gm * gn * gk * _GRID_STEP_OVERHEAD_S) * 1e3


# ---------------------------------------------------------------------------
# codegen


def build_reference(spec: SubgraphSpec):
    """Replay the recorded op fns on the given external inputs — ONE
    definition of the subgraph's semantics, shared by the XLA-only twin
    (the measured-win gate's baseline and numerics oracle, fed
    ORIGINAL-shaped inputs) and the kernel's block-level trace
    (_chain_body, fed block-shaped inputs)."""
    ext_vids = [e.vid for e in spec.ext]

    def ref(*vals):
        import jax

        env = dict(zip(ext_vids, vals))
        for op in spec.ops:
            var_vals = [env[s[1]] for s in op.arg_spec if s[0] == "var"]
            out = op.fn(*var_vals)
            for vid, v in zip(op.out_vids, jax.tree_util.tree_leaves(out)):
                env[vid] = v
        return env[spec.out_vid]

    return ref


def _chain_body(spec):
    """build_reference's replay at block shape, plus the block-level
    normalization of a non-keepdim root reduction to 2-D."""
    ref = build_reference(spec)

    def body(*vals):
        r = ref(*vals)
        if r.ndim == 1:
            r = r.reshape(r.shape[0], 1)
        return r

    return body


def _epilogue_body(spec, mm_op, mm_dtype):
    """The chain replay with the matmul's output SUBSTITUTED: the K-tiled
    kernel accumulates x@w across contraction grid steps and feeds the
    finished accumulator here on the last one.  A 3-arg matmul/linear adds
    its bias now (the partial products must sum before the epilogue)."""
    import jax
    import jax.numpy as jnp

    ext_vids = [e.vid for e in spec.ext]

    def body(mm_out, *vals):
        env = dict(zip(ext_vids, vals))
        for op in spec.ops:
            if op is mm_op:
                r = mm_out
                if len(op.arg_spec) == 3:
                    s = op.arg_spec[2]
                    bv = env[s[1]] if s[0] == "var" else jnp.asarray(s[1])
                    r = r + bv
                env[op.out_vids[0]] = r.astype(mm_dtype)
            else:
                var_vals = [env[s[1]] for s in op.arg_spec if s[0] == "var"]
                out = op.fn(*var_vals)
                for vid, v in zip(op.out_vids,
                                  jax.tree_util.tree_leaves(out)):
                    env[vid] = v
        r = env[spec.out_vid]
        if r.ndim == 1:
            r = r.reshape(r.shape[0], 1)
        return r

    return body


def _build_kernel_ktiled(spec: SubgraphSpec, config: dict):
    """K-tiled variant of build_kernel: grid (gm, gn, gk) with the
    contraction axis INNERMOST, an f32 accumulator carried across the k
    revisits as an extra (i, j)-indexed output, and the epilogue (every
    chain op beyond the matmul) replayed once on the final k step.  Only
    (br, bk) activation and (bk, bc) weight slices are VMEM-resident per
    step — large-K matmul chains fit the budget instead of being
    auto-disabled."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from paddle_tpu.ops import _pl_utils
    from paddle_tpu.ops._pl_utils import imap

    br, bc, gm, gn = _grid_dims(spec, config)
    bk, gk = _k_split(spec, config)
    rows, cols = spec.rows, spec.cols
    tiled = gn > 1
    mm_op = next(op for op in spec.ops
                 if _base_type(op.type) in _MATMUL_OPS)
    xrow_i = next(i for i, e in enumerate(spec.ext) if e.role == "xrow")
    w_i = next(i for i, e in enumerate(spec.ext) if e.role == "weight")
    mm_dtype = jnp.result_type(spec.ext[xrow_i].dtype, spec.ext[w_i].dtype)

    def view2d(e, v):
        if e.role in ("row", "xrow"):
            return v.reshape(rows, e.cols)
        if e.role == "bcast":
            return v.reshape(1, e.cols)
        return v  # weight: already 2-D

    def block_shape(e):
        if e.role == "xrow":
            return (br, bk)
        if e.role == "row":
            return (br, bc) if (tiled and e.cols == cols) else (br, e.cols)
        if e.role == "bcast":
            return (1, bc) if (tiled and e.cols == cols) else (1, e.cols)
        return (bk, bc) if tiled else (bk, e.cols)  # weight

    def index_fn(e):
        if e.role == "xrow":
            return lambda i, j, k: (i, k)
        if e.role == "weight":
            return lambda i, j, k: (k, j)  # j fixed 0 when untiled cols
        if e.role == "row":
            if tiled and e.cols == cols:
                return lambda i, j, k: (i, j)
            return lambda i, j, k: (i, 0)
        if tiled and e.cols == cols:  # bcast sliced along cols
            return lambda i, j, k: (0, j)
        return lambda i, j, k: (0, 0)

    acc_block = (br, bc if tiled else cols)
    out_block = (br, bc if (tiled and spec.out_cols == cols)
                 else spec.out_cols)
    ij = imap(lambda i, j, k: (i, j))

    block_avals = [jax.ShapeDtypeStruct(block_shape(e), e.dtype)
                   for e in spec.ext]
    acc_aval = jax.ShapeDtypeStruct(acc_block, mm_dtype)
    closed = jax.make_jaxpr(_epilogue_body(spec, mm_op, mm_dtype))(
        acc_aval, *block_avals)
    np_consts = [np.asarray(c) for c in closed.consts]
    n_in = len(spec.ext)

    def kernel(*refs):
        ins, o_ref, acc_ref = refs[:n_in], refs[n_in], refs[n_in + 1]
        k = pl.program_id(2)

        @pl.when(k == 0)
        def _zero():
            acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

        acc_ref[...] += jnp.dot(
            ins[xrow_i][...].astype(jnp.float32),
            ins[w_i][...].astype(jnp.float32),
            preferred_element_type=jnp.float32)

        @pl.when(k == gk - 1)
        def _epilogue():
            out = jax.core.eval_jaxpr(
                closed.jaxpr, np_consts,
                acc_ref[...].astype(mm_dtype),
                *(r[...] for r in ins))[0]
            o_ref[...] = out.astype(o_ref.dtype)

    in_specs = [pl.BlockSpec(block_shape(e), imap(index_fn(e)))
                for e in spec.ext]
    out_specs = [pl.BlockSpec(out_block, ij), pl.BlockSpec(acc_block, ij)]
    out_shape = [
        jax.ShapeDtypeStruct((rows, spec.out_cols), spec.out_dtype),
        jax.ShapeDtypeStruct((rows, cols), jnp.float32),
    ]

    def fused(*vals):
        flat = [view2d(e, v) for e, v in zip(spec.ext, vals)]
        out, _acc = pl.pallas_call(
            kernel,
            grid=(gm, gn, gk),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=_pl_utils.interpret(),
        )(*flat)
        return out.reshape(spec.out_shape)

    return fused


def build_kernel(spec: SubgraphSpec, config: dict):
    """One Pallas kernel for the whole subgraph at `config`'s tiling: the
    recorded op fns are pre-traced at block shape (jax.make_jaxpr, closure
    constants baked as numpy — Pallas kernels may not capture traced
    arrays) and replayed over VMEM blocks, so an N-op chain makes one HBM
    round trip.  Returns a callable over ORIGINAL-shaped external inputs.
    Candidates carrying a genuine ``block_k`` split route to the K-tiled
    accumulator variant (_build_kernel_ktiled)."""
    import jax
    from jax.experimental import pallas as pl

    from paddle_tpu.ops import _pl_utils
    from paddle_tpu.ops._pl_utils import imap

    if _k_split(spec, config)[1] > 1:
        return _build_kernel_ktiled(spec, config)

    br, bc, gm, gn = _grid_dims(spec, config)
    rows, cols = spec.rows, spec.cols
    order = config.get("grid_order", "rows_first")
    tiled = gn > 1

    def view2d(e, v):
        if e.role in ("row", "xrow"):
            return v.reshape(rows, e.cols)
        if e.role == "bcast":
            return v.reshape(1, e.cols)
        return v  # weight: already 2-D

    def block_shape(e):
        if e.role == "xrow":  # contraction dim: never col-sliced
            return (br, e.cols)
        if e.role == "row":
            return (br, bc) if (tiled and e.cols == cols) else (br, e.cols)
        if e.role == "bcast":
            return (1, bc) if (tiled and e.cols == cols) else (1, e.cols)
        return (e.shape[0], bc) if (tiled and e.cols == cols) else tuple(e.shape)

    def index_fn(e):
        if e.role == "xrow":
            return lambda i, j: (i, 0)
        if e.role == "row":
            if tiled and e.cols == cols:
                return lambda i, j: (i, j)
            return lambda i, j: (i, 0)
        if tiled and e.cols == cols:  # bcast/weight sliced along cols
            return lambda i, j: (0, j)
        return lambda i, j: (0, 0)

    out_tiled = tiled and spec.out_cols == cols
    out_block = (br, bc if out_tiled else spec.out_cols)
    out_index = (lambda i, j: (i, j)) if out_tiled else (lambda i, j: (i, 0))

    # grid layout + dimension order: the kernel's index maps receive grid
    # coordinates in grid order; `wrap` restores (row_block, col_block)
    if gn > 1 and order == "cols_first":
        grid = (gn, gm)
        def wrap(f):
            return imap(lambda a, b: f(b, a))
    elif gn > 1:
        grid = (gm, gn)
        def wrap(f):
            return imap(lambda a, b: f(a, b))
    else:
        grid = (gm,)
        def wrap(f):
            return imap(lambda a: f(a, 0))

    block_avals = [jax.ShapeDtypeStruct(block_shape(e), e.dtype)
                   for e in spec.ext]
    closed = jax.make_jaxpr(_chain_body(spec))(*block_avals)
    np_consts = [np.asarray(c) for c in closed.consts]
    n_in = len(spec.ext)

    def kernel(*refs):
        ins, o_ref = refs[:n_in], refs[n_in]
        out = jax.core.eval_jaxpr(
            closed.jaxpr, np_consts, *(r[:] for r in ins))[0]
        o_ref[:] = out.astype(o_ref.dtype)

    in_specs = [pl.BlockSpec(block_shape(e), wrap(index_fn(e)))
                for e in spec.ext]
    out_specs = pl.BlockSpec(out_block, wrap(out_index))

    def fused(*vals):
        flat = [view2d(e, v) for e, v in zip(spec.ext, vals)]
        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=jax.ShapeDtypeStruct((rows, spec.out_cols),
                                           spec.out_dtype),
            interpret=_pl_utils.interpret(),
        )(*flat)
        return out.reshape(spec.out_shape)

    return fused


# ---------------------------------------------------------------------------
# the searcher + measured-win gate

_MEASURE_OVERRIDE = None


@contextlib.contextmanager
def measure_override(fn):
    """Route every schedule measurement through `fn(run, args, *, label,
    config)` -> ms.  config is None for the XLA-only twin.  Tests and the
    bench --smoke twin use this for deterministic CPU decisions."""
    global _MEASURE_OVERRIDE
    prev, _MEASURE_OVERRIDE = _MEASURE_OVERRIDE, fn
    try:
        yield
    finally:
        _MEASURE_OVERRIDE = prev


@dataclass
class Decision:
    """Outcome of one subgraph search."""

    status: str  # accepted | disabled | cache | cache_disabled | build_error
    config: dict | None = None
    pallas_ms: float = 0.0
    xla_ms: float = 0.0
    win: float = 0.0
    error: str = ""         # build_error only: the exception that said no

    @property
    def accepted(self) -> bool:
        return self.status in ("accepted", "cache")


def build_error_decision(exc) -> Decision:
    """A kernel that raised while building, compiling or running: counted
    and reported under its own status with the cause, so a compiler refusal
    on the chip is never read as a measured loss."""
    _COUNTERS["build_errors"] += 1
    return Decision("build_error", error=f"{type(exc).__name__}: {exc}"[:2000])


class ScheduleSearcher:
    """Enumerate → roofline-prune → VMEM-prune → measure → gate → persist.

    measure(fn, args, *, label, config) -> ms overrides the default
    OpCostModel.measure timing (deterministic tests / bench smoke)."""

    def __init__(self, cost_model=None, measure=None, budget=None,
                 min_win=None, roofline_margin=1.5, iters=3, warmup=1):
        from paddle_tpu._core import flags

        if cost_model is None:
            from paddle_tpu.cost_model import OpCostModel

            cost_model = OpCostModel()
        self.cost_model = cost_model
        self._measure = measure
        self.budget = (int(flags.flag("FLAGS_schedule_search_budget"))
                       if budget is None else int(budget))
        self.min_win = (float(flags.flag("FLAGS_schedule_search_min_win"))
                        if min_win is None else float(min_win))
        self.roofline_margin = float(roofline_margin)
        self.iters = int(iters)
        self.warmup = int(warmup)

    # ----------------------------------------------------------- plumbing
    def _measure_ms(self, label, fn, args, config):
        cb = _MEASURE_OVERRIDE or self._measure
        if cb is not None:
            return float(cb(fn, args, label=label, config=config))
        return self.cost_model.measure(
            label, fn, *args, iters=self.iters, warmup=self.warmup) * 1e3

    @staticmethod
    def _cached(spec):
        from paddle_tpu.ops import autotune as at

        return at.lookup(spec.kernel_name(), spec.key())

    def _persist(self, spec, config, ms, meta):
        from paddle_tpu._core import flags
        from paddle_tpu.ops import autotune as at

        if not flags.flag("FLAGS_use_autotune_cache"):
            return  # cache disabled: decisions stay process-local
        c = at.cache()
        c.put(spec.kernel_name(), spec.key(), config, ms, meta=meta)
        c.save()

    # -------------------------------------------------------------- search
    def search(self, spec) -> Decision:
        """Drive a SubgraphSpec through enumerate → roofline → VMEM →
        measure → gate → persist.  A substituted kernel's numerics are
        checked by the differential replay under FLAGS_verify_programs."""
        cached = self._cached(spec)
        if cached is not None:
            if cached.get("disabled"):
                _COUNTERS["disabled_hits"] += 1
                return Decision("cache_disabled")
            _COUNTERS["cache_hits"] += 1
            return Decision("cache", cached)

        import jax

        _COUNTERS["subgraphs_found"] += 1
        args = spec.synthetic_args()
        candidates = spec.enumerate_configs()
        _COUNTERS["candidates"] += len(candidates)

        ranked = [(spec.roofline_ms(c, self.cost_model), c)
                  for c in candidates]
        best_roof = min(r for r, _ in ranked)
        kept = [(r, c) for r, c in ranked
                if r <= best_roof * self.roofline_margin]
        _COUNTERS["pruned_roofline"] += len(ranked) - len(kept)

        from paddle_tpu.ops.autotune import validate_tile

        fit = [(r, c) for r, c in kept
               if validate_tile(spec.vmem_bytes(c)) is None]
        _COUNTERS["pruned_vmem"] += len(kept) - len(fit)

        fit.sort(key=lambda rc: rc[0])

        ref_fn = jax.jit(spec.reference())
        best_cfg, best_ms = None, float("inf")
        failed = None  # last candidate that raised, as a Decision
        budget_left = max(1, self.budget)
        for _, cfg in fit:
            if budget_left <= 0:
                break
            try:
                fn = jax.jit(spec.build(cfg))
                ms = self._measure_ms(
                    spec.label() + spec.config_label(cfg), fn, args, cfg)
            except Exception as e:  # noqa: BLE001 — search must go on
                # unbuildable/unrunnable on this backend: does NOT burn a
                # budget slot — a later buildable candidate still gets
                # measured instead of the subgraph being disabled outright
                failed = build_error_decision(e)
                continue
            _COUNTERS["measured"] += 1
            budget_left -= 1
            if ms < best_ms:
                best_cfg, best_ms = dict(cfg), float(ms)

        if best_cfg is None:
            # nothing measured: NOT a measured loss — do not persist, so a
            # later version whose builder handles this subgraph gets to
            # retry.  If a candidate raised, say so (and what) instead of
            # "disabled"
            if failed is not None:
                return failed
            _COUNTERS["disabled"] += 1
            return Decision("disabled")

        xla_ms = float(self._measure_ms(
            f"{spec.label()}#xla", ref_fn, args, None))
        win = xla_ms / best_ms if best_ms > 0 else 0.0
        meta = {"win": round(win, 4), "xla_ms": round(xla_ms, 6)}
        if win >= self.min_win:
            self._persist(spec, best_cfg, best_ms, meta)
            _COUNTERS["accepted"] += 1
            return Decision("accepted", best_cfg, best_ms, xla_ms, win)
        self._persist(spec, {"disabled": True}, best_ms, meta)
        _COUNTERS["disabled"] += 1
        return Decision("disabled", None, best_ms, xla_ms, win)
