"""Pattern rewriting over captured Programs — the DRR/pattern_match role.

Reference: paddle/pir/pattern_rewrite/pattern_match.h (RewritePattern /
PatternRewriter / greedy driver) + paddle/fluid/pir/drr/ (declarative
source->result patterns), and the fusion-extraction role of
paddle/fluid/pir/transforms/build_cinn_pass.cc + sub_graph_detector.cc.

TPU-native role: XLA already fuses elementwise chains, so the profitable
Program-level rewrites are the ones XLA can NOT do — substituting an
algebraic subgraph with a hand-written Pallas kernel that changes the
algorithm (flash attention's online softmax, fused-norm's single pass).
The pass family here (`PallasFusionPass`) is the SURVEY §7 "Pallas codegen
pass for flagged subgraphs": a captured vanilla-jnp attention block gets
flash-attention substituted before lowering; rms-norm and swiglu chains get
their fused kernels.  Replaced final ops keep their output vids, so
downstream consumers / fetches are untouched and orphaned intermediates die
in the executor's dead-code-elimination pass.
"""

from __future__ import annotations

from collections import defaultdict

import numpy as np

__all__ = [
    "ProgramGraph",
    "RewritePattern",
    "PatternRewritePass",
    "PallasFusionPass",
    "FlashAttentionPattern",
    "RMSNormPattern",
    "SwiGLUPattern",
    "MatmulEpiloguePattern",
    "AddNormPattern",
    "GenericElementwiseFusionPass",
    "ScheduleSearchPattern",
    "ScheduleSearchPass",
]


# Strip pass-inserted namespaces ('fp16::matmul' -> 'matmul') so patterns
# still anchor after the fp16 program rewrite has run — the rewrite order
# (user-applied fp16 pass, then the Executor's default fusion pass) would
# otherwise silently defeat every substitution.
from ..framework.op_registry import base_op_type as _base_type


def _const_scalar(spec):
    """('const', v) -> python float if v is a scalar, else None."""
    if spec[0] != "const":
        return None
    v = spec[1]
    try:
        arr = np.asarray(v)
    except Exception:
        return None
    if arr.ndim == 0 or arr.size == 1:
        try:
            return float(arr.reshape(()))
        except (TypeError, ValueError):
            return None
    return None


def _is_causal_mask_const(spec, S):
    """('const', v) holding an additive causal mask over an [.., S, S]
    score matrix: 0 on/below the diagonal, <= -1e9 (or -inf) strictly
    above.  Leading broadcast dims of size 1 are allowed."""
    if spec[0] != "const":
        return False
    try:
        arr = np.asarray(spec[1], np.float32)
    except Exception:
        return False
    if arr.ndim < 2 or arr.shape[-1] != S or arr.shape[-2] != S:
        return False
    if any(d != 1 for d in arr.shape[:-2]):
        return False
    m = arr.reshape(S, S)
    lower = np.tril(np.ones((S, S), bool))
    if not np.all(m[lower] == 0):
        return False
    upper_vals = m[~lower]
    if upper_vals.size == 0:
        return True
    return bool(np.all(np.isneginf(upper_vals) | (upper_vals <= -1e9)))


class ProgramGraph:
    """Def-use view of a Program's global block (the pattern matcher's
    working set; reference pattern_match.h works over Operation/Value
    use-def chains the same way)."""

    def __init__(self, program, fetch_vids=()):
        self.program = program
        self.block = program.global_block()
        self.producer = {}
        self.consumers = defaultdict(list)
        for op in self.block.ops:
            for vid in op.out_vids:
                self.producer[vid] = op
            for vid in op.input_vids():
                self.consumers[vid].append(op)
        # vids visible outside the op list: fetches and state writes
        self.external = set(fetch_vids)
        self.external.update(program.writes.keys())
        self.external.update(program.writes.values())

    def single_use(self, vid) -> bool:
        return len(self.consumers[vid]) == 1 and vid not in self.external

    def shape(self, vid):
        var = self.program._var_by_vid.get(vid)
        return tuple(var._value.shape) if var is not None else None

    def def_op(self, vid, type_=None):
        op = self.producer.get(vid)
        if op is None:
            return None
        if type_ is not None and _base_type(op.type) != type_:
            return None
        return op

    def replace_op(self, old_op, new_op):
        """Swap old_op for new_op at the same position (same out vids →
        consumers unchanged; orphaned producers go to DCE)."""
        idx = self.block.ops.index(old_op)
        self.block.ops[idx] = new_op
        self.program.version += 1


class RewritePattern:
    """One source->result rule; anchored at a root op type (reference
    RewritePattern::match_and_rewrite)."""

    name = "base"
    root_type = None  # op.type this pattern anchors at

    def match_and_rewrite(self, op, graph: ProgramGraph) -> bool:
        raise NotImplementedError


class PatternRewritePass:
    """Greedy driver: apply patterns to fixpoint (bounded), reference
    ApplyPatternsGreedily.

    Every successful rewrite is use-def verified against the program's
    fetch frontier before it is accepted: a pattern that consumes an
    interior var whose producer other ops (or the fetch list) still need is
    ROLLED BACK and counted in `self.refused` — patterns cannot break
    def-before-use no matter what they match.  Under FLAGS_verify_programs
    the whole pass additionally runs between full verifier invocations."""

    name = "pattern_rewrite"

    def __init__(self, patterns, fetch_vids=(), max_iterations=8):
        self._patterns = list(patterns)
        self._fetch_vids = tuple(fetch_vids)
        self._max_iterations = max_iterations
        self.refused = 0

    def _rewrite_ok(self, program) -> bool:
        """Structural use-def + live-producer check of the post-rewrite
        program (registry/abstract tiers skipped: a rewrite cannot
        introduce those violation classes cheaply checkable here)."""
        from .verify import ProgramVerifier

        v = ProgramVerifier(check_registry=False, check_kwargs=False,
                            abstract_eval=False)
        bad = v._check_structure(program, self._fetch_vids)
        bad += v._check_live_producers(program, self._fetch_vids)
        return not bad

    def apply(self, program) -> int:
        from paddle_tpu._core import flags

        verify = flags.flag("FLAGS_verify_programs")
        if verify:
            from .verify import verify_program

            verify_program(program, self._fetch_vids)
        total = 0
        refused_sites: set = set()  # (pattern, op) identities already rolled back
        for _ in range(self._max_iterations):
            graph = ProgramGraph(program, self._fetch_vids)
            changed = 0
            for op in list(graph.block.ops):
                for pat in self._patterns:
                    if pat.root_type is not None and _base_type(op.type) != pat.root_type:
                        continue
                    if op not in graph.block.ops:
                        break  # already replaced this round
                    if (id(pat), id(op)) in refused_sites:
                        continue  # rolled back while the program was in
                        # this state; re-attempted only after another
                        # rewrite changes it (the op object survives
                        # rollbacks verbatim, so the identity is stable)
                    ops_before = list(graph.block.ops)
                    version_before = program.version
                    if pat.match_and_rewrite(op, graph):
                        if not self._rewrite_ok(program):
                            # refuse to fuse: restore the pre-rewrite op
                            # list; an interior matched var had consumers
                            # outside the matched set or sat in the fetch
                            # list
                            graph.block.ops[:] = ops_before
                            program.version = version_before
                            refused_sites.add((id(pat), id(op)))
                            self.refused += 1
                            from .verify import _COUNTERS

                            _COUNTERS["rewrites_refused"] += 1
                            graph = ProgramGraph(program, self._fetch_vids)
                            continue
                        changed += 1
                        graph = ProgramGraph(program, self._fetch_vids)
                        break
            total += changed
            if not changed:
                break
            # progress made: a refused site's outside consumers may have
            # been fused away, so it gets one fresh attempt per change round
            refused_sites.clear()
        if verify:
            from .verify import verify_program

            verify_program(program, self._fetch_vids)
        return total


def _make_op(type_, fn, var_vids, template_op, kwargs=None):
    """New Operator producing template_op's outputs from var inputs.
    kwargs are METADATA for later passes (the fn has them baked in)."""
    from paddle_tpu.static.program import Operator

    return Operator(
        type=type_,
        fn=fn,
        arg_spec=[("var", vid) for vid in var_vids],
        kwargs=dict(kwargs or {}),
        out_vids=list(template_op.out_vids),
        out_tree=template_op.out_tree,
    )


class FlashAttentionPattern(RewritePattern):
    """matmul(q,kᵀ) [→ scale] [→ +causal mask] → softmax → matmul(·,v)
    ⇒ Pallas flash attention (ops/flash_attention.py — online softmax,
    O(S) memory).

    Anchored at the second matmul.  Conservative: 4-D [B, N, S, D] layouts
    only; an additive CONST mask fuses only when it is recognizably the
    causal triangle (maps to the kernel's causal flag) — arbitrary masks
    have no kernel parameter and block fusion; unique consumers for every
    interior value; and S != D so the kᵀ layout is unambiguous."""

    name = "flash_attention_fuse"
    root_type = "matmul"

    def match_and_rewrite(self, op, graph):
        import jax.numpy as jnp

        # root: out = matmul(probs, v)
        if len(op.arg_spec) != 2 or any(s[0] != "var" for s in op.arg_spec):
            return False
        if op.kwargs.get("transpose_x") or op.kwargs.get("transpose_y"):
            # probs is square [B,N,S,S], so a transposed root is shape-
            # indistinguishable from the attention form but computes
            # probs^T @ v — never fusable
            return False
        probs_vid, v_vid = op.arg_spec[0][1], op.arg_spec[1][1]
        out_shape = graph.shape(op.out_vids[0]) if op.out_vids else None
        v_shape = graph.shape(v_vid)
        p_shape = graph.shape(probs_vid)
        if not (out_shape and v_shape and p_shape):
            return False
        if len(out_shape) != 4 or len(v_shape) != 4 or len(p_shape) != 4:
            return False
        B, N, S, D = out_shape
        if p_shape != (B, N, S, S) or v_shape != (B, N, S, D) or S == D:
            return False

        sm = graph.def_op(probs_vid, "softmax")
        if sm is None or not graph.single_use(probs_vid):
            return False
        if len(sm.arg_spec) != 1 or sm.arg_spec[0][0] != "var":
            return False
        # flash attention's online softmax is last-axis only
        sm_axis = sm.kwargs.get("axis", -1)
        if sm_axis not in (-1, 3):
            return False

        # optional scale / causal-mask-add chain between qk-matmul and
        # softmax (vanilla LLaMA writes scores/sqrt(d) + causal_mask)
        scale = None
        causal = False
        cur_vid = sm.arg_spec[0][1]
        if not graph.single_use(cur_vid):
            return False
        cur = graph.def_op(cur_vid)
        for _ in range(2):  # at most one scale + one mask-add, any order
            if cur is None:
                return False
            var_ins = [s for s in cur.arg_spec if s[0] == "var"]
            consts = [s for s in cur.arg_spec if s[0] == "const"]
            if (
                _base_type(cur.type) in ("divide", "multiply")
                and len(var_ins) == 1
                and len(consts) == 1
                and _const_scalar(consts[0]) is not None
                and scale is None
            ):
                c = _const_scalar(consts[0])
                scale = (1.0 / c) if _base_type(cur.type) == "divide" else c
            elif (
                _base_type(cur.type) == "add"
                and len(var_ins) == 1
                and len(consts) == 1
                and not causal
                and _is_causal_mask_const(consts[0], S)
            ):
                causal = True
            else:
                break
            cur_vid = var_ins[0][1]
            if not graph.single_use(cur_vid):
                return False
            cur = graph.def_op(cur_vid)
        qk = cur
        if qk is None or _base_type(qk.type) != "matmul":
            return False
        if len(qk.arg_spec) != 2 or any(s[0] != "var" for s in qk.arg_spec):
            return False
        if qk.kwargs.get("transpose_x"):
            return False  # q^T @ k is not the attention form
        q_vid, k_vid = qk.arg_spec[0][1], qk.arg_spec[1][1]
        q_shape, k_shape = graph.shape(q_vid), graph.shape(k_vid)
        if q_shape != (B, N, S, D):
            return False
        if k_shape == (B, N, S, D):
            k_transposed = True  # user wrote matmul(q, k, transpose_y=True)
        elif k_shape == (B, N, D, S):
            k_transposed = False
        else:
            return False
        # the recorded transpose_y must agree with the shape-inferred layout
        # (with S != D they can only disagree on malformed programs — keep
        # the cross-check so the kernel can never silently flip k)
        if bool(qk.kwargs.get("transpose_y")) != k_transposed:
            return False

        if scale is None:
            scale = 1.0  # plain matmul softmax: no 1/sqrt(d) in source

        # matched through fp16::-wrapped matmuls (fp16 pass ran first):
        # keep the low-dtype compute the user asked for — downcast fp32
        # inputs, run the kernel there, upcast the result back, exactly
        # Fp16ProgramRewrite's contract
        low = getattr(op, "fp16_low", None) or getattr(qk, "fp16_low", None)

        def fused(q, k, v):
            from paddle_tpu.ops import flash_attention

            downcast = False
            if low is not None:
                ins = []
                for t in (q, k, v):
                    if t.dtype == jnp.float32:
                        ins.append(t.astype(low))
                        downcast = True
                    else:
                        ins.append(t)
                q, k, v = ins
            if not k_transposed:
                k = jnp.swapaxes(k, -1, -2)
            qt = jnp.swapaxes(q, 1, 2)  # [B,N,S,D] -> kernel's [B,S,N,D]
            kt = jnp.swapaxes(k, 1, 2)
            vt = jnp.swapaxes(v, 1, 2)
            o = flash_attention(qt, kt, vt, scale=scale, causal=causal)
            if downcast and o.dtype == low:
                o = o.astype(jnp.float32)
            return jnp.swapaxes(o, 1, 2)

        new_type = "flash_attention" if low is None else "fp16::flash_attention"
        graph.replace_op(op, _make_op(new_type, fused, [q_vid, k_vid, v_vid], op))
        return True


class RMSNormPattern(RewritePattern):
    """x·rsqrt(mean(x²)+ε)·w  ⇒  Pallas fused_rms_norm (ops/fused_norm.py).

    Anchored at the final weight multiply; accepts square(x) or
    multiply(x, x) for the square."""

    name = "rms_norm_fuse"
    root_type = "multiply"

    def _match_square_mean(self, vid, graph, x_vid):
        mean = graph.def_op(vid, "mean")
        if mean is None or not graph.single_use(vid):
            return False
        if len(mean.arg_spec) != 1 or mean.arg_spec[0][0] != "var":
            return False
        sq_vid = mean.arg_spec[0][1]
        if not graph.single_use(sq_vid):
            return False
        sq = graph.def_op(sq_vid)
        if sq is None:
            return False
        if _base_type(sq.type) == "square":
            return sq.arg_spec[0] == ("var", x_vid)
        if _base_type(sq.type) in ("multiply", "pow"):
            vids = [s[1] for s in sq.arg_spec if s[0] == "var"]
            if _base_type(sq.type) == "multiply":
                return vids == [x_vid, x_vid]
            c = next((_const_scalar(s) for s in sq.arg_spec if s[0] == "const"), None)
            return vids == [x_vid] and c == 2.0
        return False

    def match_and_rewrite(self, op, graph):
        # root: out = multiply(normed, w)   (w: 1-D over last axis)
        if len(op.arg_spec) != 2 or any(s[0] != "var" for s in op.arg_spec):
            return False
        normed_vid, w_vid = op.arg_spec[0][1], op.arg_spec[1][1]
        w_shape = graph.shape(w_vid)
        out_shape = graph.shape(op.out_vids[0]) if op.out_vids else None
        if not w_shape or not out_shape or len(w_shape) != 1 or w_shape[0] != out_shape[-1]:
            return False
        if not graph.single_use(normed_vid):
            return False
        # normed = multiply(x, rsqrt(mean(x*x) + eps))
        mul = graph.def_op(normed_vid, "multiply")
        if mul is None or len(mul.arg_spec) != 2 or any(s[0] != "var" for s in mul.arg_spec):
            return False
        x_vid, r_vid = mul.arg_spec[0][1], mul.arg_spec[1][1]
        if graph.shape(x_vid) != out_shape:
            x_vid, r_vid = r_vid, x_vid
        if graph.shape(x_vid) != out_shape:
            return False
        if not graph.single_use(r_vid):
            return False
        rs = graph.def_op(r_vid, "rsqrt")
        if rs is None or len(rs.arg_spec) != 1 or rs.arg_spec[0][0] != "var":
            return False
        add_vid = rs.arg_spec[0][1]
        if not graph.single_use(add_vid):
            return False
        add = graph.def_op(add_vid, "add")
        if add is None:
            return False
        eps = next((_const_scalar(s) for s in add.arg_spec if s[0] == "const"), None)
        var_ins = [s[1] for s in add.arg_spec if s[0] == "var"]
        if eps is None or len(var_ins) != 1:
            return False
        if not self._match_square_mean(var_ins[0], graph, x_vid):
            return False
        # mean must reduce the last axis with keepdim
        mean_shape = graph.shape(var_ins[0])
        if mean_shape is None or mean_shape != out_shape[:-1] + (1,):
            return False

        def fused(x, w):
            from paddle_tpu.ops import fused_rms_norm

            return fused_rms_norm(x, w, epsilon=eps)

        graph.replace_op(op, _make_op("fused_rms_norm", fused, [x_vid, w_vid],
                                      op, kwargs={"epsilon": eps}))
        return True


class SwiGLUPattern(RewritePattern):
    """silu(g)·u  ⇒  Pallas swiglu (ops/swiglu.py)."""

    name = "swiglu_fuse"
    root_type = "multiply"

    def match_and_rewrite(self, op, graph):
        if len(op.arg_spec) != 2 or any(s[0] != "var" for s in op.arg_spec):
            return False
        a_vid, b_vid = op.arg_spec[0][1], op.arg_spec[1][1]
        for gate_vid, up_vid in ((a_vid, b_vid), (b_vid, a_vid)):
            silu = graph.def_op(gate_vid, "silu")
            if silu is None or not graph.single_use(gate_vid):
                continue
            if len(silu.arg_spec) != 1 or silu.arg_spec[0][0] != "var":
                continue
            g_vid = silu.arg_spec[0][1]
            if graph.shape(g_vid) != graph.shape(up_vid):
                continue

            def fused(g, u):
                from paddle_tpu.ops import swiglu

                return swiglu(g, u)

            graph.replace_op(op, _make_op("swiglu", fused, [g_vid, up_vid], op))
            return True
        return False


def _entry_shape(graph, entry):
    if entry[0] == "var":
        return graph.shape(entry[1])
    import numpy as _np

    try:
        return tuple(_np.shape(entry[1]))
    except Exception:
        return None


def _mixed(entries):
    """(var_vids, rebuild): rebuild(var_vals) -> full positional values with
    const entries baked in (weights captured as concrete tensors record as
    consts, not vars)."""
    var_vids = [e[1] for e in entries if e[0] == "var"]

    def rebuild(var_vals):
        it = iter(var_vals)
        return [next(it) if e[0] == "var" else e[1] for e in entries]

    return var_vids, rebuild


class MatmulEpiloguePattern(RewritePattern):
    """act(linear(x, w[, b]))  ⇒  Pallas matmul_bias_act
    (ops/matmul_epilogue.py — the epilogue runs on the f32 accumulator in
    VMEM; the pre-activation never round-trips HBM).

    Anchored at the activation (tanh-gelu/silu/relu) whose single input is
    the single-use output of a linear/matmul op."""

    name = "matmul_epilogue_fuse"
    root_type = None  # three root types; filtered in match
    _ROOTS = {"gelu", "silu", "relu"}

    def match_and_rewrite(self, op, graph):
        base = _base_type(op.type)
        if base not in self._ROOTS:
            return False
        if len(op.arg_spec) != 1 or op.arg_spec[0][0] != "var":
            return False
        if base == "silu" and op.out_vids:
            # silu feeding a multiply is SwiGLUPattern's subgraph (the
            # LLaMA-canonical kernel with analytic backward): stand down
            cons = graph.consumers.get(op.out_vids[0], [])
            if any(_base_type(c.type) == "multiply" for c in cons):
                return False
        pre_vid = op.arg_spec[0][1]
        if not graph.single_use(pre_vid):
            return False
        mm = graph.def_op(pre_vid)
        if mm is None or _base_type(mm.type) not in ("linear", "matmul"):
            return False
        if mm.type.startswith("wq::"):
            # weight-only-quantized op: different arg contract (int8 q +
            # scale appended) — fusing would add the scale as a bias
            return False
        if mm.kwargs.get("transpose_x") or mm.kwargs.get("transpose_y"):
            # paddle.matmul(..., transpose_y=True) computes x @ w.T; the
            # fused kernel has no transpose contract — for square weights
            # the shape check below cannot catch it, so bail out
            return False
        if len(mm.arg_spec) not in (2, 3):
            return False
        x_entry, w_entry = mm.arg_spec[0], mm.arg_spec[1]
        b_entry = mm.arg_spec[2] if len(mm.arg_spec) == 3 else None
        if x_entry[0] != "var":  # activations are always program values
            return False
        w_shape = _entry_shape(graph, w_entry)
        x_shape = graph.shape(x_entry[1])
        if not w_shape or not x_shape or len(w_shape) != 2 or x_shape[-1] != w_shape[0]:
            return False
        # defense in depth: the weight must be a FLOAT tensor (an int8
        # quantized weight means a dequant contract this kernel lacks)
        if w_entry[0] == "var":
            wvar = graph.program._var_by_vid.get(w_entry[1])
            import jax.numpy as _jnp

            if wvar is None or not _jnp.issubdtype(wvar._value.dtype, _jnp.inexact):
                return False
        if b_entry is not None and _entry_shape(graph, b_entry) != (w_shape[1],):
            return False
        act = base
        if base == "gelu" and op.kwargs.get("approximate"):
            act = "gelu_tanh"
        from paddle_tpu.ops.matmul_epilogue import FUSIBLE_ACTS

        if act not in FUSIBLE_ACTS:
            # leave linear+act to XLA rather than emit a kernel the chip
            # refuses (exact-erf GELU)
            return False

        entries = [x_entry, w_entry] + ([b_entry] if b_entry is not None else [])
        var_vids, rebuild = _mixed(entries)
        has_bias = b_entry is not None
        # keep the fp16 rewrite's low-dtype compute (see FlashAttentionPattern:
        # replacing an fp16:: op with an fp32 kernel would silently revert
        # the precision choice)
        low = getattr(mm, "fp16_low", None)

        def fused(*var_vals, act=act, has_bias=has_bias, rebuild=rebuild, low=low):
            import jax.numpy as _jnp

            from paddle_tpu.ops import matmul_bias_act

            full = rebuild(var_vals)
            x, w = full[0], full[1]
            b = full[2] if has_bias else None
            downcast = False
            if low is not None and x.dtype == _jnp.float32:
                x, downcast = x.astype(low), True
                w = w.astype(low) if w.dtype == _jnp.float32 else w
                if b is not None and b.dtype == _jnp.float32:
                    b = b.astype(low)
            out = matmul_bias_act(x, w, b, act)
            return out.astype(_jnp.float32) if downcast else out

        new_type = ("fp16::" if low is not None else "") + "matmul_epilogue"
        new_op = _make_op(new_type, fused, var_vids, op)
        if low is not None:
            new_op.fp16_low = low
        graph.replace_op(op, new_op)
        return True


class AddNormPattern(RewritePattern):
    """norm(x + residual)  ⇒  fused residual-add norm (ops/fused_norm.py
    residual= contract) — the transformer residual-stream chain.

    Anchors on fused_rms_norm (produced by RMSNormPattern, so this fires on
    the same pass's fixpoint iteration), raw rms_norm, or layer_norm, whose
    input comes from an add of two same-shape tensors.  The fused op emits
    BOTH the normed output and the sum (the residual stream usually feeds
    the next block too), replacing the add at its own position so every
    consumer of the sum still reads a defined value."""

    name = "add_norm_fuse"
    root_type = None
    _ROOTS = {"fused_rms_norm", "rms_norm", "layer_norm"}

    def match_and_rewrite(self, op, graph):
        import jax as _jax

        base = _base_type(op.type)
        if base not in self._ROOTS:
            return False
        if op.arg_spec[0][0] != "var":
            return False
        if base == "layer_norm":
            if len(op.arg_spec) != 3:  # x, weight, bias (elementwise affine)
                return False
            x_vid = op.arg_spec[0][1]
            w_entry, b_entry = op.arg_spec[1], op.arg_spec[2]
        else:
            if len(op.arg_spec) != 2:  # x, weight
                return False
            x_vid = op.arg_spec[0][1]
            w_entry, b_entry = op.arg_spec[1], None
        add = graph.def_op(x_vid, "add")
        if add is None:
            return False
        if len(add.arg_spec) != 2 or any(s[0] != "var" for s in add.arg_spec):
            return False
        a_vid, r_vid = add.arg_spec[0][1], add.arg_spec[1][1]
        if graph.shape(a_vid) != graph.shape(r_vid):
            return False
        eps = op.kwargs.get("epsilon", op.kwargs.get("eps"))
        if eps is None:
            return False  # can't recover the recorded epsilon: don't fuse

        # the fused op replaces the ADD at its position: every other VAR
        # input (norm weight/bias) must already be defined there
        block = graph.block
        add_idx = block.ops.index(add)

        def _defined_before(entry):
            if entry is None or entry[0] != "var":
                return True
            prod = graph.producer.get(entry[1])
            return prod is None or block.ops.index(prod) < add_idx

        if not (_defined_before(w_entry) and _defined_before(b_entry)):
            return False

        from paddle_tpu.static.program import Operator

        entries = [("var", a_vid), ("var", r_vid), w_entry] + (
            [b_entry] if b_entry is not None else [])
        var_vids, rebuild = _mixed(entries)
        is_ln = base == "layer_norm"

        def fused(*var_vals, eps=eps, is_ln=is_ln, rebuild=rebuild):
            from paddle_tpu.ops import fused_layer_norm, fused_rms_norm

            full = rebuild(var_vals)
            if is_ln:
                out, s = fused_layer_norm(full[0], full[2], full[3],
                                          residual=full[1], epsilon=eps)
            else:
                out, s = fused_rms_norm(full[0], full[2], residual=full[1],
                                        epsilon=eps)
            return s, out

        new_op = Operator(
            "add_" + ("layer_norm" if is_ln else "rms_norm"),
            fused,
            [("var", v) for v in var_vids],
            {"epsilon": eps},
            [add.out_vids[0], op.out_vids[0]],
            _jax.tree_util.tree_structure((0, 0)),
        )
        block.ops[add_idx] = new_op
        block.ops.remove(op)
        graph.program.version += 1
        return True


class PallasFusionPass(PatternRewritePass):
    """The default Pallas-substitution pipeline (SURVEY §7's CINN analog)."""

    name = "pallas_fusion"

    def __init__(self, fetch_vids=()):
        super().__init__(
            [FlashAttentionPattern(), RMSNormPattern(), SwiGLUPattern(),
             MatmulEpiloguePattern(), AddNormPattern()],
            fetch_vids=fetch_vids,
        )


# ---------------------------------------------------------------------------
# generic elementwise-chain fusion (the CINN auto-discovery role)


_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "pow",
    "exp", "log", "tanh", "sigmoid", "relu", "gelu", "silu", "abs", "neg",
    "sqrt", "rsqrt", "square", "floor", "ceil", "round", "clip", "cast",
    "scale", "leaky_relu", "elu", "hardtanh", "softplus", "mish",
    "hardswish", "hardsigmoid", "erf", "sin", "cos", "amp_cast",
    "fake_quant",
}


class GenericElementwiseFusionPass:
    """Discover maximal chains of same-shape elementwise ops and generate
    ONE Pallas VPU kernel per chain (reference: CINN's fusible-subgraph
    discovery + codegen, paddle/cinn/hlir/framework/op_lowering_impl.cc —
    the mechanism, not a fixed pattern set).

    A chain is a maximal straight line of whitelisted ops where every link
    is single-use and every participating tensor has the output's shape
    (scalar/python constants are already baked inside the recorded op fns).
    The generated kernel replays the recorded op fns over VMEM blocks, so
    an N-op bandwidth-bound chain makes one HBM round trip instead of N.
    Opt-in (`apply_pass(prog, "generic_elementwise_fusion")` or the
    save_inference_model passes= list): XLA fuses most of these itself —
    this pass exists for tile control and for chains fusion boundaries
    would otherwise split.
    """

    name = "generic_elementwise_fusion"

    def __init__(self, fetch_vids=(), min_chain=3):
        self._fetch_vids = tuple(fetch_vids)
        self._min_chain = int(min_chain)

    # ------------------------------------------------------------ discovery
    def _eligible(self, op, graph, shape):
        if _base_type(op.type) not in _ELEMENTWISE:
            return False
        if not op.out_vids or len(op.out_vids) != 1:
            return False
        if graph.shape(op.out_vids[0]) != shape:
            return False
        for s in op.arg_spec:
            if s[0] == "var" and graph.shape(s[1]) not in (shape, None):
                return False
            if s[0] == "var" and graph.shape(s[1]) is None:
                return False
        return True

    def _collect_chain(self, root, graph):
        """Walk producers from `root` collecting the fusible upstream set
        (a tree of single-use elementwise producers), returned in
        execution order."""
        shape = graph.shape(root.out_vids[0])
        block_ops = graph.block.ops
        chain = {id(root): root}
        frontier = [root]
        while frontier:
            op = frontier.pop()
            for s in op.arg_spec:
                if s[0] != "var":
                    continue
                prod = graph.def_op(s[1])
                if (prod is None or id(prod) in chain
                        or not graph.single_use(s[1])
                        or not self._eligible(prod, graph, shape)):
                    continue
                chain[id(prod)] = prod
                frontier.append(prod)
        ordered = [op for op in block_ops if id(op) in chain]
        return ordered

    # -------------------------------------------------------------- codegen
    def _build_kernel(self, ordered, ext_vids, final_vid, shape, dtype):
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        from paddle_tpu.ops import _pl_utils
        from paddle_tpu.ops._pl_utils import imap

        def chain_body(*vals):
            env = dict(zip(ext_vids, vals))
            for op in ordered:
                var_vals = [env[s[1]] for s in op.arg_spec if s[0] == "var"]
                out = op.fn(*var_vals)
                flat = jax.tree_util.tree_leaves(out)
                for vid, v in zip(op.out_vids, flat):
                    env[vid] = v
            return env[final_vid]

        n_in = len(ext_vids)

        def fused(*vals):
            flat = [v.reshape(-1, shape[-1]) if len(shape) > 1 else
                    v.reshape(1, -1) for v in vals]
            rows, cols = flat[0].shape
            # tile like the swiglu kernel: bounded VMEM, 128-multiple lanes
            from paddle_tpu.ops import autotune as _at

            tuned = _at.lookup("vpu_chain", {
                "rows": rows, "cols": cols, "n_ops": len(ordered),
                "dtype": jnp.dtype(dtype).name})
            br = int(tuned["rows_block"]) if tuned else min(256, rows)
            bc = int(tuned["cols_block"]) if tuned else cols
            if rows % br:
                br = rows
            if cols % bc:
                bc = cols
                for cand in (2048, 1024, 512, 256, 128):
                    if cols % cand == 0:
                        bc = cand
                        break
            # Pre-trace the chain at BLOCK shape and bake closure constants
            # as numpy literals — Pallas kernels may not capture traced
            # jax arrays (scalar consts recorded inside op fns are such).
            block_avals = [jax.ShapeDtypeStruct((br, bc), f.dtype)
                           for f in flat]
            closed = jax.make_jaxpr(chain_body)(*block_avals)
            np_consts = [np.asarray(c) for c in closed.consts]

            def kernel(*refs):
                ins, o_ref = refs[:n_in], refs[n_in]
                out = jax.core.eval_jaxpr(
                    closed.jaxpr, np_consts, *(r[:] for r in ins))[0]
                o_ref[:] = out.astype(o_ref.dtype)

            out = pl.pallas_call(
                kernel,
                grid=(rows // br, cols // bc),
                in_specs=[pl.BlockSpec((br, bc), imap(lambda i, j: (i, j)))
                          for _ in flat],
                out_specs=pl.BlockSpec((br, bc), imap(lambda i, j: (i, j))),
                out_shape=jax.ShapeDtypeStruct((rows, cols), dtype),
                interpret=_pl_utils.interpret(),
            )(*flat)
            return out.reshape(shape)

        return fused

    # ----------------------------------------------------------------- apply
    def apply(self, program) -> int:
        import jax

        n = 0
        while True:
            graph = ProgramGraph(program, self._fetch_vids)
            block = graph.block
            done = False
            for root in reversed(list(block.ops)):
                shape = graph.shape(root.out_vids[0]) if root.out_vids else None
                if shape is None or len(shape) < 1:
                    continue
                if not self._eligible(root, graph, shape):
                    continue
                # root must be the DOWNSTREAM end: its single output is not
                # consumed by another fusible op (that op would be the root)
                out_vid = root.out_vids[0]
                cons = graph.consumers.get(out_vid, [])
                if (len(cons) == 1 and graph.single_use(out_vid)
                        and self._eligible(cons[0], graph, shape)):
                    continue
                ordered = self._collect_chain(root, graph)
                if len(ordered) < self._min_chain:
                    continue
                in_chain_out = {vid for op in ordered for vid in op.out_vids}
                ext_vids = []
                for op in ordered:
                    for s in op.arg_spec:
                        if s[0] == "var" and s[1] not in in_chain_out and s[1] not in ext_vids:
                            ext_vids.append(s[1])
                var = program._var_by_vid[out_vid]
                dtype = var._value.dtype
                fused = self._build_kernel(
                    ordered, list(ext_vids), out_vid, shape, dtype)
                new_op = _make_op(
                    f"vpu_chain_{len(ordered)}", fused, ext_vids, root)
                idx = block.ops.index(root)
                block.ops[idx] = new_op
                for op in ordered:
                    if op is not root and op in block.ops:
                        block.ops.remove(op)
                program.version += 1
                n += 1
                done = True
                break
            if not done:
                return n


# ---------------------------------------------------------------------------
# schedule-searched fusion (the discovery tier beyond elementwise chains)


class ScheduleSearchPattern(RewritePattern):
    """Discover a reduction-/matmul-rooted subgraph anchored at `op` (the
    downstream end), hand it to the ScheduleSearcher (static/
    schedule_search.py: enumerate tilings → roofline prune → VMEM prune →
    measure → measured-win gate → per-device cache), and substitute ONE
    generated Pallas kernel when the searched schedule beat XLA.

    The classes hunted are the fusion misses named patterns skip: matmul→
    bias→act→reduce tails, softmax-adjacent reduction chains (discovery is
    DAG-shaped — manual softmax's exp feeding both sum and divide fuses as
    one subgraph).  Fetch-frontier/write-visible interior values are
    refused by the PatternRewritePass use-def rollback (PR 4's machinery,
    counted in `.refused`); side-effect ops and collectives are never
    crossed (op_registry.side_effect_op_types)."""

    name = "schedule_search"
    root_type = None

    def __init__(self, searcher=None):
        self._searcher = searcher
        self._seen: set = set()  # (sig, root identity) already searched

    def match_and_rewrite(self, op, graph):
        from . import schedule_search as ss

        spec = ss.match_subgraph(op, graph)
        if spec is None:
            return False
        tag = (spec.sig, id(spec.root))
        if tag in self._seen:
            return False  # searched this site already (disabled/rolled back)
        self._seen.add(tag)
        searcher = self._searcher
        if searcher is None:
            searcher = self._searcher = ss.ScheduleSearcher()
        decision = searcher.search(spec)
        if not decision.accepted:
            return False
        try:
            fused = ss.build_kernel(spec, decision.config)
        except Exception:
            return False  # cached config no longer buildable here
        new_op = _make_op(
            f"sched_chain_{len(spec.ops)}", fused,
            [e.vid for e in spec.ext], spec.root,
            kwargs={"kind": spec.kind, "schedule": dict(decision.config)})
        graph.replace_op(spec.root, new_op)
        block = graph.block
        for o in spec.ops:
            if o is not spec.root and o in block.ops:
                block.ops.remove(o)
        return True


class ScheduleSearchPass(PatternRewritePass):
    """Schedule-searched Pallas substitution over discovered subgraphs
    (ROADMAP item 2; docs/SCHEDULE_SEARCH.md).  Runs after PallasFusionPass
    in the Executor pipeline (FLAGS_schedule_search) so the named patterns
    take their subgraphs first and fused ops act as chain breakers here."""

    name = "schedule_search"

    def __init__(self, fetch_vids=(), searcher=None):
        super().__init__([ScheduleSearchPattern(searcher)],
                         fetch_vids=fetch_vids)
