"""Pattern-rewrite infra + Pallas fusion pass (VERDICT r2 items 4+5).

Reference: paddle/pir/pattern_rewrite/pattern_match.h (greedy rewrite
driver) + paddle/fluid/pir/transforms/build_cinn_pass.cc (fusible-subgraph
substitution).  Here: a captured vanilla-jnp attention / rms-norm / swiglu
subgraph gets the Pallas kernel substituted, numerics preserved, via the
Executor's default pipeline.
"""

import numpy as np
import pytest
import jax

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
import paddle_tpu.static as static
from paddle_tpu.static.program import Program, program_guard
from paddle_tpu.static.rewrite import PallasFusionPass


def _feed(prog, name, shape, dtype=np.float32):
    return prog.add_feed(prog.new_var(jax.ShapeDtypeStruct(shape, dtype), name))


def _capture_vanilla(B=2, N=4, S=128, D=16, H=32, F_=64):
    """One program holding vanilla attention + rms-norm + swiglu."""
    prog = Program()
    with program_guard(prog):
        q = _feed(prog, "q", (B, N, S, D))
        k = _feed(prog, "k", (B, N, S, D))
        v = _feed(prog, "v", (B, N, S, D))
        x = _feed(prog, "x", (B, S, H))
        w = _feed(prog, "w", (H,))
        g = _feed(prog, "g", (B, S, F_))
        u = _feed(prog, "u", (B, S, F_))
        scores = paddle.matmul(q, k, transpose_y=True) / (D ** 0.5)
        probs = F.softmax(scores, axis=-1)
        attn = paddle.matmul(probs, v)
        var = (x * x).mean(axis=-1, keepdim=True)
        normed = x * paddle.rsqrt(var + 1e-6) * w
        sw = F.silu(g) * u
    return prog, (attn, normed, sw)


def _optypes(prog):
    return [op.type for op in prog.global_block().ops]


def test_fusion_pass_substitutes_all_three_patterns():
    prog, (attn, normed, sw) = _capture_vanilla()
    n = PallasFusionPass([attn._vid, normed._vid, sw._vid]).apply(prog)
    assert n == 3
    types = _optypes(prog)
    assert "flash_attention" in types
    assert "fused_rms_norm" in types
    assert "swiglu" in types
    assert "softmax" not in [
        op.type
        for op in prog.global_block().ops
        if any(vid in (attn._vid,) for vid in op.out_vids)
    ]


def test_fusion_preserves_numerics_via_executor():
    rng = np.random.default_rng(0)
    B, N, S, D, H, F_ = 2, 4, 128, 16, 32, 64
    feed = {
        "q": rng.normal(size=(B, N, S, D)).astype(np.float32),
        "k": rng.normal(size=(B, N, S, D)).astype(np.float32),
        "v": rng.normal(size=(B, N, S, D)).astype(np.float32),
        "x": rng.normal(size=(B, S, H)).astype(np.float32),
        "w": rng.normal(size=(H,)).astype(np.float32),
        "g": rng.normal(size=(B, S, F_)).astype(np.float32),
        "u": rng.normal(size=(B, S, F_)).astype(np.float32),
    }

    paddle.set_flags({"FLAGS_use_pallas_fusion": False})
    try:
        prog, fetches = _capture_vanilla()
        exe = static.Executor()
        ref = exe.run(prog, feed=feed, fetch_list=list(fetches))
        assert "flash_attention" not in _optypes(prog)

        paddle.set_flags({"FLAGS_use_pallas_fusion": True})
        prog2, fetches2 = _capture_vanilla()
        exe2 = static.Executor()
        got = exe2.run(prog2, feed=feed, fetch_list=list(fetches2))
        assert "flash_attention" in _optypes(prog2)  # pass ran inside run()
        assert "fused_rms_norm" in _optypes(prog2)
        assert "swiglu" in _optypes(prog2)
    finally:
        paddle.set_flags({"FLAGS_use_pallas_fusion": True})

    for r, g_ in zip(ref, got):
        np.testing.assert_allclose(r, g_, rtol=2e-3, atol=2e-3)


def test_fusion_bails_when_intermediate_is_fetched():
    """Fetching attention probs keeps the pattern unfused (externally
    visible intermediates make substitution unsound)."""
    prog = Program()
    with program_guard(prog):
        q = _feed(prog, "q", (2, 4, 128, 16))
        k = _feed(prog, "k", (2, 4, 128, 16))
        v = _feed(prog, "v", (2, 4, 128, 16))
        scores = paddle.matmul(q, k, transpose_y=True) / 4.0
        probs = F.softmax(scores, axis=-1)
        out = paddle.matmul(probs, v)
    n = PallasFusionPass([out._vid, probs._vid]).apply(prog)
    assert n == 0
    assert "flash_attention" not in _optypes(prog)


def test_fusion_handles_untransposed_k_layout():
    prog = Program()
    with program_guard(prog):
        q = _feed(prog, "q", (2, 2, 128, 16))
        kT = _feed(prog, "kT", (2, 2, 16, 128))  # [B,N,D,S]: plain matmul
        v = _feed(prog, "v", (2, 2, 128, 16))
        probs = F.softmax(paddle.matmul(q, kT) * (1 / 4.0), axis=-1)
        out = paddle.matmul(probs, v)
    n = PallasFusionPass([out._vid]).apply(prog)
    assert n == 1

    rng = np.random.default_rng(1)
    qv = rng.normal(size=(2, 2, 128, 16)).astype(np.float32)
    kv = rng.normal(size=(2, 2, 16, 128)).astype(np.float32)
    vv = rng.normal(size=(2, 2, 128, 16)).astype(np.float32)
    exe = static.Executor()
    got = exe.run(prog, feed={"q": qv, "kT": kv, "v": vv}, fetch_list=[out])[0]
    s = qv @ kv / 4.0
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    np.testing.assert_allclose(got, p @ vv, rtol=2e-3, atol=2e-3)


class VanillaLlamaBlock(paddle.nn.Layer):
    """A LLaMA decoder block written in VANILLA paddle ops only — no calls
    into paddle_tpu.ops — so fusion must come from the rewrite pass."""

    def __init__(self, hidden, heads, inter):
        super().__init__()
        self.h, self.n = hidden, heads
        self.d = hidden // heads
        self.wq = paddle.nn.Linear(hidden, hidden, bias_attr=False)
        self.wk = paddle.nn.Linear(hidden, hidden, bias_attr=False)
        self.wv = paddle.nn.Linear(hidden, hidden, bias_attr=False)
        self.wo = paddle.nn.Linear(hidden, hidden, bias_attr=False)
        self.gate = paddle.nn.Linear(hidden, inter, bias_attr=False)
        self.up = paddle.nn.Linear(hidden, inter, bias_attr=False)
        self.down = paddle.nn.Linear(inter, hidden, bias_attr=False)
        self.norm_w1 = paddle.create_parameter([hidden], "float32")
        self.norm_w2 = paddle.create_parameter([hidden], "float32")

    def _rms(self, x, w):
        var = (x * x).mean(axis=-1, keepdim=True)
        return x * paddle.rsqrt(var + 1e-6) * w

    def forward(self, x):
        B, S, _ = x.shape
        h = self._rms(x, self.norm_w1)
        q = self.wq(h).reshape([B, S, self.n, self.d]).transpose([0, 2, 1, 3])
        k = self.wk(h).reshape([B, S, self.n, self.d]).transpose([0, 2, 1, 3])
        v = self.wv(h).reshape([B, S, self.n, self.d]).transpose([0, 2, 1, 3])
        scores = paddle.matmul(q, k, transpose_y=True) / (self.d ** 0.5)
        probs = F.softmax(scores, axis=-1)
        o = paddle.matmul(probs, v).transpose([0, 2, 1, 3]).reshape([B, S, self.h])
        x = x + self.wo(o)
        h2 = self._rms(x, self.norm_w2)
        return x + self.down(F.silu(self.gate(h2)) * self.up(h2))


def test_vanilla_llama_block_gets_flash_substituted():
    """The VERDICT's done-criterion: a vanilla-jnp LLaMA block captured as
    a Program shows flash-attention substitution and matches numerics."""
    paddle.seed(5)
    blk = VanillaLlamaBlock(hidden=64, heads=4, inter=128)
    x_np = np.random.default_rng(2).normal(size=(2, 128, 64)).astype(np.float32)

    with paddle.no_grad():
        ref = np.asarray(blk(paddle.to_tensor(x_np))._value)

    prog = Program()
    with program_guard(prog):
        xv = _feed(prog, "x", (2, 128, 64))
        out = blk(xv)
    exe = static.Executor()
    got = exe.run(prog, feed={"x": x_np}, fetch_list=[out])[0]
    types = _optypes(prog)
    assert "flash_attention" in types
    # the residual-stream norm upgrades further to add_rms_norm
    assert types.count("fused_rms_norm") + types.count("add_rms_norm") == 2
    assert "swiglu" in types
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)


def test_causal_mask_attention_fuses_with_causal_flag():
    """Vanilla causal attention — scores/sqrt(d) + triangular -inf mask —
    fuses to flash_attention(causal=True) and matches the unfused numerics."""
    B, N, S, D = 2, 2, 128, 16
    mask = np.triu(np.full((S, S), -1e9, np.float32), k=1)[None, None]

    prog = Program()
    with program_guard(prog):
        q = _feed(prog, "q", (B, N, S, D))
        k = _feed(prog, "k", (B, N, S, D))
        v = _feed(prog, "v", (B, N, S, D))
        scores = paddle.matmul(q, k, transpose_y=True) / (D ** 0.5)
        scores = scores + paddle.to_tensor(mask)
        probs = F.softmax(scores, axis=-1)
        out = paddle.matmul(probs, v)
    from paddle_tpu.static.rewrite import PallasFusionPass

    n = PallasFusionPass([out._vid]).apply(prog)
    assert n == 1
    assert "flash_attention" in _optypes(prog)

    rng = np.random.default_rng(4)
    qv = rng.normal(size=(B, N, S, D)).astype(np.float32)
    kv = rng.normal(size=(B, N, S, D)).astype(np.float32)
    vv = rng.normal(size=(B, N, S, D)).astype(np.float32)
    exe = static.Executor()
    got = exe.run(prog, feed={"q": qv, "k": kv, "v": vv}, fetch_list=[out])[0]
    s = qv @ np.swapaxes(kv, -1, -2) / np.sqrt(D) + mask
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    np.testing.assert_allclose(got, p @ vv, rtol=2e-3, atol=2e-3)


def test_non_causal_arbitrary_mask_blocks_fusion():
    """An arbitrary additive mask has no kernel parameter: must NOT fuse."""
    B, N, S, D = 1, 2, 128, 16
    mask = np.random.default_rng(0).normal(size=(1, 1, S, S)).astype(np.float32)

    prog = Program()
    with program_guard(prog):
        q = _feed(prog, "q", (B, N, S, D))
        k = _feed(prog, "k", (B, N, S, D))
        v = _feed(prog, "v", (B, N, S, D))
        scores = paddle.matmul(q, k, transpose_y=True) + paddle.to_tensor(mask)
        out = paddle.matmul(F.softmax(scores, axis=-1), v)
    from paddle_tpu.static.rewrite import PallasFusionPass

    n = PallasFusionPass([out._vid]).apply(prog)
    assert n == 0
    assert "flash_attention" not in _optypes(prog)


def test_fp16_rewrite_then_fusion_still_substitutes_in_low_dtype():
    """ADVICE r3: the fp16 program rewrite renames matmul -> fp16::matmul;
    the fusion pass must still anchor, and the substituted flash kernel must
    keep the low-dtype compute the user asked for (fp16::flash_attention)."""
    from paddle_tpu.static.passes import apply_pass

    rng = np.random.default_rng(1)
    B, N, S, D, H, F_ = 2, 4, 128, 16, 32, 64
    feed = {
        "q": rng.normal(size=(B, N, S, D)).astype(np.float32),
        "k": rng.normal(size=(B, N, S, D)).astype(np.float32),
        "v": rng.normal(size=(B, N, S, D)).astype(np.float32),
        "x": rng.normal(size=(B, S, H)).astype(np.float32),
        "w": rng.normal(size=(H,)).astype(np.float32),
        "g": rng.normal(size=(B, S, F_)).astype(np.float32),
        "u": rng.normal(size=(B, S, F_)).astype(np.float32),
    }
    prog, fetches = _capture_vanilla()
    ref_exe = static.Executor()
    paddle.set_flags({"FLAGS_use_pallas_fusion": False})
    try:
        ref = ref_exe.run(prog, feed=feed, fetch_list=list(fetches))
    finally:
        paddle.set_flags({"FLAGS_use_pallas_fusion": True})

    prog2, fetches2 = _capture_vanilla()
    n16 = apply_pass(prog2, "auto_parallel_fp16", dtype="bfloat16")
    assert n16 >= 2  # both attention matmuls rewritten
    assert "fp16::matmul" in _optypes(prog2)
    n = PallasFusionPass([f._vid for f in fetches2]).apply(prog2)
    assert n == 3, f"fusion defeated after fp16 rewrite: {_optypes(prog2)}"
    assert "fp16::flash_attention" in _optypes(prog2)  # low dtype preserved
    exe = static.Executor()
    got = exe.run(prog2, feed=feed, fetch_list=list(fetches2))
    # bf16-tolerance match against the fp32 unfused program
    for r, g_ in zip(ref, got):
        np.testing.assert_allclose(r, g_, rtol=3e-2, atol=3e-2)


# ------------------------------------------------- matmul epilogue / add-norm

def _capture(fn, *feed_shapes):
    from paddle_tpu import static

    main = static.Program()
    with static.program_guard(main):
        feeds = [static.data(f"x{i}", list(s), "float32")
                 for i, s in enumerate(feed_shapes)]
        out = fn(*feeds)
    return main, feeds, out


@pytest.mark.parametrize("act,fuses", [
    ("relu", True), ("silu", True),
    # exact-erf GELU has no Mosaic lowering (tests/test_tpu_compile.py):
    # the pattern declines it on every backend and XLA runs linear+gelu
    ("gelu", False)])
def test_matmul_epilogue_pattern_fires_and_matches(act, fuses):
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    from paddle_tpu import static
    from paddle_tpu.static.rewrite import PallasFusionPass

    paddle.seed(0)
    lin = nn.Linear(64, 128)

    main, (x,), out = _capture(lambda v: getattr(F, act)(lin(v)), (8, 64))
    exe = static.Executor()
    xv = np.random.default_rng(0).standard_normal((8, 64)).astype(np.float32)
    (ref,) = exe.run(main, feed={"x0": xv}, fetch_list=[out])

    n = PallasFusionPass([out._vid]).apply(main)
    types = [op.type for op in main.global_block().ops]
    assert ("matmul_epilogue" in types) == fuses, (n, types)
    (got,) = static.Executor().run(main, feed={"x0": xv}, fetch_list=[out])
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_matmul_epilogue_gelu_tanh_variant():
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    from paddle_tpu import static
    from paddle_tpu.static.rewrite import PallasFusionPass

    paddle.seed(1)
    lin = nn.Linear(64, 128)
    main, (x,), out = _capture(lambda v: F.gelu(lin(v), approximate=True), (8, 64))
    exe = static.Executor()
    xv = np.random.default_rng(1).standard_normal((8, 64)).astype(np.float32)
    (ref,) = exe.run(main, feed={"x0": xv}, fetch_list=[out])
    PallasFusionPass([out._vid]).apply(main)
    ep = next(op for op in main.global_block().ops
              if op.type == "matmul_epilogue")
    (got,) = static.Executor().run(main, feed={"x0": xv}, fetch_list=[out])
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_add_norm_pattern_fuses_residual_stream():
    """norm(x + residual) with the sum ALSO consumed later (the transformer
    residual stream) — the fused op must emit both outputs."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu import static
    from paddle_tpu.static.rewrite import PallasFusionPass

    paddle.seed(2)
    wv = np.random.default_rng(2).standard_normal(32).astype(np.float32)

    def body(a, b):
        w = paddle.to_tensor(wv)
        h = a + b
        normed = F.rms_norm(h, weight=w, epsilon=1e-5)
        return normed * 2.0 + h  # h reused: the residual stream

    main, feeds, out = _capture(body, (4, 32), (4, 32))
    rng = np.random.default_rng(3)
    av = rng.standard_normal((4, 32)).astype(np.float32)
    bv = rng.standard_normal((4, 32)).astype(np.float32)
    (ref,) = static.Executor().run(main, feed={"x0": av, "x1": bv},
                                   fetch_list=[out])
    n = PallasFusionPass([out._vid]).apply(main)
    types = [op.type for op in main.global_block().ops]
    assert "add_rms_norm" in types, (n, types)
    (got,) = static.Executor().run(main, feed={"x0": av, "x1": bv},
                                   fetch_list=[out])
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_add_layer_norm_pattern():
    import paddle_tpu.nn.functional as F
    from paddle_tpu import static
    from paddle_tpu.static.rewrite import PallasFusionPass

    paddle.seed(3)
    rng = np.random.default_rng(4)
    wv = rng.standard_normal(32).astype(np.float32)
    bv_ = rng.standard_normal(32).astype(np.float32)

    def body(a, b):
        w = paddle.to_tensor(wv)
        bb = paddle.to_tensor(bv_)
        return F.layer_norm(a + b, 32, weight=w, bias=bb, epsilon=1e-5)

    main, feeds, out = _capture(body, (4, 32), (4, 32))
    av = rng.standard_normal((4, 32)).astype(np.float32)
    bv = rng.standard_normal((4, 32)).astype(np.float32)
    (ref,) = static.Executor().run(main, feed={"x0": av, "x1": bv},
                                   fetch_list=[out])
    PallasFusionPass([out._vid]).apply(main)
    types = [op.type for op in main.global_block().ops]
    assert "add_layer_norm" in types, types
    (got,) = static.Executor().run(main, feed={"x0": av, "x1": bv},
                                   fetch_list=[out])
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_epilogue_patterns_fire_on_bert_program():
    """The reference criterion: the new patterns fire on a captured
    real-model program (BERT: gelu FFN + residual layer-norms)."""
    from paddle_tpu import static
    from paddle_tpu.models import BertForSequenceClassification, bert_tiny
    from paddle_tpu.static.rewrite import PallasFusionPass

    paddle.seed(0)
    m = BertForSequenceClassification(bert_tiny(), num_classes=2)
    m.eval()
    main = static.Program()
    with static.program_guard(main):
        ids = static.data("ids", [2, 16], "int32")
        out = m(ids)
        out = out[0] if isinstance(out, (tuple, list)) else out
    ids_v = np.random.default_rng(0).integers(1, 500, (2, 16)).astype(np.int32)
    (ref,) = static.Executor().run(main, feed={"ids": ids_v}, fetch_list=[out])
    PallasFusionPass([out._vid]).apply(main)
    types = [op.type for op in main.global_block().ops]
    # BERT's FFN activation is exact-erf GELU, which stays with XLA
    assert "matmul_epilogue" not in types, set(types)
    assert "add_layer_norm" in types, set(types)
    (got,) = static.Executor().run(main, feed={"ids": ids_v}, fetch_list=[out])
    np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)


def test_generic_elementwise_chain_fusion():
    """The CINN-discovery role: an arbitrary elementwise chain (not one of
    the fixed patterns) collapses to ONE generated VPU kernel op with
    numerics preserved (opt-in pass)."""
    from paddle_tpu import static
    from paddle_tpu.static.passes import apply_pass

    def body(a, b):
        t = paddle.tanh(a * b + a)
        u = paddle.exp(t * 0.5)
        return paddle.sqrt(u + 1.0) * b

    main, feeds, out = _capture(body, (8, 128), (8, 128))
    rng = np.random.default_rng(0)
    av = rng.standard_normal((8, 128)).astype(np.float32)
    bv = rng.standard_normal((8, 128)).astype(np.float32)
    (ref,) = static.Executor().run(main, feed={"x0": av, "x1": bv},
                                   fetch_list=[out])
    before = len(main.global_block().ops)
    n = apply_pass(main, "generic_elementwise_fusion",
                   fetch_vids=[out._vid])
    after = len(main.global_block().ops)
    types = [op.type for op in main.global_block().ops]
    assert n >= 1 and after < before, (n, types)
    assert any(t.startswith("vpu_chain_") for t in types), types
    (got,) = static.Executor().run(main, feed={"x0": av, "x1": bv},
                                   fetch_list=[out])
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_generic_fusion_respects_fetch_and_multi_use():
    """Intermediates that are fetched or multiply-consumed stay
    materialized (not swallowed into a chain)."""
    from paddle_tpu import static
    from paddle_tpu.static.passes import apply_pass

    main = static.Program()
    from paddle_tpu.static.program import program_guard

    with program_guard(main):
        a = static.data("a", [4, 32], "float32")
        t = paddle.tanh(a * 2.0)      # fetched below: must survive
        u = paddle.exp(t + 1.0)
        v = paddle.sqrt(u * u + 1.0)
    rng = np.random.default_rng(1)
    av = rng.standard_normal((4, 32)).astype(np.float32)
    ref_t, ref_v = static.Executor().run(main, feed={"a": av},
                                         fetch_list=[t, v])
    apply_pass(main, "generic_elementwise_fusion",
               fetch_vids=[t._vid, v._vid])
    got_t, got_v = static.Executor().run(main, feed={"a": av},
                                         fetch_list=[t, v])
    np.testing.assert_allclose(got_t, ref_t, rtol=1e-6)
    np.testing.assert_allclose(got_v, ref_v, rtol=1e-5, atol=1e-6)


def test_epilogue_pattern_skips_quantized_linear():
    """A weight-only-quantized linear (wq:: namespace; int8 weight + scale
    appended) must NOT be epilogue-fused — the pattern would read the
    scale as a bias and produce garbage."""
    import paddle_tpu.nn as nn
    from paddle_tpu.static.passes import apply_pass

    paddle.seed(0)
    lin = paddle.nn.Linear(64, 128, bias_attr=False)  # 3-arg wq form
    main = static.Program()
    with program_guard(main):
        x = static.data("x", [8, 64], "float32")
        out = F.relu(lin(x))
    xv = np.random.default_rng(0).standard_normal((8, 64)).astype(np.float32)
    paddle.set_flags({"FLAGS_use_pallas_fusion": False})
    try:
        (ref,) = static.Executor().run(main, feed={"x": xv}, fetch_list=[out])
        apply_pass(main, "weight_only_quant")
        n = PallasFusionPass([out._vid]).apply(main)
        types = [op.type for op in main.global_block().ops]
        assert "matmul_epilogue" not in types, (n, types)
        (got,) = static.Executor().run(main, feed={"x": xv}, fetch_list=[out])
    finally:
        paddle.set_flags({"FLAGS_use_pallas_fusion": True})
    # int8 weight quantization error only — no structural corruption
    assert np.abs(got - ref).max() < 0.05 * max(1.0, np.abs(ref).max())


def test_epilogue_fusion_keeps_fp16_compute():
    """fp16-rewritten linear + tanh-gelu must fuse into an fp16:: epilogue op
    that computes in the low dtype (not silently revert to fp32)."""
    from paddle_tpu.static.passes import apply_pass

    paddle.seed(4)
    lin = paddle.nn.Linear(64, 128)
    main = static.Program()
    with program_guard(main):
        x = static.data("x", [8, 64], "float32")
        out = F.gelu(lin(x), approximate=True)
    xv = np.random.default_rng(2).standard_normal((8, 64)).astype(np.float32)
    paddle.set_flags({"FLAGS_use_pallas_fusion": False})
    try:
        (ref,) = static.Executor().run(main, feed={"x": xv}, fetch_list=[out])
        apply_pass(main, "auto_parallel_fp16", dtype="bfloat16")
        PallasFusionPass([out._vid]).apply(main)
        types = [op.type for op in main.global_block().ops]
        assert "fp16::matmul_epilogue" in types, types
        (got,) = static.Executor().run(main, feed={"x": xv}, fetch_list=[out])
    finally:
        paddle.set_flags({"FLAGS_use_pallas_fusion": True})
    np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-2)  # bf16
    # bf16 compute really happened: outputs differ from exact fp32
    assert np.abs(got - ref).max() > 0
