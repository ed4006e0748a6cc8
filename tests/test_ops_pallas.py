"""Pallas kernel library numerics (interpret mode on the CPU test mesh) —
SURVEY.md §4 OpTest analog: each kernel vs a jnp oracle, fwd + grads."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu import ops
from paddle_tpu.ops.flash_attention import flash_attention, flash_attention_reference


def _rand(*shape, dtype=np.float32, seed=0):
    return np.random.default_rng(seed).standard_normal(shape).astype(dtype)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_forward(causal):
    b, s, n, h = 1, 256, 2, 64
    q, k, v = (jnp.asarray(_rand(b, s, n, h, seed=i)) for i in range(3))
    out = flash_attention(q, k, v, causal=causal)
    ref = flash_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_attention_gqa():
    b, s, n, nkv, h = 1, 256, 4, 2, 64
    q = jnp.asarray(_rand(b, s, n, h, seed=0))
    k = jnp.asarray(_rand(b, s, nkv, h, seed=1))
    v = jnp.asarray(_rand(b, s, nkv, h, seed=2))
    out = flash_attention(q, k, v, causal=True)
    ref = flash_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_attention_grads():
    b, s, n, h = 1, 128, 2, 64
    q, k, v = (jnp.asarray(_rand(b, s, n, h, seed=i)) for i in range(3))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(flash_attention_reference(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4, rtol=1e-4)


def test_fused_rms_norm_matches_reference():
    x = jnp.asarray(_rand(6, 256))
    w = jnp.asarray(_rand(256, seed=3))

    def ref(x, w, eps=1e-6):
        var = jnp.mean(x * x, -1, keepdims=True)
        return x * jax.lax.rsqrt(var + eps) * w

    np.testing.assert_allclose(
        np.asarray(ops.fused_rms_norm(x, w)), np.asarray(ref(x, w)), atol=1e-5, rtol=1e-5
    )
    g1 = jax.grad(lambda x, w: jnp.sum(ops.fused_rms_norm(x, w) ** 2), argnums=(0, 1))(x, w)
    g2 = jax.grad(lambda x, w: jnp.sum(ref(x, w) ** 2), argnums=(0, 1))(x, w)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


def test_fused_layer_norm_matches_reference():
    x = jnp.asarray(_rand(6, 256))
    w = jnp.asarray(_rand(256, seed=4))
    b = jnp.asarray(_rand(256, seed=5))

    def ref(x, w, b, eps=1e-5):
        mean = jnp.mean(x, -1, keepdims=True)
        var = jnp.var(x, -1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + eps) * w + b

    np.testing.assert_allclose(
        np.asarray(ops.fused_layer_norm(x, w, b)), np.asarray(ref(x, w, b)), atol=1e-5, rtol=1e-5
    )
    g1 = jax.grad(lambda *a: jnp.sum(ops.fused_layer_norm(*a) ** 2), argnums=(0, 1, 2))(x, w, b)
    g2 = jax.grad(lambda *a: jnp.sum(ref(*a) ** 2), argnums=(0, 1, 2))(x, w, b)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4, rtol=1e-4)


def test_fused_rope_matches_model_rope():
    from paddle_tpu.models.llama import _rope_tables

    b, s, n, h = 2, 16, 2, 64
    x = jnp.asarray(_rand(b, s, n, h))
    cos, sin = _rope_tables(h, 32, 10000.0)
    out = ops.fused_rotary_position_embedding(x, cos=cos, sin=sin)

    c = cos[:s][None, :, None, :]
    sn = sin[:s][None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    ref = jnp.stack([x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-1).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)

    # backward = inverse rotation: grad of sum(out * g) wrt x is rope^{-1}(g)
    g = jax.grad(lambda x: jnp.sum(ops.fused_rotary_position_embedding(x, cos=cos, sin=sin) * ref))(x)
    g_ref = jax.grad(lambda x: jnp.sum(
        jnp.stack([x[..., 0::2] * c - x[..., 1::2] * sn, x[..., 1::2] * c + x[..., 0::2] * sn], -1).reshape(x.shape) * ref
    ))(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-5, rtol=1e-5)


def test_swiglu():
    x = jnp.asarray(_rand(4, 256))
    y = jnp.asarray(_rand(4, 256, seed=7))
    ref = x * jax.nn.sigmoid(x) * y
    np.testing.assert_allclose(np.asarray(ops.swiglu(x, y)), np.asarray(ref), atol=1e-5, rtol=1e-5)
    g1 = jax.grad(lambda x, y: jnp.sum(ops.swiglu(x, y) ** 2), argnums=(0, 1))(x, y)
    g2 = jax.grad(lambda x, y: jnp.sum((x * jax.nn.sigmoid(x) * y) ** 2), argnums=(0, 1))(x, y)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


def test_incubate_functional_tape():
    """Fused ops through the Tensor tape: forward values + backward flow."""
    import paddle_tpu.incubate.nn.functional as FF

    x = paddle.to_tensor(_rand(4, 256))
    x.stop_gradient = False
    w = paddle.to_tensor(np.ones(256, np.float32))
    w.stop_gradient = False
    out = FF.fused_rms_norm(x, w)
    out.sum().backward()
    assert x.grad is not None and w.grad is not None
    assert out.shape == [4, 256]

    a = paddle.to_tensor(_rand(4, 128, seed=9))
    b = paddle.to_tensor(_rand(128, 64, seed=10))
    c = paddle.to_tensor(_rand(64, seed=11))
    y = FF.fused_matmul_bias(a, b, c)
    ref = np.asarray(a._value) @ np.asarray(b._value) + np.asarray(c._value)
    np.testing.assert_allclose(np.asarray(y._value), ref, atol=1e-5, rtol=1e-5)


def test_masked_multihead_attention_decode():
    """Decode-with-cache equals full attention on the prefix."""
    import paddle_tpu.incubate.nn.functional as FF

    b, n, h, smax = 2, 2, 32, 8
    np.random.seed(0)
    cache = paddle.to_tensor(np.zeros((2, b, n, smax, h), np.float32))
    xs = [_rand(b, 3 * n * h, seed=20 + t) for t in range(4)]
    outs = []
    for t, xv in enumerate(xs):
        out, cache = FF.masked_multihead_attention(
            paddle.to_tensor(xv), cache, num_heads=n, head_dim=h, position_offset=t
        )
        outs.append(np.asarray(out._value))

    # reference: full causal attention over the 4 tokens
    qkv = np.stack(xs).reshape(4, b, 3, n, h)  # [T, B, 3, N, H]
    q = np.moveaxis(qkv[:, :, 0], 0, 1)  # [B, T, N, H]
    k = np.moveaxis(qkv[:, :, 1], 0, 1)
    v = np.moveaxis(qkv[:, :, 2], 0, 1)
    ref = np.asarray(
        flash_attention_reference(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True)
    )  # [B, T, N, H]
    for t in range(4):
        np.testing.assert_allclose(outs[t], ref[:, t].reshape(b, n * h), atol=1e-4, rtol=1e-4)


def test_fused_rope_position_ids():
    from paddle_tpu.models.llama import _rope_tables

    b, s, n, h = 2, 8, 2, 32
    x = jnp.asarray(_rand(b, s, n, h, seed=30))
    cos, sin = _rope_tables(h, 64, 10000.0)
    pids = jnp.asarray(np.array([[5, 6, 7, 8, 9, 10, 11, 12], [0, 1, 2, 3, 4, 5, 6, 7]]))
    out = ops.fused_rotary_position_embedding(x, cos=cos, sin=sin, position_ids=pids)

    c = cos[np.asarray(pids).reshape(-1)].reshape(b, s, 1, h // 2)
    sn = sin[np.asarray(pids).reshape(-1)].reshape(b, s, 1, h // 2)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    ref = jnp.stack([x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-1).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# TPU-lowering guards (round-1 regression: kernels only ever ran in interpret
# mode; on the chip, any 64-bit value in a kernel trace makes Mosaic's
# convert-helper recurse forever).  Guard 1 runs everywhere: scan every
# kernel's jaxpr for 64-bit types.  Guard 2 runs only with a real TPU:
# lower+compile each kernel for the chip.
# ---------------------------------------------------------------------------

def _kernel_calls():
    """(name, fn, example ShapeDtypeStruct args) for every Pallas entry."""
    import importlib

    # the ops package re-exports functions under the kernel-module names, so
    # attribute imports resolve to functions; go through importlib instead
    fused_norm = importlib.import_module("paddle_tpu.ops.fused_norm")
    swiglu_mod = importlib.import_module("paddle_tpu.ops.swiglu")
    fa = importlib.import_module("paddle_tpu.ops.flash_attention")

    bf = jnp.bfloat16
    B, S, N, H = 2, 256, 4, 64
    qs = jax.ShapeDtypeStruct((B, S, N, H), bf)
    x2 = jax.ShapeDtypeStruct((B * S, N * H), bf)
    w = jax.ShapeDtypeStruct((N * H,), bf)
    calls = []
    for causal in (False, True):
        calls.append((
            f"flash_fwd_causal{causal}",
            lambda q, k, v, c=causal: fa.flash_attention(q, k, v, causal=c),
            (qs, qs, qs),
        ))
        calls.append((
            f"flash_grad_causal{causal}",
            lambda q, k, v, c=causal: jax.grad(
                lambda a, b_, c_: fa.flash_attention(a, b_, c_, causal=c).astype(jnp.float32).sum(),
                argnums=(0, 1, 2),
            )(q, k, v),
            (qs, qs, qs),
        ))
    calls.append(("rms_norm", lambda x, w_: fused_norm.fused_rms_norm(x, w_), (x2, w)))
    calls.append((
        "rms_norm_grad",
        lambda x, w_: jax.grad(lambda a: fused_norm.fused_rms_norm(a, w_).astype(jnp.float32).sum())(x),
        (x2, w),
    ))
    calls.append(("layer_norm", lambda x, w_: fused_norm.fused_layer_norm(x, w_, w_), (x2, w)))
    calls.append(("swiglu", lambda x: swiglu_mod.swiglu(x, x), (x2,)))
    calls.append((
        "swiglu_grad",
        lambda x: jax.grad(lambda a: swiglu_mod.swiglu(a, a).astype(jnp.float32).sum())(x),
        (x2,),
    ))
    return calls


@pytest.mark.parametrize("name,fn,args", _kernel_calls(), ids=lambda v: v if isinstance(v, str) else "")
def test_kernel_jaxpr_no_64bit(name, fn, args):
    import re

    # the jaxpr print embeds function reprs ("<function ... at 0x7eb699f64...>")
    # whose heap addresses can contain "f64"/"i64" by sheer ASLR luck — strip
    # hex literals so only genuine dtype tokens can match
    jaxpr = re.sub(r"0x[0-9a-f]+", "0xADDR", str(jax.make_jaxpr(fn)(*args)))
    for bad in ("i64", "f64", "u64", "c128"):
        assert bad not in jaxpr, f"{name}: {bad} value in kernel trace breaks Mosaic lowering"


# ---------------------------------------------------------------------------
# The operand type of the flash kernels' matrix products follows the
# inputs' (PR 30): bfloat16 blocks reach the matrix unit as stored, anything
# else is multiplied in float32 as ever.
# ---------------------------------------------------------------------------

def _kernel_eqns(jaxpr, kernel=None):
    """(kernel name, equation) for every equation inside a pallas_call of
    `jaxpr`, loops and branches included."""
    for eqn in jaxpr.eqns:
        inside = eqn.params["name"] if eqn.primitive.name == "pallas_call" else kernel
        if inside is not None and inside is kernel:
            yield kernel, eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _kernel_eqns(sub, inside)


def _flash_jaxpr(dtype, grad, v_width=64):
    import importlib

    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    q = jax.ShapeDtypeStruct((1, 256, 4, 64), dtype)
    k = jax.ShapeDtypeStruct((1, 256, 2, 64), dtype)
    v = jax.ShapeDtypeStruct((1, 256, 2, v_width), dtype)
    fn = lambda q, k, v: fa.flash_attention(q, k, v, causal=True)  # noqa: E731
    if grad:
        fn = jax.grad(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True).astype(jnp.float32).sum(), (0, 1, 2))
    return jax.make_jaxpr(fn)(q, k, v).jaxpr


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("dtype,operands", [
    (jnp.float32, jnp.float32), (jnp.float16, jnp.float32),
    (jnp.bfloat16, jnp.bfloat16)], ids=["float32", "float16", "bfloat16"])
def test_flash_dot_operands_follow_the_inputs(dtype, operands, grad):
    """Every product of every kernel takes operands of ONE type with float32
    sums: the inputs' for bfloat16, float32 for the rest; and a bfloat16
    kernel widens no block it read (no convert to float32 of a bfloat16
    value: q, k, v and dO are the only bfloat16 values it has besides the
    rounded `p` and `ds`), while a float32 kernel holds no bfloat16 at all."""
    dots = {}
    for kernel, eqn in _kernel_eqns(_flash_jaxpr(dtype, grad)):
        if eqn.primitive.name == "dot_general":
            assert {v.aval.dtype for v in eqn.invars} == {jnp.dtype(operands)}, (kernel, eqn)
            assert eqn.params["preferred_element_type"] == jnp.float32
            dots[kernel] = dots.get(kernel, 0) + 1
        if eqn.primitive.name == "convert_element_type":
            src, dst = eqn.invars[0].aval.dtype, eqn.params["new_dtype"]
            if dtype == jnp.bfloat16:
                assert not (src == jnp.bfloat16 and dst == jnp.float32), (kernel, eqn)
            else:
                assert jnp.bfloat16 not in (src, dst), (kernel, eqn)
    want = {"flash_fwd": 2}
    if grad:
        want.update(flash_bwd_dq=3, flash_bwd_dkv=4)
    assert dots == want  # all nine products were seen


def test_flash_counts_its_traces_by_operand_type():
    """`profiler.compile_stats()` says which products a program was traced
    with (`flash_bf16_operand_traces`, `flash_f32_operand_traces`), counts
    one per `flash_attention` call that reached the kernels (V with a width
    of its own included), and resets with its family."""
    from paddle_tpu import profiler

    profiler.compile_stats(reset=True)
    _flash_jaxpr(jnp.bfloat16, grad=True)
    _flash_jaxpr(jnp.bfloat16, grad=False, v_width=128)
    _flash_jaxpr(jnp.float32, grad=False)
    stats = profiler.compile_stats(reset=True)
    assert (stats["flash_bf16_operand_traces"],
            stats["flash_f32_operand_traces"]) == (2, 1)
    stats = profiler.compile_stats()
    assert (stats["flash_bf16_operand_traces"],
            stats["flash_f32_operand_traces"]) == (0, 0)


def test_flash_block_size_flags():
    """FLAGS_flash_block_q/_k apply only when a positive multiple of 8 that
    divides the sequence; anything else keeps the 128 default, and ragged
    lengths still reach the caller's reference fallback."""
    import paddle_tpu as paddle
    from paddle_tpu.ops.flash_attention import _block_sizes

    try:
        assert _block_sizes(1024, 1024) == (128, 128)
        assert _block_sizes(130, 130) == (128, 128)  # 130 % 128 != 0 -> caller falls back
        paddle.set_flags({"FLAGS_flash_block_q": 256, "FLAGS_flash_block_k": 64})
        assert _block_sizes(1024, 1024) == (256, 64)
        paddle.set_flags({"FLAGS_flash_block_q": 0, "FLAGS_flash_block_k": -64})
        assert _block_sizes(1024, 1024) == (128, 128)
        paddle.set_flags({"FLAGS_flash_block_q": 100, "FLAGS_flash_block_k": 128})
        assert _block_sizes(400, 400) == (128, 128)  # 100 not a sublane multiple
    finally:
        paddle.set_flags({"FLAGS_flash_block_q": 0, "FLAGS_flash_block_k": 0})


def test_flash_nondefault_blocks_match_reference():
    import numpy as np
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.ops import flash_attention as fa_fn
    from paddle_tpu.ops.flash_attention import flash_attention_reference

    q = jnp.asarray(np.random.default_rng(0).standard_normal((1, 256, 2, 128)).astype(np.float32))
    try:
        paddle.set_flags({"FLAGS_use_pallas": "true", "FLAGS_flash_block_q": 256, "FLAGS_flash_block_k": 64})
        out = fa_fn(q, q, q, causal=True)
    finally:
        paddle.set_flags({"FLAGS_use_pallas": "auto", "FLAGS_flash_block_q": 0, "FLAGS_flash_block_k": 0})
    ref = flash_attention_reference(q, q, q, causal=True)
    assert float(jnp.abs(out - ref).max()) < 2e-5


@pytest.mark.parametrize("seq", [5, 37, 100, 200])
def test_flash_causal_self_attention_pads_any_length(seq):
    """A prompt of any length runs the kernel: causal self-attention is
    zero-padded to the block (exact — padded keys sit after every real
    query) instead of meeting Mosaic with a 37-row block or falling to the
    O(S^2) reference.  Values and grads match the reference."""
    import warnings

    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.flash_attention import (flash_attention,
                                                flash_attention_reference)

    rng = np.random.default_rng(seq)
    q, k, v = (jnp.asarray(rng.standard_normal((1, seq, 2, 64)), jnp.float32)
               for _ in range(3))

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v, causal=True) ** 2).sum()

    with warnings.catch_warnings():
        warnings.simplefilter("error")  # the reference fallback warns
        out = flash_attention(q, k, v, causal=True)
        grads = jax.grad(loss(flash_attention), (0, 1, 2))(q, k, v)
    assert out.shape == q.shape
    ref = flash_attention_reference(q, k, v, causal=True)
    assert float(jnp.abs(out - ref).max()) < 2e-5
    for g, r in zip(grads, jax.grad(loss(flash_attention_reference),
                                    (0, 1, 2))(q, k, v)):
        assert g.shape == r.shape and float(jnp.abs(g - r).max()) < 2e-4


def test_flash_causal_cross_length_bottom_right_alignment():
    """Sq != Sk causal must be bottom-right aligned (kv-cache/decode
    convention), matching flash_attention_reference — fwd AND bwd.  The
    kernel previously used top-left (query i sees keys <= i), silently
    wrong for any chunked-prefill / cache-extension call."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.flash_attention import _flash_bnsh

    ffn = flash_attention

    rng = jax.random.PRNGKey(0)
    B, N, H = 1, 2, 8
    Sq, Sk = 128, 256  # block-multiples: the Pallas path, not the fallback
    q, k, v = (jax.random.normal(kk, (B, Sq if i == 0 else Sk, N, H),
                                 jnp.float32)
               for i, kk in enumerate(jax.random.split(rng, 3)))

    out = ffn(q, k, v, causal=True)
    ref = flash_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)

    # bwd: compare flash vjp against autodiff through the reference
    def loss_flash(q, k, v):
        qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
        return jnp.sum(_flash_bnsh(qt, kt, vt, H ** -0.5, True, 64, 64) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(flash_attention_reference(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4, err_msg=name)
