"""nn.LayerStack — scan-over-layers numerics equivalence + layout round-trip.

The stack must be OBSERVATIONALLY identical to the unrolled loop: same
outputs (to a few ulp on CPU f32 — the scan body runs the same op sequence,
but as another XLA program), same grads (to accumulation-order tolerance),
and state_dict layouts must
interconvert so checkpoints survive flipping fuse_layer_stack.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn


class _Block(nn.Layer):
    def __init__(self, width=8):
        super().__init__()
        self.fc = nn.Linear(width, width)
        self.ln = nn.LayerNorm(width)

    def forward(self, h, scale):
        return h + self.fc(self.ln(h)) * scale


def _block(width=8):
    return _Block(width)


def _twin_stacks(n=4, width=8):
    paddle.seed(7)
    blocks = [_block(width) for _ in range(n)]
    loop_blocks = [_block(width) for _ in range(n)]
    for lb, b in zip(loop_blocks, blocks):
        lb.set_state_dict(b.state_dict())
    return nn.LayerStack(blocks), loop_blocks


def test_scan_matches_unrolled_forward_and_grads():
    stack, loop = _twin_stacks()
    rng = np.random.default_rng(0)
    x1 = paddle.to_tensor(rng.standard_normal((2, 3, 8)).astype(np.float32),
                          stop_gradient=False)
    x2 = paddle.to_tensor(np.asarray(x1._value), stop_gradient=False)
    s = paddle.to_tensor(np.float32(0.5))

    out = stack(x1, s)
    h = x2
    for b in loop:
        h = b(h, s)
    # same op sequence, same backend, but two XLA programs: the scan body
    # is compiled as one computation and may fuse (and so round) where the
    # unrolled loop's op-by-op executables do not.  4 ulp of float32 at the
    # output's largest magnitude: every element is a sum of O(1) terms and
    # carries their rounding, however small it is itself
    want = np.asarray(h._value)
    np.testing.assert_allclose(
        np.asarray(out._value), want, rtol=0,
        atol=4 * np.spacing(np.abs(want).max()))

    out.sum().backward()
    h.sum().backward()
    for key in ("fc.weight", "fc.bias", "ln.weight", "ln.bias"):
        g_stack = np.asarray(stack._parameters[key].grad._value)
        g_loop = np.stack([np.asarray(dict(b.named_parameters())[key].grad._value)
                           for b in loop])
        np.testing.assert_allclose(g_stack, g_loop, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(x1.grad._value),
                               np.asarray(x2.grad._value), rtol=1e-5, atol=1e-6)


def test_scan_under_trainstep_matches_eager_loop_losses():
    from paddle_tpu import jit
    import paddle_tpu.optimizer as opt

    def build(fuse):
        paddle.seed(3)
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

        cfg = llama_tiny(num_hidden_layers=3, hidden_size=64,
                         intermediate_size=128, num_attention_heads=4,
                         num_key_value_heads=4, vocab_size=128,
                         max_position_embeddings=32, dtype="float32",
                         fuse_layer_stack=fuse)
        m = LlamaForCausalLM(cfg)
        o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
        return m, jit.TrainStep(m, o, lambda mm, x, y: mm(x, y)[0])

    rng = np.random.default_rng(1)
    x = paddle.to_tensor(rng.integers(0, 128, (2, 8)).astype(np.int32))
    y = paddle.to_tensor(rng.integers(0, 128, (2, 8)).astype(np.int32))
    _, step_loop = build(False)
    _, step_scan = build(True)
    losses_loop = [float(step_loop(x, y)._value) for _ in range(3)]
    losses_scan = [float(step_scan(x, y)._value) for _ in range(3)]
    np.testing.assert_allclose(losses_scan, losses_loop, rtol=2e-5)


@pytest.mark.parametrize("gran", ["full", "full_attn", "core_attn"])
def test_recompute_tiers_preserve_loss_and_grads(gran):
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    def build(recompute, fuse):
        paddle.seed(5)
        cfg = llama_tiny(num_hidden_layers=2, hidden_size=32,
                         intermediate_size=64, num_attention_heads=2,
                         num_key_value_heads=2, vocab_size=64,
                         max_position_embeddings=16, dtype="float32",
                         use_recompute=recompute, recompute_granularity=gran,
                         fuse_layer_stack=fuse)
        return LlamaForCausalLM(cfg)

    rng = np.random.default_rng(2)
    x = paddle.to_tensor(rng.integers(0, 64, (2, 8)).astype(np.int32))
    y = paddle.to_tensor(rng.integers(0, 64, (2, 8)).astype(np.int32))

    ref = build(False, False)
    loss_ref, _ = ref(x, y)
    loss_ref.backward()
    m = build(True, True)
    m.set_state_dict(ref.state_dict())
    loss, _ = m(x, y)
    np.testing.assert_allclose(float(loss._value), float(loss_ref._value),
                               rtol=1e-5)
    loss.backward()
    g = np.asarray(
        m.model.layers._parameters["self_attn.q_proj.weight"].grad._value)
    g_ref = np.stack([np.asarray(b.self_attn.q_proj.weight.grad._value)
                      for b in ref.model.layers])
    np.testing.assert_allclose(g, g_ref, rtol=1e-4, atol=1e-6)


def test_state_dict_stack_unstack_round_trip():
    from paddle_tpu.nn.layer.stack import stack_state_dict, unstack_state_dict

    stack, loop = _twin_stacks(n=3)
    keys = stack.stack_keys()
    # unstacked dict -> stacked dict -> load
    per_layer = {}
    for i, b in enumerate(loop):
        for k, v in b.state_dict().items():
            per_layer[f"layers.{i}.{k}"] = v
    stacked = stack_state_dict(per_layer, "layers", 3, keys)
    assert set(stacked) == {f"layers.{k}" for k in keys}
    back = unstack_state_dict(stacked, "layers", 3, keys)
    assert set(back) == set(per_layer)
    for k in per_layer:
        assert np.array_equal(np.asarray(per_layer[k]._value),
                              np.asarray(back[k]._value))


def test_root_level_stack_loads_per_layer_checkpoint():
    """A per-layer checkpoint loads into a LayerStack that IS the root model
    (path prefix is empty — the adapt path must not synthesize '.0.key')."""
    stack, loop = _twin_stacks(n=3)
    per_layer = {}
    for i, b in enumerate(loop):
        for k, v in b.state_dict().items():
            per_layer[f"{i}.{k}"] = v
    missing, unexpected = stack.set_state_dict(per_layer)
    assert not missing and not unexpected, (missing, unexpected)
    got = np.asarray(stack._parameters["fc.weight"]._value)
    want = np.stack([np.asarray(b.fc.weight._value) for b in loop])
    np.testing.assert_array_equal(got, want)


def test_checkpoints_cross_load_between_layouts():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    def build(fuse):
        paddle.seed(9)
        cfg = llama_tiny(num_hidden_layers=2, hidden_size=32,
                         intermediate_size=64, num_attention_heads=2,
                         num_key_value_heads=2, vocab_size=64,
                         max_position_embeddings=16, dtype="float32",
                         fuse_layer_stack=fuse)
        return LlamaForCausalLM(cfg)

    rng = np.random.default_rng(4)
    x = paddle.to_tensor(rng.integers(0, 64, (2, 8)).astype(np.int32))
    loop_model, scan_model = build(False), build(True)

    # per-layer checkpoint loads into the scanned model...
    missing, unexpected = scan_model.set_state_dict(loop_model.state_dict())
    assert not missing and not unexpected
    np.testing.assert_array_equal(np.asarray(loop_model(x)._value),
                                  np.asarray(scan_model(x)._value))
    # ...and a scanned checkpoint loads back into a fresh loop model
    loop2 = build(False)
    missing, unexpected = loop2.set_state_dict(scan_model.state_dict())
    assert not missing and not unexpected
    np.testing.assert_array_equal(np.asarray(loop_model(x)._value),
                                  np.asarray(loop2(x)._value))


def test_generate_parity_scan_vs_loop():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    def build(fuse):
        paddle.seed(11)
        cfg = llama_tiny(num_hidden_layers=2, hidden_size=32,
                         intermediate_size=64, num_attention_heads=2,
                         num_key_value_heads=2, vocab_size=64,
                         max_position_embeddings=64, dtype="float32",
                         fuse_layer_stack=fuse)
        return LlamaForCausalLM(cfg)

    loop_model, scan_model = build(False), build(True)
    scan_model.set_state_dict(loop_model.state_dict())
    rng = np.random.default_rng(6)
    prompt = paddle.to_tensor(rng.integers(0, 64, (1, 8)).astype(np.int32))
    for cache in ("naive", "paged"):
        a = loop_model.generate(prompt, max_new_tokens=4, cache=cache)
        b = scan_model.generate(prompt, max_new_tokens=4, cache=cache)
        assert np.array_equal(np.asarray(a._value), np.asarray(b._value)), cache
    # macro-step decode threads the paged pools THROUGH the scan body
    # (decode_scan): chunked scan == per-token loop, bit for bit,
    # including the max_new % D tail chunk
    c = scan_model.generate(prompt, max_new_tokens=6, cache="paged",
                            decode_chunk=4)
    d = loop_model.generate(prompt, max_new_tokens=6, cache="paged",
                            decode_chunk=1)
    assert np.array_equal(np.asarray(c._value), np.asarray(d._value))


def test_engine_on_layer_stack_matches_loop_engine():
    """GenerationEngine over a fuse_layer_stack model: the macro-step
    program scans ONE layer body with the paged pools as scan state, and
    its tokens equal the unrolled-loop engine's exactly (greedy + a
    sampled slot, request joining at a macro-step boundary)."""
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
    from paddle_tpu.serving import GenerationEngine

    def build(fuse):
        paddle.seed(11)
        cfg = llama_tiny(num_hidden_layers=2, hidden_size=32,
                         intermediate_size=64, num_attention_heads=2,
                         num_key_value_heads=2, vocab_size=64,
                         max_position_embeddings=64, dtype="float32",
                         fuse_layer_stack=fuse)
        m = LlamaForCausalLM(cfg)
        m.eval()
        return m

    def run(fuse, D):
        eng = GenerationEngine(build(fuse), max_batch=2, block_size=8,
                               num_blocks=16, decode_chunk=D)
        eng.add_request("a", [5, 9, 17, 33, 2], max_new_tokens=8)
        eng.step()
        eng.add_request("b", [7, 11, 3], max_new_tokens=6,
                        temperature=4.0, seed=9)
        while eng.has_work():
            eng.step()
        return eng.result("a"), eng.result("b")

    ref = run(False, 1)
    assert run(True, 4) == ref
    assert run(True, 1) == ref


def test_flags_scan_layers_forces_stack():
    from paddle_tpu.models.llama import LlamaModel, llama_tiny

    paddle.set_flags({"FLAGS_scan_layers": True})
    try:
        cfg = llama_tiny(num_hidden_layers=2, dtype="float32")
        m = LlamaModel(cfg)
        assert isinstance(m.layers, nn.LayerStack)
    finally:
        paddle.set_flags({"FLAGS_scan_layers": False})
    m2 = LlamaModel(llama_tiny(num_hidden_layers=2, dtype="float32"))
    assert not isinstance(m2.layers, nn.LayerStack)


def test_heterogeneous_blocks_rejected():
    paddle.seed(0)
    with pytest.raises((TypeError, ValueError)):
        nn.LayerStack([_block(8), nn.Linear(8, 8)])

    class Wide(nn.Layer):
        def __init__(self, w):
            super().__init__()
            self.fc = nn.Linear(w, w)

        def forward(self, h):
            return self.fc(h)

    with pytest.raises(ValueError):
        nn.LayerStack([Wide(4), Wide(8)])


def test_dropout_stack_rng_and_eval_mode():
    """Stochastic stacks draw fresh per-call randomness in train mode and
    are deterministic in eval — eval() must reach the hidden template (the
    mode sync), and MHA's functional dropout must trip needs_rng.

    The eval forwards are compared with the eager dispatch cache off, so
    that both run the same executables: with it on, an op's fourth sighting
    swaps its op-by-op run for one jitted program, which rounds differently
    by an ulp (not dropout: the same happens at dropout=0.0)."""
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny

    paddle.seed(0)
    m = GPTForCausalLM(gpt_tiny(dropout=0.1, fuse_layer_stack=True))
    assert m.gpt.h._needs_rng
    rng = np.random.default_rng(7)
    x = paddle.to_tensor(rng.integers(0, 512, (2, 8)).astype(np.int32))
    a, b = m(x), m(x)
    assert not np.array_equal(np.asarray(a._value), np.asarray(b._value)), (
        "train-mode dropout produced identical outputs across calls")
    m.eval()
    paddle.set_flags({"FLAGS_eager_op_jit": False})
    try:
        c, d = m(x), m(x)
    finally:
        paddle.set_flags({"FLAGS_eager_op_jit": True})
    assert not m.gpt.h.__dict__["_template"].training
    assert np.array_equal(np.asarray(c._value), np.asarray(d._value)), (
        "eval() did not reach the scan body (dropout still active)")


def test_gpt_scan_matches_loop():
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny

    def build(fuse):
        paddle.seed(13)
        return GPTForCausalLM(gpt_tiny(fuse_layer_stack=fuse))

    loop_model, scan_model = build(False), build(True)
    scan_model.set_state_dict(loop_model.state_dict())
    rng = np.random.default_rng(8)
    x = paddle.to_tensor(rng.integers(0, 512, (2, 12)).astype(np.int32))
    y = paddle.to_tensor(rng.integers(0, 512, (2, 12)).astype(np.int32))
    la, _ = loop_model(x, labels=y)
    lb, _ = scan_model(x, labels=y)
    np.testing.assert_allclose(float(la._value), float(lb._value), rtol=1e-5)
