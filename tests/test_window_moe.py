"""The window / full attention model with a per-head gate and routed experts
(models/window_moe.py) against its plain reference
(perfbench/reference_window_moe.py) at a small size on the CPU, float32,
seeded: `GenerationEngine` through the paged pools AND the window rings over
several ring revolutions, the held-expert shares adding up to the uncut layer,
the windowed flash forward and the windowed paged read, YaRN and partial
rotary against the formula, softmax routing, the ring's fixed size, and the
engine's optional features refusing a window class by name."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler, serving
from paddle_tpu.models import attention, experts
from paddle_tpu.models.contract import CacheClass, CacheSpec, PoolSpec
from paddle_tpu.models.window_moe import (FULL, SLIDING, WindowMoeForCausalLM,
                                          rope_inv_freq, window_moe_tiny)
from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.ops.flash_attention import (flash_attention,
                                            flash_attention_reference)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from perfbench import reference_window_moe as ref  # noqa: E402
from perfbench.families import window_moe as fam  # noqa: E402

LAGUNA_FULL = {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
               "original_max_position_embeddings": 8192, "beta_slow": 1,
               "beta_fast": 32, "attention_factor": 1.4852030263919618,
               "partial_rotary_factor": 0.5}


def _model(seed=0, **kw):
    paddle.seed(seed)
    m = WindowMoeForCausalLM(window_moe_tiny(**kw))
    fam.perturb_norms(m, seed)      # gains away from exactly 1
    fam.spread_gates(m, seed)       # gates away from 1/2
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _model(held_experts=(2, 4))


def _reference(m):
    return fam.reference_weights(m), fam._sizes_of(m.config)


# --------------------------------------------- (a) the engine and the reference

def test_forward_logits_match_the_reference():
    m = _model(seed=3)
    ids = np.random.default_rng(3).integers(0, 256, 45).astype(np.int32)
    got = np.asarray(m(paddle.to_tensor(ids[None]))._value[0])
    w, sizes = _reference(m)
    want = np.asarray(ref.logits_at(w, sizes, ids, list(range(45))))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)


def test_engine_streams_match_the_reference_through_ring_revolutions(model):
    """Rows of different lengths in one batch, one prompt SHORTER than the
    window (5 < 8), 30 tokens each: the ring of 3 blocks x 4 positions turns
    more than three times under the longest row.  Every token the engine
    emits (the prefill program's, then the macro-step's through the pages
    and the rings) is the reference's argmax given the tokens before it."""
    serving.reset_decode_stats()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (5, 19, 34)]
    eng = serving.GenerationEngine(model, max_batch=3, block_size=4,
                                   num_blocks=60)
    firsts = [eng.add_request(f"r{i}", p, max_new_tokens=30)
              for i, p in enumerate(prompts)]
    while eng.has_work():
        eng.step()
    w, sizes = _reference(model)
    for i, p in enumerate(prompts):
        toks = eng.result(f"r{i}")
        assert toks[0] == firsts[i] and len(toks) == 30
        ids = np.concatenate([p, toks[:-1]])
        lg = np.asarray(ref.logits_at(w, sizes, ids,
                                      list(range(len(p) - 1, len(ids)))))
        assert toks == [int(t) for t in lg.argmax(-1)], i
    st = serving.decode_stats()
    # two classes: 2 full layers x (60 + 3 scratch) pages, 3 sliding layers
    # x 3 slots x 3 ring blocks; a block is 2 heads x 4 x 16 float32
    block = 2 * 4 * 16 * 4
    assert st["k_pool_bytes"] == st["v_pool_bytes"] == 2 * 63 * block
    assert st["wk_pool_bytes"] == st["wv_pool_bytes"] == 3 * 9 * block
    assert st["window_ring_blocks"] == 3 and st["latent_pool_bytes"] == 0
    assert st["pool_bytes"] == 2 * (2 * 63 + 3 * 9) * block
    spec = eng._spec
    assert [c.window for c in spec.classes] == [None, 8]
    assert [c.layers for c in spec.classes] == [(0, 4), (1, 2, 3)]
    assert not spec.kv_pair and spec.windowed
    # 29 decode token steps a row; the ring is read whole (12 positions)
    assert st["attn_window_positions_read"] == 3 * 29 * 12
    assert st["attn_positions_read"] == (st["attn_full_positions_read"]
                                         + st["attn_window_positions_read"])
    lens = [len(p) + 1 + t for p in prompts for t in range(29)]
    assert st["attn_window_positions_live"] == sum(min(n, 8) for n in lens)
    assert st["attn_positions_live"] == sum(lens) + sum(min(n, 8) for n in lens)
    assert st["moe_layer_steps"] == 29 * 4 and st["moe_assignments"] == 3 * 29 * 3 * 4
    assert st["moe_prefill_assignments"] == (5 + 19 + 34) * 3 * 4


def test_decode_logits_through_pages_and_rings_match_the_reference(model):
    """The logits themselves: `next_token_logits` (the contract's decode step
    over the RESIDENT pools, functional) after a prefill against the
    reference's full forward, for rows below, at and past the window; the
    pools and the streams are as they were."""
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (3, 7, 26)]
    eng = serving.GenerationEngine(model, max_batch=3, block_size=4,
                                   num_blocks=48)
    firsts = [eng.add_request(f"r{i}", p, max_new_tokens=6)
              for i, p in enumerate(prompts)]
    before = [[np.asarray(a) for a in p] for p in eng._pools]
    got = eng.next_token_logits()
    assert [len(p) for p in eng._pools] == [2, 2, 3, 3]
    for p, q in zip(before, eng._pools):
        for a, b in zip(p, q):
            np.testing.assert_array_equal(a, np.asarray(b))
    w, sizes = _reference(model)
    for i, p in enumerate(prompts):
        ids = np.concatenate([p, [firsts[i]]])
        want = np.asarray(ref.logits_at(w, sizes, ids, [len(ids) - 1]))[0]
        np.testing.assert_allclose(got[f"r{i}"], want, atol=2e-4, rtol=0)
    out = eng.step()
    assert [out[f"r{i}"][0] for i in range(3)] == [
        int(got[f"r{i}"].argmax()) for i in range(3)]


def test_a_single_window_class_gets_its_rings_never_the_block_table():
    """An ALL-SLIDING model has ONE cache class, a window: the macro-step
    must hand `decode` the slots' rings (a 1-tuple), never the requests'
    block table, whose page numbers run up to num_blocks in a pool of
    max_batch x ring blocks.  Every token through three ring revolutions is
    the reference's argmax."""
    m = _model(seed=4, num_hidden_layers=3,
               layer_types=(SLIDING, SLIDING, SLIDING),
               num_attention_heads_per_layer=(6, 6, 6),
               mlp_layer_types=("dense", "sparse", "sparse"),
               held_experts=(2, 4))
    spec = m.serving_contract().spec
    assert [c.window for c in spec.classes] == [8]
    assert spec.per_class_tables and not spec.kv_pair
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (5, 21)]
    eng = serving.GenerationEngine(m, max_batch=2, block_size=4, num_blocks=40)
    assert [np.asarray(p[0]).shape[0] for p in eng._pools] == [6, 6]
    for i, p in enumerate(prompts):
        eng.add_request(f"r{i}", p, max_new_tokens=30)
    while eng.has_work():
        eng.step()
    w, sizes = _reference(m)
    for i, p in enumerate(prompts):
        toks = eng.result(f"r{i}")
        ids = np.concatenate([p, toks[:-1]])
        lg = np.asarray(ref.logits_at(w, sizes, ids,
                                      list(range(len(p) - 1, len(ids)))))
        assert toks == [int(t) for t in lg.argmax(-1)], i


@pytest.mark.parametrize("control,limit", [
    ("ignore_window", 1e-2), ("no_gate", 1e-2)])
def test_a_mechanism_left_out_of_the_reference_does_not_compare_equal(
        model, control, limit):
    ids = np.random.default_rng(8).integers(0, 256, 40).astype(np.int32)
    got = np.asarray(model(paddle.to_tensor(ids[None]))._value[0, -1])
    w, sizes = _reference(model)
    right = np.asarray(ref.logits_at(w, sizes, ids, [39]))[0]
    wrong = np.asarray(ref.logits_at(w, {**sizes, control: True}, ids, [39]))[0]
    assert np.abs(got - right).max() < 2e-4
    assert np.abs(got - wrong).max() > limit


def test_scopes_reach_the_programs(model):
    eng = serving.GenerationEngine(model, max_batch=2, block_size=4,
                                   num_blocks=32)
    text = eng._build_step(2).lower(*eng._step_avals()).as_text(debug_info=True)
    for scope in ("attn.full.decode", "attn.window.decode", "attn.gate",
                  "moe.route", "moe.experts", "moe.shared"):
        assert scope in text, scope
    fn = eng._prefill_program(16, 0)
    text = fn.lower([t._value for t in eng._state], np.zeros((1, 16), np.int32),
                    np.int32(16), None).as_text(debug_info=True)
    for scope in ("attn.full.prefill", "attn.window.prefill", "attn.gate",
                  "moe.experts"):
        assert scope in text, scope
    assert {"attn.full.prefill", "attn.window.prefill", "attn.full.decode",
            "attn.window.decode", "attn.gate"} <= set(profiler.SCOPE_NAMES)
    # warmup compiles the macro-step from avals, the ring tables among them
    assert eng.warmup(prefill=False, adopt=False)["chunks"]


# --------------------------------------------------- (b) the shares add up

@pytest.mark.parametrize("stacked", [False, True])
def test_the_shares_of_a_two_and_four_way_split_add_up_to_the_uncut_layer(stacked):
    """What all shares give, the shared expert counted once, is the uncut
    reference's expert layer (softmax scoring, top-3 of 8), whether a share's
    weights come as a list (the loop over experts unrolled) or as one stack
    (one loop): both forms give the same numbers."""
    rng = np.random.default_rng(11)
    h, f, e, t = 32, 24, 8, 50
    m = jnp.asarray(rng.standard_normal((t, h)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((h, e)), jnp.float32)
    gate_up = [jnp.asarray(rng.standard_normal((h, 2 * f)) / 6, jnp.float32)
               for _ in range(e)]
    down = [jnp.asarray(rng.standard_normal((f, h)) / 5, jnp.float32)
            for _ in range(e)]
    shared = (jnp.asarray(rng.standard_normal((h, 2 * f)) / 6, jnp.float32),
              jnp.asarray(rng.standard_normal((f, h)) / 5, jnp.float32))
    sizes = {"top_k": 3, "scale": 2.5, "normalize": True}

    def reference_layer(held):
        first, count = held
        weight, _gap, _top = ref._route(m, router, top_k=3, scale=2.5,
                                        normalize=True, dt=jnp.float32)
        out = jnp.zeros_like(m)
        for i in range(first, first + count):
            out = out + weight[:, i, None] * ref._ffn(m, gate_up[i], down[i],
                                                      jnp.float32)
        return out

    whole = reference_layer((0, e)) + ref._ffn(m, *shared, jnp.float32)
    for ways in (2, 4):
        n = e // ways
        parts = jnp.zeros_like(m)
        for s in range(ways):
            held = (s * n, n)
            mine = (gate_up[s * n:(s + 1) * n], down[s * n:(s + 1) * n])
            out, counts = experts.routed_experts(
                m, router, *(map(jnp.stack, mine) if stacked else mine),
                held=held, scoring="softmax", **sizes)
            np.testing.assert_allclose(out, reference_layer(held), atol=2e-5)
            if stacked:
                np.testing.assert_array_equal(out, experts.routed_experts(
                    m, router, *mine, held=held, scoring="softmax", **sizes)[0])
            parts = parts + out
            assert int(counts["assignments"]) == t * 3
        total = parts + ref._ffn(m, *shared, jnp.float32)   # counted ONCE
        np.testing.assert_allclose(total, whole, atol=5e-5)


# ------------------------------------------------ (c) the windowed flash forward

@pytest.mark.parametrize("seq,window", [(256, 40), (200, 40), (256, 128),
                                        (96, 200), (384, 1)])
def test_flash_window_forward_matches_the_reference(seq, window):
    k = jax.random.split(jax.random.PRNGKey(seq + window), 3)
    q = jax.random.normal(k[0], (1, seq, 6, 32))
    kk = jax.random.normal(k[1], (1, seq, 2, 32))
    v = jax.random.normal(k[2], (1, seq, 2, 32))
    got = flash_attention(q, kk, v, causal=True, window=window)
    want = flash_attention_reference(q, kk, v, causal=True, window=window)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the mask is the stated one: a query sees itself and window - 1 before
    # (heads 0 and 3 are the first of the two K/V groups of three)
    s = jnp.einsum("bqnh,bknh->bnqk", q[:, :, ::3], kk) / math.sqrt(32)
    i, j = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    p = jax.nn.softmax(jnp.where((j <= i) & (j > i - window), s, -jnp.inf), -1)
    plain = jnp.einsum("bnqk,bknh->bqnh", p, v)
    np.testing.assert_allclose(want[:, :, ::3], plain, atol=2e-5)


def test_flash_window_never_visits_the_key_blocks_before_the_window():
    """Keys and values before every query block's window are NaN: a kernel
    that loaded them (even under a mask: NaN * 0 is NaN in the PV product)
    would return NaN.  At 1,024 positions with 128-row blocks and a window
    of 128, each query block reads two key blocks of eight."""
    from paddle_tpu.ops import flash_attention as fa_mod  # the function
    import importlib

    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    seq, w, blk = 1024, 128, 128
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k[0], (1, 2, seq, 32))
    kk = jax.random.normal(k[1], (1, 2, seq, 32))
    v = jax.random.normal(k[2], (1, 2, seq, 32))
    want = flash_attention_reference(*(jnp.swapaxes(x, 1, 2) for x in (q, kk, v)),
                                     causal=True, window=w)
    # the last query block alone, against keys whose first 6 blocks are NaN
    nan_head = jnp.full((1, 2, 6 * blk, 32), jnp.nan)
    kk_n = jnp.concatenate([nan_head, kk[:, :, 6 * blk:]], axis=2)
    v_n = jnp.concatenate([nan_head, v[:, :, 6 * blk:]], axis=2)
    out, _lse = fa._fwd(q[:, :, -blk:], kk_n, v_n, 1 / math.sqrt(32), True,
                        blk, blk, w)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(jnp.swapaxes(out, 1, 2), want[:, -blk:],
                               atol=2e-5)
    # without the window the same call reads them
    out, _ = fa._fwd(q[:, :, -blk:], kk_n, v_n, 1 / math.sqrt(32), True, blk,
                     blk)
    assert not bool(jnp.isfinite(out).all())
    assert fa_mod is flash_attention


def test_flash_window_backward_refuses_by_name_and_needs_causal():
    q = jnp.ones((1, 128, 2, 32))
    with pytest.raises(NotImplementedError, match="backward kernels take no window"):
        jax.grad(lambda q: flash_attention(q, q, q, causal=True,
                                           window=16).sum())(q)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, window=16)
    # without a window the backward is what it was
    g = jax.grad(lambda q: flash_attention(q, q, q, causal=True).sum())(q)
    assert bool(jnp.isfinite(g).all())


def test_require_vmem_says_a_window_does_not_lift_the_limit():
    import importlib

    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    with pytest.raises(ValueError, match="with or without a window"):
        fa._require_vmem("forward", "seq_k", 65536, 512, 128, 512)


# ------------------------------------------------- (d) the windowed paged read

@pytest.mark.parametrize("lens", [(1, 3, 7), (8, 9, 12), (13, 40, 31)])
def test_window_paged_read_matches_a_dense_computation(lens):
    """Rings filled position by position (as decode writes them), read at
    lengths below, at and past the window (8) and the ring (12)."""
    b, n, nkv, h, bs, w = 3, 6, 2, 8, 4, 8
    cls = CacheClass((0,), (PoolSpec("wk", nkv, h, "float32"),), window=w)
    r = cls.ring_blocks(bs)
    assert r == 3
    rng = np.random.default_rng(sum(lens))
    top = max(lens)
    keys = jnp.asarray(rng.standard_normal((b, top, nkv, h)), jnp.float32)
    vals = jnp.asarray(rng.standard_normal((b, top, nkv, h)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, n, h)), jnp.float32)
    tab = jnp.arange(b * r, dtype=jnp.int32).reshape(b, r)
    kp = jnp.zeros((b * r, nkv, bs, h))
    vp = jnp.zeros((b * r, nkv, bs, h))
    for t in range(top):
        # rows shorter than t + 1 stop writing: rewrite their last position
        pos = jnp.asarray([[min(t, n_ - 1)] for n_ in lens], jnp.int32)
        at = pos[:, 0]
        kp = pa.ring_write_chunk(kp, keys[jnp.arange(b), at][:, None], tab, pos)
        vp = pa.ring_write_chunk(vp, vals[jnp.arange(b), at][:, None], tab, pos)
    lens_a = jnp.asarray(lens, jnp.int32)
    got = pa.paged_window_attention(q[:, None], kp, vp, tab, lens_a, w)[:, 0]
    want = ref.window_attention(q, keys, vals, lens_a, w)
    np.testing.assert_allclose(got, want, atol=1e-5)
    read, live = pa.window_positions(tab, bs, lens_a, w,
                                     jnp.asarray([True, True, False]))
    assert int(read) == 2 * r * bs
    assert int(live) == min(lens[0], w) + min(lens[1], w)


def test_ring_blocks_cover_a_window_wherever_it_falls():
    for w, bs in ((512, 128), (8, 4), (500, 128), (1, 16), (129, 128)):
        r = CacheClass((0,), (), window=w).ring_blocks(bs)
        for t in range(w - 1, w + 3 * bs):
            assert t // bs - (t - w + 1) // bs + 1 <= r, (w, bs, t)
    assert CacheClass((0,), (), window=512).ring_blocks(128) == 5


# ----------------------------------------------------- (e) YaRN, partial rotary

def test_yarn_table_is_the_formula_written_out():
    inv, af, rot = rope_inv_freq(LAGUNA_FULL, 128)
    d = 64
    assert rot == d and inv.shape == (32,) and inv.dtype == np.float32
    corr = lambda r: d * math.log(8192 / (2 * math.pi * r)) / (2 * math.log(500000))  # noqa: E731
    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (9, 18)
    want = []
    for i in range(32):
        pos = 500000 ** (2 * i / d)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append((1 - ramp) / pos + ramp / (128 * pos))
    np.testing.assert_allclose(inv, np.asarray(want), rtol=1e-6)
    assert inv[0] == 1.0
    assert inv[9] == pytest.approx(0.0249554, rel=1e-5)
    assert inv[18] == pytest.approx(4.86541e-6, rel=1e-5)
    assert af == pytest.approx(1.48520, abs=1e-5)
    assert af == pytest.approx(0.1 * math.log(128) + 1, abs=1e-12)
    # where the config gives no factor, the standard one
    assert rope_inv_freq({**LAGUNA_FULL, "attention_factor": None}, 128)[1] \
        == pytest.approx(af, abs=1e-12)
    # the reference computes the same table on its own
    r_inv, r_af, r_rot = ref.inv_freq(LAGUNA_FULL, 128)
    np.testing.assert_array_equal(inv, r_inv)
    assert (r_af, r_rot) == (af, rot)
    # plain rope on the sliding layers, all lanes
    inv, af, rot = rope_inv_freq({"rope_type": "default", "rope_theta": 10000,
                                  "partial_rotary_factor": 1}, 128)
    np.testing.assert_allclose(inv, 10000.0 ** (-np.arange(64) / 64), rtol=1e-6)
    assert (af, rot) == (1.0, 128)
    with pytest.raises(ValueError, match="rope_type"):
        rope_inv_freq({"rope_type": "llama3", "rope_theta": 1.0}, 128)


def test_partial_rotary_leaves_the_upper_lanes_untouched():
    inv, af, rot = rope_inv_freq(LAGUNA_FULL, 128)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 3, 4, 128)),
                    jnp.float32)
    pos = jnp.asarray([[5, 900, 8000], [0, 1, 77]], jnp.int32)
    cos, sin = attention.rope_at_freq(pos.reshape(-1), inv, af)
    at = jnp.arange(6, dtype=jnp.int32).reshape(2, 3)
    y = attention.rotate(x, cos, sin, at, rot)
    np.testing.assert_array_equal(y[..., 64:], x[..., 64:])
    # the rotated lanes: pairs (2i, 2i+1) turned by pos * inv_i, times af
    ang = np.asarray(pos, np.float64)[..., None] * inv.astype(np.float64)
    even, odd = np.asarray(x[..., 0:64:2]), np.asarray(x[..., 1:64:2])
    c, s = np.cos(ang)[:, :, None] * af, np.sin(ang)[:, :, None] * af
    np.testing.assert_allclose(y[..., 0:64:2], even * c - odd * s, atol=2e-3)
    np.testing.assert_allclose(y[..., 1:64:2], odd * c + even * s, atol=2e-3)
    # position 0 scales the rotated lanes by af and turns nothing
    np.testing.assert_allclose(y[1, 0, :, :64], x[1, 0, :, :64] * af, rtol=1e-6)


# ------------------------------------------------------------- (f) the router

def _old_sigmoid_route(m, router_w, *, top_k, scale, normalize=True):
    """`route` as it stood before it took a scoring (PR 30's tree)."""
    logits = jnp.dot(m.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    top_s, top_i = jax.lax.top_k(jax.nn.sigmoid(logits), top_k)
    w = top_s
    if normalize:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return top_i.astype(jnp.int32), w * jnp.float32(scale)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_sigmoid_route_is_bit_for_bit_what_it_was(dtype):
    rng = np.random.default_rng(1)
    m = jnp.asarray(rng.standard_normal((300, 64)), dtype)
    w = jnp.asarray(rng.standard_normal((64, 256)) / 8, dtype)
    for normalize in (True, False):
        new = experts.route(m, w, top_k=8, scale=2.5, normalize=normalize)
        old = _old_sigmoid_route(m, w, top_k=8, scale=2.5, normalize=normalize)
        np.testing.assert_array_equal(new[0], old[0])
        np.testing.assert_array_equal(new[1], old[1])
    from paddle_tpu.models import mla_moe

    assert mla_moe.route is experts.route
    assert mla_moe.routed_experts is experts.routed_experts
    assert mla_moe.RoutedExperts is experts.RoutedExperts


def test_softmax_route_matches_a_float64_top_10():
    rng = np.random.default_rng(2)
    m = rng.standard_normal((400, 64))
    w = rng.standard_normal((64, 256)) / 8
    top_i, wt = experts.route(jnp.asarray(m, jnp.float32),
                              jnp.asarray(w, jnp.float32), top_k=10, scale=2.5,
                              scoring="softmax")
    logits = m.astype(np.float32).astype(np.float64) @ w.astype(np.float32).astype(np.float64)
    s = np.exp(logits - logits.max(-1, keepdims=True))
    s /= s.sum(-1, keepdims=True)
    order = np.argsort(-s, axis=-1)[:, :10]
    np.testing.assert_array_equal(np.sort(np.asarray(top_i), -1),
                                  np.sort(order, -1))
    chosen = np.take_along_axis(s, np.asarray(top_i), -1)
    np.testing.assert_allclose(wt, 2.5 * chosen / chosen.sum(-1, keepdims=True),
                               rtol=2e-5)
    np.testing.assert_allclose(np.asarray(wt).sum(-1), 2.5, rtol=1e-5)
    with pytest.raises(KeyError):
        experts.route(jnp.zeros((2, 4)), jnp.zeros((4, 8)), top_k=2, scale=1.0,
                      scoring="tanh")


# ----------------------------------------- (g) the ring's size, and admission

def test_window_pools_do_not_grow_with_the_output_and_admit_what_pages_refuse(model):
    """The window class's pools are max_batch rings whatever is asked for;
    the paged class alone is sized by `num_blocks`.  A request whose prompt
    + output needs 12 blocks is admitted by an engine with 12 pages and 2
    slots; all five layers paged would need 12 pages in each of them, the
    same count, but the window layers' bytes stay at 3 blocks a slot."""
    sizes = {}
    for new in (8, 40):
        serving.reset_decode_stats()
        eng = serving.GenerationEngine(model, max_batch=2, block_size=4,
                                       num_blocks=24)
        p = np.arange(7, dtype=np.int32)
        assert eng.add_request("a", p, max_new_tokens=new) is not None
        while eng.has_work():
            eng.step()
        st = serving.decode_stats()
        sizes[new] = (st["wk_pool_bytes"], st["wv_pool_bytes"],
                      st["window_ring_blocks"])
        assert len(eng.result("a")) == new
        assert [p_[0].shape[0] for p_ in eng._pools] == [26, 26, 6, 6]
    assert sizes[8] == sizes[40]
    # the allocator's free list is whole again, and no ring page was ever in it
    assert sorted(eng._free) == list(range(24))
    # a request wider than the paged table still raises, as ever
    with pytest.raises(RuntimeError, match="per-seq table width"):
        eng.add_request("wide", np.arange(40, dtype=np.int32),
                        max_new_tokens=40)
    # all-paged sizing of the same bytes: 5 layers share what 2 + ring hold.
    # k/v bytes of this engine = 2 layers x 26 + 3 layers x 6 = 70 blocks a
    # pool; five paged layers would get 70 // 5 - 2 = 12 pages: a 7 + 40
    # token request (12 blocks) would fill the engine alone, two would queue
    eng = serving.GenerationEngine(model, max_batch=2, block_size=4,
                                   num_blocks=24)
    assert eng.add_request("a", np.arange(7, dtype=np.int32), 40) is not None
    assert eng.add_request("b", np.arange(7, dtype=np.int32) + 1, 40) is not None
    assert not eng.pending_requests()


def test_a_slot_serves_request_after_request_through_one_ring(model):
    """The ring is the slot's: a second request in the same slot pours its
    own window over what the first left, and reads none of it."""
    eng = serving.GenerationEngine(model, max_batch=1, block_size=4,
                                   num_blocks=32)
    w, sizes = _reference(model)
    rng = np.random.default_rng(9)
    for name, n in (("first", 30), ("second", 6), ("third", 17)):
        p = rng.integers(0, 256, n).astype(np.int32)
        eng.add_request(name, p, max_new_tokens=12)
        while eng.has_work():
            eng.step()
        toks = eng.result(name)
        ids = np.concatenate([p, toks[:-1]])
        lg = np.asarray(ref.logits_at(w, sizes, ids,
                                      list(range(len(p) - 1, len(ids)))))
        assert toks == [int(t) for t in lg.argmax(-1)], name


# ------------------------------------------------- (h) the refusals, by name

def _draft():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    return LlamaForCausalLM(llama_tiny(dtype="float32", vocab_size=256))


@pytest.mark.parametrize("kwargs,named", [
    ({"kv_cache_dtype": "int8"}, "int8 pool"),
    ({"prefix_cache": True}, "prefix cache"),
    ({"prefill_chunk": 16}, "chunked prefill"),
    ({"prefill_chunk_blocks": 2}, "interleaved prefill"),
    ({"adapters": 4}, "LoRA adapter slots"),
    ({"draft_model": "llama"}, "speculative decoding"),
    ({"mesh": "mesh"}, "a mesh"),
])
def test_features_built_for_kv_pages_refuse_a_window_class_by_name(
        model, kwargs, named):
    if kwargs.get("draft_model"):
        kwargs = {"draft_model": _draft()}
    if kwargs.get("mesh"):
        from jax.sharding import Mesh

        kwargs = {"mesh": Mesh(np.array(jax.devices()[:2]), ("mp",))}
    with pytest.raises(NotImplementedError, match=named) as e:
        serving.GenerationEngine(model, max_batch=2, block_size=4,
                                 num_blocks=16, **kwargs)
    assert "a window class" in str(e.value) and "'wk'" in str(e.value)


def test_snapshot_page_shipping_and_parking_refuse_or_stand_aside(model, tmp_path):
    serving.reset_decode_stats()
    eng = serving.GenerationEngine(model, max_batch=1, block_size=4,
                                   num_blocks=16)
    with pytest.raises(NotImplementedError, match="engine snapshot"):
        eng.snapshot(str(tmp_path))
    with pytest.raises(NotImplementedError, match="page shipping"):
        eng.adopt_pages([1] * 8, [], [])
    p = np.arange(10, dtype=np.int32)
    eng.add_request("low", p, max_new_tokens=4, priority="low")
    assert eng.add_request("high", p + 1, max_new_tokens=4,
                           priority="high") is None
    while eng.has_work():
        eng.step()
    assert len(eng.result("low")) == len(eng.result("high")) == 4
    assert serving.decode_stats()["preemptions"] == 0


def test_the_contract_refuses_a_prefix_and_kv_only_arguments(model):
    c = model.serving_contract()
    with pytest.raises(NotImplementedError, match="whole prompt"):
        c.forward_cached(paddle.to_tensor(np.zeros((1, 4), np.int32)), [], 4)
    with pytest.raises(NotImplementedError, match="chunk"):
        c.decode(None, None, None, None, chunk=True)
    # a specification: names unique, layers covered once
    with pytest.raises(ValueError, match="unique"):
        CacheSpec.of([CacheClass((0,), (PoolSpec("k", 1, 1, "float32"),)),
                      CacheClass((1,), (PoolSpec("k", 1, 1, "float32"),), 4)])
    with pytest.raises(ValueError, match="cover"):
        CacheSpec.of([CacheClass((0, 2), (PoolSpec("k", 1, 1, "float32"),))])
    # a model of full layers only is one paged K/V class
    full = WindowMoeForCausalLM(window_moe_tiny(
        layer_types=(FULL,) * 5, num_attention_heads_per_layer=(4,) * 5))
    assert full.serving_contract().spec.kv_pair
    with pytest.raises(ValueError, match="group"):
        window_moe_tiny(num_attention_heads_per_layer=(4, 5, 6, 6, 4))
    assert SLIDING in window_moe_tiny().layer_types
