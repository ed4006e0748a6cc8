"""The model with attention in a convolved latent, a one-token value shift, an
MLP router carried across depth and top-1 experts with a skip choice
(models/cca_moe.py) against its plain reference (perfbench/reference_cca_moe.py)
at a small size on the CPU, float32, seeded: `GenerationEngine` through the
paged pools AND the state a slot (prompts right-padded to their bucket, so that
the state is the one after `n_real - 1`), a slot reused, inactive rows leaving
state alone, the two shares of the experts adding up to the uncut layer, each
mechanism telling when it is left out, `experts.py`'s split bit for bit what it
was, the engine's optional features refusing a state class by name, and a dense
engine building exactly the programs it built before."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler, serving
from paddle_tpu.models import cca_moe, experts
from paddle_tpu.models.cca_moe import CcaMoeForCausalLM, cca_moe_tiny
from paddle_tpu.models.contract import CacheClass, CacheSpec, PoolSpec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from perfbench import reference_cca_moe as ref  # noqa: E402
from perfbench.families import cca_moe as fam  # noqa: E402


def _model(seed=0, **kw):
    paddle.seed(seed)
    m = CcaMoeForCausalLM(cca_moe_tiny(**kw))
    fam.perturb(m, seed)     # scales off 1; biases, tau, gamma, beta off 0
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _model(held_experts=(2, 5))


def _reference(m):
    return fam.reference_weights(m), fam._sizes_of(m.config)


def _state_pools(eng):
    """{name: [layer arrays]} of the engine's state class."""
    names = [p.name for p in eng._spec.pools]
    return {n: list(np.asarray(eng._pools[names.index(n)][0]))
            for n in ("cca_z", "cca_c", "cca_v2")}


# --------------------------------------------- (a) the engine and the reference

def test_forward_logits_match_the_reference():
    m = _model(seed=3)
    ids = np.random.default_rng(3).integers(0, 256, 45).astype(np.int32)
    got = np.asarray(m(paddle.to_tensor(ids[None]))._value[0])
    w, sizes = _reference(m)
    want = np.asarray(ref.logits_at(w, sizes, ids, list(range(45))))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)


def test_prefill_then_decode_logits_match_the_reference(model):
    """Prompts of 5, 19 and 34 tokens are right-padded to buckets of 8, 32
    and 64: the state a slot must be the one after position n_real - 1, or
    the first decode step's convolutions and value shift read padding.  The
    LOGITS of the next token, from the resident pools and state
    (`next_token_logits`), after the prefill and again after two macro-steps,
    against the reference's full forward."""
    serving.reset_decode_stats()
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (5, 19, 34)]
    eng = serving.GenerationEngine(model, max_batch=3, block_size=8,
                                   num_blocks=48)
    for i, p in enumerate(prompts):
        assert eng._prefill_bucket(len(p), 0) > len(p)
        eng.add_request(f"r{i}", p, max_new_tokens=30)
    w, sizes = _reference(model)

    def compare():
        before = _state_pools(eng)
        got = eng.next_token_logits()
        for n, layers in _state_pools(eng).items():      # functional
            for a, b in zip(before[n], layers):
                np.testing.assert_array_equal(a, b)
        for i, p in enumerate(prompts):
            ids = np.concatenate([p, eng.result(f"r{i}")]).astype(np.int32)
            want = np.asarray(ref.logits_at(w, sizes, ids, [len(ids) - 1]))[0]
            np.testing.assert_allclose(got[f"r{i}"], want, atol=3e-4, rtol=0)
        return got

    compare()
    eng.step()
    eng.step()
    got = compare()
    out = eng.step()
    assert [out[f"r{i}"][0] for i in range(3)] == [
        int(got[f"r{i}"].argmax()) for i in range(3)]
    spec = eng._spec
    assert [c.slot_state for c in spec.classes] == [False, True]
    assert spec.slot_state and not spec.kv_pair and spec.per_class_tables
    st = serving.decode_stats()
    # 3 layers x 3 slots of (2 x 96 + 16) float32 values, whatever the lengths
    assert st["slot_state_bytes"] == 3 * 3 * (96 + 96 + 16) * 4
    assert st["slot_state_bytes"] == (st["cca_z_pool_bytes"]
                                      + st["cca_c_pool_bytes"]
                                      + st["cca_v2_pool_bytes"])
    assert st["moe_prefill_assignments"] == (5 + 19 + 34) * 3
    assert st["moe_layer_steps"] == 24 * 3
    assert st["moe_assignments"] == 3 * 24 * 3
    assert 0 < st["moe_skipped"] + st["moe_held_assignments"] <= 3 * 24 * 3


def test_engine_streams_match_the_reference(model):
    """Every token the engine emits (the prefill program's, then the
    macro-step's through the pages and the state) is the reference's argmax
    given the tokens before it, for rows of different lengths in one batch."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (3, 21, 40)]
    eng = serving.GenerationEngine(model, max_batch=3, block_size=8,
                                   num_blocks=48)
    firsts = [eng.add_request(f"r{i}", p, max_new_tokens=20)
              for i, p in enumerate(prompts)]
    while eng.has_work():
        eng.step()
    w, sizes = _reference(model)
    for i, p in enumerate(prompts):
        toks = eng.result(f"r{i}")
        assert toks[0] == firsts[i] and len(toks) == 20
        ids = np.concatenate([p, toks[:-1]])
        lg = np.asarray(ref.logits_at(w, sizes, ids,
                                      list(range(len(p) - 1, len(ids)))))
        assert toks == [int(t) for t in lg.argmax(-1)], i


# ------------------------------------------------- (b) a slot's own state

def test_a_reused_slot_starts_from_its_own_prompts_state(model):
    """One slot serves request after request: the second request's logits
    are those of a fresh engine, whatever the first left in the slot's
    state and pages."""
    rng = np.random.default_rng(9)
    a, b = (rng.integers(0, 256, n).astype(np.int32) for n in (13, 6))
    eng = serving.GenerationEngine(model, max_batch=1, block_size=8,
                                   num_blocks=16)
    eng.add_request("a", a, max_new_tokens=10)
    while eng.has_work():
        eng.step()
    left = _state_pools(eng)
    assert all(np.abs(x).max() > 0 for x in left["cca_z"])
    eng.add_request("b", b, max_new_tokens=10)
    eng.step()
    got = eng.next_token_logits()["b"]
    fresh = serving.GenerationEngine(model, max_batch=1, block_size=8,
                                     num_blocks=16)
    fresh.add_request("b", b, max_new_tokens=10)
    fresh.step()
    np.testing.assert_array_equal(got, fresh.next_token_logits()["b"])
    assert eng.result("b") == fresh.result("b")


def test_inactive_rows_leave_state_untouched(model):
    """A lane that is empty, or whose row finished inside a macro-step, is
    still computed; what it computes never reaches a slot's state."""
    eng = serving.GenerationEngine(model, max_batch=3, block_size=8,
                                   num_blocks=32)
    names = [p.name for p in eng._spec.pools]
    for n in ("cca_z", "cca_c", "cca_v2"):            # a mark in every slot
        at = names.index(n)
        eng._pools[at] = [jnp.full_like(p, 7.0) for p in eng._pools[at]]
    p = np.arange(10, dtype=np.int32)
    eng.add_request("long", p, max_new_tokens=20)      # slot 0
    eng.add_request("short", p + 3, max_new_tokens=4)  # slot 1: ends mid-step
    eng.step()
    after_short = _state_pools(eng)
    eng.step()
    end = _state_pools(eng)
    for n in end:
        for first, last in zip(after_short[n], end[n]):
            assert (last[2] == 7.0).all()                       # never served
            np.testing.assert_array_equal(first[1], last[1])    # finished
        # live: rewritten (the first layer's state is a function of the
        # token alone, and a row may emit one token twice: some layer's)
        assert any(not np.array_equal(first[0], last[0])
                   for first, last in zip(after_short[n], end[n])), n


# --------------------------------------------------- (c) the shares add up

def test_two_shares_of_the_experts_add_up_to_the_uncut_layer():
    """What the two shares of 8 experts give, the skip choice (which every
    share computes alike) counted ONCE, is the uncut reference's expert
    sublayer output y: MLP router with a carried state, top-1 of 16 + skip."""
    rng = np.random.default_rng(11)
    h, f, e, r, t = 32, 24, 16, 12, 200
    mat = lambda *s: jnp.asarray(  # noqa: E731
        rng.standard_normal(s) / np.sqrt(s[0]), jnp.float32)
    vec = lambda n, at=0.0: jnp.asarray(  # noqa: E731
        at + 0.3 * rng.standard_normal(n), jnp.float32)
    m = jnp.asarray(rng.standard_normal((t, h)), jnp.float32)
    r_prev = jnp.asarray(rng.standard_normal((t, r)), jnp.float32)
    router = {"down_w": mat(h, r), "down_b": vec(r), "gamma": vec(r, 0.7),
              "norm_g": vec(r, 1.0), "w1": mat(r, r), "b1": vec(r),
              "w2": mat(r, r), "b2": vec(r), "w3": 6 * mat(r, e + 1),
              "beta": 0.2 * vec(e + 1)}
    gate_up = [mat(h, 2 * f) for _ in range(e)]
    down = [mat(f, h) for _ in range(e)]

    p, chosen, r_ref, _gap, _top = ref._route(
        m, r_prev, router, eps=1e-5, dt=jnp.float32, no_carry=False,
        no_balance=False)
    weight = jnp.take_along_axis(p, chosen[:, None], axis=1)
    whole = jnp.where(chosen[:, None] == e, weight * m, 0.0)
    for i in range(e):
        whole = whole + jnp.where(chosen[:, None] == i, weight, 0.0) \
            * ref._expert(m, gate_up[i], down[i], dt=jnp.float32)
    picked, w, r_got = cca_moe.route_mlp(m, r_prev, router, eps=1e-5)
    np.testing.assert_array_equal(picked[:, 0], chosen)
    np.testing.assert_allclose(r_got, r_ref, atol=1e-5)
    assert int((chosen == e).sum()) > 0 and len(set(np.asarray(chosen))) > 8
    parts = jnp.where(picked == e, w * m, 0.0)          # skip, counted ONCE
    skipped = held = 0
    for first in (0, 8):
        out, counts = experts.expert_loop(
            m, picked, w, jnp.stack(gate_up[first:first + 8]),
            jnp.stack(down[first:first + 8]), held=(first, 8), routed=e)
        parts = parts + out
        held += int(counts["held"])
        skipped = int(counts["skipped"])
        assert int(counts["assignments"]) == t
    assert skipped == int((chosen == e).sum()) and held + skipped == t
    np.testing.assert_allclose(parts, whole, atol=5e-5)


def test_a_stack_of_several_layers_experts_is_read_from_its_base():
    rng = np.random.default_rng(12)
    h, f, t = 16, 8, 40
    m = jnp.asarray(rng.standard_normal((t, h)), jnp.float32)
    chosen = jnp.asarray(rng.integers(0, 5, (t, 1)), jnp.int32)   # 4 = skip
    w = jnp.asarray(rng.random((t, 1)), jnp.float32)
    gate_up = jnp.asarray(rng.standard_normal((12, h, 2 * f)), jnp.float32)
    down = jnp.asarray(rng.standard_normal((12, f, h)), jnp.float32)
    for layer in range(3):
        want, _ = experts.expert_loop(
            m, chosen, w, gate_up[layer * 4:layer * 4 + 4],
            down[layer * 4:layer * 4 + 4], held=(0, 4), routed=4)
        got, counts = jax.jit(lambda b: experts.expert_loop(
            m, chosen, w, gate_up, down, held=(0, 4), routed=4, base=b))(
                jnp.int32(layer * 4))
        np.testing.assert_array_equal(got, want)
        assert int(counts["skipped"]) == int((chosen == 4).sum())


# ------------------------------------- (d) every mechanism tells when left out

@pytest.mark.parametrize("control", ["no_conv0", "no_conv1", "no_shift",
                                     "no_carry", "skip_zero", "no_balance"])
def test_a_mechanism_left_out_of_the_reference_does_not_compare_equal(
        model, control):
    ids = np.random.default_rng(8).integers(0, 256, 60).astype(np.int32)
    got = np.asarray(model(paddle.to_tensor(ids[None]))._value[0])
    w, sizes = _reference(model)
    at = list(range(60))
    right = np.asarray(ref.logits_at(w, sizes, ids, at))
    wrong = np.asarray(ref.logits_at(w, {**sizes, control: True}, ids, at))
    assert np.abs(got - right).max() < 3e-4
    assert np.abs(got - wrong).max() > 1e-2, control


def test_scopes_and_counters_reach_the_programs(model):
    eng = serving.GenerationEngine(model, max_batch=2, block_size=8,
                                   num_blocks=32)
    text = eng._build_step(2).lower(*eng._step_avals()).as_text(debug_info=True)
    for scope in ("cca.conv", "cca.attend", "moe.router_mlp", "moe.route",
                  "moe.experts"):
        assert scope in text, scope
    fn = eng._prefill_program(16, 0)
    text = fn.lower([t._value for t in eng._state], np.zeros((1, 16), np.int32),
                    np.int32(16), None).as_text(debug_info=True)
    for scope in ("cca.conv", "cca.attend", "moe.router_mlp", "moe.experts"):
        assert scope in text, scope
    assert {"cca.conv", "cca.attend",
            "moe.router_mlp"} <= set(profiler.SCOPE_NAMES)
    # one layer's body whatever the depth: the macro-step of a deeper model
    # holds no more dots
    deep = serving.GenerationEngine(_model(num_hidden_layers=6), max_batch=2,
                                    block_size=8, num_blocks=32)
    deeper = deep._build_step(2).lower(*deep._step_avals()).as_text()
    plain = eng._build_step(2).lower(*eng._step_avals()).as_text()
    assert deeper.count("dot_general") == plain.count("dot_general")
    assert eng.warmup(prefill=False, adopt=False)["chunks"]


# ------------------------------------ (e) experts.py's split, bit for bit

def _parent_routed_experts(m, router_w, gate_up, down, *, held, top_k, scale,
                           normalize=True, scoring="sigmoid", active=None,
                           tile=experts.EXPERT_TILE):
    """`models.experts.routed_experts` as it stood before the split (PR 32's
    tree), cloned: what pangu's and laguna's layers computed."""
    first, count = held
    t = m.shape[0]
    top_i, w = experts.route(m, router_w, top_k=top_k, scale=scale,
                             normalize=normalize, scoring=scoring)
    local = top_i - first
    mine = (local >= 0) & (local < count)
    live = jnp.ones((t,), bool) if active is None else active
    mine = mine & live[:, None]
    key = jnp.where(mine, local, count).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    rows_of = order // top_k
    w_of = w.reshape(-1)[order]
    per = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0,
                  dtype=jnp.int32)
    start = jnp.cumsum(per) - per
    tile = min(tile, t)
    n_pairs = t * top_k
    stacked = not isinstance(gate_up, (list, tuple))
    f = down[0].shape[0]

    def one_tile(e, i, out):
        w_gu, w_d = gate_up[e], down[e]
        at = start[e] + i * tile + jnp.arange(tile, dtype=jnp.int32)
        ok = at < start[e] + per[e]
        at = jnp.minimum(at, n_pairs - 1)
        rows = rows_of[at]
        x = m[rows]
        gu = jnp.dot(x, w_gu, preferred_element_type=jnp.float32)
        act = (jax.nn.silu(gu[:, :f]) * gu[:, f:]).astype(m.dtype)
        y = jnp.dot(act, w_d, preferred_element_type=jnp.float32)
        y = y * jnp.where(ok, w_of[at], 0.0)[:, None]
        return out.at[rows].add(y)

    def expert_pass(e, out):
        if stacked and tile == t:
            return jax.lax.cond(per[e] > 0, lambda o: one_tile(e, 0, o),
                                lambda o: o, out)
        return jax.lax.fori_loop(0, -(-per[e] // tile),
                                 lambda i, o: one_tile(e, i, o), out)

    out = jnp.zeros((t, m.shape[1]), jnp.float32)
    if stacked:
        out = jax.lax.fori_loop(0, count, expert_pass, out)
    else:
        for e in range(count):
            out = expert_pass(e, out)
    counts = {"assignments": jnp.sum(live, dtype=jnp.int32) * top_k,
              "held": jnp.sum(per), "peak": jnp.max(per),
              "touched": jnp.sum(per > 0, dtype=jnp.int32),
              "layer_steps": jnp.any(live).astype(jnp.int32)}
    return out, counts


@pytest.mark.parametrize("scoring,top_k,held,rows,tile", [
    ("sigmoid", 8, (16, 16), 32, 256),      # pangu's decode step
    ("sigmoid", 8, (16, 16), 300, 128),     # ... and a prefill's tiles
    ("softmax", 10, (32, 32), 32, 256),     # laguna's
    ("softmax", 10, (32, 32), 300, 128),
])
@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_one_matrix_routers_layers_compute_bit_for_bit_what_they_did(
        scoring, top_k, held, rows, tile, stacked, dtype):
    rng = np.random.default_rng(21)
    h, f, e = 32, 16, 256
    dt = jnp.dtype(dtype)
    m = jnp.asarray(rng.standard_normal((rows, h)), dt)
    router = jnp.asarray(rng.standard_normal((h, e)), dt)
    gate_up = [jnp.asarray(rng.standard_normal((h, 2 * f)) / 6, dt)
               for _ in range(held[1])]
    down = [jnp.asarray(rng.standard_normal((f, h)) / 5, dt)
            for _ in range(held[1])]
    if stacked:
        gate_up, down = jnp.stack(gate_up), jnp.stack(down)
    active = jnp.asarray(rng.random(rows) < 0.8)
    kw = dict(held=held, top_k=top_k, scale=2.5, normalize=True,
              scoring=scoring, active=active, tile=tile)
    got, counts = jax.jit(lambda: experts.routed_experts(
        m, router, gate_up, down, **kw))()
    want, was = jax.jit(lambda: _parent_routed_experts(
        m, router, gate_up, down, **kw))()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(counts.pop("skipped")) == 0
    assert {k: int(v) for k, v in counts.items()} == {
        k: int(v) for k, v in was.items()}


# ------------------------------------------------- (f) the refusals, by name

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
def test_features_built_for_kv_pages_refuse_a_state_class_by_name(
        model, kwargs, named):
    if kwargs.get("draft_model"):
        kwargs = {"draft_model": _draft()}
    if kwargs.get("mesh"):
        from jax.sharding import Mesh

        kwargs = {"mesh": Mesh(np.array(jax.devices()[:2]), ("mp",))}
    with pytest.raises(NotImplementedError, match=named) as e:
        serving.GenerationEngine(model, max_batch=2, block_size=8,
                                 num_blocks=16, **kwargs)
    assert "a state class" in str(e.value) and "'cca_z'" in str(e.value)


def test_snapshot_page_shipping_and_parking_refuse_or_stand_aside(model, tmp_path):
    serving.reset_decode_stats()
    eng = serving.GenerationEngine(model, max_batch=1, block_size=8,
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


def test_the_contract_and_the_specification(model):
    c = model.serving_contract()
    with pytest.raises(NotImplementedError, match="whole prompt"):
        c.forward_cached(paddle.to_tensor(np.zeros((1, 4), np.int32)), [], 4)
    with pytest.raises(NotImplementedError, match="chunk"):
        c.decode(None, None, None, None, chunk=True)
    k = PoolSpec("k", 1, 1, "float32")
    s = PoolSpec("s", 1, 4, "float32")
    # a state class names layers that have a paged class too, or stands alone
    both = CacheSpec.of([CacheClass((0, 1), (k,)),
                         CacheClass((1,), (s,), slot_state=True)])
    assert [[p.name for _c, p in both.layer_pools(i)] for i in range(2)] == [
        ["k"], ["k", "s"]]
    alone = CacheSpec.of([CacheClass((0,), (s,), slot_state=True)])
    assert alone.per_class_tables and alone.slot_state and not alone.kv_pair
    assert not both.classes[1].paged and both.classes[0].paged
    with pytest.raises(ValueError, match="not both"):
        CacheClass((0,), (s,), window=4, slot_state=True)
    with pytest.raises(ValueError, match="cover"):       # layer 1 kept twice
        CacheSpec.of([CacheClass((0, 1), (k,)),
                      CacheClass((1,), (PoolSpec("w", 1, 1, "float32"),), 4)])
    with pytest.raises(ValueError, match="kernels of 2"):
        cca_moe_tiny(cca_time0=4)


# ------------------------- (g) a model without a state class: the parent's programs

_DENSE_DRIVE = """
import numpy as np, jax
import paddle_tpu as paddle
from paddle_tpu import serving, profiler
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
jax.config.update("jax_enable_compilation_cache", False)
paddle.seed(0)
m = LlamaForCausalLM(llama_tiny(dtype="float32")); m.eval()
n = lambda: profiler.compile_stats()["compiles"]
c = [n()]
eng = serving.GenerationEngine(m, max_batch=2, block_size=8, num_blocks=16)
c.append(n())
eng.warmup()
c.append(n())
eng.add_request("a", np.arange(11, dtype=np.int32)[None] % 7, max_new_tokens=10)
eng.add_request("b", np.arange(5, dtype=np.int32)[None] % 7, max_new_tokens=4)
while eng.has_work():
    eng.step()
c.append(n())
print("PROGRAMS", [b - a for a, b in zip(c, c[1:])], sorted(eng._step_fns),
      sorted(eng._prefill_fns))
"""


def test_a_dense_engine_builds_the_programs_it_built_before():
    """An engine for a model WITHOUT a state class compiles what it compiled
    at the parent commit, counted in a process of its own with the persistent
    cache off: 2 programs in the constructor, 2 in `warmup` (the macro-step
    and one prefill bucket), 6 while serving two requests (a second bucket,
    the pours, sampling), and the same macro-step and prefill keys.  The
    numbers are the parent's own (PR 32's tree, the same script): PR 33's
    third lifetime was refused for 13% of `setup_s` on a model that had no
    state class."""
    out = subprocess.run(
        [sys.executable, "-c", _DENSE_DRIVE], capture_output=True, text=True,
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu",
                       "PYTHONPATH": REPO})
    line = [l for l in out.stdout.splitlines() if l.startswith("PROGRAMS")]
    assert line, out.stderr[-2000:]
    assert line[0] == "PROGRAMS [2, 2, 6] [8] [(8, 0), (16, 0)]"
