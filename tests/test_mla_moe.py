"""The latent-attention / routed-expert model (models/mla_moe.py) against its
plain reference (perfbench/reference_mla_moe.py) at a small size on the CPU,
float32, seeded: the full forward pass, `GenerationEngine` through the paged
latent pool, the held-expert shares adding up to the uncut layer, droplessness
under a collapsed router, and the model contract's refusals by name."""

import ast
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler, serving
from paddle_tpu.models import mla_moe
from paddle_tpu.models.contract import CacheSpec, PoolSpec
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.models.mla_moe import (MlaMoeForCausalLM, mla_moe_tiny,
                                       routed_experts)
from paddle_tpu.ops import paged_attention as pa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from perfbench import reference_mla_moe as ref  # noqa: E402
from perfbench.families import mla_moe as fam  # noqa: E402


def _model(seed=0, **kw):
    paddle.seed(seed)
    m = MlaMoeForCausalLM(mla_moe_tiny(**kw))
    fam.perturb_norms(m, seed)      # gains away from exactly 1
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _model(held_experts=(2, 4))


def _reference(m):
    return fam.reference_weights(m), fam._sizes_of(m.config)


# ----------------------------------------------------------- (a) full forward

@pytest.mark.parametrize("held", [None, (2, 4), (6, 2)])
def test_forward_logits_match_the_reference(held):
    m = _model(seed=3, held_experts=held)
    ids = np.random.default_rng(0).integers(0, 256, (1, 45)).astype(np.int32)
    got = np.asarray(m(paddle.to_tensor(ids))._value)[0]
    w, sizes = _reference(m)
    want = np.asarray(ref.logits_at(w, sizes, ids[0], list(range(45))))
    assert want.std() > 0.1
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


def test_no_sandwich_norm_and_unnormalised_weights_match_too():
    m = _model(seed=4, sandwich_norm=False, norm_topk_prob=False)
    assert m.model.layers[1].post_attn_norm is None
    ids = np.random.default_rng(1).integers(0, 256, (1, 20)).astype(np.int32)
    w, sizes = _reference(m)
    assert w["layers"][0]["g_post_mlp"] is None and not sizes["normalize"]
    got = np.asarray(m(paddle.to_tensor(ids))._value)[0]
    want = np.asarray(ref.logits_at(w, sizes, ids[0], list(range(20))))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


# ------------------------------------------- (b) the engine, paged latent pool

def _serve(m, prompts, new, **kw):
    eng = serving.GenerationEngine(m, max_batch=len(prompts), block_size=8,
                                   num_blocks=48, **kw)
    firsts = [eng.add_request(f"r{i}", p, max_new_tokens=n)
              for i, (p, n) in enumerate(zip(prompts, new))]
    while eng.has_work():
        eng.step()
    return eng, firsts


def test_engine_streams_match_the_reference_forward(model):
    """Prompts over several blocks, two rows of different length: every token
    the engine emits — the prefill program's, then the macro-step's through
    the paged latent pool — is the reference's argmax given the tokens
    before it, with the reference logit of it the row's maximum to 1e-4."""
    serving.reset_decode_stats()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (21, 37)]
    eng, firsts = _serve(model, prompts, [12, 9])
    w, sizes = _reference(model)
    for i, p in enumerate(prompts):
        toks = eng.result(f"r{i}")
        assert toks[0] == firsts[i] and len(toks) == (12, 9)[i]
        ids = np.concatenate([p, toks[:-1]])
        lg = np.asarray(ref.logits_at(w, sizes, ids,
                                      list(range(len(p) - 1, len(ids)))))
        assert toks == [int(t) for t in lg.argmax(-1)]
    st = serving.decode_stats()
    # one pool a layer, one row a token (24 values, rounded up to one whole
    # 128-lane row: `pool_width`): no K or V pool anywhere
    assert model.config.latent_width == 24 and model.config.pool_width == 128
    assert st["latent_pool_bytes"] == st["pool_bytes"] == 3 * 50 * 8 * 128 * 4
    assert st["k_pool_bytes"] == st["v_pool_bytes"] == 0
    assert eng._spec == CacheSpec(3, (PoolSpec("latent", 1, 128, "float32"),))
    assert len(eng._pools) == 1 and len(eng._pools[0]) == 3


def test_decode_logits_through_the_paged_pool_match_the_reference(model):
    """The logits themselves, where a test can reach them: the contract's
    decode step over the RESIDENT pool after a prefill (what the macro-step
    scans, minus sampling) against the reference's full forward."""
    from paddle_tpu._core.autograd import no_grad

    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (19, 42)]
    eng = serving.GenerationEngine(model, max_batch=2, block_size=8,
                                   num_blocks=32)
    firsts = [eng.add_request(f"r{i}", p, max_new_tokens=6)
              for i, p in enumerate(prompts)]
    W = eng._max_blocks_per_seq
    tables = jnp.asarray([list(s.blocks) + [s.blocks[-1]] * (W - len(s.blocks))
                          for s in eng._slots], jnp.int32)
    lens = jnp.asarray([s.seq_len + 1 for s in eng._slots], jnp.int32)
    tok = jnp.asarray([[f] for f in firsts], jnp.int32)
    contract = model.serving_contract()
    with no_grad():
        h, _, aux = contract.decode(tok, [list(p) for p in eng._pools],
                                    tables, lens)
        got = np.asarray(contract.logits(h)._value[:, -1])
    w, sizes = _reference(model)
    for i, p in enumerate(prompts):
        ids = np.concatenate([p, [firsts[i]]])
        want = np.asarray(ref.logits_at(w, sizes, ids, [len(ids) - 1]))[0]
        np.testing.assert_allclose(got[i], want, atol=1e-4, rtol=0)
    assert int(aux["moe_assignments"]) == 2 * 2 * 2   # rows x top-2 x 2 layers
    assert int(aux["moe_layer_steps"]) == 2


def test_expert_counters_reach_decode_stats_and_decode_line(model):
    serving.reset_decode_stats()
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (16, 24)]
    _serve(model, prompts, [10, 10])
    st = serving.decode_stats()
    # 9 decode token steps a row, 2 rows, top-2, 2 expert layers
    assert st["moe_assignments"] == 9 * 2 * 2 * 2
    assert st["moe_layer_steps"] == 9 * 2
    assert 0 < st["moe_held_assignments"] <= st["moe_assignments"]
    assert st["moe_experts_touched"] <= 4 * st["moe_layer_steps"]
    assert (st["moe_held_assignments"] / 4 <= st["moe_peak_expert_assignments"]
            <= st["moe_held_assignments"])
    # a committed admission's prompt tokens, pads not counted
    assert st["moe_prefill_assignments"] == (16 + 24) * 2 * 2
    from paddle_tpu.profiler.statistics import decode_line

    assert "Expert load: 18 expert-layer steps, 72 assignments" in decode_line(st)
    assert {"mla.prefill", "mla.decode", "moe.route", "moe.experts",
            "moe.shared"} <= set(profiler.SCOPE_NAMES)


def test_scopes_reach_the_programs(model):
    eng = serving.GenerationEngine(model, max_batch=2, block_size=8,
                                   num_blocks=32)
    text = eng._build_step(2).lower(*eng._step_avals()).as_text(debug_info=True)
    for scope in ("mla.decode", "moe.route", "moe.experts", "moe.shared"):
        assert scope in text, scope
    fn = eng._prefill_program(16, 0)
    text = fn.lower([t._value for t in eng._state], np.zeros((1, 16), np.int32),
                    np.int32(16), None).as_text(debug_info=True)
    assert "mla.prefill" in text and "moe.experts" in text


# --------------------------------------------------- (c) the shares add up

def _layer_weights(seed, h=32, f=24, experts=8):
    rng = np.random.default_rng(seed)
    g = lambda *s: jnp.asarray(rng.standard_normal(s) / np.sqrt(s[0]), jnp.float32)  # noqa: E731
    return (g(h, experts), [g(h, 2 * f) for _ in range(experts)],
            [g(f, h) for _ in range(experts)])


def _reference_routed(m, router, gate_up, down, held, top_k, scale):
    """The reference's routed part (no shared expert) for a held range."""
    weight, _, _ = ref._route(m, router, top_k=top_k, scale=scale,
                              normalize=True, dt=jnp.dtype("float32"))
    out = jnp.zeros_like(m)
    for e in range(held[0], held[0] + held[1]):
        out = out + weight[:, e, None] * ref._ffn(m, gate_up[e], down[e],
                                                  jnp.dtype("float32"))
    return out


def test_the_shares_of_a_four_way_split_add_up_to_the_uncut_layer():
    """Every `held` range of a 4-way split computes its own experts' part;
    the parts, with the shared expert counted ONCE, are the uncut layer."""
    router, gate_up, down = _layer_weights(11)
    m = jnp.asarray(np.random.default_rng(12).standard_normal((50, 32)),
                    jnp.float32)
    whole = _reference_routed(m, router, gate_up, down, (0, 8), 3, 2.5)
    total, held_sum = jnp.zeros_like(m), 0
    for first in (0, 2, 4, 6):
        part, counts = routed_experts(
            m, router, gate_up[first:first + 2], down[first:first + 2],
            held=(first, 2), top_k=3, scale=2.5, tile=16)
        np.testing.assert_allclose(
            part, _reference_routed(m, router, gate_up, down, (first, 2), 3, 2.5),
            atol=1e-5)
        assert int(counts["assignments"]) == 50 * 3
        total, held_sum = total + part, held_sum + int(counts["held"])
    assert held_sum == 50 * 3                    # every assignment lives somewhere
    np.testing.assert_allclose(total, whole, atol=1e-5)
    # in the model: two shares of one seed's weights differ by their routed
    # parts only, so the shared expert is in each share once
    full = _model(seed=8)
    layer = full.model.layers[1].mlp
    x = paddle.to_tensor(np.random.default_rng(13).standard_normal((1, 9, 64))
                         .astype(np.float32))
    f_full, _ = layer(x)
    shared = layer.shared_experts(x)._value
    gu = [e.gate_up_proj.weight._value for e in layer.experts]
    dn = [e.down_proj.weight._value for e in layer.experts]
    parts = sum(routed_experts(x._value[0], layer.gate.weight._value,
                               gu[a:a + 2], dn[a:a + 2], held=(a, 2), top_k=2,
                               scale=2.5)[0] for a in (0, 2, 4, 6))
    np.testing.assert_allclose(f_full._value[0], parts + shared[0], atol=1e-5)


# ------------------------------------------------ (d) dropless under collapse

@pytest.mark.parametrize("tile", [8, 64])
def test_a_collapsed_router_drops_nothing(tile):
    """A router rigged so that every token picks the same two experts: all
    40 rows land on each, several passes of the expert loop, none lost."""
    router, gate_up, down = _layer_weights(21)
    m = jnp.abs(jnp.asarray(np.random.default_rng(22).standard_normal((40, 32)),
                            jnp.float32))
    # positive hidden states, so two columns of +1 outscore the rest for all
    router = router.at[:, 3].set(1.0).at[:, 5].set(1.0)
    got, counts = routed_experts(m, router, gate_up[2:6], down[2:6],
                                 held=(2, 4), top_k=2, scale=2.5, tile=tile)
    assert int(counts["peak"]) == 40 and int(counts["held"]) == 80
    assert int(counts["touched"]) == 2
    want = _reference_routed(m, router, gate_up, down, (2, 4), 2, 2.5)
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(got, want, atol=1e-5)
    # rows that are not committed work route nowhere and are not counted
    active = jnp.arange(40) < 25
    got, counts = routed_experts(m, router, gate_up[2:6], down[2:6],
                                 held=(2, 4), top_k=2, scale=2.5, tile=tile,
                                 active=active)
    assert int(counts["held"]) == 50 and int(counts["assignments"]) == 50
    np.testing.assert_allclose(got[:25], want[:25], atol=1e-5)
    assert float(jnp.abs(got[25:]).max()) == 0.0


# ------------------------------------- (e) features that refuse a latent pool

def _draft():
    paddle.seed(0)
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
def test_features_built_for_kv_pools_refuse_at_construction_by_name(
        model, kwargs, named):
    if kwargs.get("draft_model"):
        kwargs = {"draft_model": _draft()}
    if kwargs.get("mesh"):
        from jax.sharding import Mesh

        kwargs = {"mesh": Mesh(np.array(jax.devices()[:2]), ("mp",))}
    with pytest.raises(NotImplementedError, match=named) as e:
        serving.GenerationEngine(model, max_batch=2, block_size=8,
                                 num_blocks=16, **kwargs)
    assert "'latent'" in str(e.value)


def test_snapshot_page_shipping_and_parking_refuse_or_stand_aside(model, tmp_path):
    serving.reset_decode_stats()
    eng = serving.GenerationEngine(model, max_batch=1, block_size=8,
                                   num_blocks=16)
    with pytest.raises(NotImplementedError, match="engine snapshot"):
        eng.snapshot(str(tmp_path))
    with pytest.raises(NotImplementedError, match="page shipping"):
        eng.adopt_pages([1] * 8, [], [])
    # a LOW resident is never parked on a pool the parking lot cannot hold:
    # the HIGH request waits for the slot and both streams complete
    p = np.arange(10, dtype=np.int32)
    eng.add_request("low", p, max_new_tokens=4, priority="low")
    assert eng.add_request("high", p + 1, max_new_tokens=4,
                           priority="high") is None
    while eng.has_work():
        eng.step()
    assert len(eng.result("low")) == len(eng.result("high")) == 4
    assert serving.decode_stats()["preemptions"] == 0


def test_flags_that_turn_a_feature_on_refuse_too(model):
    for flag, value, named in (("FLAGS_prefix_cache", True, "prefix cache"),
                               ("FLAGS_kv_cache_dtype", "int8", "int8 pool"),
                               ("FLAGS_prefill_chunk_blocks", 2, "interleaved")):
        was = paddle.get_flags([flag])[flag]
        paddle.set_flags({flag: value})
        try:
            with pytest.raises(NotImplementedError, match=named):
                serving.GenerationEngine(model, max_batch=1, block_size=8,
                                         num_blocks=16)
        finally:
            paddle.set_flags({flag: was})


# ------------------------------------------------- (f) the engine's imports

def test_the_engine_imports_no_private_name_of_a_model_module():
    path = os.path.join(REPO, "paddle_tpu", "serving", "__init__.py")
    tree = ast.parse(open(path).read())
    seen = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module \
                and node.module.startswith("paddle_tpu.models"):
            seen += [(node.module, a.name) for a in node.names]
        if isinstance(node, ast.Import):
            seen += [(a.name, "") for a in node.names
                     if a.name.startswith("paddle_tpu.models")]
    assert all(not name.startswith("_") for _m, name in seen), seen
    # and it names no model: everything goes through the contract
    assert not [m for m, _n in seen if m.rsplit(".", 1)[-1]
                in ("llama", "mla_moe", "gpt", "bert")], seen
    src = open(path).read()
    assert "isinstance(model" not in src and "model_type" not in src


def test_the_contract_names_no_kernel_and_ops_know_no_higher_layer():
    """The contract carries a model's mathematics, never a kernel or a
    schedule option, and the kernel layer imports nothing from the
    Program tier above it."""
    import inspect

    from paddle_tpu.models import llama
    from paddle_tpu.models.contract import ServingContract

    assert not hasattr(ServingContract, "prefill_scope")
    kv_only = {"chunk", "adapters", "slots", "scaling"}
    # what the engine passes `decode` beyond the positional contract ...
    path = os.path.join(REPO, "paddle_tpu", "serving", "__init__.py")
    passed = set()
    for node in ast.walk(ast.parse(open(path).read())):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Dict) \
                and any(isinstance(t, ast.Name) and t.id == "kv_only"
                        for t in node.targets):
            passed |= {k.value for k in node.value.keys}
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "update" \
                and isinstance(node.func.value, ast.Name) \
                and node.func.value.id == "kv_only":
            passed |= {k.arg for k in node.keywords}
    assert passed == kv_only, passed
    # ... is what the dense model's decode hands on, and no more
    params = inspect.signature(llama._decode_layers_paged).parameters
    assert {n for n, p in params.items()
            if p.default is not inspect.Parameter.empty} == kv_only

    ops = os.path.join(REPO, "paddle_tpu", "ops")
    seen = []
    for root, _dirs, files in os.walk(ops):
        for name in files:
            if not name.endswith(".py"):
                continue
            fp = os.path.join(root, name)
            for node in ast.walk(ast.parse(open(fp).read())):
                if isinstance(node, ast.ImportFrom):
                    mods = [node.module or ""] + [
                        f"{node.module or ''}.{a.name}" for a in node.names]
                elif isinstance(node, ast.Import):
                    mods = [a.name for a in node.names]
                else:
                    continue
                seen += [(os.path.relpath(fp, REPO), mod) for mod in mods
                         if "static" in mod.split(".")]
    assert not seen, seen


def test_the_dense_model_serves_through_the_same_contract():
    paddle.seed(0)
    m = LlamaForCausalLM(llama_tiny(dtype="float32"))
    spec = m.serving_contract().spec
    assert spec.kv_pair and [p.name for p in spec.pools] == ["k", "v"]
    assert not mla_moe.MlaMoeServing(_model()).spec.kv_pair
    serving.reset_decode_stats()
    eng = serving.GenerationEngine(m, max_batch=1, block_size=8, num_blocks=8)
    st = serving.decode_stats()
    assert st["k_pool_bytes"] == st["v_pool_bytes"] == st["pool_bytes"] // 2
    assert st["latent_pool_bytes"] == 0 and len(eng._pools) == 2


# ------------------------------------- the router and the softmax, alone ----

@pytest.mark.parametrize("dtype,low,high", [("float32", 0.9995, 1.0),
                                            ("bfloat16", 0.5, 0.85)])
def test_router_on_the_references_inputs_tells_float32_from_bfloat16(
        dtype, low, high):
    """The two readings of the benchmark cell's routing limit (0.95), at the
    published router's widths (7,680 -> 256, top-8): handed the reference's
    own router inputs, `route` chooses the reference's experts for every
    (token, layer) pair; against a reference run in bfloat16 for about seven
    in ten, because a token's 8th and 9th scores lie within bfloat16's
    rounding of each other that often."""
    k_m, k_w = jax.random.split(jax.random.key(5))
    m = jax.random.normal(k_m, (2048, 7680), jnp.float32).astype(dtype)
    w = (jax.random.normal(k_w, (7680, 256), jnp.float32)
         * np.sqrt(2 / (7680 + 256))).astype(jnp.bfloat16)
    got, weight = jax.jit(lambda m, w: mla_moe.route(m, w, top_k=8, scale=2.5))(m, w)
    _dense, _gap, top = ref._route(m, w, top_k=8, scale=2.5, normalize=True,
                                   dt=jnp.dtype(dtype))
    same = float((jnp.sort(got, -1) == jnp.sort(top[:, :8], -1)).all(-1).mean())
    assert low <= same <= high
    assert weight.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(weight.sum(-1)), 2.5, rtol=1e-5)


@pytest.fixture
def pallas_on():
    """Select the Pallas path off a TPU (it then runs interpreted)."""
    paddle.set_flags({"FLAGS_use_pallas": "true"})
    yield
    paddle.set_flags({"FLAGS_use_pallas": "auto"})


# the latent pool of the probes below: a row of 96 + 8 values in a pool 128
# wide (`pool_width`), pages of 16, a table of 4 pages a row
RANK, ROPE, ROW, BS, TABLE = 96, 8, 128, 16, 4
# one position; one short of a page; exactly a page; across pages; the table
RAGGED = (1, BS - 1, BS, 2 * BS + 5, TABLE * BS)


def _latent_pool(dtype, seed, foreign=np.nan):
    """A latent pool whose pages lie in a shuffled order: a row's live
    positions hold seeded values (lanes past the 104 zero, as the model
    writes them), the rest of its last page large finite garbage, and every
    page past a row's own `foreign` (NaN: a page read beyond a row's own
    would put NaN into that row).  (pool, tables, lens, rows) with rows
    [B, TABLE * BS, 104] float32, the values a reference may see: zero past
    a row's length."""
    rng = np.random.default_rng(seed)
    b = len(RAGGED)
    nb = b * TABLE + 3
    tables = rng.permutation(nb)[:b * TABLE].astype(np.int32).reshape(b, TABLE)
    pool = np.full((nb, 1, BS, ROW), foreign, np.float32)
    rows = np.zeros((b, TABLE * BS, RANK + ROPE), np.float32)
    for r, n in enumerate(RAGGED):
        own = tables[r, :-(-n // BS)]
        pool[own] = 300.0 * rng.standard_normal((len(own), 1, BS, ROW))
        live = rng.standard_normal((n, RANK + ROPE)).astype(np.float32)
        live = np.asarray(jnp.asarray(live, dtype), np.float32)
        rows[r, :n] = live
        for t in range(n):
            pool[tables[r, t // BS], 0, t % BS] = np.pad(
                live[t], (0, ROW - RANK - ROPE))
    return (jnp.asarray(pool, dtype), jnp.asarray(tables),
            jnp.asarray(RAGGED, jnp.int32), rows)


def _rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(((got - want) ** 2).mean() / (want ** 2).mean())


@pytest.mark.parametrize("spread", [0.3, 4.0])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_absorbed_attention_matches_a_float32_softmax_on_the_same_rows(
        form, dtype, spread, request):
    """The decode attention alone, in both its forms (XLA's gather and two
    einsums; the Pallas kernel `paged_decode`'s shared-row case,
    interpreted here), over a paged pool in shuffled page order, ragged
    lengths (1, one short of a page, exactly a page, the whole table) and
    garbage past every row's length, against the reference's softmax on the
    same queries and rows; counted by the form it took."""
    if form == "kernel":
        request.getfixturevalue("pallas_on")
    # XLA's form gathers the table's whole width and multiplies what it
    # masked by zero: its foreign pages must be finite
    pool, tables, lens, rows = _latent_pool(
        dtype, seed=7, foreign=np.nan if form == "kernel" else 50.0)
    assert pa.reads_own_pages(pool) == (form == "kernel")
    heads, width = 4, 24
    q = (jax.random.normal(jax.random.key(1), (len(RAGGED), heads, RANK + ROPE))
         * spread).astype(dtype)
    before = profiler.compile_stats()
    got = mla_moe.absorbed_attention(q, pool, tables, lens, rank=RANK,
                                     width=width)
    after = profiler.compile_stats()
    assert (after["paged_kernel_traces"] - before["paged_kernel_traces"],
            after["paged_xla_traces"] - before["paged_xla_traces"]) == (
                (1, 0) if form == "kernel" else (0, 1))
    want = np.asarray(ref.absorbed_attention(q, rows, lens, RANK, width))
    assert got.shape == (len(RAGGED), heads, RANK) and got.dtype == jnp.float32
    assert not np.isnan(np.asarray(got)).any()
    # bfloat16: the probabilities meet the rows in bfloat16
    assert _rel_rms(got, want) < (5e-3 if dtype == "bfloat16" else 1e-5)


@pytest.mark.parametrize("pages", [1, 2, 3])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_kernel_and_the_xla_form_agree_on_the_same_pool(dtype, pages):
    """The two forms of `absorbed_attention` on one pool: for a float32 pool
    the same products in another order of float32 sums (an online softmax,
    at any pages a step: a tile, not a result); for a bfloat16 pool the
    probabilities are also rounded to bfloat16 before (XLA: normalised) or
    after (the kernel: against the running maximum) their division."""
    pool, tables, lens, _rows = _latent_pool(dtype, seed=11, foreign=50.0)
    q = (jax.random.normal(jax.random.key(2), (len(RAGGED), 8, RANK + ROPE))
         * 2.0).astype(dtype)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, ROW - RANK - ROPE)))
    scale = 1.0 / np.sqrt(24.0)
    xla = mla_moe.absorbed_attention(q, pool, tables, lens, rank=RANK, width=24)
    got = pa._paged_decode_pallas(q, pool, None, tables, lens, scale,
                                  pages=pages, rank=RANK)
    assert got.shape == xla.shape and got.dtype == jnp.float32
    assert _rel_rms(got, xla) < (5e-3 if dtype == "bfloat16" else 2e-6)


@pytest.mark.parametrize("case", ["row_of_whole_lanes", "row_of_576",
                                  "chunk", "flag_off"])
def test_which_latent_pools_read_their_own_pages(case, pallas_on):
    """`reads_own_pages` answers for a latent pool by what it can see, as
    for a K pool of one head: a row of whole 128-lane rows (640, what
    `pool_width` makes of 576), pages of whole sublane tiles, one token a
    row; a 576-wide row (Mosaic refuses a slice of it), T > 1 and
    FLAGS_use_pallas=false keep XLA's form."""
    cfg = mla_moe.MlaMoeConfig()
    assert (cfg.latent_width, cfg.pool_width) == (576, 640)
    width, t = cfg.pool_width, 1
    if case == "row_of_576":
        width = cfg.latent_width
    elif case == "chunk":
        t = 2
    elif case == "flag_off":
        paddle.set_flags({"FLAGS_use_pallas": "false"})
    pool = jax.ShapeDtypeStruct((8, 1, 128, width), jnp.bfloat16)
    assert pa.reads_own_pages(pool, t) == (case == "row_of_whole_lanes")


@pytest.mark.parametrize("selected", ["xla", "kernel"])
def test_latent_decode_reports_what_its_attention_read(model, selected,
                                                       request):
    """`MlaMoeServing.decode` hands `attn_positions_read` / `_live` back in
    the contract's `aux`, and `decode_stats()` of a tiny latent engine sums
    them over the token steps: XLA's form reads the table's whole width of
    every active row, the kernel each row's own pages; the trace counters
    say which form the engine's programs took, and the streams are the
    same, token for token."""
    if selected == "kernel":
        request.getfixturevalue("pallas_on")
    pool = jnp.zeros((12, 1, 8, model.config.pool_width), jnp.float32)
    tables = jnp.arange(12, dtype=jnp.int32).reshape(2, 6)
    lens = jnp.asarray([9, 30], jnp.int32)
    _h, _pools, aux = model.serving_contract().decode(
        jnp.zeros((2, 1), jnp.int32), [[pool] * 3], tables, lens,
        jnp.asarray([True, True]))
    assert int(aux["attn_positions_live"]) == 39
    assert int(aux["attn_positions_read"]) == (
        (2 + 4) * 8 if selected == "kernel" else 2 * 6 * 8)



def test_a_tiny_latent_engine_through_the_kernel_emits_the_xla_forms_tokens(
        model):
    """`decode_stats()` of a tiny latent engine sums what `decode` reports
    over the token steps, and the trace counters say which form the
    engine's programs took: by default on the CPU XLA's, reading the
    table's whole width of every active row; with the kernel selected each
    row's own pages, and the same streams, token for token."""
    block, prompts, new = 8, (5, 29), (6, 12)
    rng = np.random.default_rng(0)
    ids = [rng.integers(0, 256, n).astype(np.int32) for n in prompts]

    def serve(flag):
        paddle.set_flags({"FLAGS_use_pallas": flag})
        serving.reset_decode_stats()
        before = profiler.compile_stats()
        eng, _firsts = _serve(model, ids, new)
        after = profiler.compile_stats()
        traces = tuple(after[k] - before[k] > 0 for k in (
            "paged_kernel_traces", "paged_xla_traces"))
        return ([eng.result(f"r{i}") for i in range(2)],
                serving.decode_stats(), traces, eng._max_blocks_per_seq)

    try:
        want, xla, xla_traces, width = serve("auto")
        got, st, kernel_traces, _w = serve("true")
    finally:
        paddle.set_flags({"FLAGS_use_pallas": "auto"})
    assert got == want and st["tokens"] == xla["tokens"] == sum(new) - len(new)
    assert (xla_traces, kernel_traces) == ((False, True), (True, False))
    # the j-th later token is one token step over a row of prompt + j
    steps = [n + j for n, k in zip(prompts, new) for j in range(1, k)]
    assert st["attn_positions_live"] == xla["attn_positions_live"] == sum(steps)
    assert st["attn_positions_read"] == sum(-(-n // block) * block
                                            for n in steps)
    assert xla["attn_positions_read"] == len(steps) * width * block
    assert 1.0 <= st["attn_positions_read"] / st["attn_positions_live"] < 1.3
    assert xla["attn_positions_read"] / xla["attn_positions_live"] > 3.0


def test_paged_gather_of_a_one_row_pool_is_the_general_gather():
    """`ops.paged_attention.paged_gather` takes whole pages in one pass when a
    token is one row; the view is the general form's."""
    from paddle_tpu.ops import paged_attention as pa

    pool = jax.random.normal(jax.random.key(2), (10, 1, 4, 6))
    tables = jnp.asarray([[3, 9, 0], [7, 7, 1]], jnp.int32)
    general = jnp.moveaxis(jnp.take(pool, tables, axis=0), 2, 1).reshape(2, 1, 12, 6)
    np.testing.assert_array_equal(np.asarray(pa.paged_gather(pool, tables)),
                                  np.asarray(general))
