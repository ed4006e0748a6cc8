"""Latent attention with a learned sparse indexer on the full layers and a
latent window on the others (models/dsa_moe.py) against its plain reference
(perfbench/reference_dsa_moe.py) at a small size on the CPU, float32, seeded:
`GenerationEngine` through the paged latent and indexer pools AND the latent
rings, LOGITS after a prefill and nine decode tokens at prompts shorter and
longer than the indexer's top-k and across a ring wrap; the selection on the
reference's own inputs; the held-expert shares with the correction bias adding
up to the uncut layer; the counters by cache class; the controls that must not
compare equal."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler, serving
from paddle_tpu._core.tensor import Tensor
from paddle_tpu.models import dsa_moe, experts
from paddle_tpu.models.dsa_moe import DsaMoeForCausalLM, dsa_moe_tiny

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from perfbench import reference_dsa_moe as ref  # noqa: E402
from perfbench.families import dsa_moe as fam  # noqa: E402

TOP_K, WINDOW, RING = 12, 9, 12     # dsa_moe_tiny; blocks of 4: 3 ring blocks


def _model(seed=0, **kw):
    paddle.seed(seed)
    m = DsaMoeForCausalLM(dsa_moe_tiny(**kw))
    fam.perturb_norms(m, seed)      # gains away from exactly 1
    fam.spread_gates(m, seed)       # gates away from 1/2
    fam.draw_bias(m, seed)          # the correction bias away from 0
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _model(held_experts=(2, 4))


def _reference(m):
    return fam.reference_weights(m), fam._sizes_of(m.config)


def _padded(ids, to=64):
    """ids with zeros behind them up to `to` tokens: the reference is causal,
    so rows before the padding are what they were, and every call has one
    length (one compile of the reference's pieces)."""
    return np.pad(ids, (0, to - len(ids)))


# --------------------------------------------- (a) the engine and the reference

def test_engine_logits_and_streams_match_the_reference(model):
    """Two rows in one batch: prompts of 5 (under the indexer's top-k and
    the window) and 34 (over both).  After the prefill program and one
    macro-step (nine tokens a row) the next-token LOGITS over the resident
    pools (pages, indexer keys, rings that have wrapped) are the reference's
    full forward; every token to the end is the reference's argmax; the
    pools and the counters are the classes' own; the rings have wrapped and
    hold the rows a forward computes (`_rings_and_pages_hold_the_rows`)."""
    serving.reset_decode_stats()
    rng = np.random.default_rng(5)
    lens = (5, 34)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in lens]
    eng = serving.GenerationEngine(model, max_batch=2, block_size=4,
                                   num_blocks=40)
    for i, p in enumerate(prompts):
        eng.add_request(f"r{i}", p, max_new_tokens=17)
    eng.step()
    got = eng.next_token_logits()
    w, sizes = _reference(model)
    for i, p in enumerate(prompts):
        ids = np.concatenate([p, _slot(eng, f"r{i}").generated])
        assert len(ids) == len(p) + 9
        want = np.asarray(ref.logits_at(w, sizes, _padded(ids),
                                        [len(ids) - 1]))[0]
        np.testing.assert_allclose(got[f"r{i}"], want, atol=2e-4, rtol=0)
    _rings_and_pages_hold_the_rows(model, eng, _slot(eng, "r1"), prompts[1])
    while eng.has_work():
        eng.step()
    for i, p in enumerate(prompts):
        toks = eng.result(f"r{i}")
        ids = np.concatenate([p, toks[:-1]])
        lg = np.asarray(ref.logits_at(w, sizes, _padded(ids),
                                      list(range(len(p) - 1, len(ids)))))
        assert toks == [int(t) for t in lg.argmax(-1)], i
    st = serving.decode_stats()
    # two classes: 2 full layers x (40 + 2 scratch) pages of a latent row
    # (16 + 8 values in 128 lanes) and an indexer key (16), 3 sliding layers
    # x 2 slots x 3 ring blocks of their own latent row (32 + 8 in 128)
    assert st["latent_pool_bytes"] == 2 * 42 * 4 * 128 * 4
    assert st["index_pool_bytes"] == 2 * 42 * 4 * 16 * 4
    assert st["wlatent_pool_bytes"] == 3 * 6 * 4 * 128 * 4
    assert st["window_ring_blocks"] == 3 and st["k_pool_bytes"] == 0
    spec = eng._spec
    assert [c.window for c in spec.classes] == [None, WINDOW]
    assert [[p.name for p in c.pools] for c in spec.classes] == [
        ["latent", "index"], ["wlatent"]]
    # 16 decode token steps a row, counted once a step for each class
    steps = [n + 1 + t for n in lens for t in range(16)]
    # the indexer gathers and scores each row's whole table, live or not
    width = eng._max_blocks_per_seq * 4
    assert st["attn_indexer_positions_read"] == len(steps) * width
    assert st["attn_sparse_positions_read"] == sum(min(n, TOP_K) for n in steps)
    assert st["attn_full_positions_read"] == st["attn_sparse_positions_read"]
    assert st["attn_window_positions_read"] == len(lens) * 16 * RING
    assert st["attn_window_positions_live"] == sum(min(n, WINDOW) for n in steps)
    assert st["attn_positions_live"] == sum(steps) + sum(min(n, WINDOW)
                                                         for n in steps)
    # each admission's (query, key) pairs over the two full layers
    assert st["dsa_prefill_pairs_scored"] == 2 * sum(n * (n + 1) // 2
                                                     for n in lens)
    assert st["dsa_prefill_pairs_selected"] == 2 * sum(
        sum(min(t + 1, TOP_K) for t in range(n)) for n in lens)
    assert st["moe_layer_steps"] == 16 * 4


def _slot(eng, rid):
    return next(s for s in eng._slots if s.rid == rid and s.active)


def _rings_and_pages_hold_the_rows(model, eng, slot, prompt):
    """The prefill poured a 34-token prompt's latent rows and indexer keys
    into its pages and the last ring's worth of its sliding latent rows into
    the slot's ring; each decode step wrote its own row at position % ring:
    after one macro-step (42 positions stored) the ring holds positions
    30..41 and the pages every row and key 0..41, each the one a forward
    over those 42 tokens computes."""
    n = slot.seq_len
    assert n == len(prompt) + 8 == 42
    ids = np.concatenate([prompt, slot.generated])[:n]
    contract = model.serving_contract()
    caches = jax.jit(lambda x: [[r._value for r in rows] for rows in
                                contract.forward_cached(Tensor(x), [], 0)[1]]
                     )(ids[None])
    ring_pages = np.asarray(eng._slot_rings(eng._slots.index(slot))[2])
    for j, layer in enumerate((2, 3, 4)):
        want = np.asarray(caches[layer][0][0, :, 0])            # [42, width]
        ring = np.asarray(eng._pools[2][j])[ring_pages][:, 0]    # [3, 4, width]
        for t in range(n - RING, n):
            np.testing.assert_allclose(ring[(t // 4) % 3, t % 4], want[t],
                                       atol=1e-5)
    for j, layer in enumerate((0, 1)):
        for q in (0, 1):                       # the latent row, the key
            want = np.asarray(caches[layer][q][0, :, 0])
            pool = np.asarray(eng._pools[q][j])
            got = np.stack([pool[slot.blocks[t // 4], 0, t % 4]
                            for t in range(n)])
            np.testing.assert_allclose(got[:, :want.shape[-1]], want,
                                       atol=1e-5)


def test_forward_logits_match_the_reference_and_the_controls_do_not(model):
    """The model's own forward (the prefill path: the indexer's selection as
    a mask over spans of 128 queries, two spans at 160 tokens; the window's
    band through the flash forward's XLA form) is the reference's; a
    reference that chooses every position or leaves the correction bias out
    is not (the window and the gate: the attention agreement below)."""
    ids = np.random.default_rng(8).integers(0, 256, 160).astype(np.int32)
    got = np.asarray(jax.jit(lambda x: model(Tensor(x))._value)(ids[None])[0])
    w, sizes = _reference(model)
    right = np.asarray(ref.logits_at(w, sizes, ids, list(range(160))))
    np.testing.assert_allclose(got, right, atol=2e-4, rtol=0)
    for control in ("dense_index", "no_bias"):
        wrong = np.asarray(ref.logits_at(w, {**sizes, control: True}, ids,
                                         [159]))[0]
        assert np.abs(got[-1] - wrong).max() > 1e-2, control


@pytest.mark.parametrize("control", [None, "ignore_window", "no_gate",
                                     "dense_index"])
def test_each_layers_decode_attention_is_the_references_on_its_inputs(
        model, control):
    """`families/dsa_moe.attention_agreement`: every layer's attention on the
    program's decode path (its cache written from the program's own
    projections of the reference's layer input: pages and indexer keys, or
    a ring that has wrapped), at positions inside and past the window and
    the indexer's top-k, is the reference's attention sublayer; a reference
    that ignores the window, leaves the gate out or attends every position
    parts from it in the layers, and at the positions, that mechanism
    acts."""
    w, sizes = _reference(model)
    if control:
        sizes = {**sizes, control: True}
    # 64 tokens: the length the engine test's reference calls compiled for
    ids = np.random.default_rng(4).integers(0, 256, 64).astype(np.int32)
    at = [3, 8, 9, 11, 12, 20, 63]
    out = fam.attention_agreement(model, w, sizes, ids, ref, at, block_size=4)
    assert [(i, kind) for i, kind, _r in out] == [
        (0, "full"), (1, "full"), (2, "sliding"), (3, "sliding"),
        (4, "sliding")]
    err = np.asarray([r for _i, _k, r in out])          # [layer, position]
    sound = np.full(err.shape, True)
    if control == "ignore_window":      # the sliding layers past W positions
        sound[2:, 2:] = False
    if control == "no_gate":
        sound[:] = False
    if control == "dense_index":        # the full layers past top-k
        sound[:2, 4:] = False
    assert (err[sound] < 1e-5).all(), err
    assert (err[~sound] > 0.1).all(), err


# --------------------------------------------------- (b) the indexer's choice

def _approx_top_k(scores, allowed, top_k):
    """A control: an APPROXIMATE top-k as a TPU's approx_max_k finds it (the
    best of each of 2k bins of positions, then the k best of those)."""
    s = jnp.where(allowed, scores, -jnp.inf)
    n = s.shape[-1]
    bins = 2 * top_k
    pad = -n % bins
    s = jnp.pad(s, [(0, 0)] * (s.ndim - 1) + [(0, pad)],
                constant_values=-jnp.inf)
    by_bin = s.reshape(s.shape[:-1] + (-1, bins))
    best = jnp.argmax(by_bin, axis=-2) * bins + jnp.arange(bins)
    vals = jnp.take_along_axis(s, best, axis=-1)
    _, at = jax.lax.top_k(vals, top_k)
    at = jnp.take_along_axis(best, at, axis=-1).astype(jnp.int32)
    return at, jnp.take_along_axis(
        jnp.broadcast_to(allowed, scores.shape), jnp.minimum(at, n - 1), -1)


@pytest.mark.parametrize("control", [None, "index_dtype", "approx_top_k"])
def test_the_selection_is_the_references_on_its_own_inputs(model, control,
                                                             monkeypatch):
    """Handed the reference's own indexer queries, weights and keys, the
    program's `index_scores` and `index_select` select every position the
    reference selects; the reference's scores in bfloat16, or an
    approximate top-k in the program, part from it."""
    w, sizes = _reference(model)
    ids = np.random.default_rng(4).integers(0, 256, 160).astype(np.int32)
    if control == "index_dtype":
        sizes = {**sizes, "index_dtype": "bfloat16"}
    if control == "approx_top_k":
        monkeypatch.setattr(dsa_moe, "index_select", _approx_top_k)
    share, pairs = fam.index_agreement(model, w, sizes, ids, ref)
    assert pairs == 2 * (160 - TOP_K) * TOP_K
    if control is None:
        assert share == 1.0
    else:
        assert share < 0.999, (control, share)


def test_index_select_is_exact_and_counts_the_pairs():
    rng = np.random.default_rng(0)
    scores = jnp.asarray(rng.standard_normal((2, 30)), jnp.float32)
    allowed = jnp.arange(30)[None, :] < jnp.asarray([[7], [30]])
    at, ok = dsa_moe.index_select(scores, allowed, 12)
    at, ok = np.asarray(at), np.asarray(ok)
    assert sorted(at[0][ok[0]]) == list(range(7)) and ok[0].sum() == 7
    assert set(at[1]) == set(np.argsort(-np.asarray(scores[1]))[:12])
    scored, chosen = dsa_moe.selected_pairs(20, 12)
    assert (int(scored), int(chosen)) == (210, 78 + 8 * 12)


# --------------------------------------------------- (c) the shares add up

def test_the_shares_of_an_eight_way_split_add_up_to_the_uncut_layer():
    """Sigmoid scores chosen by s + b, the weights the unbiased s normalised
    over the chosen: what the eight shares give, the shared expert counted
    once, is the uncut reference's expert layer, and a router without the
    bias chooses otherwise."""
    rng = np.random.default_rng(11)
    h, f, e, t = 32, 24, 8, 60
    m = jnp.asarray(rng.standard_normal((t, h)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((h, e)) / 4, jnp.float32)
    bias = jnp.asarray(0.3 * rng.standard_normal(e), jnp.float32)
    gate_up = jnp.asarray(rng.standard_normal((e, h, 2 * f)) / 6, jnp.float32)
    down = jnp.asarray(rng.standard_normal((e, f, h)) / 5, jnp.float32)
    shared = (jnp.asarray(rng.standard_normal((h, 2 * f)) / 6, jnp.float32),
              jnp.asarray(rng.standard_normal((f, h)) / 5, jnp.float32))
    weight, _gap, _top = ref._route(m, router, bias, top_k=3, scale=1.0,
                                    normalize=True, dt=jnp.float32)
    whole = ref._ffn(m, *shared, jnp.float32)
    for i in range(e):
        whole = whole + weight[:, i, None] * ref._ffn(m, gate_up[i], down[i],
                                                      jnp.float32)
    parts = ref._ffn(m, *shared, jnp.float32)         # counted ONCE
    for s in range(e):
        out, counts = experts.routed_experts(
            m, router, gate_up[s:s + 1], down[s:s + 1], held=(s, 1), top_k=3,
            scale=1.0, normalize=True, scoring="sigmoid", bias=bias)
        parts = parts + out
        assert int(counts["assignments"]) == t * 3
    np.testing.assert_allclose(parts, whole, atol=5e-5)
    chosen, wt = experts.route(m, router, top_k=3, scale=1.0, bias=bias)
    plain, _ = experts.route(m, router, top_k=3, scale=1.0)
    assert not np.array_equal(np.sort(chosen, -1), np.sort(plain, -1))
    s = jax.nn.sigmoid(m @ router)
    picked = np.take_along_axis(np.asarray(s), np.asarray(chosen), -1)
    np.testing.assert_allclose(wt, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)


# ------------------------------------------ (d) scopes, refusals, the geometry

def test_scopes_reach_the_programs_and_the_contract_refuses_by_name(model):
    eng = serving.GenerationEngine(model, max_batch=2, block_size=4,
                                   num_blocks=32)
    text = eng._build_step(2).lower(*eng._step_avals()).as_text(
        debug_info=True)
    for scope in ("dsa.index", "dsa.select", "mla.sparse.decode",
                  "mla.window.decode", "attn.gate", "moe.route"):
        assert scope in text, scope
    fn = eng._prefill_program(16, 0)
    text = fn.lower([t._value for t in eng._state], np.zeros((1, 16), np.int32),
                    np.int32(16), None).as_text(debug_info=True)
    for scope in ("dsa.index", "dsa.select", "mla.prefill",
                  "mla.window.prefill", "attn.gate", "moe.experts"):
        assert scope in text, scope
    assert {"dsa.index", "dsa.select", "mla.sparse.decode",
            "mla.window.prefill", "mla.window.decode"} <= set(
                profiler.SCOPE_NAMES)
    c = model.serving_contract()
    with pytest.raises(NotImplementedError, match="whole prompt"):
        c.forward_cached(paddle.to_tensor(np.zeros((1, 4), np.int32)), [], 4)
    with pytest.raises(NotImplementedError, match="chunk"):
        c.decode(None, None, None, None, chunk=True)
    with pytest.raises(NotImplementedError, match="a window class"):
        serving.GenerationEngine(model, max_batch=2, block_size=4,
                                 num_blocks=16, prefix_cache=True)


def test_each_layer_takes_its_own_geometry():
    c = dsa_moe.DsaMoeConfig()
    assert c.layer_types[:5] == ("full_attention", "full_attention",
                                 "sliding_attention", "sliding_attention",
                                 "sliding_attention")
    full, sliding = c.geometry(dsa_moe.FULL), c.geometry(dsa_moe.SLIDING)
    assert (full.num_attention_heads, full.kv_lora_rank, full.latent_width,
            full.pool_width) == (128, 512, 576, 640)
    assert (sliding.num_attention_heads, sliding.kv_lora_rank,
            sliding.qk_nope_head_dim, sliding.latent_width,
            sliding.pool_width) == (64, 1024, 192, 1088, 1152)
    with pytest.raises(ValueError, match="entries"):
        dsa_moe_tiny(layer_types=("full_attention",) * 3)
