"""The full layers' sparse prefill attention through the Pallas kernels
(models/dsa_moe.sparse_attention_kernel: the causal flash forward for a
first span that selects nothing, ops/sparse_flash.py over a packed keep mask
for the rest) against the XLA form it replaced on a TPU
(`sparse_attention_xla`), float32, interpreted on the CPU; the packed mask
against `index_select`'s scatter bit for bit; the kernel's tile skipping; the
trace counters that say which form a prefill took."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.models import dsa_moe
from paddle_tpu.ops import sparse_flash as sf

D, DV, IH, ID = 24, 16, 4, 16     # q/k width, v width, indexer heads x width


def _unpack(words, n):
    """The keep bits of words [..., W] (ops/sparse_flash's layout): key s is
    bit (s % 4096) // 128 of word (s // 4096) * 128 + s % 128."""
    s = np.arange(n)
    w = np.asarray(words)[..., (s // sf.GROUP) * 128 + s % 128]
    return (w >> ((s % sf.GROUP) // 128)) & 1 != 0


def _inputs(b, s, heads=2, seed=0):
    r = np.random.default_rng(seed)

    def f(*shape):
        return jnp.asarray(r.standard_normal(shape), jnp.float32)

    return (f(b, s, heads, D), f(b, s, heads, D), f(b, s, heads, DV),
            f(b, s, IH, ID), f(b, s, IH), f(b, s, ID))


@pytest.mark.parametrize("b,s,top_k", [
    (1, 100, 130),   # every query sees at most k keys: the causal forward only
    (1, 200, 128),   # a first span AT k (causal forward), a ragged span
                     # above it (200 is no multiple of 128)
    (2, 200, 12),    # two rows; every span selects, the first block's first
                     # queries see fewer than k keys
], ids=["within_top_k", "span_at_then_above_top_k_ragged", "batch_of_two"])
def test_the_kernels_attend_what_the_xla_form_attends(b, s, top_k):
    args = _inputs(b, s)
    want = jax.jit(lambda *a: dsa_moe.sparse_attention_xla(*a, top_k))(*args)
    got = jax.jit(lambda *a: dsa_moe.sparse_attention_kernel(*a, top_k))(*args)
    assert got.shape == want.shape == (b, s, 2, DV)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def _scattered(scores, allowed, top_k):
    at, ok = dsa_moe.index_select(scores, allowed, top_k)
    lead = jnp.indices(at.shape)[:-1]
    return jnp.zeros(scores.shape, bool).at[(*lead, at)].set(ok) & allowed


@pytest.mark.parametrize("hi,top_k", [(300, 12), (4200, 2048)])
def test_the_packed_mask_is_index_selects_scatter_bit_for_bit(hi, top_k):
    """Scores on a grid of quarters, so that many rows tie at their k-th
    score (zeros of both signs among them: top_k ranks -0.0 below +0.0);
    rows that see fewer than k positions keep all they see; a prompt over
    4,096 keys takes a second group of words."""
    rng = np.random.default_rng(1)
    rows = 24
    scores = jnp.asarray(np.round(rng.standard_normal((2, rows, hi)) * 4) / 4,
                         jnp.float32)
    t = np.linspace(0, hi - 1, rows).astype(np.int32)
    t[:3] = (top_k // 2, top_k - 1, top_k)      # fewer than, exactly, one over
    allowed = jnp.arange(hi)[None, None, :] <= jnp.asarray(t)[None, :, None]
    words = jax.jit(dsa_moe.select_words, static_argnums=2)(scores, allowed,
                                                            top_k)
    assert words.shape == (2, rows, sf.words_for(hi)) and words.dtype == jnp.int32
    want = jax.jit(_scattered, static_argnums=2)(scores, allowed, top_k)
    np.testing.assert_array_equal(_unpack(words, hi),
                                  np.asarray(want))
    # the rows did tie at their threshold, and the short rows kept all
    kth = jax.lax.top_k(jnp.where(allowed, scores, -jnp.inf),
                        top_k)[0][..., -1:]
    at_edge = np.asarray(jnp.sum(allowed & (scores == kth), -1))
    assert (at_edge[:, 3:] > 1).any()
    assert np.asarray(want.sum(-1))[:, :3].tolist() == [
        [top_k // 2 + 1, top_k, top_k]] * 2


def test_the_kernel_visits_only_the_key_tiles_some_query_keeps():
    """Two query tiles over four key tiles of 128: the first keeps keys in
    tiles 0 and 2 only, the second in tile 3 only (and one row keeps
    nothing: it reads zeros).  The visited tiles are counted
    and ordered, and the result is the masked softmax's."""
    rows, seq, heads = 16, 512, 2
    keep = np.zeros((1, rows, seq), bool)
    keep[0, :8, 5:40] = True
    keep[0, :8, 300:301] = True
    keep[0, 8:15, 400:512:3] = True
    words = sf.pack_keep(jnp.asarray(keep))
    count, order = sf._kept_tiles(words, 8, 128, 4)
    assert count.tolist() == [2, 1]
    assert order.reshape(2, 4)[0, :2].tolist() == [0, 2]
    assert order.reshape(2, 4)[1, :1].tolist() == [3]
    r = np.random.default_rng(2)
    q, k = (jnp.asarray(r.standard_normal((1, heads, n, D)), jnp.float32)
            for n in (rows, seq))
    v = jnp.asarray(r.standard_normal((1, heads, seq, DV)), jnp.float32)
    got = sf.sparse_flash_forward(q, k, v, words, scale=0.2, block_q=8,
                                  block_k=128)
    s = np.einsum("bnqd,bnkd->bnqk", np.asarray(q, np.float64)[:, :, :15],
                  np.asarray(k, np.float64)) * 0.2
    s = np.where(keep[:, None, :15], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bnqk,bnkd->bnqd", p / p.sum(-1, keepdims=True),
                     np.asarray(v, np.float64))
    np.testing.assert_allclose(np.asarray(got)[:, :, :15], want, rtol=1e-5,
                               atol=1e-5)
    assert not np.asarray(got)[:, :, 15].any()


@pytest.mark.parametrize("pallas,counted", [
    ("true", "sparse_prefill_kernel_traces"),
    ("false", "sparse_prefill_xla_traces")])
def test_a_prefill_counts_the_form_it_took(pallas, counted):
    paddle.set_flags({"FLAGS_use_pallas": pallas})
    try:
        before = profiler.compile_stats()
        jax.jit(lambda *a: dsa_moe.sparse_attention(*a, 12)).lower(
            *_inputs(1, 40))
        after = profiler.compile_stats()
    finally:
        paddle.set_flags({"FLAGS_use_pallas": "auto"})
    moved = {k: after[k] - before[k] for k in (
        "sparse_prefill_kernel_traces", "sparse_prefill_xla_traces")}
    assert moved == {k: int(k == counted) for k in moved}
