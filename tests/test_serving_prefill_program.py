"""The admission prefill program (GenerationEngine._prefill_program): one
compiled program per (padded suffix length, prefix length) in place of the
eager `_model_forward_cached` walk and the per-layer reshapes of the pour.

The reference everywhere is a direct eager call of `_model_forward_cached` +
`model._logits` on the same prompt: no switch reaches the engine's eager path.
CPU, `llama_tiny`: first tokens, K/V bytes read back with `pool_get_blocks`,
streams and counts — never a time.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler, serving
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.models.llama import _empty_caches, _model_forward_cached
from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.profiler.statistics import decode_line

BS = 16
_PROGRAM_COUNTERS = ("prefill_program_calls", "prefill_programs_built",
                     "prefill_pad_tokens")


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(llama_tiny(dtype="float32"))
    m.eval()
    return m


def _engine(model, **kw):
    kw = dict(dict(max_batch=2, block_size=BS, num_blocks=32), **kw)
    return serving.GenerationEngine(model, **kw)


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, 1000, n).astype(np.int32)


def _reference(model, prompt):
    """(first token, per-layer K, per-layer V), K/V as [S, Nkv, H] numpy."""
    with paddle.no_grad():
        h, caches = _model_forward_cached(
            model.model, paddle.to_tensor(prompt[None]),
            _empty_caches(model.config, 1), 0)
        logits = model._logits(h[:, -1:, :])._value[0, -1]
    return (int(np.asarray(jnp.argmax(logits))),
            [np.asarray(k._value)[0] for k, _ in caches],
            [np.asarray(v._value)[0] for _, v in caches])


def _pages(pool, blocks):
    """A request's pool pages as one [n * bs, Nkv, H] token sequence."""
    got = np.asarray(pa.pool_get_blocks(pool, blocks)["payload"])
    return np.moveaxis(got, 1, 2).reshape(-1, got.shape[1], got.shape[3])


def _drain(eng):
    while eng.has_work():
        eng.step()


@pytest.mark.parametrize("n,bucket", [(32, 32), (37, 64)])
def test_admission_matches_the_eager_forward(model, n, bucket):
    """A full-bucket prompt and a padded one: the first token, the K/V of the
    real positions and the 16-token greedy stream are the eager reference's;
    past the real tokens the pages hold zeros, as the eager pour left them."""
    serving.reset_decode_stats()
    eng = _engine(model)
    prompt = _prompt(n, n)
    first = eng.add_request("a", prompt, max_new_tokens=16)
    ref_first, ref_k, ref_v = _reference(model, prompt)
    assert first == ref_first
    assert list(eng._prefill_fns) == [(bucket, 0)]
    blocks = eng._slots[0].blocks
    assert len(blocks) == -(-(n + 16) // BS)
    for li in range(model.config.num_hidden_layers):
        for pool, ref in ((eng._pools[0][li], ref_k[li]),
                          (eng._pools[1][li], ref_v[li])):
            got = _pages(pool, blocks)
            np.testing.assert_allclose(got[:n], ref, rtol=1e-5, atol=1e-5)
            assert not got[n:].any()     # the block's tail and the decode pages
    st = serving.decode_stats()
    assert st["prefill_pad_tokens"] == bucket - n
    assert st["prefill_program_calls"] == st["prefill_programs_built"] == 1
    _drain(eng)
    want = model.generate(paddle.to_tensor(prompt[None]), max_new_tokens=16)
    assert eng.result("a") == [int(t) for t in np.asarray(want._value)[0][-16:]]


def test_prefix_hit_runs_the_program_of_its_prefix_length(model):
    """Prefix cache on: a second request sharing two blocks prefills only its
    suffix, through the (bucket, m_len=32) program, and matches the eager
    forward over its whole prompt."""
    serving.reset_decode_stats()
    eng = _engine(model, prefix_cache=True)
    shared = _prompt(1, 2 * BS)
    eng.add_request("a", np.concatenate([shared, _prompt(2, 5)]), max_new_tokens=4)
    prompt = np.concatenate([shared, _prompt(3, 11)])
    first = eng.add_request("b", prompt, max_new_tokens=4)
    assert set(eng._prefill_fns) == {(64, 0), (BS, 2 * BS)}
    st = serving.decode_stats()
    assert st["prefix_hits"] == 1 and st["prefix_hit_tokens"] == 2 * BS
    assert st["prefill_pad_tokens"] == (64 - 37) + (BS - 11)
    ref_first, ref_k, ref_v = _reference(model, prompt)
    assert first == ref_first
    slot = next(s for s in eng._slots if s.rid == "b")
    assert slot.blocks[:2] == next(s for s in eng._slots if s.rid == "a").blocks[:2]
    for li in range(model.config.num_hidden_layers):
        got_k = _pages(eng._pools[0][li], slot.blocks)
        got_v = _pages(eng._pools[1][li], slot.blocks)
        np.testing.assert_allclose(got_k[:43], ref_k[li], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(got_v[:43], ref_v[li], rtol=1e-5, atol=1e-5)
        assert not got_k[43:].any() and not got_v[43:].any()
    _drain(eng)
    want = model.generate(paddle.to_tensor(prompt[None]), max_new_tokens=4)
    assert eng.result("b") == [int(t) for t in np.asarray(want._value)[0][-4:]]


def test_buckets_are_powers_of_two_inside_the_rope_table(model):
    eng = _engine(model)
    rope = int(model.model.rope_cos.shape[0])
    assert [eng._prefill_bucket(s, 0) for s in (1, 16, 17, 37, 64, 65)] == \
        [16, 16, 32, 64, 64, 128]
    # the bucket never reaches past the rope table, whatever the prefix
    assert eng._prefill_bucket(rope - 40, 32) == rope - 32
    assert eng._prefill_bucket(5, rope - 16) == 16


def test_two_admissions_of_one_length_share_one_program(model):
    serving.reset_decode_stats()
    eng = _engine(model)
    eng.add_request("a", _prompt(0, 20), max_new_tokens=6)
    eng.add_request("b", _prompt(1, 20), max_new_tokens=6)
    st = serving.decode_stats()
    assert st["admissions"] == 2
    assert st["prefill_program_calls"] == 2 and st["prefill_programs_built"] == 1
    assert st["admit_eager_ops"] == 2 and st["prefill_eager_fallbacks"] == 0
    assert st["prefill_pad_tokens"] == 2 * 12
    line = decode_line(st)
    assert "prefill programs: 2 calls, 50% ready (1 built)" in line
    # both slots taken: a third request queues, its attempts add nothing
    assert eng.add_request("c", _prompt(2, 20), max_new_tokens=6) is None
    eng.step()
    mid = serving.decode_stats()
    assert all(mid[k] == st[k] for k in _PROGRAM_COUNTERS + ("admit_eager_ops",))
    _drain(eng)
    st = serving.decode_stats()
    assert st["prefill_program_calls"] == 3 and st["prefill_programs_built"] == 1
    assert st["admit_eager_ops"] == 3


def test_pool_exhaustion_backs_out_and_counts_no_program(model):
    serving.reset_decode_stats()
    eng = _engine(model, num_blocks=3)       # one request of 2 blocks fits, not two
    assert eng.add_request("a", _prompt(0, 20), max_new_tokens=6) is not None
    one = serving.decode_stats()
    assert eng.add_request("b", _prompt(1, 20), max_new_tokens=6) is None
    now = serving.decode_stats()
    assert all(now[k] == one[k] for k in _PROGRAM_COUNTERS)
    assert one["prefill_program_calls"] == 1


def test_chunked_prefill_stays_eager_and_is_counted(model):
    """A suffix longer than prefill_chunk walks the eager chunks; one that
    fits takes the program, on the same engine."""
    serving.reset_decode_stats()
    eng = _engine(model, prefill_chunk=BS)
    prompt = _prompt(5, 37)
    first = eng.add_request("a", prompt, max_new_tokens=4)
    st = serving.decode_stats()
    assert st["prefill_eager_fallbacks"] == 1 and st["admit_eager_ops"] > 1
    assert not any(st[k] for k in _PROGRAM_COUNTERS) and not eng._prefill_fns
    assert first == _reference(model, prompt)[0]
    eng.add_request("b", _prompt(6, 12), max_new_tokens=4)
    st = serving.decode_stats()
    assert st["prefill_eager_fallbacks"] == 1 and st["prefill_program_calls"] == 1
    assert "1 eager fallbacks" in decode_line(st)


def test_interleaved_prefill_stays_eager_and_is_counted(model):
    serving.reset_decode_stats()
    eng = _engine(model, prefill_chunk_blocks=1)
    assert eng.add_request("a", _prompt(7, 37), max_new_tokens=4) is None
    _drain(eng)
    st = serving.decode_stats()
    assert st["prefill_eager_fallbacks"] == 1 and st["prefill_chunks"] == 3
    assert not any(st[k] for k in _PROGRAM_COUNTERS) and not eng._prefill_fns


def test_adapter_request_stays_eager_and_the_base_request_does_not(model):
    from paddle_tpu.nn.lora import apply_lora, lora_state_dict

    ft = LlamaForCausalLM(llama_tiny(dtype="float32"))
    ft.set_state_dict(model.state_dict())
    apply_lora(ft, rank=4, alpha=8)
    serving.reset_decode_stats()
    eng = _engine(model, adapters={"rank": 4, "max_adapters": 1})
    eng.register_adapter("t", lora_state_dict(ft), alpha=8)
    eng.add_request("a", _prompt(8, 20), max_new_tokens=4, adapter="t")
    st = serving.decode_stats()
    assert st["prefill_eager_fallbacks"] == 1 and st["admit_eager_ops"] > 1
    assert not any(st[k] for k in _PROGRAM_COUNTERS)
    eng.add_request("b", _prompt(9, 20), max_new_tokens=4)     # slot 0: the base model
    st = serving.decode_stats()
    assert st["prefill_eager_fallbacks"] == 1 and st["prefill_program_calls"] == 1


def test_third_admission_of_a_seen_length_compiles_nothing(model):
    eng = _engine(model, max_batch=4)
    for i in range(2):
        eng.add_request(f"r{i}", _prompt(i, 20), max_new_tokens=6)
    before = profiler.compile_stats()
    eng.add_request("r2", _prompt(2, 20), max_new_tokens=6)
    after = profiler.compile_stats()
    assert after["compiles"] == before["compiles"]
    assert after["traces"] == before["traces"]


def test_warmup_readies_the_single_block_program(model):
    serving.reset_decode_stats()
    eng = _engine(model)
    eng.warmup()
    assert list(eng._prefill_fns) == [(BS, 0)]
    eng.add_request("a", _prompt(3, BS), max_new_tokens=4)
    st = serving.decode_stats()
    assert st["prefill_program_calls"] == 1 and st["prefill_programs_built"] == 0
    assert "100% ready (0 built)" in decode_line(st)


def test_int8_pool_scales_see_no_padding_and_recycled_pages_are_reset(model):
    """An int8 pool takes each block's scale from the block's whole content:
    the partial block's scale is the real tokens' amax, and a recycled page's
    stale scale and bytes are gone from the future decode pages."""
    eng = _engine(model, max_batch=1, num_blocks=4, kv_cache_dtype="int8")
    eng.add_request("old", _prompt(10, 40), max_new_tokens=20)
    _drain(eng)                               # all four pages written, then freed
    assert all(float(jnp.abs(p.scale[:4]).min()) > 0 for p in eng._pools[0])
    prompt = _prompt(11, 21)
    eng.add_request("new", prompt, max_new_tokens=30)
    blocks = eng._slots[0].blocks
    assert len(blocks) == 4
    _first, ref_k, ref_v = _reference(model, prompt)
    for pools, ref in ((eng._pools[0], ref_k), (eng._pools[1], ref_v)):
        for li, pool in enumerate(pools):
            got = pa.pool_get_blocks(pool, blocks)
            want = pa.paged_pour_blocks(
                pa.alloc_paged_cache(2, ref[li].shape[1], BS, ref[li].shape[2],
                                     jnp.int8)[0],
                np.moveaxis(np.pad(ref[li], ((0, 11), (0, 0), (0, 0))),
                            0, 1).reshape(-1, 2, BS, ref[li].shape[2]).swapaxes(0, 1),
                [0, 1])
            np.testing.assert_allclose(np.asarray(got["scale"])[:2],
                                       np.asarray(want.scale), rtol=1e-5)
            diff = np.abs(np.asarray(got["payload"])[:2].astype(np.int32)
                          - np.asarray(want.data).astype(np.int32))
            assert diff.max() <= 1            # float32 tolerance, in int8 steps
            assert not np.asarray(got["scale"])[2:].any()
            assert not np.asarray(got["payload"])[2:].any()
