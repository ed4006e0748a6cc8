"""Decode attention over the live pages (ops/paged_attention.py): the width
read is chosen on the device from max(seq_lens) among `page_ladder`, the
pages are contracted as gathered, in the pool's type, with grouped heads.

Against a dense float32 softmax on the values the pool holds; each ladder
branch against the full width; what the bfloat16 path may not materialise;
and the two counters a tiny engine hands to `decode_stats()`."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import paged_attention as pa

NKV, H, BS, W = 2, 8, 2, 40          # 40 pages of 2: 80 positions a row
LADDER = (16, 32, 40)                # pages; 32 / 64 / 80 positions
# one under, on and one over every boundary of the ladder (80 is the table)
BOUNDARY_LENS = (31, 32, 33, 63, 64, 65, 79, 80)


def test_page_ladder_is_powers_of_two_from_16_capped_at_the_table():
    assert pa.page_ladder(W) == LADDER
    assert pa.page_ladder(96) == (16, 32, 64, 96)
    assert pa.page_ladder(64) == (16, 32, 64)
    assert pa.page_ladder(16) == (16,)
    assert pa.page_ladder(3) == (3,)


def _pools(dtype, rows, seed, h=H):
    """K and V pools holding `rows` x W pages of seeded values, the pages of
    a row scattered over the pool; (kc, vc, tables, K, V) with K / V the
    float32 values the pools hold, [rows, W * BS, NKV, h]."""
    rng = np.random.default_rng(seed)
    nb = rows * W + 3
    ids = rng.permutation(nb)[:rows * W].astype(np.int32)
    tables = ids.reshape(rows, W)
    kc, vc = pa.alloc_paged_cache(nb, NKV, BS, h, dtype)
    held = []
    for cache in (kc, vc):
        vals = jnp.asarray(rng.standard_normal((rows * W, NKV, BS, h)),
                           jnp.float32)
        cache = pa.paged_pour_blocks(cache, vals, ids)
        view = np.asarray(pa.paged_gather(cache, jnp.asarray(tables)),
                          np.float32)              # [rows, NKV, W*BS, H]
        held.append((cache, np.moveaxis(view, 1, 2)))
    (kc, k), (vc, v) = held
    return kc, vc, jnp.asarray(tables), k, v


def _dense_reference(q, k, v, lens):
    """softmax(q k^T / sqrt(H)) v in float64 over the first `lens` positions
    (bottom-right causal inside a chunk): q [B, T, N, H]; k / v
    [B, S, NKV, H]."""
    b, t, n, h = q.shape
    g = n // k.shape[2]
    out = np.zeros((b, t, n, h))
    for bi in range(b):
        for ti in range(t):
            upto = int(lens[bi]) - t + ti + 1
            for ni in range(n):
                kk = k[bi, :upto, ni // g].astype(np.float64)
                vv = v[bi, :upto, ni // g].astype(np.float64)
                s = kk @ q[bi, ti, ni].astype(np.float64) / math.sqrt(h)
                p = np.exp(s - s.max())
                out[bi, ti, ni] = (p / p.sum()) @ vv
    return out


@pytest.mark.parametrize("pool", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("t", [1, 4])
@pytest.mark.parametrize("group", [1, 2, 4])
def test_matches_a_dense_float32_softmax_at_every_ladder_boundary(group, t,
                                                                  pool):
    """Rows whose longest sits one under, on and one over each boundary of
    the ladder, beside a parked 1-token row (t tokens for a chunk) and a
    short one; the last batch mixes the parked row with a full-width row."""
    n = NKV * group
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
             "int8": jnp.int8}[pool]
    qdt = jnp.bfloat16 if pool == "bfloat16" else jnp.float32
    kc, vc, tables, k, v = _pools(dtype, 3, seed=group * 10 + t)
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((3, t, n, H)) * 2.0, qdt)
    attend = jax.jit(pa.paged_chunk_attention)
    # bfloat16: exact products, float32 sums, one rounding of the output
    tol = dict(rtol=2e-2, atol=2e-2) if pool == "bfloat16" else dict(
        rtol=2e-5, atol=2e-5)
    for longest in BOUNDARY_LENS:
        lens = np.asarray([t, longest, max(t, longest // 3)], np.int32)
        got = attend(q, kc, vc, tables, jnp.asarray(lens))
        assert got.dtype == qdt
        want = _dense_reference(np.asarray(q, np.float32), k, v, lens)
        np.testing.assert_allclose(np.asarray(got, np.float64), want, **tol,
                                   err_msg=f"longest row {longest}")


@pytest.mark.parametrize("pool", ["float32", "bfloat16"])
def test_each_ladder_branch_equals_the_full_width(pool):
    """Positions beyond a row's length contribute exactly 0, so reading
    fewer pages changes nothing but the order of a float32 sum."""
    dtype = jnp.float32 if pool == "float32" else jnp.bfloat16
    kc, vc, tables, _k, _v = _pools(dtype, 2, seed=3)
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((2, 1, 2 * NKV, H)) * 2.0,
                    jnp.float32)          # float32 out: no rounding to hide in

    def at(width, lens):
        tb = tables[:, :width]
        return np.asarray(pa.gathered_attention(
            q, pa._take_pages(kc, tb), pa._take_pages(vc, tb), lens))

    for width in LADDER:
        for longest in (1, width * BS - 1, width * BS):
            lens = jnp.asarray([longest, max(1, longest // 2)], jnp.int32)
            np.testing.assert_allclose(at(width, lens), at(W, lens),
                                       rtol=2e-6, atol=2e-6)
            # and the width the function itself takes for these lengths
            np.testing.assert_allclose(
                np.asarray(pa.paged_chunk_attention(q, kc, vc, tables, lens)),
                at(W, lens), rtol=2e-6, atol=2e-6)


def _every_eqn(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _every_eqn(sub)


@pytest.mark.parametrize("t", [1, 4])
def test_bfloat16_path_holds_no_float32_kv_and_no_repeated_kv(t):
    """No float32 array the size of the gathered K or V, no array larger
    than the gathered pages of the widest branch (a repeat of 2 KV heads to
    4 would be), and K / V meet the contractions in bfloat16."""
    b, n, h = 3, 2 * NKV, 32     # wide heads: K / V dwarf the score arrays
    kc, vc, tables, _k, _v = _pools(jnp.bfloat16, b, seed=5, h=h)
    q = jnp.zeros((b, t, n, h), jnp.bfloat16)
    lens = jnp.full((b,), 9, jnp.int32)
    jaxpr = jax.make_jaxpr(pa.paged_chunk_attention)(q, kc, vc, tables, lens)
    branches = [e for e in _every_eqn(jaxpr.jaxpr) if e.primitive.name == "cond"]
    assert len(branches) == 1 and len(branches[0].params["branches"]) == len(LADDER)
    pages = lambda w: b * w * NKV * BS * h           # noqa: E731
    dots = 0
    for eqn in _every_eqn(jaxpr.jaxpr):
        for var in eqn.outvars:
            size = math.prod(var.aval.shape)
            assert size <= pages(W), (eqn.primitive.name, var.aval)
            if var.aval.dtype == jnp.float32:
                assert size < pages(LADDER[0]), (eqn.primitive.name, var.aval)
        if eqn.primitive.name == "dot_general":
            dots += 1
            kv = max(eqn.invars, key=lambda x: math.prod(x.aval.shape))
            assert kv.aval.dtype == jnp.bfloat16, kv.aval
            assert eqn.params["preferred_element_type"] == jnp.float32
    assert dots == 2 * len(LADDER)


def test_attn_positions_counts_the_width_taken_and_the_live_lengths():
    tables = jnp.zeros((4, W), jnp.int32)
    lens = jnp.asarray([1, 33, 20, 7], jnp.int32)
    active = jnp.asarray([False, True, True, True])
    read, live = pa.attn_positions(tables, BS, lens, active)
    assert (int(read), int(live)) == (3 * 64, 60)    # 33 needs 32 pages
    read, live = pa.attn_positions(tables, BS, lens)
    assert (int(read), int(live)) == (4 * 64, 61)
    for longest, pages in ((31, 16), (32, 16), (33, 32), (64, 32), (65, 40),
                           (80, 40)):
        read, live = pa.attn_positions(
            tables, BS, jnp.asarray([longest, 1, 1, 1], jnp.int32))
        assert (int(read), int(live)) == (4 * pages * BS, longest + 3)


def test_a_tiny_engine_reports_what_its_lengths_say():
    """`attn_positions_read` / `attn_positions_live` in `decode_stats()`:
    per token step, active rows x the ladder width over the longest row, and
    the sum of the active rows' lengths (counted on the device, summed over
    the macro-step, read at its one sync)."""
    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    paddle.seed(0)
    model = LlamaForCausalLM(llama_tiny(dtype="float32",
                                        num_key_value_heads=2))
    model.eval()
    block = 2
    eng = serving.GenerationEngine(model, max_batch=3, block_size=block,
                                   num_blocks=3 * W)      # a table of W pages
    rng = np.random.default_rng(0)
    prompts = {"a": 5, "b": 29}
    new = {"a": 6, "b": 12}
    serving.reset_decode_stats()
    for rid, n in prompts.items():
        eng.add_request(rid, rng.integers(0, 1000, n).tolist(),
                        max_new_tokens=new[rid])
    # replay the schedule: the first token came with the admission; each
    # later token is one token step over a row of prompt + generated so far
    # + the token being decoded, while the row is active
    lens = dict(prompts)
    left = {rid: n - 1 for rid, n in new.items()}
    want_read = want_live = 0
    while any(left.values()):
        rows = [lens[r] + 1 for r in lens if left[r] > 0]
        pages = next(w for w in pa.page_ladder(W)
                     if w * block >= max(rows))
        want_read += len(rows) * pages * block
        want_live += sum(rows)
        for r in lens:
            if left[r] > 0:
                lens[r] += 1
                left[r] -= 1
    while eng.has_work():
        eng.step()
    st = serving.decode_stats()
    assert st["tokens"] == sum(n - 1 for n in new.values())
    assert (st["attn_positions_read"], st["attn_positions_live"]) == (
        want_read, want_live)
    assert 1.0 < st["attn_positions_read"] / st["attn_positions_live"]
