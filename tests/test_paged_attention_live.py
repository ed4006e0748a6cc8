"""Decode attention over the live pages (ops/paged_attention.py): the width
read is chosen on the device from max(seq_lens) among `page_ladder`, the
pages are contracted as gathered, in the pool's type, with grouped heads.

Against a dense float32 softmax on the values the pool holds; each ladder
branch against the full width; what the bfloat16 path may not materialise;
and the two counters a tiny engine hands to `decode_stats()`.

Then the Pallas kernel (`paged_decode`, interpret mode here): one token a
row over each row's OWN pages, against the same dense softmax and against
`gathered_attention` on the same pools, with every page a row does not
own holding NaN; which path is selected, what `attn_positions` counts for
each, and the two trace counters."""

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


# --------------------------------------------------------------------------
# the kernel: each row's own pages (interpret mode)

KH = 128                 # the kernel takes heads of whole 128-lane rows
KW = {16: 5, 128: 3}     # pages a row of the table, by block size


@pytest.fixture
def pallas_on():
    """`ops.use_pallas()` true off a TPU: the kernel, interpreted."""
    import paddle_tpu as paddle

    paddle.set_flags({"FLAGS_use_pallas": "true"})
    yield
    paddle.set_flags({"FLAGS_use_pallas": "auto"})


def _ragged_lens(bs, w):
    """One batch: 1; one under, at and over a page boundary; the full
    table; and an inactive row, parked at length 1 as the engine parks it."""
    return np.asarray([1, bs - 1, bs, bs + 1, w * bs, 1], np.int32)


def _own_page_pools(dtype, bs, nkv, lens, seed):
    """K and V pools in which a row's live pages (ceil(len / bs) of its
    table row, the table shuffled over the pool) hold seeded values and
    EVERY OTHER page of the pool NaN: (kc, vc, tables, K, V), K / V the
    float32 values of each row's whole table width, [B, W * bs, nkv, KH]
    (NaN beyond the live pages)."""
    rng = np.random.default_rng(seed)
    b, w = len(lens), KW[bs]
    nb = b * w + 2
    tables = rng.permutation(nb)[:b * w].astype(np.int32).reshape(b, w)
    pools, views = [], []
    for _ in range(2):
        pool = np.full((nb, nkv, bs, KH), np.nan, np.float32)
        for r, n in enumerate(lens):
            own = tables[r, :-(-int(n) // bs)]
            pool[own] = rng.standard_normal((len(own), nkv, bs, KH))
        pool = jnp.asarray(pool, dtype)
        pools.append(pool)
        view = np.asarray(pool, np.float32)[tables]        # [B, W, nkv, bs, KH]
        views.append(np.moveaxis(view, 2, 3).reshape(b, w * bs, nkv, KH))
    return pools[0], pools[1], jnp.asarray(tables), views[0], views[1]


@pytest.mark.parametrize("pool", ["float32", "bfloat16"])
@pytest.mark.parametrize("bs", [16, 128])
@pytest.mark.parametrize("group", [1, 2, 6])
def test_kernel_reads_own_pages_and_matches_the_dense_softmax(group, bs, pool,
                                                              pallas_on):
    """Ragged rows of one batch through `paged_decode_attention` with the
    kernel selected: against the dense softmax of the live positions and
    against `gathered_attention` over the same values.  A page the kernel
    read beyond a row's own would put NaN into that row."""
    from paddle_tpu import profiler

    nkv = 2
    dtype = jnp.float32 if pool == "float32" else jnp.bfloat16
    lens = _ragged_lens(bs, KW[bs])
    kc, vc, tables, k, v = _own_page_pools(dtype, bs, nkv, lens,
                                           seed=group * 100 + bs)
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((len(lens), group * nkv, KH)) * 2.0,
                    dtype)
    before = profiler.compile_stats()
    got = pa.paged_decode_attention(q, kc, vc, tables, jnp.asarray(lens))
    after = profiler.compile_stats()
    assert (after["paged_kernel_traces"] - before["paged_kernel_traces"],
            after["paged_xla_traces"] - before["paged_xla_traces"]) == (1, 0)
    assert got.dtype == dtype and not np.isnan(np.asarray(got, np.float32)).any()
    tol = dict(rtol=2e-2, atol=2e-2) if pool == "bfloat16" else dict(
        rtol=2e-5, atol=2e-5)
    want = _dense_reference(np.asarray(q, np.float32)[:, None], k, v, lens)
    np.testing.assert_allclose(np.asarray(got, np.float64), want[:, 0], **tol)
    # the ONE definition, on the same values with the foreign pages zeroed
    # (0 x NaN is NaN there): the same products, another order of sums
    clean = [jnp.nan_to_num(pa._take_pages(c, tables)) for c in (kc, vc)]
    same = pa.gathered_attention(q[:, None], *clean, jnp.asarray(lens))[:, 0]
    np.testing.assert_allclose(
        np.asarray(got, np.float64), np.asarray(same, np.float64),
        **(dict(rtol=1e-2, atol=1e-2) if pool == "bfloat16" else dict(
            rtol=2e-6, atol=2e-6)))


@pytest.mark.parametrize("pages", [1, 2, 3, 8])
@pytest.mark.parametrize("bs", [16, 128])
def test_kernel_gives_the_same_numbers_at_any_pages_a_step(bs, pages):
    """Pages a step is a tile, not a result: a step whose last pages lie
    beyond the row fetches only the row's own (the rest of the buffer is
    masked, and finite)."""
    lens = _ragged_lens(bs, KW[bs])
    kc, vc, tables, k, v = _own_page_pools(jnp.float32, bs, 2, lens, seed=bs)
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((len(lens), 4, KH)) * 2.0,
                    jnp.float32)
    got = pa._paged_decode_pallas(q, kc, vc, tables, jnp.asarray(lens),
                                  1.0 / math.sqrt(KH), pages=pages)
    want = _dense_reference(np.asarray(q)[:, None], k, v, lens)
    np.testing.assert_allclose(np.asarray(got, np.float64), want[:, 0],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("case", ["chunk", "int8", "narrow_heads", "flag_off"])
def test_everything_else_keeps_the_xla_form(case, pallas_on):
    """T > 1, an int8 pool, heads that are no whole 128-lane rows, and
    FLAGS_use_pallas=false: `reads_own_pages` says no, the trace counter
    says XLA, and `attn_positions` counts the ladder."""
    import paddle_tpu as paddle
    from paddle_tpu import profiler

    h, t, dtype = KH, 1, jnp.bfloat16
    if case == "chunk":
        t = 2
    elif case == "int8":
        dtype = jnp.int8
    elif case == "narrow_heads":
        h = 64
    elif case == "flag_off":
        paddle.set_flags({"FLAGS_use_pallas": "false"})
    kc, vc = pa.alloc_paged_cache(8, 2, 16, h, dtype)
    tables = jnp.arange(8, dtype=jnp.int32).reshape(2, 4)
    lens = jnp.asarray([5, 40], jnp.int32)
    assert not pa.reads_own_pages(kc, t)
    before = profiler.compile_stats()
    out = pa.paged_chunk_attention(jnp.ones((2, t, 4, h), jnp.bfloat16), kc,
                                   vc, tables, lens)
    after = profiler.compile_stats()
    assert out.shape == (2, t, 4, h)
    assert (after["paged_kernel_traces"] - before["paged_kernel_traces"],
            after["paged_xla_traces"] - before["paged_xla_traces"]) == (0, 1)
    if t == 1:
        read, live = pa.attn_positions(tables, 16, lens, pool=kc)
        assert (int(read), int(live)) == (2 * 4 * 16, 45)


@pytest.mark.parametrize("selected", [True, False])
def test_attn_positions_counts_own_pages_with_the_kernel_and_the_ladder_without(
        selected, pallas_on):
    import paddle_tpu as paddle

    if not selected:
        paddle.set_flags({"FLAGS_use_pallas": "false"})
    kc, _vc = pa.alloc_paged_cache(4, 2, BS * 8, KH, jnp.bfloat16)
    bs = pa.pool_block_size(kc)                       # 16
    tables = jnp.zeros((4, W), jnp.int32)
    lens = jnp.asarray([1, 33, 16, 7 * 16 + 1], jnp.int32)
    active = jnp.asarray([False, True, True, True])
    assert pa.reads_own_pages(kc) == selected
    read, live = pa.attn_positions(tables, bs, lens, active, pool=kc)
    own = (3 + 1 + 8) * bs                           # ceil(len / 16) pages
    ladder = 3 * 16 * bs                             # 113 positions: 16 pages
    assert (int(read), int(live)) == (own if selected else ladder, 162)
    # no pool named: the XLA form's count, as before
    read, _ = pa.attn_positions(tables, bs, lens, active)
    assert int(read) == ladder


@pytest.mark.parametrize("order", ["rows", "slots"])
@pytest.mark.parametrize("t", [1, 3])
def test_the_two_orders_of_the_slot_writes_write_the_same_pool(t, order):
    """`_write_rows` (the order a pool read by the kernel keeps) and
    `_write_slots` are one scatter seen two ways."""
    rng = np.random.default_rng(t)
    cache = jnp.asarray(rng.standard_normal((12, 2, 4, 8)), jnp.float32)
    tables = jnp.asarray(rng.permutation(12).reshape(3, 4).astype(np.int32))
    pos = jnp.asarray([[0], [5], [13]], jnp.int32) + jnp.arange(t)[None, :]
    new = jnp.asarray(rng.standard_normal((3, t, 2, 8)), jnp.float32)
    write = pa._write_rows if order == "rows" else pa._write_slots
    got = np.asarray(write(cache, new, tables, pos))
    want = np.array(cache)
    for b in range(3):
        for j in range(t):
            p = int(pos[b, j])
            want[int(tables[b, p // 4]), :, p % 4] = np.asarray(new[b, j])
    np.testing.assert_array_equal(got, want)


def test_a_tiny_engine_through_the_kernel_emits_the_xla_forms_tokens(pallas_on):
    """The macro-step with the kernel selected (rows writes, own pages):
    the streams of the XLA form, token for token, and `attn_positions_read`
    counting each active row's own pages."""
    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    block, prompts, new = 8, {"a": 5, "b": 29}, {"a": 6, "b": 12}

    def serve(flag):
        paddle.set_flags({"FLAGS_use_pallas": flag})
        paddle.seed(0)
        model = LlamaForCausalLM(llama_tiny(
            dtype="float32", num_attention_heads=2, num_key_value_heads=1))
        model.eval()
        eng = serving.GenerationEngine(model, max_batch=3, block_size=block,
                                       num_blocks=3 * 20)
        rng = np.random.default_rng(0)
        serving.reset_decode_stats()
        for rid, n in prompts.items():
            eng.add_request(rid, rng.integers(0, 1000, n).tolist(),
                            max_new_tokens=new[rid])
        out = {}
        while eng.has_work():
            for rid, toks in eng.step().items():
                out.setdefault(rid, []).extend(toks)
        return out, serving.decode_stats()

    want, xla = serve("false")
    got, st = serve("true")
    assert got == want and st["tokens"] == xla["tokens"] == 16
    # the j-th later token is one token step over a row of prompt + j
    own = sum(-(-(prompts[r] + j) // block) * block
              for r in prompts for j in range(1, new[r]))
    assert st["attn_positions_read"] == own < xla["attn_positions_read"]
    assert st["attn_positions_live"] == xla["attn_positions_live"]
