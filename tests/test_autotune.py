"""Kernel autotune cache + tile search (VERDICT r3 #3, #10).

Reference: paddle/cinn/auto_schedule/auto_tuner.h (measured-cost config
search) + paddle/phi/kernels/autotune/cache.h (per-(op, key) config cache).
"""

import json
import os
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.ops import autotune as at


@pytest.fixture()
def tmp_cache(tmp_path):
    """Fresh cache rooted in tmp_path under a synthetic device slug."""
    paddle.set_flags({"FLAGS_autotune_cache_dir": str(tmp_path)})
    at._CACHES.clear()
    yield tmp_path
    paddle.set_flags({"FLAGS_autotune_cache_dir": ""})
    at._CACHES.clear()


def test_cache_round_trip_and_persistence(tmp_cache):
    key = {"seq_q": 256, "seq_k": 256, "head_dim": 64, "dtype": "float32",
           "causal": True}
    assert at.lookup("flash_fwd", key, slug="testdev") is None
    at.record("flash_fwd", key, {"block_q": 64, "block_k": 128}, 1.5,
              slug="testdev")
    got = at.lookup("flash_fwd", key, slug="testdev")
    assert got == {"block_q": 64, "block_k": 128}
    # survives a cold reload
    at._CACHES.clear()
    got = at.lookup("flash_fwd", key, slug="testdev")
    assert got == {"block_q": 64, "block_k": 128}
    raw = json.load(open(os.path.join(tmp_cache, "testdev.json")))
    assert raw["flash_fwd"]
    # disabled via flag
    paddle.set_flags({"FLAGS_use_autotune_cache": False})
    try:
        assert at.lookup("flash_fwd", key, slug="testdev") is None
    finally:
        paddle.set_flags({"FLAGS_use_autotune_cache": True})


def test_tune_kernel_picks_fastest_and_skips_invalid(tmp_cache):
    costs = {16: 3.0, 32: 1.0, 64: 2.0}

    def build(cfg):
        if cfg["b"] == 8:  # invalid candidate: build explodes
            raise ValueError("bad tile")
        return lambda: cfg["b"]

    def timer(fn, args):
        return costs[fn()]

    cfg, ms = at.tune_kernel(
        "k", {"s": 1}, build,
        [{"b": 8}, {"b": 16}, {"b": 32}, {"b": 64}],
        (), timer=timer, slug="testdev")
    assert cfg == {"b": 32} and ms == 1.0
    assert at.lookup("k", {"s": 1}, slug="testdev") == {"b": 32}


def test_tune_kernel_all_invalid_is_loud(tmp_cache):
    def build(cfg):
        raise ValueError("nope")

    with pytest.raises(RuntimeError, match="no valid candidate"):
        at.tune_kernel("k2", {"s": 1}, build, [{"b": 1}], (),
                       timer=lambda f, a: 0.0, slug="testdev")


def test_validate_flash_tile_vmem_budget_v5p_geometry():
    # fine at training shapes
    assert at.validate_flash_tile(128, 128, 2048, 2048, 128) is None
    # long-context K/V residency blows the 16 MiB budget -> loud reason
    reason = at.validate_flash_tile(128, 128, 32768, 32768, 128)
    assert reason is not None and "VMEM" in reason
    # misaligned / non-dividing tiles
    assert "multiple of 8" in at.validate_flash_tile(12, 128, 256, 256, 64)
    assert "does not divide" in at.validate_flash_tile(128, 96, 256, 256, 64)


def test_block_sizes_precedence_flags_cache_default(tmp_cache):
    from paddle_tpu.ops.flash_attention import _block_sizes

    slug = at.device_kind_slug()
    # 3. default
    assert _block_sizes(256, 256, 64, np.float32, True) == (128, 128)
    # 2. cache hit
    at.record("flash_fwd", {"seq_q": 256, "seq_k": 256, "head_dim": 64,
                            "dtype": "float32", "causal": True},
              {"block_q": 64, "block_k": 64}, 1.0, slug=slug)
    assert _block_sizes(256, 256, 64, np.float32, True) == (64, 64)
    # 1. explicit flag overrides the cache
    paddle.set_flags({"FLAGS_flash_block_q": 32, "FLAGS_flash_block_k": 32})
    try:
        assert _block_sizes(256, 256, 64, np.float32, True) == (32, 32)
        # invalid flag: loud warning, falls back to the cache entry
        paddle.set_flags({"FLAGS_flash_block_q": 100})  # not a multiple of 8
        with pytest.warns(UserWarning, match="invalid"):
            assert _block_sizes(256, 256, 64, np.float32, True) == (64, 64)
    finally:
        paddle.set_flags({"FLAGS_flash_block_q": 0, "FLAGS_flash_block_k": 0})
    # invalid CACHED tile: loud warning, 128 default
    at.record("flash_fwd", {"seq_q": 512, "seq_k": 512, "head_dim": 64,
                            "dtype": "float32", "causal": False},
              {"block_q": 100, "block_k": 128}, 1.0, slug=slug)
    with pytest.warns(UserWarning, match="cached tile"):
        assert _block_sizes(512, 512, 64, np.float32, False) == (128, 128)


def test_fused_norm_and_swiglu_consult_cache(tmp_cache):
    from paddle_tpu.ops.fused_norm import _rows_block

    slug = at.device_kind_slug()
    assert _rows_block(4096, 4096, np.float32) == 256  # analytic default
    at.record("rms_rows", {"rows": 4096, "hidden": 4096, "dtype": "float32"},
              {"rows_block": 64}, 1.0, slug=slug)
    assert _rows_block(4096, 4096, np.float32) == 64
    # swiglu: cached tiles reach the kernel grid and numerics hold
    import jax.numpy as jnp

    from paddle_tpu.ops.swiglu import _swiglu_apply

    at.record("swiglu", {"rows": 8, "cols": 256, "dtype": "float32"},
              {"rows_block": 4, "cols_block": 128}, 1.0, slug=slug)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 256)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(8, 256)).astype(np.float32))
    out = _swiglu_apply(x, y)
    ref = np.asarray(x) * (1 / (1 + np.exp(-np.asarray(x)))) * np.asarray(y)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-6)


def test_tuner_end_to_end_with_fake_timer(tmp_cache):
    """tune_swiglu drives the real candidate space and kernel builder."""
    calls = []

    def timer(fn, args):
        calls.append(1)
        return float(len(calls))  # first valid candidate wins

    cfg, ms = at.tune_swiglu(rows=8, cols=256, dtype="float32",
                             timer=timer, slug="testdev")
    assert ms == 1.0 and cfg["cols_block"] in (128, 256)
    assert at.lookup("swiglu", {"rows": 8, "cols": 256, "dtype": "float32"},
                     slug="testdev") == cfg


@pytest.mark.parametrize("kernel", ["flash_bwd_dq", "flash_bwd_dkv"])
def test_tune_flash_runs_each_backward_kernel_under_its_own_key(tmp_cache,
                                                                kernel):
    """The two backward kernels are tuned apart from the forward (their best
    tiles differ: PERF.md section 6, PR 30): the driver builds and RUNS the
    kernel it was asked for and records the winner under that kernel's
    name, where `_block_sizes(kernel=...)` finds it."""
    from paddle_tpu.ops.flash_attention import _block_sizes

    cfg, ms = _tune_retry(lambda: at.tune_flash(
        batch=1, num_heads=1, seq=256, head_dim=128, dtype="float32",
        kernel=kernel, slug=at.device_kind_slug(), iters=1, inner=4))
    assert ms > 1e-4
    tile = (cfg["block_q"], cfg["block_k"])
    assert _block_sizes(256, 256, 128, np.float32, True, kernel=kernel) == tile
    assert at.lookup("flash_fwd", at.flash_key(256, 256, 128, "float32", True)) is None


def test_seeded_v5e_cache_is_well_formed():
    """Every flash entry, of each of the three kernels and of the sparse
    prefill forward, is a valid tile for its shape in the blocks' own type,
    and a measurement (`ms` > 0): the `"ms": 0.0` seeds of an earlier era
    are gone."""
    from paddle_tpu.ops import sparse_flash as sf

    path = os.path.join(os.path.dirname(at.__file__), "tuned", "tpu_v5_lite.json")
    data = json.load(open(path))
    assert set(data) <= {*at.FLASH_KERNELS, "paged_decode", sf.KERNEL}
    assert "flash_fwd" in data
    for key, entry in data.get(sf.KERNEL, {}).items():
        dims = dict(kv.split("=") for kv in key.split("|"))
        assert sf._valid(entry["config"]["block_q"], entry["config"]["block_k"],
                         int(dims["seq_k"]), int(dims["head_dim"]),
                         int(dims["v_dim"]), dims["dtype"])
        assert entry["ms"] > 0 and "measured" in entry["meta"]
    for kernel in set(data) & set(at.FLASH_KERNELS):
        for key, entry in data[kernel].items():
            cfg = entry["config"]
            dims = dict(kv.split("=") for kv in key.split("|"))
            assert at.validate_flash_tile(
                cfg["block_q"], cfg["block_k"],
                int(dims["seq_q"]), int(dims["seq_k"]), int(dims["head_dim"]),
                dtype=dims["dtype"],
                v_dim=int(dims["v_dim"]) if "v_dim" in dims else None) is None
            assert entry["ms"] > 0 and "measured" in entry["meta"]


def test_seeded_v5e_paged_entries_are_candidates_and_measurements():
    """Every `paged_decode` entry is a pages-a-step the tuner would offer
    for its page geometry, with its measured time."""
    path = os.path.join(os.path.dirname(at.__file__), "tuned", "tpu_v5_lite.json")
    entries = json.load(open(path))["paged_decode"]
    assert len(entries) >= 2
    for key, entry in entries.items():
        dims = dict(kv.split("=") for kv in key.split("|"))
        offered = at.paged_candidates(
            int(dims["block_size"]), int(dims["num_kv_heads"]),
            int(dims["head_dim"]), table_width=64,
            itemsize=np.dtype(dims["dtype"]).itemsize,
            pools=1 if "rank" in dims else 2)     # a shared row: one pool
        assert entry["config"] in offered
        assert entry["ms"] > 0 and "measured" in entry["meta"]


@pytest.mark.parametrize("rank", [None, 128], ids=["kv_pair", "shared_row"])
@pytest.mark.parametrize("block_size", [16, 128])
def test_tune_paged_records_one_calls_time_under_the_kernels_key(tmp_cache,
                                                                 block_size,
                                                                 rank):
    """`tune_paged` drives the real kernel builder over its candidates (a
    fake timer here: the second candidate is the fastest) and records the
    winner under the key `_pages_per_step` builds, as ONE call's time of the
    `calls` a dispatch holds; an untuned geometry takes the rule by bytes
    (a megabyte of K and V a step, one pool's in the shared-row case).  A
    shared-row pool (one pool, a `rank`: the latent model's) has a key of
    its own beside a K/V pair's of the same page."""
    from paddle_tpu.ops import paged_attention as pa

    seen = []

    def timer(fn, args):
        seen.append(1)
        return {1: 8.0, 2: 2.0}.get(len(seen), 4.0)

    nkv, width = (2, 128) if rank is None else (1, 256)
    geometry = (block_size, nkv, width, "float32", rank)

    def by_bytes(pools):        # these pages divide a megabyte exactly
        return (1 << 20) // (pools * nkv * block_size * width * 4)

    assert pa._pages_per_step(*geometry) == by_bytes(1 if rank else 2)
    cfg, ms = at.tune_paged(
        batch=2, num_heads=4, num_kv_heads=nkv, head_dim=width, rank=rank,
        block_size=block_size, table_width=4, lens=(5, 4 * block_size),
        dtype="float32", calls=4, timer=timer, slug=at.device_kind_slug())
    assert (cfg, ms) == ({"pages_per_step": 2}, 0.5) and len(seen) == 3
    assert pa._pages_per_step(*geometry) == 2
    if rank is not None:        # the same page as a K pool: not this entry
        assert pa._pages_per_step(*geometry[:4]) == by_bytes(2)
    raw = json.load(open(os.path.join(
        tmp_cache, at.device_kind_slug() + ".json")))["paged_decode"]
    assert [e["ms"] for e in raw.values()] == [0.5]


# the served page geometries (block size, K/V heads, head width, type, the
# shared row's value width, table width) and the pages a step the rule by
# bytes gives them: K and V of a page 64 KB at decode-sat's, 512 KB at
# laguna's, 128 KB at zaya's, the latent row's one pool 160 KB
_SERVED_PAGES = {
    "internlm2-1.8b": ((16, 8, 128, "bfloat16", None, 96), 16),
    "laguna-s-2.1": ((128, 8, 128, "bfloat16", None, 67), 2),
    "zaya1-8b": ((128, 2, 128, "bfloat16", None, 67), 8),
    "latent row": ((128, 1, 640, "bfloat16", 512, 67), 8),
    "zaya1-8b float32": ((128, 2, 128, "float32", None, 67), 4),
}


@pytest.mark.parametrize("cell", sorted(_SERVED_PAGES))
def test_untuned_pages_a_step_bring_in_about_a_megabyte(cell):
    """With the tuned table off, `_pages_per_step` sizes a step by bytes:
    the pages' K and V (one pool for a shared row) within 0.5-2 MB, the
    double-buffered buffers within the candidates' quarter of VMEM, no more
    pages than the table is wide; and counts that the rule chose."""
    from paddle_tpu import profiler
    from paddle_tpu.ops import paged_attention as pa

    (bs, nkv, h, dtype, rank, width), want = _SERVED_PAGES[cell]
    paddle.set_flags({"FLAGS_use_autotune_cache": False})
    try:
        before = profiler.compile_stats()
        pages = pa._pages_per_step(bs, nkv, h, dtype, rank, width)
        narrow = pa._pages_per_step(bs, nkv, h, dtype, rank, 3)
        after = profiler.compile_stats()
    finally:
        paddle.set_flags({"FLAGS_use_autotune_cache": True})
    pools = 1 if rank else 2
    step = pools * nkv * bs * h * np.dtype(dtype).itemsize * pages
    assert pages == want
    assert 0.5 * (1 << 20) <= step <= 2 * (1 << 20)
    assert 2 * step <= at._VMEM_BUDGET // 4 and pages <= width
    assert narrow <= 3
    assert (after["paged_tile_rule_traces"] - before["paged_tile_rule_traces"],
            after["paged_tile_tuned_traces"]
            - before["paged_tile_tuned_traces"]) == (2, 0)


def test_the_kernels_tile_counters_count_by_source(tmp_cache):
    """In the interpreter: a jitted call of the kernel whose page geometry
    the table lacks counts `paged_tile_rule_traces` once, at trace time (a
    second call of the same shapes traces nothing); once the table has an
    entry for it, `paged_tile_tuned_traces`; pages given by the caller (the
    tuner's candidates) count neither.  All three agree with one page a
    step."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import profiler
    from paddle_tpu.ops import paged_attention as pa

    bs, nkv, h = 8, 2, 128
    r = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(r[0], (2, 4, h), jnp.float32)
    kc, vc = (jax.random.normal(x, (12, nkv, bs, h), jnp.float32)
              for x in r[1:])
    tables = jnp.asarray([[3, 7, 1, 9], [2, 5, 11, 0]], jnp.int32)
    lens = jnp.asarray([29, 11], jnp.int32)

    def counts(pages=None):
        before = profiler.compile_stats()
        fn = jax.jit(lambda *a: pa._paged_decode_pallas(*a, 0.1,
                                                        pages=pages))
        outs = [fn(q, kc, vc, tables, lens) for _ in range(2)]
        after = profiler.compile_stats()
        return outs[0], tuple(after[k] - before[k] for k in (
            "paged_tile_rule_traces", "paged_tile_tuned_traces"))

    want, none = counts(pages=1)
    assert none == (0, 0)
    got, by_rule = counts()
    assert by_rule == (1, 0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    at.record("paged_decode", pa.paged_key(bs, nkv, h, jnp.float32),
              {"pages_per_step": 2}, 0.1, slug=at.device_kind_slug())
    got, by_table = counts()
    assert by_table == (0, 1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_v5p_readiness_geometry_and_peaks(tmp_cache):
    """VERDICT r3 #10: tile configs validated for v5p geometry, per-device-
    kind caches keyed by slug, peak table knows v5p, and no candidate that
    busts the VMEM budget is ever proposed."""
    from paddle_tpu.device.peaks import device_peak_tflops

    assert device_peak_tflops("TPU v5p", "tpu") == 459.0
    assert device_peak_tflops("TPU v5 lite", "tpu") == 197.0
    assert device_peak_tflops("cpu", "cpu") == 0.0
    # an unknown accelerator is an error, never a default peak — and a bare
    # "v5" is not a v5p
    for kind in ("TPU v5", "TPU v9", "Tesla X"):
        with pytest.raises(ValueError, match="no published peak"):
            device_peak_tflops(kind, "tpu")

    # candidates at training shapes are all VMEM-valid
    for seq in (2048, 4096):
        cands = at.flash_candidates(seq, seq, 128)
        assert cands, seq
        for c in cands:
            assert at.validate_flash_tile(
                c["block_q"], c["block_k"], seq, seq, 128) is None
    # beyond ~8k the whole-K/V-resident kernel cannot fit ANY tile in the
    # 16 MiB VMEM budget: the candidate space is EMPTY rather than silently
    # proposing an invalid tile (ring attention is the long-context path)
    assert at.flash_candidates(8192, 8192, 128) == []
    assert at.flash_candidates(32768, 32768, 128) == []

    # a v5p cache is consulted independently of the v5e cache
    key = {"seq_q": 4096, "seq_k": 4096, "head_dim": 128,
           "dtype": "bfloat16", "causal": True}
    at.record("flash_fwd", key, {"block_q": 256, "block_k": 128}, 1.0,
              slug="tpu_v5p")
    assert at.lookup("flash_fwd", key, slug="tpu_v5p") == {"block_q": 256, "block_k": 128}
    assert at.lookup("flash_fwd", key, slug="tpu_v5_lite") != {"block_q": 256, "block_k": 128}


def _tune_retry(search, attempts=3):
    """Run a tune_* driver, absorbing load-induced degenerate timings.

    Under parallel tier-1 load (run_tier1 --jobs 6) scheduler preemption
    between the back-to-back `inner` / `2*inner` batches can make every
    timing difference nonpositive, and _time_fn then refuses to record a
    winner (RuntimeError "every timing sample was degenerate") — correct
    tuner behavior, but this test is about END-TO-END candidate
    execution, not timing quality, so the whole search retries."""
    for i in range(attempts):
        try:
            return search()
        except RuntimeError as e:
            if "degenerate" not in str(e) or i == attempts - 1:
                raise


def test_tune_drivers_execute_real_kernels(tmp_cache):
    """The tune_* drivers must build AND RUN their kernels end-to-end.

    Regression: the ops package exports *functions* named flash_attention /
    swiglu that shadow the submodule attributes, so `from paddle_tpu.ops
    import flash_attention as fa` bound the function and every candidate
    died with AttributeError on-chip.  The fake-timer test never called the
    built fn, so only a real execution catches this class.
    """
    # inner=4 (not 1): a 4-vs-8 dispatch difference keeps a measurable
    # signal above scheduler jitter when six test jobs share the host
    cfg, ms = _tune_retry(lambda: at.tune_flash(
        batch=1, num_heads=1, seq=128, head_dim=8,
        dtype="float32", slug="testdev", iters=1, inner=4))
    # strictly above the degenerate-sample floor: a clamped/failed timing
    # must not satisfy this (1e-4 is _time_fn's failed-sample sentinel)
    assert cfg["block_q"] in (64, 128) and ms > 1e-4
    cfg, _ = _tune_retry(lambda: at.tune_fused_norm(
        rows=16, hidden=128, dtype="float32",
        slug="testdev", iters=1, inner=4))
    assert 16 % cfg["rows_block"] == 0
    cfg, _ = _tune_retry(lambda: at.tune_swiglu(
        rows=64, cols=128, dtype="float32",
        slug="testdev", iters=1, inner=4))
    assert 64 % cfg["rows_block"] == 0 and 128 % cfg["cols_block"] == 0


@pytest.fixture()
def fake_seed_dir(tmp_path, monkeypatch):
    """Redirect AutotuneCache.seed_path into a tmp dir so precedence tests
    never touch the installed package's ops/tuned/ (read-only on a
    site-packages install)."""
    d = tmp_path / "fake_seed"
    d.mkdir()
    monkeypatch.setattr(
        at.AutotuneCache, "seed_path",
        property(lambda self: str(d / f"{self.slug}.json")))
    at._CACHES.clear()
    yield d
    at._CACHES.clear()


def _write_seed(seed_dir, slug, data):
    """Plant a synthetic checked-in seed cache for `slug` in the fake dir."""
    path = os.path.join(str(seed_dir), f"{slug}.json")
    with open(path, "w") as f:
        json.dump(data, f)
    return path


def test_seed_vs_runtime_precedence_on_reload(tmp_cache, fake_seed_dir):
    """A runtime-tuned entry for a key PRESENT in the seed must win on a
    cold reload, while seed keys the runtime never touched must keep
    following the (possibly updated) seed — the runtime file may not
    fossilize a copy of the seed (regression: save() used to dump the
    whole seed-merged table into FLAGS_autotune_cache_dir, so a later
    seed update was silently shadowed by the stale copy)."""
    slug = "seeddev"
    k1, k2 = {"s": 1}, {"s": 2}
    seed_path = _write_seed(fake_seed_dir, slug, {"k": {
        at._key_str(k1): {"config": {"b": 1}, "ms": 1.0},
        at._key_str(k2): {"config": {"b": 2}, "ms": 1.0},
    }})
    try:
        at._CACHES.clear()
        assert at.lookup("k", k1, slug=slug) == {"b": 1}  # seed serves
        at.record("k", k1, {"b": 99}, 0.5, slug=slug)     # runtime retune
        at._CACHES.clear()
        assert at.lookup("k", k1, slug=slug) == {"b": 99}  # runtime wins
        assert at.lookup("k", k2, slug=slug) == {"b": 2}
        # the runtime file holds ONLY the runtime delta
        runtime = json.load(open(os.path.join(str(tmp_cache), f"{slug}.json")))
        assert at._key_str(k2) not in runtime.get("k", {})
        # simulate a package seed update for the untouched key
        _write_seed(fake_seed_dir, slug, {"k": {
            at._key_str(k1): {"config": {"b": 1}, "ms": 1.0},
            at._key_str(k2): {"config": {"b": 22}, "ms": 1.0},
        }})
        at._CACHES.clear()
        assert at.lookup("k", k2, slug=slug) == {"b": 22}  # update visible
        assert at.lookup("k", k1, slug=slug) == {"b": 99}  # runtime still wins
    finally:
        at._CACHES.clear()


def test_unwritable_cache_dir_falls_back_to_user_cache(tmp_cache, monkeypatch):
    """FLAGS_autotune_cache_dir pointing somewhere uncreatable (parent is a
    regular file — even root cannot mkdir through it) must fall back to
    the ~/.cache user path, and the entry must survive a cold reload while
    the flag still points at the bad dir.  user_path is monkeypatched into
    the pytest tmp dir so the test never touches the real home."""
    slug = "fallbackdev"
    blocker = os.path.join(str(tmp_cache), "blocker")
    with open(blocker, "w") as f:
        f.write("x")
    fake_home = tmp_cache / "fake_home_cache"
    monkeypatch.setattr(
        at.AutotuneCache, "user_path",
        property(lambda self: str(fake_home / f"{self.slug}.json")))
    user_path = str(fake_home / f"{slug}.json")
    paddle.set_flags(
        {"FLAGS_autotune_cache_dir": os.path.join(blocker, "sub")})
    at._CACHES.clear()
    try:
        c = at.cache(slug)
        c.put("k", {"s": 1}, {"b": 7}, 0.1)
        assert c.save() == user_path
        assert os.path.exists(user_path)
        at._CACHES.clear()
        assert at.lookup("k", {"s": 1}, slug=slug) == {"b": 7}
    finally:
        at._CACHES.clear()


def test_cost_model_table_keys_by_name_and_shape():
    """OpCostModel.load()/save() round-trips per-shape entries: two shapes
    of one op must not overwrite each other (regression: the table was
    keyed by bare name, so the docstring's round-trip contract silently
    kept only the last-measured shape)."""
    import jax.numpy as jnp

    from paddle_tpu.cost_model import OpCostModel

    m = OpCostModel()
    small = jnp.ones((8, 8), jnp.float32)
    big = jnp.ones((32, 32), jnp.float32)
    t_small = m.measure("mm", lambda a: a @ a, small, iters=1, warmup=0)
    t_big = m.measure("mm", lambda a: a @ a, big, iters=1, warmup=0)
    assert len(m.table) == 2  # both shapes present
    k_small = m.table_key("mm", (small,))
    k_big = m.table_key("mm", (big,))
    assert m.query(k_small) == t_small and m.query(k_big) == t_big
    # bare-name query on an ambiguous op is loud, not arbitrary
    with pytest.raises(KeyError, match="shape"):
        m.query("mm")
    assert m.query("mm", default=0.5) == 0.5
    # single-shape ops keep resolving by bare name (back-compat)
    t1 = m.measure("tanh", jnp.tanh, small, iters=1, warmup=0)
    assert m.query("tanh") == t1


def test_cost_model_round_trip_preserves_shape_entries(tmp_path):
    import jax.numpy as jnp

    from paddle_tpu.cost_model import OpCostModel

    m = OpCostModel()
    a = jnp.ones((8, 4), jnp.float32)
    b = jnp.ones((16, 4), jnp.float32)
    m.measure("sum", lambda v: v.sum(), a, iters=1, warmup=0)
    m.measure("sum", lambda v: v.sum(), b, iters=1, warmup=0)
    p = tmp_path / "table.json"
    m.save(str(p))
    m2 = OpCostModel.load(str(p))
    assert m2.table == m.table and len(m2.table) == 2


def test_validate_tile_generic_budget():
    """The generalized VMEM check shared by the kernel validators and the
    schedule searcher's candidate prune."""
    assert at.validate_tile(1024) is None
    reason = at.validate_tile(at._VMEM_BUDGET + 1)
    assert reason is not None and "VMEM" in reason
    assert at.validate_tile(2048, budget=1024) is not None
    # flash validator routes its VMEM tier through the shared check
    r = at.validate_flash_tile(1024, 1024, 8192, 8192, 256)
    assert r is not None and "VMEM" in r


def test_prefix_era_runtime_dump_is_healed_on_load(tmp_cache, fake_seed_dir):
    """A runtime cache file written by the PRE-fix save() (an UNMARKED full
    copy of the seed-merged table) must never shadow a later seed update:
    once the seed changes, a stale copy is value-indistinguishable from a
    genuine retune, so unmarked files keep only keys the seed lacks
    (seeded keys re-tune once).  Post-fix files carry the runtime marker
    and keep the runtime-wins contract."""
    slug = "healdev"
    k1, k2, k3 = {"s": 1}, {"s": 2}, {"s": 3}
    seed_entries = {
        at._key_str(k1): {"config": {"b": 1}, "ms": 1.0},
        at._key_str(k2): {"config": {"b": 2}, "ms": 1.0},
    }
    seed_path = _write_seed(fake_seed_dir, slug, {"k": dict(seed_entries)})
    try:
        # pre-fix era dump: whole seed copied + a retune of k1 + a key the
        # seed never had (k3) — NO runtime marker
        stale = {"k": dict(seed_entries)}
        stale["k"][at._key_str(k1)] = {"config": {"b": 99}, "ms": 0.5}
        stale["k"][at._key_str(k3)] = {"config": {"b": 3}, "ms": 0.5}
        with open(os.path.join(str(tmp_cache), f"{slug}.json"), "w") as f:
            json.dump(stale, f)
        # seed update for the never-retuned key
        _write_seed(fake_seed_dir, slug, {"k": {
            at._key_str(k1): {"config": {"b": 1}, "ms": 1.0},
            at._key_str(k2): {"config": {"b": 22}, "ms": 1.0},
        }})
        at._CACHES.clear()
        assert at.lookup("k", k2, slug=slug) == {"b": 22}  # update visible
        assert at.lookup("k", k3, slug=slug) == {"b": 3}   # unseeded key kept
        assert at.lookup("k", k1, slug=slug) == {"b": 1}   # one-time retune cost
        # a fresh retune writes a MARKED file whose entries win on reload
        at.record("k", k1, {"b": 100}, 0.4, slug=slug)
        raw = json.load(open(os.path.join(str(tmp_cache), f"{slug}.json")))
        assert raw.get(at._RUNTIME_MARKER) == 1
        assert at._key_str(k2) not in raw["k"]  # runtime delta only
        at._CACHES.clear()
        assert at.lookup("k", k1, slug=slug) == {"b": 100}
    finally:
        at._CACHES.clear()
