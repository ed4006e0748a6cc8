"""Kernel autotune: per-shape tile search with a persistent per-device cache.

Reference: the CINN auto-scheduler (paddle/cinn/auto_schedule/auto_tuner.h —
search over schedule configs driven by measured cost) and the phi kernel
autotune cache (paddle/phi/kernels/autotune/cache.h — per-(op, key) config
cache consulted by kernel launch).

TPU-native redesign: XLA already schedules fused HLO, so the tunable surface
is the Pallas tile geometry — flash-attention block_q/block_k, fused-norm row
blocks, swiglu tile widths.  The tuner times candidate tiles ON DEVICE for a
given shape signature, persists winners per DEVICE KIND (v5e and v5p disagree
on the best tiles; a cache tuned on one must not silently apply to the
other), and the kernels consult the cache at trace time — so the
`PallasFusionPass` substitutions pick tuned tiles automatically with zero
call-site changes.

Layout:
- checked-in seed caches: `paddle_tpu/ops/tuned/<device_kind_slug>.json`
- runtime-tuned entries merge over the seed and save to
  `FLAGS_autotune_cache_dir` (defaults to the seed dir; falls back to
  `~/.cache/paddle_tpu/autotune` when unwritable)
- `python -m paddle_tpu.ops.autotune --kernel all` sweeps the standard
  shape set within a time budget and writes the cache.
"""

from __future__ import annotations

import json
import os
import time

__all__ = [
    "AutotuneCache",
    "cache",
    "lookup",
    "record",
    "tune_kernel",
    "tune_flash",
    "tune_fused_norm",
    "tune_swiglu",
    "tune_paged",
    "tune_sparse_flash",
    "device_kind_slug",
    "flash_vmem_bytes",
    "lane_padded",
    "validate_tile",
    "validate_flash_tile",
]

_VMEM_BUDGET = 16 << 20  # ~16 MB/core on every current TPU generation

# Format marker written into runtime cache files so loads can tell a
# post-fix runtime delta (runtime-wins contract applies) from a pre-fix
# seed-merged dump (healed at load: seeded keys dropped).
_RUNTIME_MARKER = "__paddle_tpu_runtime__"


def device_kind_slug(device=None):
    import jax

    if device is None:
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "") or device.platform
    return "".join(c if c.isalnum() else "_" for c in kind.lower()).strip("_")


def _key_str(key: dict) -> str:
    return "|".join(f"{k}={key[k]}" for k in sorted(key))


class AutotuneCache:
    """Per-device-kind persistent (kernel, shape-key) -> config cache."""

    def __init__(self, slug=None):
        self.slug = slug or device_kind_slug()
        self._data: dict = {}
        self._dirty = False
        self._load()

    # ------------------------------------------------------------- paths
    @property
    def seed_path(self):
        return os.path.join(os.path.dirname(__file__), "tuned", f"{self.slug}.json")

    def _save_path(self):
        from paddle_tpu._core import flags as _flags

        d = str(_flags.flag("FLAGS_autotune_cache_dir") or "")
        if d:
            return os.path.join(d, f"{self.slug}.json")
        return self.seed_path

    @property
    def user_path(self):
        """Fallback written when the package dir is read-only — also read
        back at load time, newest-priority."""
        return os.path.join(os.path.expanduser("~/.cache/paddle_tpu/autotune"),
                            f"{self.slug}.json")

    def _load(self):
        # priority (last wins): seed < user fallback < explicitly configured
        # dir; when no dir is configured _save_path() IS the seed path —
        # dedupe so the seed cannot re-apply over newer user entries.
        # Seed-originated and runtime entries are tracked separately: the
        # runtime save must NOT fossilize a copy of the seed into the
        # configured dir, or a later package seed update for a key the
        # runtime never tuned would be silently shadowed by the stale copy.
        self._runtime: dict = {}
        paths = [(self.seed_path, False), (self.user_path, True)]
        sp = self._save_path()
        if sp not in (self.seed_path, self.user_path):
            paths.append((sp, True))
        seed: dict = {}
        for path, is_runtime in paths:
            try:
                with open(path) as f:
                    loaded = json.load(f)
            except (OSError, ValueError):
                continue
            marked = bool(loaded.pop(_RUNTIME_MARKER, None))
            for kernel, entries in loaded.items():
                if is_runtime and not marked:
                    # heal dumps written by the pre-marker save() (it
                    # copied the whole seed-merged table): a stale copy of
                    # a seed entry is value-indistinguishable from a
                    # genuine retune once the seed updates, so an UNMARKED
                    # runtime file keeps only keys the seed doesn't have —
                    # seeded keys re-tune once, stale copies can never
                    # shadow a seed update again
                    entries = {k: v for k, v in entries.items()
                               if k not in seed.get(kernel, {})}
                elif is_runtime:
                    # marked (post-fix) file: runtime wins per contract;
                    # entries identical to the seed carry no information
                    entries = {k: v for k, v in entries.items()
                               if seed.get(kernel, {}).get(k) != v}
                if is_runtime:
                    self._runtime.setdefault(kernel, {}).update(entries)
                else:
                    seed.setdefault(kernel, {}).update(entries)
                self._data.setdefault(kernel, {}).update(entries)

    def save(self):
        if not self._dirty:
            return None
        path = self._save_path()
        for candidate in (path, self.user_path):
            # writing INTO the seed file keeps its seed entries (merged
            # payload); any runtime location gets runtime entries only,
            # tagged with the format marker so reloads trust them
            if candidate == self.seed_path:
                payload = self._data
            else:
                payload = dict(self._runtime)
                payload[_RUNTIME_MARKER] = 1
            try:
                os.makedirs(os.path.dirname(candidate), exist_ok=True)
                with open(candidate, "w") as f:
                    json.dump(payload, f, indent=1, sort_keys=True)
                self._dirty = False
                return candidate
            except OSError:
                continue
        return None

    # ------------------------------------------------------------- access
    @property
    def runtime_entries(self) -> int:
        """How many entries came from OUTSIDE the committed seed table (the
        user fallback dir, FLAGS_autotune_cache_dir, or this process)."""
        return sum(len(v) for v in self._runtime.values())

    def get(self, kernel: str, key: dict):
        entry = self._data.get(kernel, {}).get(_key_str(key))
        return dict(entry["config"]) if entry else None

    def put(self, kernel: str, key: dict, config: dict, ms: float, meta=None):
        entry = {
            "config": dict(config),
            "ms": round(float(ms), 6),
            **({"meta": meta} if meta else {}),
        }
        self._data.setdefault(kernel, {})[_key_str(key)] = entry
        self._runtime.setdefault(kernel, {})[_key_str(key)] = dict(entry)
        self._dirty = True


_CACHES: dict = {}


def cache(slug=None) -> AutotuneCache:
    from paddle_tpu._core import flags as _flags

    slug = slug or device_kind_slug()
    # keyed on the configured dir too: changing FLAGS_autotune_cache_dir
    # after a lookup must take effect, not be silently memoized away
    key = (slug, str(_flags.flag("FLAGS_autotune_cache_dir") or ""))
    if key not in _CACHES:
        _CACHES[key] = AutotuneCache(slug)
    return _CACHES[key]


def lookup(kernel: str, key: dict, slug=None):
    """Cache consultation used by the kernels at trace time; None when the
    shape was never tuned on this device kind (or the cache is disabled)."""
    from paddle_tpu._core import flags as _flags

    if not _flags.flag("FLAGS_use_autotune_cache"):
        return None
    try:
        return cache(slug).get(kernel, key)
    except Exception:
        return None


def record(kernel, key, config, ms, slug=None, save=True):
    c = cache(slug)
    c.put(kernel, key, config, ms)
    if save:
        c.save()
    return c


# ---------------------------------------------------------------------------
# measurement


def _time_fn(fn, args, warmup=1, iters=3, timer=None, inner=None,
             target_ms=300.0):
    """Estimate per-call device ms of fn(*args).

    The only true barrier on the remote transport is a device→host
    readback (see paddle_tpu.device.hard_sync — block_until_ready
    resolves at dispatch), and that round trip is both large (~tens of
    ms) and NOISY (±tens of ms), so neither per-call timing nor a
    fixed-length difference survives it.  Methodology:

    1. measure the pure readback round trip on an already-ready array;
    2. pilot-run a short batch to rough-estimate the per-call cost;
    3. size `inner` so one batch costs ~`target_ms` of device time —
       the RTT noise then perturbs the estimate by noise/target only;
    4. per sample, time `inner` and `2*inner` back-to-back dispatches
       and difference the totals: the constant readback + dispatch
       latency cancels, leaving inner * kernel_ms.  Median over iters.

    Pass `inner` explicitly to skip the adaptive sizing (tests)."""
    import jax.numpy as jnp

    from paddle_tpu.device import hard_sync

    if timer is not None:  # deterministic tests inject a fake timer
        return timer(fn, args)
    for _ in range(warmup):
        hard_sync(fn(*args))

    def total_ms(n):
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            out = fn(*args)
        hard_sync(out)
        return (time.perf_counter() - t0) * 1e3

    if inner is None:
        ready = jnp.zeros(8)
        hard_sync(ready)
        rtt_samples = []
        for _ in range(3):
            t0 = time.perf_counter()
            hard_sync(ready)
            rtt_samples.append((time.perf_counter() - t0) * 1e3)
        rtt = min(rtt_samples)
        pilot = total_ms(8)
        per_call = max((pilot - rtt) / 8, 1e-3)
        inner = int(min(max(target_ms / per_call, 8), 4096))

    times = []
    for _ in range(iters):
        cur = inner
        for _attempt in range(3):
            t1 = total_ms(cur)
            t2 = total_ms(2 * cur)
            diff = (t2 - t1) / cur
            if diff > 1e-4:
                times.append(diff)
                break
            # RTT noise swamped the signal: a nonpositive difference is a
            # FAILED sample, never a result — grow the batch and retry
            # (silently clamping here once shipped noise-picked tiles)
            cur = min(cur * 4, 8192)
        else:
            import warnings

            warnings.warn(
                "autotune: timing sample degenerate even at inner=%d "
                "(readback RTT noise exceeds the kernel signal)" % cur)
    if not times:
        raise RuntimeError(
            "autotune: every timing sample was degenerate — transport too "
            "noisy to rank candidates; not recording a winner")
    times.sort()
    return times[len(times) // 2]


def tune_kernel(kernel, key, build, candidates, args, *, iters=3, inner=None,
                budget_s=None, timer=None, slug=None, save=True, verbose=False):
    """Search `candidates` (list of config dicts) for the fastest
    `build(config)(*args)`; record and return (best_config, best_ms).

    Invalid configs (build or execution raises) are skipped — an exhausted
    candidate list raises so tuning failures are loud, not silent."""
    best_cfg, best_ms = None, float("inf")
    t_start = time.perf_counter()
    for cfg in candidates:
        if budget_s is not None and time.perf_counter() - t_start > budget_s and best_cfg is not None:
            break
        try:
            fn = build(cfg)
            ms = _time_fn(fn, args, iters=iters, timer=timer, inner=inner)
        except Exception as e:  # noqa: BLE001 — candidate invalid on this device
            if verbose:
                print(f"  {kernel} {cfg}: invalid ({type(e).__name__})")
            continue
        if verbose:
            print(f"  {kernel} {cfg}: {ms:.3f} ms")
        if ms < best_ms:
            best_cfg, best_ms = dict(cfg), ms
    if best_cfg is None:
        raise RuntimeError(
            f"autotune: no valid candidate for {kernel} {_key_str(key)} "
            f"out of {len(list(candidates))}")
    record(kernel, key, best_cfg, best_ms, slug=slug, save=save)
    return best_cfg, best_ms


# ---------------------------------------------------------------------------
# per-kernel candidate spaces + drivers


def lane_padded(width):
    """A row of `width` values as VMEM holds it: whole 128-lane tiles."""
    return -(-width // 128) * 128


def flash_vmem_bytes(block_q, block_k, seq_k, head_dim, itemsize=4,
                     v_dim=None):
    """Working-set estimate for one fwd grid step, in the blocks' own type
    (`itemsize` bytes a value, rows lane-padded, as
    `flash_attention._require_vmem` counts them): whole-K/V residency + q/o
    blocks + the float32 lse block, double-buffered by the pipeline, + the
    float32 scores and probabilities tiles."""
    row = lane_padded(head_dim) + lane_padded(
        head_dim if v_dim is None else v_dim)
    blocks = ((seq_k + block_q) * row * itemsize  # k, v; q, o
              + block_q * 128 * 4)                # lse
    return 2 * blocks + 2 * block_q * block_k * 4


def validate_tile(vmem_bytes, budget=None):
    """Generic VMEM-budget check for any candidate tiling: None when a
    working-set estimate fits the per-core budget, else a human-readable
    reason.  The kernel-specific validators (validate_flash_tile) and the
    schedule searcher's candidate prune (static/schedule_search.py) share
    this single budget definition."""
    b = _VMEM_BUDGET if budget is None else int(budget)
    need = int(vmem_bytes)
    if need > b:
        return (f"working set ~{max(need >> 20, 1)} MiB VMEM "
                f"> {b >> 20} MiB budget")
    return None


def validate_flash_tile(block_q, block_k, seq_q, seq_k, head_dim, *,
                        dtype=None, v_dim=None):
    """None when valid; else a human-readable reason (kernels warn with it
    rather than silently falling back — VERDICT r3 #10).  `dtype` is the
    blocks' (float32 where none is given), `v_dim` V's width where it has
    one of its own."""
    import numpy as np

    if block_q < 8 or block_q % 8:
        return f"block_q={block_q} must be a positive multiple of 8"
    if block_k < 8 or block_k % 8:
        return f"block_k={block_k} must be a positive multiple of 8"
    if seq_q % block_q:
        return f"block_q={block_q} does not divide seq_q={seq_q}"
    if seq_k % block_k:
        return f"block_k={block_k} does not divide seq_k={seq_k}"
    itemsize = 4 if dtype is None else np.dtype(dtype).itemsize
    reason = validate_tile(flash_vmem_bytes(block_q, block_k, seq_k, head_dim,
                                            itemsize, v_dim))
    if reason:
        return f"tile ({block_q},{block_k}): {reason}"
    return None


def flash_candidates(seq_q, seq_k, head_dim, *, dtype=None, v_dim=None):
    # a block under 128 rows leaves the 128 x 128 matrix unit part empty:
    # offered only to sequences shorter than that
    sizes = [b for b in (64, 128, 256, 512, 1024)
             if b >= min(128, seq_q, seq_k)]
    out = []
    for bq in sizes:
        for bk in sizes:
            if validate_flash_tile(bq, bk, seq_q, seq_k, head_dim,
                                   dtype=dtype, v_dim=v_dim) is None:
                out.append({"block_q": bq, "block_k": bk})
    return out


FLASH_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def flash_key(seq_q, seq_k, head_dim, dtype, causal, v_dim=None, window=None):
    """The table key of a flash kernel's shape signature; `v_dim` is part
    of it only where V has a width of its own (latent attention's
    prefill), `window` only where the forward takes one (a sliding
    layer's prefill)."""
    import numpy as np

    key = {"seq_q": seq_q, "seq_k": seq_k, "head_dim": head_dim,
           "dtype": np.dtype(dtype).name, "causal": bool(causal)}
    if v_dim not in (None, head_dim):
        key["v_dim"] = v_dim
    if window is not None:
        key["window"] = int(window)
    return key


def tune_flash(batch=1, num_heads=8, seq=2048, head_dim=128, dtype="bfloat16",
               causal=True, v_dim=None, window=None, num_kv_heads=None,
               kernel="flash_fwd", **kw):
    """Tune one flash-attention kernel's tile for one shape signature: the
    forward (`v_dim` where V has a width of its own, `window` for a
    sliding layer's prefill, `num_kv_heads` for grouped K/V heads) or
    either backward kernel, each under its own table key
    (`FLASH_KERNELS`).  What the
    Mosaic compiler refuses (a tile whose blocks overflow VMEM) is
    skipped by `tune_kernel`."""
    import jax
    import jax.numpy as jnp

    import importlib

    # NOT `from paddle_tpu.ops import flash_attention`: the package exports a
    # *function* named flash_attention that shadows the submodule attribute.
    fa = importlib.import_module("paddle_tpu.ops.flash_attention")

    jd = jnp.dtype(dtype)
    key = flash_key(seq, seq, head_dim, jd, causal, v_dim, window)
    if window is not None and kernel != "flash_fwd":
        raise ValueError("the backward kernels take no window")
    scale = 1.0 / head_dim ** 0.5
    widths = (head_dim, head_dim, v_dim or head_dim, v_dim or head_dim)
    heads = (num_heads, num_kv_heads or num_heads, num_kv_heads or num_heads,
             num_heads)
    q, k, v, do = (
        jax.random.normal(r, (batch, n, seq, w), jd)
        for r, n, w in zip(jax.random.split(jax.random.PRNGKey(0), 4), heads,
                           widths))
    if kernel == "flash_fwd":
        args = (q, k, v)

        def build(cfg):
            return jax.jit(lambda q, k, v: fa._fwd(
                q, k, v, scale, causal, cfg["block_q"], cfg["block_k"],
                window)[0])
    else:
        out, lse = fa._fwd(q, k, v, scale, causal, *fa._block_sizes(
            seq, seq, head_dim, jd, causal))
        args = (q, k, v, do, *fa._row_stats(out, lse, do))
        run = {"flash_bwd_dq": fa._bwd_dq, "flash_bwd_dkv": fa._bwd_dkv}[kernel]

        def build(cfg):
            return jax.jit(lambda *a: run(
                *a, scale, causal, cfg["block_q"], cfg["block_k"]))

    return tune_kernel(kernel, key, build,
                       flash_candidates(seq, seq, head_dim, dtype=jd,
                                        v_dim=v_dim), args, **kw)


def norm_candidates(rows, hidden):
    out = []
    for br in (8, 16, 32, 64, 128, 256, 512):
        if br <= rows and rows % br == 0 and br * hidden * 4 * 2 <= _VMEM_BUDGET:
            out.append({"rows_block": br})
    return out or [{"rows_block": rows}]


def tune_fused_norm(rows=4096, hidden=4096, dtype="bfloat16", **kw):
    import jax
    import jax.numpy as jnp

    import importlib

    fnorm = importlib.import_module("paddle_tpu.ops.fused_norm")

    jd = jnp.dtype(dtype)
    key = {"rows": rows, "hidden": hidden, "dtype": jd.name}
    x = jax.random.normal(jax.random.PRNGKey(0), (rows, hidden), jd)
    w = jax.random.normal(jax.random.PRNGKey(1), (hidden,), jd)

    def build(cfg):
        import functools

        br = cfg["rows_block"]

        def run(x, w):
            return fnorm._pallas_rows(
                functools.partial(fnorm._rms_kernel, eps=1e-6), x, (w,),
                x.dtype, rows_block=br)

        return jax.jit(run)

    return tune_kernel("rms_rows", key, build, norm_candidates(rows, hidden),
                       (x, w), **kw)


def swiglu_candidates(rows, cols):
    out = []
    for br in (64, 128, 256, 512):
        for bc in (128, 256, 512, 1024, 2048):
            if (br <= rows and rows % br == 0 and bc <= cols and cols % bc == 0
                    and br * bc * 4 * 3 * 2 <= _VMEM_BUDGET):
                out.append({"rows_block": br, "cols_block": bc})
    return out or [{"rows_block": rows, "cols_block": cols}]


def tune_swiglu(rows=4096, cols=11008, dtype="bfloat16", **kw):
    import jax
    import jax.numpy as jnp

    import importlib

    # see tune_flash: the swiglu function shadows its submodule on the package
    sw = importlib.import_module("paddle_tpu.ops.swiglu")

    jd = jnp.dtype(dtype)
    key = {"rows": rows, "cols": cols, "dtype": jd.name}
    x = jax.random.normal(jax.random.PRNGKey(0), (rows, cols), jd)
    y = jax.random.normal(jax.random.PRNGKey(1), (rows, cols), jd)

    def build(cfg):
        return jax.jit(lambda a, b: sw._swiglu_apply(
            a, b, rows_block=cfg["rows_block"], cols_block=cfg["cols_block"]))

    return tune_kernel("swiglu", key, build, swiglu_candidates(rows, cols),
                       (x, y), **kw)


def matmul_epilogue_candidates(M, K, N):
    out = []
    for bm in (128, 256, 512):
        for bn in (128, 256):
            for bk in (256, 512, 1024):
                if (bm <= M and M % bm == 0 and bn <= N and N % bn == 0
                        and bk <= K and K % bk == 0
                        # f32 acc + double-buffered in/out blocks
                        and (bm * bn * 4 + 2 * (bm * bk + bk * bn + bm * bn) * 2)
                        <= _VMEM_BUDGET):
                    out.append({"bm": bm, "bk": bk, "bn": bn})
    return out or [{"bm": min(M, 128), "bk": K, "bn": min(N, 128)}]


def tune_matmul_epilogue(m=4096, k=4096, n=4096, dtype="bfloat16", **kw):
    import jax
    import jax.numpy as jnp

    import importlib

    me = importlib.import_module("paddle_tpu.ops.matmul_epilogue")

    jd = jnp.dtype(dtype)
    key = {"m": m, "k": k, "n": n, "dtype": jd.name}
    x = jax.random.normal(jax.random.PRNGKey(0), (m, k), jd)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n), jd)
    b = jax.random.normal(jax.random.PRNGKey(2), (n,), jd)

    def build(cfg):
        tiles = (cfg["bm"], cfg["bk"], cfg["bn"])
        # tanh-GELU: the heaviest epilogue Mosaic lowers (exact erf is XLA's)
        return jax.jit(lambda a, ww, bb: me._fused_2d(a, ww, bb, "gelu_tanh",
                                                      tiles=tiles))

    return tune_kernel("matmul_epilogue", key, build,
                       matmul_epilogue_candidates(m, k, n), (x, w, b), **kw)


def paged_candidates(block_size, num_kv_heads, head_dim, table_width,
                     itemsize=2, pools=2):
    """Pages a step of the paged decode kernel: powers of two whose
    double-buffered buffers (K and V; one pool in the shared-row case) fit
    a quarter of VMEM."""
    out = []
    for pages in (1, 2, 4, 8, 16, 32, 64):
        buffers = 2 * pools * num_kv_heads * pages * block_size * lane_padded(
            head_dim) * itemsize
        if pages <= table_width and buffers <= _VMEM_BUDGET // 4:
            out.append({"pages_per_step": pages})
    return out


def tune_paged(batch=32, num_heads=16, num_kv_heads=8, head_dim=128,
               block_size=16, table_width=96, lens=(130, 512),
               dtype="bfloat16", calls=16, rank=None, **kw):
    """Tune the paged decode kernel's pages a step for one page geometry
    (`ops.paged_attention.paged_key`): `batch` rows whose lengths are
    spread evenly over `lens`, their pages scattered over the pool.  With
    `rank` the shared-row case (`paged_shared_row_attention`: ONE pool of
    one `head_dim`-wide row a token, its first `rank` lanes the value).
    One timed dispatch is `calls` dependent calls (a call is shorter than a
    dispatch); `ms` is one call's.  Prints the XLA form's ms on the same
    rows beside the candidates': the selection rule is read off that."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import paged_attention as pa

    jd = jnp.dtype(dtype)
    key = pa.paged_key(block_size, num_kv_heads, head_dim, jd, rank)
    rng = np.random.default_rng(0)
    nb = batch * table_width + batch
    tables = jnp.asarray(rng.permutation(nb)[:batch * table_width].reshape(
        batch, table_width).astype(np.int32))
    seq = jnp.asarray(rng.permutation(np.linspace(
        lens[0], lens[1], batch).astype(np.int32)))
    r = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(r[0], (batch, num_heads, head_dim), jd)
    pools = tuple(jax.random.normal(
        x, (nb, num_kv_heads, block_size, head_dim), jd)
        for x in (r[1:] if rank is None else r[1:2]))
    scale = 1.0 / head_dim ** 0.5

    def repeated(attend):
        def run(q, *rest):
            def one(acc, _):
                o = attend((q + acc).astype(q.dtype), *rest)
                o = o.astype(jnp.float32) * 1e-3
                if o.shape != q.shape:      # a shared row's result: `rank`
                    o = jnp.pad(o, ((0, 0), (0, 0), (0, head_dim - rank)))
                return o, None
            return jax.lax.scan(one, jnp.zeros(q.shape, jnp.float32), None,
                                length=calls)[0]
        return jax.jit(run)

    def build(cfg):
        if rank is not None:
            return repeated(lambda q, pool, *a: pa._paged_decode_pallas(
                q, pool, None, *a, scale, pages=cfg["pages_per_step"],
                rank=rank))
        return repeated(lambda *a: pa._paged_decode_pallas(
            *a, scale, pages=cfg["pages_per_step"]))

    args = (q, *pools, tables, seq)
    timing = {k: kw[k] for k in ("iters", "inner", "timer") if k in kw}
    if kw.get("verbose"):
        if rank is not None:
            xla = repeated(lambda *a: pa._shared_row_xla(*a, rank, scale))
        else:
            xla = repeated(lambda q, *a: pa._paged_chunk_xla(
                q[:, None], *a, scale)[:, 0])
        print(f"  paged_decode XLA form: "
              f"{_time_fn(xla, args, **timing) / calls:.4f} ms")
    save = kw.pop("save", True)
    cfg, ms = tune_kernel(
        "paged_decode", key, build,
        paged_candidates(block_size, num_kv_heads, head_dim, table_width,
                         jd.itemsize, pools=len(pools)), args, save=False,
        **kw)
    # one call's time in the table, not the dispatch's
    record("paged_decode", key, cfg, ms / calls, slug=kw.get("slug"),
           save=save)
    return cfg, ms / calls


def tune_sparse_flash(rows=6144, seq=8192, head_dim=192, v_dim=128,
                      num_heads=128, top_k=2048, dtype="bfloat16", **kw):
    """Tune the sparse prefill forward's tile (ops/sparse_flash.py) for the
    last `rows` queries of a prompt of `seq` keys, each keeping the exact
    `top_k` of its causal keys by random scores (an indexer's selection is
    as spread: every key tile of a query tile keeps some key).  Recorded
    under `sparse_flash.KERNEL` and `flash_key(rows, seq, ...)`.  Prints
    the FLOPs of the tiles the kernel visits (a query's causal keys) and of
    the selected pairs beside the candidates."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import sparse_flash as sf

    jd = jnp.dtype(dtype)
    key = flash_key(rows, seq, head_dim, jd, True, v_dim)
    r = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(r[0], (1, num_heads, rows, head_dim), jd)
    k = jax.random.normal(r[1], (1, num_heads, seq, head_dim), jd)
    v = jax.random.normal(r[2], (1, num_heads, seq, v_dim), jd)

    @jax.jit
    def mask(key):
        t = seq - rows + jnp.arange(rows, dtype=jnp.int32)[:, None]
        seen = jnp.arange(seq, dtype=jnp.int32)[None, :] <= t
        scores = jnp.where(seen, jax.random.uniform(key, (rows, seq)), -1.0)
        edge = jax.lax.top_k(scores, min(top_k, seq))[0][:, -1:]
        return sf.pack_keep(seen & (scores >= edge))[None]

    words = mask(r[3])
    if kw.get("verbose"):
        t0 = seq - rows
        causal = rows * t0 + rows * (rows + 1) // 2
        chosen = sum(min(t + 1, top_k) for t in range(t0, seq))
        per_pair = 2 * num_heads * (head_dim + v_dim)
        print(f"  {sf.KERNEL} {rows} x {seq}: {causal * per_pair / 1e12:.3f} "
              f"TFLOP over the causal pairs, {chosen * per_pair / 1e12:.3f} "
              f"over the selected ({197e12 / 1e3:.0f} TFLOP/s a ms at peak)")
    cands = [{"block_q": bq, "block_k": bk}
             for bq in (128, 256, 512, 1024) for bk in (256, 512, 1024, 2048)
             if sf._valid(bq, bk, seq, head_dim, v_dim, jd)]

    def build(cfg):
        return jax.jit(lambda q, k, v, w: sf.sparse_flash_forward(
            q, k, v, w, scale=head_dim ** -0.5, **cfg))

    return tune_kernel(sf.KERNEL, key, build, cands, (q, k, v, words), **kw)


# ---------------------------------------------------------------------------
# CLI: bounded-time sweep over the standard shape set


# The benchmark's cells first (PERF.md section 4): a short on-chip budget
# tunes exactly the shapes they run before the generic ones — the train
# cell's three flash kernels at 1 x 4096 x 32 heads x 128, the latent
# model's prefill buckets (128 heads, q/k 192, v 128, forward only), the
# window / full model's (48 and 72 heads over 8, forward only).
_STANDARD_SHAPES = {
    "flash": [
        *(dict(seq=4096, head_dim=128, num_heads=32, kernel=k)
          for k in FLASH_KERNELS),
        *(dict(seq=s, head_dim=192, v_dim=128, num_heads=128)
          for s in (8192, 4096, 2048)),
        *(dict(seq=2048, head_dim=128, num_heads=32, kernel=k)
          for k in FLASH_KERNELS),
        # models/window_moe.py's prefill buckets (laguna-s-2.1): 48 query
        # heads on the full layers, 72 under a window of 512 on the sliding
        # ones, 8 K/V heads, forward only.  The full layers' 2,048 and
        # 4,096 share their table key with the 32-head shapes above (the
        # key has no head count: the grid is parallel over heads)
        *(dict(seq=s, head_dim=128, num_heads=48, num_kv_heads=8)
          for s in (8192, 4096, 2048)),
        *(dict(seq=s, head_dim=128, num_heads=72, num_kv_heads=8, window=512)
          for s in (8192, 4096, 2048)),
    ],
    "norm": [
        dict(rows=4096, hidden=2048), dict(rows=8192, hidden=2048),
        dict(rows=16384, hidden=2048), dict(rows=4096, hidden=4096),
        dict(rows=8192, hidden=4096),
    ],
    "swiglu": [
        dict(rows=4096, cols=5632), dict(rows=8192, cols=5632),
        dict(rows=16384, cols=5632), dict(rows=4096, cols=11008),
    ],
    "matmul": [
        dict(m=4096, k=2048, n=8192), dict(m=4096, k=4096, n=4096),
        dict(m=8192, k=2048, n=2048),
    ],
    # the serving cells that read pages (PERF.md section 4): laguna-s-2.1's
    # full layers, internlm2-1.8b, openpangu's latent pool (the shared-row
    # case: 128 heads on one 640-wide row, 512 of it the value) and
    # zaya1-8b's (8 query heads over 2 K/V heads: 64 KB pages)
    "paged": [
        dict(num_heads=48, num_kv_heads=8, block_size=128, table_width=67,
             lens=(2200, 8600)),
        dict(num_heads=16, num_kv_heads=8, block_size=16, table_width=96,
             lens=(130, 512)),
        dict(num_heads=128, num_kv_heads=1, head_dim=640, rank=512,
             block_size=128, table_width=67, lens=(2200, 8600)),
        dict(num_heads=8, num_kv_heads=2, head_dim=128, block_size=128,
             table_width=67, lens=(2200, 8600)),
    ],
    # dots3-note-prev's full layers' prefill past the first 2,048 queries
    # (models/dsa_moe.py): the 8,192 and 4,096 prompts' selected queries
    "sparse": [dict(rows=6144, seq=8192), dict(rows=2048, seq=4096)],
}


def main(argv=None):
    import argparse

    p = argparse.ArgumentParser(description="Pallas kernel tile autotuner")
    p.add_argument("--kernel", default="all",
                   choices=["all", "flash", "norm", "swiglu", "matmul",
                            "paged", "sparse"])
    p.add_argument("--budget-seconds", type=float, default=300.0,
                   help="total wall budget; stops between candidates")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--inner", type=int, default=None,
                   help="dispatches per timing sample (default: adaptive — "
                        "sized so one sample is ~300ms of device time; the "
                        "RTT-cancelling difference times inner and 2*inner)")
    args = p.parse_args(argv)

    t0 = time.perf_counter()
    slug = device_kind_slug()
    print(f"tuning for device kind: {slug}")
    runners = {"flash": tune_flash, "norm": tune_fused_norm,
               "swiglu": tune_swiglu, "matmul": tune_matmul_epilogue,
               "paged": tune_paged, "sparse": tune_sparse_flash}
    todo = [args.kernel] if args.kernel != "all" else list(runners)
    for name in todo:
        for shape in _STANDARD_SHAPES[name]:
            left = args.budget_seconds - (time.perf_counter() - t0)
            if left <= 0:
                print("budget exhausted")
                break
            cfg, ms = runners[name](dtype=args.dtype, budget_s=left, verbose=True,
                                    inner=args.inner, **shape)
            print(f"{name} {shape}: best {cfg} @ {ms:.3f} ms")
    path = cache(slug).save()
    print(f"cache written: {path}")


if __name__ == "__main__":
    main()
