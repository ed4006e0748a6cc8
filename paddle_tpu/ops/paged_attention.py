"""Paged-KV (block) attention for serving.

Reference: the block attention serving tier —
paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu and
python/paddle/incubate/nn/functional/block_multihead_attention.py: the KV
cache is a pool of fixed-size blocks; each sequence owns a block table
mapping its logical positions onto pool blocks, so cache memory is allocated
per-16-token page instead of per-max-seq-len (vLLM-style paging).

TPU-native design: the pool is ONE [num_blocks, Nkv, block_size, H] array per
K and V (a page with all its K/V heads is contiguous); block writes are
scatter-at-index updates, and decode attention (`paged_chunk_attention`, its
T = 1 face `paged_decode_attention`) has two forms with ONE arithmetic
(`gathered_attention`'s: K and V enter the products in the pool's type with
float32 sums; scores, mask, maximum, exponent and sum float32; PV with
float32 probabilities at the exact product; query heads contracted in their
K/V groups, never a repeated K/V):

- **the Pallas kernel** (`paged_decode`, `_paged_decode_kernel`): one token
  a row.  Block tables and lengths are scalar-prefetched, the pools stay in
  HBM, and a row reads ceil(len / block_size) pages, ITS OWN, each by one
  DMA of Nkv x block_size x H into one of two VMEM buffers (the next
  step's pages, or the next row's first, arrive under this step's
  products); a running maximum, sum and [Nkv, G, H] float32 accumulator
  fold the pages in.  Nothing beyond a row's length is read, no gathered
  copy and no score array reach HBM.  How many pages a step takes is a
  tile: `ops/tuned/<device>.json`, `python -m paddle_tpu.ops.autotune
  --kernel paged`; about a megabyte of K and V a step where it has no
  entry (`_pages_per_step`).
- **the XLA form** (`_paged_chunk_xla`): ONE clipped jnp.take of the first
  `w` pages of EVERY row, contracted as gathered, [B, w, Nkv, bs, H]; `w` is
  chosen ON THE DEVICE from max(seq_lens) among a short ladder of static
  widths (`page_ladder`: powers of two pages from 16 up, capped at the
  table's width) by a lax.switch whose branches differ only in
  `block_tables[:, :w]`; masked positions contribute exactly 0.  The take
  writes the gathered pages to HBM and the contractions read them back, so
  it moves about three times the table width it takes.

`reads_own_pages(pool, T)` chooses, by what can be seen: `ops.use_pallas()`
(a TPU, no mesh, FLAGS_use_pallas), a plain bfloat16 / float32 pool, T = 1,
heads of whole 128-lane rows, pages of whole sublane tiles -> the kernel;
everything else (T > 1: speculative verify and chunked decode; an int8
pool; a TP mesh; the CPU) -> the XLA form.  Measured on one v5e at both
serving cells' geometries (the tuned table's `meta`): 0.96 against 5.32 ms
a call at 128-token pages (32 rows of 2.2k-8.6k), 0.085 against 0.253 ms at
16-token pages (32 rows of 130-512).  The same predicate chooses the ORDER
OF THE SLOT WRITES (`paged_write_chunk`): a Mosaic call takes its operands
in the default order, and XLA's scatter over (block, slot) insists on a
slot-major pool, so in front of the kernel it copied both whole pools per
layer and token step; a pool the kernel reads is written as single rows of
the pool seen as [blocks x heads x slots, H] (`_write_rows`), which keeps
the default order through the step's loop and at its edge.
`attn_positions` asks the same predicate and says what a step read and what
was live.  Everything is shape-static, so the step jits once.

A pool whose ONE row a token is key and value at once (latent attention,
models/mla_moe.py) goes through the same kernel in its SHARED-ROW case
(`paged_shared_row_attention`, a static `rank`): one pool, one buffer, ONE
copy a page; the page's first `rank` lanes are the value, and the
probabilities meet the rows in the rows' type, as that model states its
arithmetic.  Its XLA form (`_shared_row_xla`) gathers the table's whole
width, with no ladder.  The predicate is the same and was not loosened for
it: the model allocates its 576-wide row 640 wide (whole lane tiles are
what Mosaic takes a page of by DMA).

A WINDOW class of a model's cache (models/contract.py: only the last W
positions of a row are kept) lives in a per-slot RING of pool blocks and is
read by `paged_window_attention`: the ring's pages through the slot's ring
table, masked by ABSOLUTE position (`len - W <= j < len`), no ladder (the
ring's width is fixed); `ring_write_chunk` writes position t into ring block
`(t // block_size) % ring_blocks`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from paddle_tpu.ops import _pl_utils

__all__ = [
    "QuantPool",
    "alloc_paged_cache",
    "alloc_paged_pool",
    "paged_write",
    "paged_write_chunk",
    "paged_pour_blocks",
    "paged_pour_block",
    "paged_gather",
    "page_ladder",
    "attn_positions",
    "reads_own_pages",
    "gathered_attention",
    "paged_decode_attention",
    "paged_chunk_attention",
    "paged_shared_row_attention",
    "paged_window_attention",
    "ring_write_chunk",
    "window_positions",
    "pool_num_kv_heads",
    "pool_block_size",
    "pool_nbytes",
    "pool_device_nbytes",
    "pool_parts",
    "pool_state_dict",
    "pool_from_state",
    "pool_get_blocks",
    "pool_set_blocks",
    "pool_stack",
    "pool_index",
]

_QMAX = 127.0  # symmetric int8 range; -128 is never produced
_GROUP_TILE = 8  # query heads of a K/V head are padded to whole sublane tiles
_EPS = 1e-12


@jax.tree_util.register_pytree_node_class
class QuantPool:
    """Int8-quantized paged pool: `data` int8 [num_blocks, Nkv, bs, H] plus
    per-block-per-head `scale` float32 [num_blocks, Nkv].

    A stored element decodes as ``data * scale`` (symmetric, zero-point
    free).  Scales are running maxima per (block, head): a decode write
    whose amax exceeds the block's current scale grows the scale and
    RESCALES the block's existing payload against it (one small gather +
    scatter over just the touched blocks, inside the jitted step), so every
    resident token stays decodable with the single per-block scale.  A
    deliberate pytree (NOT a tuple subclass): per-layer pool LISTS keep
    meaning "unstacked" in _decode_layers_paged, and jit / donate_argnums /
    lax.scan thread the (data, scale) pair as ordinary leaves.
    """

    __slots__ = ("data", "scale")

    def __init__(self, data, scale):
        self.data = data
        self.scale = scale

    def tree_flatten(self):
        return (self.data, self.scale), None

    @classmethod
    def tree_unflatten(cls, _aux, children):
        return cls(*children)

    @property
    def nbytes(self):
        return self.data.nbytes + self.scale.nbytes


def pool_num_kv_heads(cache):
    """Nkv of a paged pool, quantized or plain."""
    return (cache.data if isinstance(cache, QuantPool) else cache).shape[1]


def pool_block_size(cache):
    """Positions a page of a paged pool holds (per-layer or stacked)."""
    return (cache.data if isinstance(cache, QuantPool) else cache).shape[-2]


def pool_nbytes(cache):
    """Resident bytes of a paged pool (payload + scales for QuantPool)."""
    return cache.nbytes


def pool_device_nbytes(cache):
    """PER-DEVICE resident bytes of a paged pool: each leaf's committed
    sharding divides its global bytes (``shard_shape``); uncommitted or
    single-device leaves count whole.  The serving telemetry's
    ``pool_bytes_per_device`` (and the mesh lint's per-device HBM
    estimate) see the TP-sharded engine's true per-chip footprint through
    this — a KV-head-sharded pool on an mp=4 mesh reports a quarter of
    ``pool_nbytes`` here."""
    total = 0
    for _name, arr in pool_parts(cache):
        shape = arr.shape
        sharding = getattr(arr, "sharding", None)
        if sharding is not None:
            try:
                shape = sharding.shard_shape(arr.shape)
            except (TypeError, ValueError):
                pass  # abstract/placeholder leaf: count it whole
        total += math.prod(shape) * arr.dtype.itemsize
    return total


def pool_parts(cache):
    """[(part_name, array)] leaves of a paged pool — ('payload', data) for
    a plain pool, plus ('scale', scales) for a QuantPool.  The ONE place
    that knows QuantPool's structure for per-leaf consumers (the mesh
    lint's placement/byte accounting walks pools through this, so an
    added QuantPool field is automatically covered there)."""
    if isinstance(cache, QuantPool):
        return [("payload", cache.data), ("scale", cache.scale)]
    return [("payload", cache)]


def pool_state_dict(prefix, cache):
    """Flat ``{f"{prefix}.{part}": array}`` view of a paged pool's leaves —
    the serialization face of `pool_parts` (engine snapshots feed these
    names to the sharded checkpoint store; serving/snapshot.py).  A
    QuantPool contributes its payload AND scales, so a serialized int8
    pool round-trips bit-exactly."""
    return {f"{prefix}.{name}": arr for name, arr in pool_parts(cache)}


def pool_from_state(template, fetch, prefix=""):
    """Rebuild a pool shaped like `template` by calling
    ``fetch(f"{prefix}.{part}", template_leaf)`` per leaf — the inverse of
    `pool_state_dict`.  `fetch` returns the restored array for that leaf
    (the caller owns assembly/resharding/placement); the ONE other place
    that knows QuantPool's structure, so an added field breaks both
    directions loudly together."""
    if isinstance(template, QuantPool):
        return QuantPool(fetch(f"{prefix}.payload", template.data),
                         fetch(f"{prefix}.scale", template.scale))
    return fetch(f"{prefix}.payload", template)


def pool_get_blocks(cache, block_ids):
    """Native-format page extraction — the wire face of `pool_parts` for
    cross-process KV shipping (serving/cluster.py): the pool's OWN leaves
    at `block_ids`, as ``{"payload": [n, Nkv, bs, H]}`` plus
    ``{"scale": [n, Nkv]}`` for a QuantPool.  An int8 pool ships its int8
    payload and f32 scales VERBATIM (about half the wire bytes of a bf16
    pool), and `pool_set_blocks` on the receiving side places the same
    bytes — ship-then-place is bit-exact by construction, never a
    re-quantization."""
    idx = jnp.asarray(block_ids, jnp.int32)
    return {name: jnp.take(arr, idx, axis=0)
            for name, arr in pool_parts(cache)}


def pool_set_blocks(cache, block_ids, blocks):
    """Place native-format pages (a `pool_get_blocks` dict) into the pool
    at `block_ids`.  The inverse wire face: leaves land verbatim (cast
    only to the pool leaf dtype, an identity for a matched pool kind) —
    quantization happened on the sending side or not at all."""
    idx = jnp.asarray(block_ids, jnp.int32)
    if isinstance(cache, QuantPool):
        return QuantPool(
            cache.data.at[idx].set(
                jnp.asarray(blocks["payload"], cache.data.dtype)),
            cache.scale.at[idx].set(
                jnp.asarray(blocks["scale"], cache.scale.dtype)))
    return cache.at[idx].set(jnp.asarray(blocks["payload"], cache.dtype))


def pool_stack(pools):
    """Per-layer pool list -> ONE stacked [N, ...] pool (leaf-wise, so a
    list of QuantPools stacks into a QuantPool of stacked leaves)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *pools)


def pool_index(pool, i):
    """Layer i's pool out of a stacked [N, ...] pool (leaf-wise)."""
    return jax.tree_util.tree_map(lambda x: x[i], pool)


def rope_rotate_by_position(t, cos, sin, positions):
    """Interleaved-pair rotation of per-token heads by gathered positions.

    t: [B, N, H]; cos/sin: [max_len, H/2] tables; positions: [B] int32.
    The SINGLE rope implementation for decode paths (model prefill uses the
    same pair convention in models/llama.py apply_rotary_pos_emb) — change
    rope semantics here and there together.
    """
    # the T=1 case of rope_rotate_chunk — ONE implementation of the pair
    # convention (change rope semantics there, not here)
    return rope_rotate_chunk(t[:, None], cos, sin, positions[:, None])[:, 0]


def alloc_paged_pool(num_blocks, heads, block_size, width, dtype=jnp.bfloat16):
    """One pool [num_blocks, heads, block_size, width] of zeros: a token
    occupies `heads` rows of `width` values (a K or a V pool: Nkv rows of
    H; a latent-attention pool: one row of the latent plus the rope key).

    dtype 'int8' (or jnp.int8) allocates a QuantPool instead — int8
    payload plus per-block-per-head float32 scales (FLAGS_kv_cache_dtype).
    """
    shape = (num_blocks, heads, block_size, width)
    if jnp.dtype(dtype) == jnp.int8:
        return QuantPool(jnp.zeros(shape, jnp.int8),
                         jnp.zeros((num_blocks, heads), jnp.float32))
    return jnp.zeros(shape, dtype)


def alloc_paged_cache(num_blocks, num_kv_heads, block_size, head_dim, dtype=jnp.bfloat16):
    """One K and one V pool (`alloc_paged_pool` twice)."""
    return tuple(alloc_paged_pool(num_blocks, num_kv_heads, block_size,
                                  head_dim, dtype) for _ in range(2))


def paged_write(cache, new, block_tables, positions):
    """Write one token per sequence into its page.

    cache: [num_blocks, Nkv, bs, H]; new: [B, Nkv, H];
    block_tables: [B, max_blocks] int32; positions: [B] int32 (token index
    within the sequence).  Returns the updated cache.
    """
    # the T=1 case of paged_write_chunk — one scatter implementation
    return paged_write_chunk(cache, new[:, None], block_tables,
                             positions[:, None])


def paged_gather(cache, block_tables):
    """Materialize each sequence's logical cache view.

    cache: [num_blocks, Nkv, bs, H] (or QuantPool); block_tables:
    [B, max_blocks] -> [B, Nkv, max_blocks*bs, H].  Quantized pools
    DEQUANTIZE on gather (float32 out): the decode step reads int8 pages +
    scales from HBM and rescales in registers — the capacity win is in the
    resident bytes, not the gathered view.
    """
    if isinstance(cache, QuantPool):
        pages = jnp.take(cache.data, block_tables, axis=0)  # [B,mb,Nkv,bs,H]
        scales = jnp.take(cache.scale, block_tables, axis=0)  # [B,mb,Nkv]
        pages = pages.astype(jnp.float32) * scales[..., None, None]
    elif cache.shape[1] == 1:
        # one row a token (a latent pool, or one K/V head): whole pages by a
        # clipped take and a reshape that moves nothing, ONE pass over the
        # pages.  The general form below also moves the heads axis and
        # fills out-of-range pages: three more passes over the table width,
        # half of a latent-attention token step (PERF.md section 6, PR 27).
        # Block tables hold valid pages only, so clipping changes nothing.
        pages = jnp.take(cache[:, 0], block_tables, axis=0, mode="clip")
        b, mb, bs, h = pages.shape
        return pages.reshape(b, 1, mb * bs, h)
    else:
        pages = jnp.take(cache, block_tables, axis=0)  # [B, mb, Nkv, bs, H]
    b, mb, nkv, bs, h = pages.shape
    return jnp.moveaxis(pages, 2, 1).reshape(b, nkv, mb * bs, h)


def paged_decode_attention(q, key_cache, value_cache, block_tables, seq_lens, *, scale=None):
    """Single-token decode attention over the paged cache.

    q: [B, N, H] (the new token's queries, rope already applied);
    key_cache/value_cache: [num_blocks, Nkv, bs, H]; block_tables:
    [B, max_blocks]; seq_lens: [B] VALID length (including the new token).
    GQA: N may be a multiple of Nkv.  Returns [B, N, H].
    """
    # the T=1 case of paged_chunk_attention — one masked-softmax
    # implementation for the decode tier
    return paged_chunk_attention(q[:, None], key_cache, value_cache,
                                 block_tables, seq_lens, scale=scale)[:, 0]


def rope_rotate_chunk(t, cos, sin, positions):
    """Chunk variant of rope_rotate_by_position: t [B, T, N, H],
    positions [B, T] int32."""
    b, tt, n, h = t.shape
    c = jnp.take(jnp.asarray(cos), positions, axis=0)[:, :, None, :]  # [B,T,1,H/2]
    s = jnp.take(jnp.asarray(sin), positions, axis=0)[:, :, None, :]
    t2 = t.astype(jnp.float32).reshape(b, tt, n, h // 2, 2)
    r1 = t2[..., 0] * c - t2[..., 1] * s
    r2 = t2[..., 1] * c + t2[..., 0] * s
    return jnp.stack([r1, r2], -1).reshape(b, tt, n, h).astype(t.dtype)


def paged_write_chunk(cache, new, block_tables, positions):
    """Write T tokens per sequence into their pages.

    cache: [num_blocks, Nkv, bs, H] (or QuantPool); new: [B, T, Nkv, H];
    positions: [B, T] int32 (token index within each sequence).  The [B, T]
    scatter is one advanced-indexing update — speculative verify writes its
    whole chunk in one shot."""
    if isinstance(cache, QuantPool):
        return _quant_write_chunk(cache, new, block_tables, positions)
    if reads_own_pages(cache, new.shape[1]):
        return _write_rows(cache, new, block_tables, positions)
    return _write_slots(cache, new, block_tables, positions)


def _write_slots(cache, new, block_tables, positions):
    """The slot writes as ONE scatter over block and slot.  On a TPU that
    scatter keeps the pool slot-major within a page through a step's loop
    (`_as_written`): the order of the XLA form's reads."""
    bs = cache.shape[2]
    block_idx = jnp.take_along_axis(block_tables, positions // bs, axis=1)  # [B,T]
    slot = positions % bs
    # advanced indexing on dims 0 and 2 with [B, T] index arrays puts the
    # broadcast [B, T] in front: value shape [B, T, Nkv, H] == new
    return cache.at[block_idx, :, slot, :].set(new)


def _write_rows(cache, new, block_tables, positions):
    """The same writes as a scatter of single rows of H into the pool seen
    as [num_blocks * Nkv * bs, H] (a view, nothing moves): one scattered
    axis, the major one, so the pool keeps the DEFAULT order through a
    step's loop, head-major within a page, which is the order a Mosaic
    kernel takes its operands in.  With `_write_slots` in front of the
    kernel XLA copies both whole pools per layer and token step (the
    write -> attend scan compiled for a described v5e; tests/
    test_tpu_compile.py holds that no such copy is left)."""
    nb, nkv, bs, h = cache.shape
    block_idx = jnp.take_along_axis(block_tables, positions // bs, axis=1)  # [B,T]
    rows = ((block_idx[..., None] * nkv
             + jnp.arange(nkv, dtype=jnp.int32)) * bs
            + (positions % bs)[..., None])                       # [B,T,Nkv]
    flat = cache.reshape(nb * nkv * bs, h).at[rows.reshape(-1)].set(
        new.reshape(-1, h).astype(cache.dtype))
    return flat.reshape(cache.shape)


def _quant_write_chunk(pool, new, block_tables, positions):
    """Quantized paged_write_chunk: per-block-per-head running-max scales.

    The incoming tokens' per-head amax grows each touched block's scale
    via scatter-max; blocks whose scale grew get their EXISTING int8
    payload rescaled against the new scale (gather + scatter over just the
    touched blocks — every gather below predates the scatters, so chunk
    rows landing in the same block compute identical rescale values and
    duplicate-index writes stay deterministic); the new tokens then
    quantize against the final scales and scatter into their slots."""
    bs = pool.data.shape[2]
    block_idx = jnp.take_along_axis(block_tables, positions // bs, axis=1)  # [B,T]
    slot = positions % bs
    af = new.astype(jnp.float32)                                 # [B,T,Nkv,H]
    tok_scale = jnp.max(jnp.abs(af), axis=-1) / _QMAX            # [B,T,Nkv]
    old_scale = pool.scale[block_idx]                            # [B,T,Nkv]
    scale = pool.scale.at[block_idx].max(tok_scale)
    new_scale = scale[block_idx]                                 # final per block
    safe = jnp.maximum(new_scale, _EPS)
    old_blocks = pool.data[block_idx].astype(jnp.float32)        # [B,T,Nkv,bs,H]
    ratio = jnp.where(new_scale > old_scale, old_scale / safe, 1.0)
    resc = jnp.clip(jnp.round(old_blocks * ratio[..., None, None]),
                    -_QMAX, _QMAX).astype(jnp.int8)
    data = pool.data.at[block_idx].set(resc)
    q = jnp.clip(jnp.round(af / safe[..., None]), -_QMAX, _QMAX).astype(jnp.int8)
    data = data.at[block_idx, :, slot, :].set(q)
    return QuantPool(data, scale)


def paged_pour_blocks(cache, kv, block_ids):
    """Pour whole blocks (prefill) into the pool at `block_ids`.

    kv: [n_blocks, Nkv, bs, H] float values.  Quantized pools compute
    fresh per-block-per-head scales over the poured content (SET, not
    running-max — a recycled block's stale scale dies here)."""
    idx = jnp.asarray(block_ids, jnp.int32)
    if isinstance(cache, QuantPool):
        af = kv.astype(jnp.float32)
        s = jnp.max(jnp.abs(af), axis=(2, 3)) / _QMAX            # [n, Nkv]
        safe = jnp.maximum(s, _EPS)
        q = jnp.clip(jnp.round(af / safe[:, :, None, None]),
                     -_QMAX, _QMAX).astype(jnp.int8)
        return QuantPool(cache.data.at[idx].set(q),
                         cache.scale.at[idx].set(s))
    return cache.at[idx].set(kv.astype(cache.dtype))


def paged_pour_block(cache, kv, block_id):
    """Pour ONE block — the chunked-prefill entry (interleaved prefill
    pours each prompt block as its chunk completes; serving docs/DECODE.md
    admission scheduler).

    kv: [Nkv, bs, H] float values.  Delegates to `paged_pour_blocks` with
    n=1, so a quantized pool's per-block-per-head scale is the amax of
    exactly this block's content — the SAME scale (and therefore the same
    int8 bytes) the batched atomic pour computes for the block, which is
    what makes the chunk boundary pure data movement."""
    return paged_pour_blocks(cache, kv[None], [int(block_id)])


def gathered_attention(q, keys, vals, seq_lens, *, scale=None, window=None):
    """The sdpa core of the decode tier over ALREADY-GATHERED K/V:
    q [B, T, N, H]; keys/vals the pages as taken from the pool,
    [B, M, Nkv, bs, H] (position m * bs + s); seq_lens [B] INCLUDING all T
    chunk tokens.  The ONE masked-softmax definition:
    paged_chunk_attention feeds it the pages of the width it chose,
    paged_window_attention a slot's ring.

    `window` W: the M pages are a RING of C = M * bs slots, slot c holding
    the LATEST position <= len - 1 that is congruent to c (mod C), and a
    query at position i sees i - W < j <= i; softmax sums in slot order,
    which is no order of positions, and needs none.

    K and V are contracted in the type they arrive in, accumulated in
    float32 (for bfloat16 values, the products a float32 contraction of
    the same values forms); scores, mask, softmax and probabilities are
    float32, and PV multiplies float32 probabilities with V converted
    inside the reduce.  The N query heads are contracted in their Nkv
    groups of N // Nkv (MHA is a group of one): K/V are never repeated."""
    b, t, n, h = q.shape
    _b, m, nkv, bs, _h = keys.shape
    if scale is None:
        scale = 1.0 / math.sqrt(h)
    # both operands in the wider of the two types (the pool's, when q is
    # the model's own type), float32 accumulation
    dt = jnp.promote_types(q.dtype, keys.dtype)
    qg = q.astype(dt).reshape(b, t, nkv, n // nkv, h)
    # highest precision: a float32 operand (the probabilities; a float32 or
    # dequantised pool) is never cut to bfloat16 passes of the matrix unit;
    # bfloat16 operands are exact in one pass whatever it says
    exact = jax.lax.Precision.HIGHEST
    logits = jnp.einsum("btkgh,bmksh->bkgtms", qg, keys.astype(dt),
                        precision=exact, preferred_element_type=jnp.float32
                        ) * jnp.float32(scale)
    kpos = (jnp.arange(m, dtype=jnp.int32)[:, None] * bs
            + jnp.arange(bs, dtype=jnp.int32)[None, :])[None]   # [1, M, bs]
    qpos = (seq_lens[:, None] - t + jnp.arange(t, dtype=jnp.int32)[None, :])
    if window is not None:
        # ring slot -> the absolute position it holds (negative: never
        # written for this row)
        last = (seq_lens - 1)[:, None, None]
        kpos = last - jnp.mod(last - kpos, m * bs)              # [B, M, bs]
    allowed = kpos[:, None] <= qpos[:, :, None, None]          # [B, T, M, bs]
    if window is not None:
        allowed = (allowed & (kpos[:, None] >= 0)
                   & (kpos[:, None] > qpos[:, :, None, None] - window))
    logits = jnp.where(allowed[:, None, None], logits, jnp.float32(-1e30))
    # one flat axis of positions: pages of any width then reduce alike
    flat = logits.reshape(logits.shape[:4] + (m * bs,))
    probs = jax.nn.softmax(flat, axis=-1).reshape(logits.shape)
    out = jnp.einsum("bkgtms,bmksh->btkgh", probs, vals,
                     precision=exact, preferred_element_type=jnp.float32)
    return out.reshape(b, t, n, h).astype(q.dtype)


def page_ladder(table_width):
    """The static widths, in pages, among which decode attention chooses
    how much of a [B, table_width] block table to read: powers of two from
    16 up, capped at the table's own width (96 -> 16, 32, 64, 96; a table
    of 16 pages or fewer has the one width)."""
    ladder, w = [], 16
    while w < table_width:
        ladder.append(w)
        w *= 2
    return (*ladder, table_width)


def _ladder_index(ladder, block_size, seq_lens):
    """Index of the narrowest width of `ladder` that covers the longest
    row of `seq_lens` (traced)."""
    longest = jnp.max(seq_lens)
    reach = jnp.asarray([w * block_size for w in ladder[:-1]], jnp.int32)
    return jnp.sum(longest > reach).astype(jnp.int32)


def attn_positions(block_tables, block_size, seq_lens, active=None, *,
                   pool=None, ladder=True):
    """What one T = 1 decode attention call over these rows reads and what
    of it is live, as two int32 scalars: (positions read, sum of the
    `active` rows' lengths).  Read is what the path selected for `pool`
    reads (`reads_own_pages`, the predicate the attention itself asks):
    through the kernel each active row's own pages, ceil(len / block_size)
    of them; in the XLA form (and with no pool given) active rows x the
    ladder width over the longest row (`paged_chunk_attention`), or x the
    table's whole width where the caller's XLA form has no ladder
    (`ladder=False`: models/mla_moe.absorbed_attention).  Their quotient is
    the step's read amplification (1 but for the last page's rounding
    through the kernel)."""
    if active is None:
        active = jnp.ones(seq_lens.shape, bool)
    if pool is not None and reads_own_pages(pool):
        own = jnp.clip(-(-seq_lens // block_size), 1, block_tables.shape[1])
        read = jnp.sum(jnp.where(active, own, 0)) * block_size
    elif not ladder:
        read = jnp.sum(active) * (block_tables.shape[1] * block_size)
    else:
        ladder = page_ladder(block_tables.shape[1])
        pages = jnp.asarray(ladder, jnp.int32)[
            _ladder_index(ladder, block_size, seq_lens)]
        read = jnp.sum(active) * pages * block_size
    live = jnp.sum(jnp.where(active, seq_lens, 0))
    return read.astype(jnp.int32), live.astype(jnp.int32)


def window_positions(ring_tables, block_size, seq_lens, window, active=None):
    """`attn_positions` for one `paged_window_attention` call: (`active`
    rows x the ring's positions, which it always reads whole; the sum over
    the active rows of min(len, window), what is live in a window)."""
    if active is None:
        active = jnp.ones(seq_lens.shape, bool)
    read = jnp.sum(active) * (ring_tables.shape[1] * block_size)
    live = jnp.sum(jnp.where(active, jnp.minimum(seq_lens, window), 0))
    return read.astype(jnp.int32), live.astype(jnp.int32)


def ring_write_chunk(cache, new, ring_tables, positions):
    """`paged_write_chunk` into a window class's ring: cache [blocks, Nkv,
    bs, H]; new [B, T, Nkv, H]; ring_tables [B, R], each row's slot's ring;
    positions [B, T] ABSOLUTE.  Position t goes to ring block
    `(t // bs) % R`, slot `t % bs`, over whatever lived there (position
    t - R * bs)."""
    span = ring_tables.shape[1] * pool_block_size(cache)
    return paged_write_chunk(cache, new, ring_tables, positions % span)


def paged_window_attention(q, key_cache, value_cache, ring_tables, seq_lens,
                           window, *, scale=None):
    """Decode attention over a window class's ring: q [B, T, N, H];
    ring_tables [B, R]; seq_lens [B] INCLUDING the T chunk tokens (already
    written by `ring_write_chunk`; the ring holds the last R * bs
    positions, which must cover window + T - 1).  A query at position i
    attends i - window < j <= i.  Reads the R pages of every row whatever
    its length: the width is fixed, so there is no ladder and no
    conditional.  Returns [B, T, N, H]."""
    return gathered_attention(
        q, _take_pages(key_cache, ring_tables),
        _take_pages(value_cache, ring_tables), seq_lens, scale=scale,
        window=int(window))


def _as_written(cache):
    """A plain pool, told to lie inside a conditional's branch as it lies
    in the step around it.  On a TPU the slot writes (`paged_write_chunk`'s
    scatter over block and slot) keep a pool slot-major within a page,
    [block][slot][head][H]; a branch takes its operands in the default
    order unless told, and XLA then copies the WHOLE pool into every
    branch: per K, per V, per layer, per token step (the macro-step compiled
    for a described v5e: 48 copies of 102 MB a token step; none with this).
    A hint, not semantics: where the pool arrives in another order (a
    program with no slot write in it) XLA copies it once, as it did."""
    if isinstance(cache, QuantPool) or not _pl_utils.on_tpu():
        return cache
    from jax.experimental.layout import Layout, with_layout_constraint

    return with_layout_constraint(cache, Layout(major_to_minor=(0, 2, 1, 3)))


def _take_pages(cache, block_tables):
    """Each row's pages as they lie in the pool: [B, w, Nkv, bs, H], ONE
    pass (a clipped take: block tables hold valid pages only, so nothing
    is filled; no moved axis).  A QuantPool dequantises here (float32)."""
    if isinstance(cache, QuantPool):
        pages = jnp.take(cache.data, block_tables, axis=0, mode="clip")
        scales = jnp.take(cache.scale, block_tables, axis=0, mode="clip")
        return pages.astype(jnp.float32) * scales[..., None, None]
    return jnp.take(cache, block_tables, axis=0, mode="clip")


# ---------------------------------------------------------------------------
# The Pallas kernel: each row's OWN live pages, straight from the pool


# what one step of the kernel's loop should bring in, K and V together (the
# one pool of a shared row), where no measurement says how many pages
_STEP_BYTES = 1 << 20


def _pages_per_step(block_size, num_kv_heads, head_dim, dtype, rank=None,
                    table_width=math.inf):
    """How many pages one step of the kernel's loop fetches and contracts
    together, counted at trace time by the source that chose
    (`paged_tile_tuned_traces` / `paged_tile_rule_traces`): the measured
    winner for this page geometry on this device kind
    (`ops/tuned/<device>.json`, kernel `paged_decode`, written by `python -m
    paddle_tpu.ops.autotune --kernel paged`), else by BYTES: the power of
    two of pages whose K and V (one pool in the shared-row case) come
    closest to `_STEP_BYTES` a step, among `autotune.paged_candidates` (the
    double-buffered buffers within a quarter of VMEM, no more pages than
    the table is wide).  A page is Nkv x block_size x H, so the rule gives
    16 pages at decode-sat's (8 heads, blocks of 16: 64 KB of K and V), 2
    at laguna's (8 heads, blocks of 128: 512 KB), 8 at zaya's (2 heads: 128
    KB) and at the latent row (one 640-wide pool: 160 KB): the measured
    winner at three of them, 1.012 times it at laguna's (0.975 against
    0.963 ms).  Steps of a few hundred KB leave HBM idle between the DMAs:
    zaya's 2 heads at 256 positions a step, 256 KB, read 52% of HBM speed
    (0.417 ms a call against 0.261 at 8 pages; the table's `meta`)."""
    from paddle_tpu._core import compile_cache
    from paddle_tpu.ops import autotune

    cfg = autotune.lookup("paged_decode", paged_key(
        block_size, num_kv_heads, head_dim, dtype, rank))
    tuned = bool(cfg) and int(cfg.get("pages_per_step", 0)) >= 1
    compile_cache.count("paged_tile_tuned_traces" if tuned
                        else "paged_tile_rule_traces")
    if tuned:
        return int(cfg["pages_per_step"])
    itemsize, pools = jnp.dtype(dtype).itemsize, 1 if rank is not None else 2
    step = pools * num_kv_heads * block_size * autotune.lane_padded(
        head_dim) * itemsize
    offered = [c["pages_per_step"] for c in autotune.paged_candidates(
        block_size, num_kv_heads, head_dim, table_width, itemsize, pools)]
    return min(offered or [1],
               key=lambda p: abs(math.log2(p * step / _STEP_BYTES)))


def paged_key(block_size, num_kv_heads, head_dim, dtype, rank=None):
    """The tuned table's key of a page geometry (`rank`: the shared-row
    case, whose value is the row's first `rank` lanes)."""
    key = {"block_size": int(block_size), "num_kv_heads": int(num_kv_heads),
           "head_dim": int(head_dim), "dtype": jnp.dtype(dtype).name}
    if rank is not None:
        key["rank"] = int(rank)
    return key


def _paged_decode_kernel(lens_ref, tables_ref, q_ref, *refs, scale, pages,
                         exact, rank=None):
    """One grid step = one row.  A step of the inner loop = `pages` pages
    of the row, fetched page by page from the pools in HBM into one of two
    VMEM buffers [Nkv, pages * bs, H] (the DMA of the next step, which may
    be the next ROW's first, runs under this step's products), contracted
    for all K/V heads at once, folded into a running softmax.

    `rank` (static) is the SHARED-ROW case, a latent pool: there is one
    pool and no V; a page is copied once and is key (all H lanes) and value
    (its first `rank` lanes) at once, and the probabilities meet the rows
    in the rows' type, as the model that owns the pool states it
    (models/mla_moe.absorbed_attention)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if rank is None:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, slot_ref = refs
    else:
        k_hbm, o_ref, k_buf, sems, slot_ref = refs
        v_hbm = v_buf = None
    b, rows = pl.program_id(0), pl.num_programs(0)
    nb, nkv, bs, h = k_hbm.shape
    width = tables_ref.shape[0] // lens_ref.shape[0]
    span = pages * bs
    mask_value = jnp.float32(-1e30)

    def pages_of(row):
        # a row reads its own pages, at least one, never past its table
        return jnp.clip((lens_ref[row] + bs - 1) // bs, 1, width)

    def copies(row, step, slot, do):
        """`do` (start or wait) the copies (K and V; one where the row is
        both) of each live page of step `step` of row `row` into buffer
        `slot`: a loop of the row's own count, so nothing beyond it is
        fetched."""
        first = step * pages

        def one(i, _):
            idx = jnp.clip(tables_ref[row * width + first + i], 0, nb - 1)
            at = pl.ds(pl.multiple_of(i * bs, bs), bs)
            do(pltpu.make_async_copy(
                k_hbm.at[idx], k_buf.at[slot, :, at, :], sems.at[0, slot]))
            if v_hbm is not None:
                do(pltpu.make_async_copy(
                    v_hbm.at[idx], v_buf.at[slot, :, at, :],
                    sems.at[1, slot]))
            return 0

        jax.lax.fori_loop(0, jnp.minimum(pages_of(row) - first, pages), one,
                          0)

    @pl.when(b == 0)
    def _():
        if pages > 1:
            # a step's last pages may lie past the row: what the buffer
            # holds there is masked, and must be finite
            k_buf[...] = jnp.zeros_like(k_buf)
            if v_buf is not None:
                v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        copies(b, 0, 0, lambda c: c.start())

    length = lens_ref[b]
    steps = (pages_of(b) + pages - 1) // pages
    slot0 = slot_ref[0]
    q = q_ref[...]                                   # [Nkv, G, H]
    g = q.shape[1]
    precision = jax.lax.Precision.HIGHEST if exact else None

    def step(c, carry):
        m_prev, l_prev, acc = carry
        slot = (slot0 + c) % 2
        last = c + 1 == steps

        @pl.when(jnp.logical_not(last) | (b + 1 < rows))
        def _():
            copies(jnp.where(last, b + 1, b), jnp.where(last, 0, c + 1),
                   1 - slot, lambda cp: cp.start())

        copies(b, c, slot, lambda cp: cp.wait())
        k = k_buf[slot].astype(q.dtype)              # [Nkv, span, H]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))), precision=precision,
            preferred_element_type=jnp.float32) * jnp.float32(scale)
        kpos = c * span + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(kpos < length, s, mask_value)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        if rank is None:
            # float32 probabilities, never cut to bfloat16: the exact product
            pv = jax.lax.dot_general(
                p, v_buf[slot].astype(jnp.float32),
                (((2,), (1,)), ((0,), (0,))),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        else:
            # the shared row: its first `rank` lanes are the value, and the
            # probabilities meet it in ITS type (bfloat16 passes with
            # float32 sums; the exact product for a float32 pool)
            v = k_buf[slot][:, :, :rank]
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                precision=(jax.lax.Precision.HIGHEST
                           if v.dtype == jnp.float32 else None),
                preferred_element_type=jnp.float32)
        return m_new, l_new, alpha * acc + pv

    init = (jnp.full((nkv, g, 1), -jnp.inf, jnp.float32),
            jnp.zeros((nkv, g, 1), jnp.float32),
            jnp.zeros((nkv, g, h if rank is None else rank), jnp.float32))
    _m, l, acc = jax.lax.fori_loop(0, steps, step, init)
    slot_ref[0] = (slot0 + steps) % 2
    o_ref[...] = (acc / l).astype(o_ref.dtype)


def _paged_decode_pallas(q, key_cache, value_cache, block_tables, seq_lens,
                         scale, pages=None, rank=None):
    """T = 1 decode attention through the kernel: q [B, N, H]; plain pools
    [num_blocks, Nkv, bs, H]; returns [B, N, H] in q's type.  `pages` a
    step from the tuned table, else by bytes (`_pages_per_step`), unless
    given.  With `rank` the shared-row case (`paged_shared_row_attention`):
    `value_cache` is None and the result is [B, N, rank] float32."""
    _nb, nkv, bs, h = key_cache.shape
    if pages is None:
        pages = _pages_per_step(bs, nkv, h, key_cache.dtype, rank,
                                block_tables.shape[1])
    pages = max(1, min(int(pages), block_tables.shape[1]))
    return _paged_decode_call(q, key_cache, value_cache, block_tables,
                              seq_lens, scale=float(scale), pages=pages,
                              interpret=_pl_utils.interpret(), rank=rank)


@functools.partial(jax.jit,
                   static_argnames=("scale", "pages", "interpret", "rank"))
def _paged_decode_call(q, key_cache, value_cache, block_tables, seq_lens, *,
                       scale, pages, interpret, rank=None):
    """The kernel's call, a jitted function of its own: the layers of a
    model that call it with the same shapes share ONE trace and ONE
    lowering of the kernel (unrolled, 24 layers of a dense model spent 12 s
    of every process's set-up lowering 24 copies: no cache holds a
    lowering).  What is looked up (the tile, the interpreter) is looked up
    by the caller and is part of this function's key."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n, h = q.shape
    nb, nkv, bs, _h = key_cache.shape
    g = n // nkv
    # both operands of QK^T in the wider of the two types, as
    # `gathered_attention`; the group padded to whole sublane tiles
    dt = jnp.promote_types(q.dtype, key_cache.dtype)
    gp = -(-g // _GROUP_TILE) * _GROUP_TILE
    qg = q.astype(dt).reshape(b, nkv, g, h)
    if gp != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    buf = (2, nkv, pages * bs, h)
    # a K/V pair: two pools, two buffers, the result in q's type; a shared
    # row (`rank`): one of each, [.., rank] float32
    pools = (key_cache,) if rank is not None else (key_cache, value_cache)
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale, pages=pages,
                          exact=dt != jnp.bfloat16, rank=rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((None, nkv, gp, h),
                             lambda i, *_: (i, 0, 0, 0)),
                *(pl.BlockSpec(memory_space=pl.ANY) for _ in pools),
            ],
            out_specs=pl.BlockSpec((None, nkv, gp, h if rank is None else rank),
                                   lambda i, *_: (i, 0, 0, 0)),
            scratch_shapes=[
                *(pltpu.VMEM(buf, pool.dtype) for pool in pools),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        out_shape=(jax.ShapeDtypeStruct((b, nkv, gp, h), q.dtype)
                   if rank is None else
                   jax.ShapeDtypeStruct((b, nkv, gp, rank), jnp.float32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode",
    )(seq_lens.astype(jnp.int32), block_tables.reshape(-1).astype(jnp.int32),
      qg, *pools)
    return out[:, :, :g].reshape(b, n, -1)


def paged_shared_row_attention(q, pool, block_tables, seq_lens, *, rank,
                               scale):
    """T = 1 decode attention over a pool whose ONE row a token is key and
    value at once (latent attention's absorbed form, models/mla_moe.py):
    q [B, N, H]; pool [num_blocks, 1, bs, H]; a row's first `rank` lanes
    are the value; seq_lens [B] INCLUDING the new token.  Returns [B, N,
    rank] float32.  Scores, maximum, exponent and sum float32; the
    probabilities meet the rows in the rows' type (bfloat16 passes with
    float32 sums for a bfloat16 pool).

    Two forms, one arithmetic but for the order of the float32 sums, chosen
    by `reads_own_pages(pool)` and counted by the form taken
    (`paged_kernel_traces` / `paged_xla_traces`): the Pallas kernel
    `paged_decode` in its shared-row case (each row's own live pages by DMA
    from the pool, ONE copy a page, an online softmax: no gathered copy and
    no score array in HBM), or XLA's (`_shared_row_xla`: the table's whole
    width of every row gathered, two einsums)."""
    from paddle_tpu._core import compile_cache

    kernel = reads_own_pages(pool)
    compile_cache.count("paged_kernel_traces" if kernel
                        else "paged_xla_traces")
    if kernel:
        return _paged_decode_pallas(q, pool, None, block_tables, seq_lens,
                                    scale, rank=int(rank))
    return _shared_row_xla(q, pool, block_tables, seq_lens, rank, scale)


def _shared_row_xla(q, pool, block_tables, seq_lens, rank, scale):
    """The XLA form of `paged_shared_row_attention`: every row's whole
    table width gathered (`paged_gather`: no ladder), scores [B, N, S]
    float32 through HBM, probabilities x rows in the rows' type."""
    keys = paged_gather(pool, block_tables)[:, 0]               # [B, S, H]
    score = jnp.einsum("bnr,bsr->bns", q, keys,
                       preferred_element_type=jnp.float32) * scale
    seen = (jnp.arange(keys.shape[1], dtype=jnp.int32)[None, :]
            < seq_lens[:, None])
    p = jax.nn.softmax(jnp.where(seen[:, None, :], score, -1e30), axis=-1)
    return jnp.einsum("bns,bsr->bnr", p.astype(keys.dtype), keys[..., :rank],
                      preferred_element_type=jnp.float32)


def paged_selected_attention(q, pool, block_tables, positions, valid, *,
                             rank, scale):
    """T = 1 decode attention over a SELECTION of each row's positions in a
    shared-row pool (latent attention with a learned sparse indexer,
    models/dsa_moe.py): q [B, N, H]; pool [num_blocks, 1, bs, H], a row's
    first `rank` lanes the value; positions [B, K] int32, the positions
    each row attends (any order), found through `block_tables`; valid
    [B, K] bool (a selection shorter than K: the rest are ignored).  Only
    those K rows are taken from the pool (one gather by page table and
    slot); scores, maximum, exponent and sum float32, the probabilities
    meet the rows in the rows' type.  Returns [B, N, rank] float32."""
    bs = pool_block_size(pool)
    blocks = jnp.take_along_axis(block_tables, positions // bs, axis=1)
    rows = pool[blocks, 0, positions % bs]                      # [B, K, H]
    score = jnp.einsum("bnr,bkr->bnk", q.astype(rows.dtype), rows,
                       preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(valid[:, None, :], score, -1e30), axis=-1)
    return jnp.einsum("bnk,bkr->bnr", p.astype(rows.dtype), rows[..., :rank],
                      preferred_element_type=jnp.float32)


def reads_own_pages(cache, t=1):
    """Whether decode attention of `t` tokens a row over `cache` goes
    through the Pallas kernel (each row's own live pages, straight from
    the pool) and not the XLA form (the ladder width of every row, taken
    and contracted).  Decided by what can be seen here, the same answer
    for the write that precedes the read (`paged_write_chunk`), the read
    (`paged_chunk_attention`) and the counter (`attn_positions`):
    `ops.use_pallas()` (a TPU, no mesh, FLAGS_use_pallas), a plain
    bfloat16 or float32 pool, one token a row, heads of whole 128-lane
    rows and pages of whole sublane tiles."""
    from paddle_tpu import ops

    if isinstance(cache, QuantPool) or t != 1 or not ops.use_pallas():
        return False
    bs, h = cache.shape[-2:]
    return (cache.dtype in (jnp.bfloat16, jnp.float32) and h % 128 == 0
            and bs % (32 // cache.dtype.itemsize) == 0)


def _paged_chunk_xla(q, key_cache, value_cache, block_tables, seq_lens, scale):
    """The XLA form: the first `w` pages of every row, `w` the narrowest
    width of `page_ladder` that covers max(seq_lens), chosen on the
    device, taken (`_take_pages`) and contracted (`gathered_attention`)."""
    ladder = page_ladder(block_tables.shape[1])
    bs = pool_block_size(key_cache)

    def at_width(w, pool=lambda cache: cache):
        def attend(q, key_cache, value_cache, block_tables, seq_lens):
            tables = block_tables[:, :w]
            return gathered_attention(
                q, _take_pages(pool(key_cache), tables),
                _take_pages(pool(value_cache), tables), seq_lens,
                scale=scale)
        return attend

    args = (q, key_cache, value_cache, block_tables, seq_lens)
    if len(ladder) == 1:
        return at_width(ladder[0])(*args)
    return jax.lax.switch(_ladder_index(ladder, bs, seq_lens),
                          [at_width(w, _as_written) for w in ladder], *args)


def paged_chunk_attention(q, key_cache, value_cache, block_tables, seq_lens,
                          *, scale=None):
    """Multi-token decode attention over the paged cache (speculative
    verify / chunked decode): q [B, T, N, H]; seq_lens [B] INCLUDING all
    T chunk tokens.  Chunk position j sits at global position
    seq_lens - T + j and attends keys <= that position (bottom-right
    causal within the chunk).  Returns [B, T, N, H].

    T = 1 over a pool that `reads_own_pages` goes through the Pallas
    kernel (`paged_decode`): each row's own pages and nothing beyond.
    Everything else (T > 1, an int8 pool, a mesh, the CPU,
    FLAGS_use_pallas=false) takes the XLA form, `_paged_chunk_xla`."""
    from paddle_tpu._core import compile_cache

    kernel = reads_own_pages(key_cache, q.shape[1])
    # the counters that say which path this trace took
    compile_cache.count("paged_kernel_traces" if kernel
                        else "paged_xla_traces")
    if kernel:
        if scale is None:
            scale = 1.0 / math.sqrt(q.shape[-1])
        return _paged_decode_pallas(q[:, 0], key_cache, value_cache,
                                    block_tables, seq_lens, scale)[:, None]
    return _paged_chunk_xla(q, key_cache, value_cache, block_tables,
                            seq_lens, scale)
