"""Continuous-batching generation engine over the paged-KV tier.

Reference lineage: the block-attention serving stack —
paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu and the
FastDeploy/PaddleNLP continuous-batching servers built on it (requests
share one block pool through per-request block tables, joining and leaving
the decode batch between steps).

TPU-native design: the decode batch has a FIXED number of slots, so every
step — any mix of live requests — reuses ONE compiled XLA program (static
shapes are the whole game on TPU; the reference's GPU kernel re-launches
per ragged batch instead).  A host-side block allocator hands pool pages
to requests and recycles them at completion; inactive slots park on a
dedicated scratch page each so the shared pool is never corrupted by
masked lanes.  Prefill runs per admitted request and pours its K/V into
pool pages; decode then advances all live slots together.
"""

from __future__ import annotations

import contextlib
import functools
import time
import warnings
import weakref
from collections import deque
from dataclasses import dataclass, field

import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu._core import autograd as _autograd
from paddle_tpu._core import flags as _flags
from paddle_tpu.profiler import RecordEvent, startup

__all__ = ["GenerationEngine", "RadixPrefixCache", "decode_stats",
           "reset_decode_stats", "lora_stats", "reset_lora_stats",
           "EngineSnapshot", "restore_engine", "snapshot_stats",
           "reset_snapshot_stats"]


# --------------------------------------------------------- decode telemetry
# Process-wide decode counters (profiler.decode_stats() reads them): one
# dispatch = one compiled-program launch; sync_seconds = host time blocked
# materializing device results (the per-token round-trip macro-stepping
# amortizes); tokens counts EMITTED tokens (masked tail lanes excluded).
_DECODE_STATS = {
    "dispatches": 0,
    "tokens": 0,
    "sync_seconds": 0.0,
    "step_seconds": 0.0,
    "macro_steps": 0,
    "last_chunk": 0,
    # prefix-cache tier (FLAGS_prefix_cache): admissions that reused at
    # least one cached page / that found nothing, prompt tokens whose
    # prefill was AVOIDED by page reuse, and LRU evictions of reclaimable
    # (refcount-zero) cached pages under pool pressure
    "prefix_hits": 0,
    "prefix_misses": 0,
    "prefix_hit_tokens": 0,
    "prefix_evictions": 0,
    # capacity tier: resident bytes of the most recent engine's pools
    # (payload + scales for int8) and the peak concurrently-active
    # requests observed — bytes/resident is the int8-KV capacity metric
    "pool_bytes": 0,
    "resident_peak": 0,
    # the same bytes by the cache specification's pool names (the model
    # contract, docs/DECODE.md): a K/V engine fills k_ and v_, a latent-
    # attention engine latent_pool_bytes (draft pools count in pool_bytes
    # only)
    "k_pool_bytes": 0,
    "v_pool_bytes": 0,
    "latent_pool_bytes": 0,
    # a WINDOW class's pools (models/contract.py CacheClass: rings of
    # window_ring_blocks blocks a slot, whatever the requests' lengths) are
    # named apart by the model: wk_ / wv_ beside the paged k_ / v_
    "wk_pool_bytes": 0,
    "wv_pool_bytes": 0,
    "window_ring_blocks": 0,
    # a STATE class's pools (state a slot: [max_batch, heads, width] a layer,
    # no positions) under the model's own names too, and all of them here
    "slot_state_bytes": 0,
    # sharded-serving tier: the most recent engine's PER-DEVICE pool
    # bytes (each pool leaf's committed sharding divides its global
    # bytes — ops.paged_attention.pool_device_nbytes over pool_parts)
    # and the mesh shape string ("" on single-device engines); the
    # Profiler.summary() serving footer prints both when sharded
    "pool_bytes_per_device": 0,
    "mesh_shape": "",
    # overload-discipline tier (docs/DECODE.md admission scheduler):
    # interleaved prefill chunks run between decode dispatches
    # (FLAGS_prefill_chunk_blocks), LOW-priority preemptions (pages
    # parked host-side) and their re-admissions, the parked-request
    # GAUGE, and the per-priority-class admitted/completed breakdown
    "prefill_chunks": 0,
    "preemptions": 0,
    "preempt_readmits": 0,
    "parked_requests": 0,
    "admitted_high": 0,
    "admitted_normal": 0,
    "admitted_low": 0,
    "completed_high": 0,
    "completed_normal": 0,
    "completed_low": 0,
    # admission split (docs/DECODE.md "Reading an admission"): COMMITTED
    # atomic admissions (_try_admit) and their host seconds — the whole
    # attempt and its four phases, each beside the `serving.admit.*` span
    # of the same boundary; admit_eager_ops counts the host dispatches of
    # the prefill phase: op-funnel calls EXECUTED eagerly plus one per
    # compiled-program call (funnel calls made while a program is traced
    # are its build, which prefill_programs_built counts).
    # queue_wait_seconds: submit -> the attempt that committed, for the
    # queued_admissions that waited in the pending queue
    "admissions": 0,
    "admit_seconds": 0.0,
    "admit_match_seconds": 0.0,
    "admit_prefill_seconds": 0.0,
    "admit_first_token_seconds": 0.0,
    "admit_pour_seconds": 0.0,
    "admit_eager_ops": 0,
    "queued_admissions": 0,
    "queue_wait_seconds": 0.0,
    # compiled prefill (docs/DECODE.md "The prefill program"): admissions
    # whose prefill ran as `jit_prefill_program`, how many of those calls
    # were the first use of their (bucket, prefix length) program, the
    # right-padding tokens the buckets cost, and admissions that stayed on
    # the eager forward (chunked / interleaved prefill, adapter requests)
    "prefill_program_calls": 0,
    "prefill_programs_built": 0,
    "prefill_pad_tokens": 0,
    "prefill_eager_fallbacks": 0,
    # expert load (docs/DECODE.md "Reading expert load"): counted ON THE
    # DEVICE by a model with routed experts (the contract's `aux`), summed
    # over the macro-step's scan and read with its tokens; committed rows
    # only; zero for a model without experts.  An expert-layer step is one
    # expert layer on one decode token step.  assignments = tokens x top-k
    # over those steps, held = the part that chose an expert this chip
    # holds, peak = tokens on the busiest held expert (summed over the
    # steps), experts_touched = held experts with at least one token
    # (summed).  The two moe_prefill_ counters are a committed admission's
    # prompt tokens x top-k x expert layers, and the held part.
    "moe_assignments": 0,
    "moe_held_assignments": 0,
    "moe_peak_expert_assignments": 0,
    "moe_experts_touched": 0,
    "moe_layer_steps": 0,
    "moe_prefill_assignments": 0,
    "moe_prefill_held_assignments": 0,
    # rows that chose NO expert (a router with a skip choice): counted apart,
    # and not among the assignments' held part
    "moe_skipped": 0,
    "moe_prefill_skipped": 0,
    # decode attention's reach (docs/DECODE.md "The decode attention"):
    # counted on the device by a model with K/V pools, the same road, once
    # a token step: the positions the step's attention read (through the
    # Pallas kernel the active rows' own pages, in the XLA form active rows
    # x the ladder width it chose), and the sum of those rows' lengths.
    # read / live is the step's read amplification.
    "attn_positions_read": 0,
    "attn_positions_live": 0,
    # the same by cache class, for a model with a window class beside its
    # paged one (read and live above are then the sum over both, once a
    # class): the ring's positions are read whole, min(len, W) are live
    "attn_full_positions_read": 0,
    "attn_window_positions_read": 0,
    "attn_window_positions_live": 0,
}

_ADMIT_PHASES = ("match", "prefill", "first_token", "pour")
# per-attempt counts that reach _DECODE_STATS only when the attempt commits
_ADMIT_COUNTS = ("admit_eager_ops", "prefill_program_calls",
                 "prefill_programs_built", "prefill_pad_tokens",
                 "prefill_eager_fallbacks")


@contextlib.contextmanager
def _admit_phase(name, acc):
    """One phase of an admission attempt: a `serving.admit.<name>` span,
    and its host seconds added to the attempt's own tally `acc` (which
    reaches _DECODE_STATS only if the attempt commits)."""
    t = time.perf_counter()
    try:
        with RecordEvent("serving.admit." + name):
            yield
    finally:
        acc[name] += time.perf_counter() - t


def decode_stats(reset: bool = False) -> dict:
    """Serving decode counters: dispatches, emitted tokens, host sync
    seconds, total step() seconds, and derived tokens_per_sec.  A healthy
    macro-stepping engine shows tokens >> dispatches; tokens ~= dispatches
    means the per-token path (FLAGS_decode_chunk=1) is active.  Also the
    prefix-cache hit/miss/avoided-token/eviction counters, the derived
    pool_bytes_per_resident capacity metric, and — for TP-sharded
    engines — pool_bytes_per_device (sharding-divided pool bytes) plus
    the mesh_shape string (docs/DECODE.md)."""
    out = dict(_DECODE_STATS)
    out["tokens_per_sec"] = (
        out["tokens"] / out["step_seconds"] if out["step_seconds"] else 0.0)
    out["pool_bytes_per_resident"] = (
        out["pool_bytes"] / out["resident_peak"] if out["resident_peak"]
        else 0.0)
    if reset:
        reset_decode_stats()
    return out


def reset_decode_stats():
    for k in _DECODE_STATS:
        if k == "parked_requests":
            # a GAUGE of live engine state (like the LoRA slot gauges):
            # a traffic-counter reset must not misreport the parking lot
            continue
        v = _DECODE_STATS[k]
        _DECODE_STATS[k] = "" if isinstance(v, str) else (
            0.0 if isinstance(v, float) else 0)


# Multi-tenant LoRA serving counters (profiler.lora_stats reads them):
# slots_resident = installed adapters on the most recent pack mutation;
# swaps = adapter installs into a slot (register_adapter, incl. LRU
# re-installs); evictions = slots vacated (explicit or LRU); gather
# dispatches = compiled decode dispatches that gathered per-row A/B from a
# pack; cache_epochs = slot-epoch bumps (each invalidates that slot's
# prefix-cache subtree); ship_ns_drops = shipped-page adoptions refused
# for a (slot, epoch) namespace mismatch (the pages were poured under
# adapter weights this engine no longer serves — dropping them loudly is
# the epoch-bump-strands-shipments contract, docs/SERVING_CLUSTER.md).
_LORA_STATS = {
    "slots_resident": 0,
    "slots_total": 0,
    "swaps": 0,
    "evictions": 0,
    "gather_dispatches": 0,
    "cache_epochs": 0,
    "ship_ns_drops": 0,
}


def lora_stats(reset: bool = False) -> dict:
    """Multi-tenant LoRA serving counters (docs/LORA.md): adapter slots
    resident / total on the most recent pack engine, hot swaps and
    evictions, decode dispatches that gathered adapter rows, prefix-cache
    epoch bumps, and shipped-page adoptions dropped for a namespace
    (slot, epoch) mismatch.  Zeros when no adapter engine ran."""
    out = dict(_LORA_STATS)
    if reset:
        reset_lora_stats()
    return out


def reset_lora_stats():
    # slots_resident/slots_total are GAUGES of live engine state, not
    # windowed traffic — a counter reset must not misreport the pack
    for k in _LORA_STATS:
        if k not in ("slots_resident", "slots_total"):
            _LORA_STATS[k] = 0


# Live engines hold compiled decode executables; any flag change may alter
# what those programs traced (FLAGS_decode_chunk, matmul precision, ...), so
# set_flags drops them — the same contract as the eager dispatch cache.
_ENGINES: "weakref.WeakSet[GenerationEngine]" = weakref.WeakSet()


@_flags.on_change
def _invalidate_decode_steps(_changed):
    for eng in list(_ENGINES):
        eng._step_fns.clear()
        eng._prefill_fns.clear()
        eng._draft_fn = eng._verify_fn = eng._logit_rows_fn = None


def _cache_blocks(spec, caches, start_tok, s0, bs, end=None):
    """Naive prefill caches (`caches[layer][p]`: [1, S, heads, width] per
    pool of the layer's cache class, Tensors or raw arrays) -> the pools'
    block layout, `blocks[p][i]` in the engine's order (`spec.pools`; `i`
    over the class's layers).  A PAGED class gets the tokens [start_tok,
    s0) as [n, heads, bs, width], the last block's tail zero-padded;
    start_tok is block-aligned (it skips the prefix-matched region: the
    caches hold the FULL logical sequence).  A WINDOW class gets its ring,
    [ring_blocks, heads, bs, width]: ring block r holds the latest block j
    <= (end - 1) // bs of the sequence with j % ring_blocks == r (zeros
    where there is none), `end` the count of real positions (s0 where not
    given; traced inside the prefill program).  A STATE class gets its one
    row, [1, heads, width]: the model's forward hands back the state after
    the last real position and nothing is shaped.  The ONE shaper: eager
    admissions call it on the host, the prefill program inside its trace."""
    n = -(-(s0 - start_tok) // bs)
    pad = start_tok + n * bs - s0

    def shape(t):
        kv = jnp.moveaxis(getattr(t, "_value", t), 1, 2)
        kv = kv[0, :, start_tok:s0]                      # [heads, S', width]
        if pad:
            kv = jnp.pad(kv, ((0, 0), (0, pad), (0, 0)))
        heads, _, width = kv.shape
        return kv.reshape(heads, n, bs, width).swapaxes(0, 1)

    def ring(t, blocks_in_ring):
        if start_tok:
            raise NotImplementedError("a window class's ring is poured from "
                                      "a whole prompt, never behind a prefix")
        blocks = shape(t)
        last = (jnp.asarray(s0 if end is None else end, jnp.int32) - 1) // bs
        r = jnp.arange(blocks_in_ring, dtype=jnp.int32)
        j = last - jnp.mod(last - r, blocks_in_ring)
        taken = jnp.take(blocks, jnp.clip(j, 0, n - 1), axis=0)
        return jnp.where((j >= 0)[:, None, None, None], taken, 0)

    named = [{ps.name: t for (_c, ps), t in zip(spec.layer_pools(li), layer)}
             for li, layer in enumerate(caches)]
    out = []
    for cls in spec.classes:
        for ps in cls.pools:
            mine = [named[li][ps.name] for li in cls.layers]
            if cls.slot_state:
                out.append([getattr(t, "_value", t)[0] for t in mine])
            elif cls.window is None:
                out.append([shape(t) for t in mine])
            else:
                out.append([ring(t, cls.ring_blocks(bs)) for t in mine])
    return out


def _empty_caches(spec, batch=1):
    """Length-0 naive caches of a cache specification, `caches[layer][p]`."""
    import paddle_tpu as paddle

    return [tuple(paddle.zeros([batch, 0, p.heads, p.width], dtype=p.dtype)
                  for _c, p in spec.layer_pools(li))
            for li in range(spec.n_layers)]


@functools.partial(jax.jit, donate_argnums=(0,))
def _pour_new_blocks(pool, blocks, idx):
    """`paged_pour_blocks` of a request's pages `idx` [n_t]: `blocks`
    [n, Nkv, bs, H] cut or zero-extended to n_t blocks.  The caller's blocks
    are all-zero past the request's real tokens (a bucket's padding), so
    cutting drops nothing; extending zeroes the future decode pages.  One
    compiled scatter per (pool, n, n_t), in place of six eager calls, and in
    place: the pool is donated (the caller rebinds it at once, as it does
    after a macro-step), so no second copy of a layer's pool waits for room."""
    from paddle_tpu.ops import paged_attention as pa

    n_t = idx.shape[0]
    zeros = jnp.zeros((n_t,) + blocks.shape[1:], blocks.dtype)
    return pa.paged_pour_blocks(
        pool, jnp.concatenate([blocks, zeros])[:n_t], idx)


@functools.partial(jax.jit, donate_argnums=(0,))
def _pour_stacked_blocks(pool, blocks, idx):
    """`_pour_new_blocks` for a STACKED class's pool `[layers, blocks, ...]`:
    `blocks[i]` is layer i's, and every layer's go to the same pages `idx` in
    one scatter along the second axis (one dispatch a pool, not one a pool
    and layer)."""
    n_t = idx.shape[0]
    new = jnp.stack(blocks).astype(pool.dtype)
    zeros = jnp.zeros(new.shape[:1] + (n_t,) + new.shape[2:], new.dtype)
    return pool.at[:, idx].set(jnp.concatenate([new, zeros], axis=1)[:, :n_t])


# SLO classes for add_request(priority=): admission order is (class, submit
# sequence) — FIFO within a class — and the deadline-pressure scheduler
# weights prefill-chunk grants by class (docs/DECODE.md admission scheduler)
_PRIORITY = {"high": 0, "normal": 1, "low": 2}
_PRI_NAMES = {v: k for k, v in _PRIORITY.items()}
# pressure = weight * (1 + boundaries waited): a request crossing
# _PRESSURE_ESCALATE doubles the macro-step's prefill-chunk budget, so
# HIGH escalates after 3 waited boundaries, NORMAL after 7, LOW after 15
_PRI_WEIGHT = {0: 4, 1: 2, 2: 1}
_PRESSURE_ESCALATE = 16


@dataclass
class _PrefillState:
    """Host bookkeeping for a PREFILLING slot (interleaved chunked
    prefill): pool pages and the slot are reserved at admission, then the
    prompt advances ONE pool block per granted chunk between decode
    dispatches — the chunk spans are fixed block-aligned offsets, never
    schedule-dependent, which is what keeps the stream bit-identical to
    atomic admission (the chunk boundary is pure data movement)."""
    req: dict                 # the queued submission (rid/prompt/nonce/...)
    caches: list              # naive per-layer K/V grown chunk-by-chunk
    matched: list             # shared prefix-cache pages (referenced)
    fresh: list               # exclusively owned pages (poured as we go)
    off: int = 0              # prompt tokens already forwarded
    poured: int = 0           # full blocks resident in the pool so far
    since: int = 0            # macro-step boundary when prefill began
    h: object = None          # last chunk's hidden states (first-token logits)


@dataclass
class _Slot:
    rid: object = None
    active: bool = False
    seq_len: int = 0          # tokens stored in the pool (incl. prompt)
    max_len: int = 0          # seq_len limit for this request
    blocks: list = field(default_factory=list)
    last_token: int = 0
    generated: list = field(default_factory=list)
    # per-request decode config (temperature-sampling tier; 0 = greedy)
    temperature: float = 0.0
    key: object = None        # precomputed PRNG key (seed + request nonce)
    d_seq_len: int = 0        # draft-pool coverage (speculative tier)
    adapter_slot: int = 0     # AdapterPack slot (0 = base-model identity)
    priority: int = 1        # SLO class (_PRIORITY; 2 = LOW = preemptible)
    req: object = None        # original submission (preemption re-queues it)
    prefill: object = None    # _PrefillState while PREFILLING, else None


class _PoolExhausted(RuntimeError):
    """Transient admission failure: not enough free (or reclaimable) pool
    blocks right now.  The engine queues the request for retry at the next
    macro-step boundary instead of surfacing this."""


class _RadixNode:
    __slots__ = ("chunk", "block", "children", "parent", "last_used")

    def __init__(self, chunk=None, block=-1, parent=None):
        self.chunk = chunk          # tuple of block_size token ids
        self.block = block          # pool block holding this chunk's K/V
        self.children = {}
        self.parent = parent
        self.last_used = 0


class RadixPrefixCache:
    """Host-side radix tree over token-id prefixes at PAGE granularity.

    Each node maps one FULL block's token chunk (a `block_size`-tuple of
    ids) to the pool block holding its K/V — for every layer at once, since
    a block id indexes all layers' pools at the same position.  `match`
    walks the prompt chunk-by-chunk and returns the longest cached run of
    blocks; `insert` adopts full prompt blocks freshly written by prefill.
    Reference-counting lives in the engine's allocator: the tree itself
    never pins a block, so a cached block with refcount zero is
    RECLAIMABLE, and `evict` frees such blocks leaf-first in LRU order
    (interior nodes only become evictable once their children are gone —
    a cached prefix is never torn out from under a longer cached one).
    Partial tail blocks are never inserted: the tail is re-prefilled
    per-request into an exclusively-owned page, which is the copy-on-write
    rule — shared pages are immutable, the mutable tail is always a
    private copy.

    Chunk keys are opaque: an adapter-aware engine namespaces the FIRST
    level with ``ns=(adapter_slot, slot_epoch)`` — root children key as
    ``(ns, chunk)`` — so tenants sharing a system prompt under the same
    adapter share pages while different adapters (whose K/V genuinely
    differ: adapted projections feed the cache) never cross-match, and a
    hot-swapped slot's bumped epoch strands exactly that slot's subtree
    (``drop_subtree`` reclaims it; docs/LORA.md).
    """

    def __init__(self, block_size):
        self.block_size = int(block_size)
        self._root = _RadixNode()
        self._by_block: dict[int, _RadixNode] = {}
        self._clock = 0

    def __len__(self):
        return len(self._by_block)

    def holds(self, block) -> bool:
        """Is this pool block owned by a tree node (i.e. cached)?"""
        return block in self._by_block

    def _tick(self):
        self._clock += 1
        return self._clock

    @staticmethod
    def _key(node_is_root, ns, chunk):
        return (ns, chunk) if (ns is not None and node_is_root) else chunk

    def match(self, tokens, max_blocks=None, ns=None):
        """Longest cached full-block prefix of `tokens` -> pool block list.

        Every matched node is LRU-touched.  `max_blocks` caps the walk
        (admission caps at (len-1)//block_size so at least one suffix
        token always prefills — the forward that produces the first
        logits).  `ns` namespaces the first chunk (adapter-aware engines
        pass (slot, epoch)); distinct namespaces never share nodes."""
        bs = self.block_size
        limit = len(tokens) // bs
        if max_blocks is not None:
            limit = min(limit, max_blocks)
        t = self._tick()
        node, out = self._root, []
        for bi in range(limit):
            chunk = tuple(tokens[bi * bs:(bi + 1) * bs])
            child = node.children.get(
                self._key(node is self._root, ns, chunk))
            if child is None:
                break
            child.last_used = t
            out.append(child.block)
            node = child
        return out

    def insert(self, tokens, blocks, ns=None):
        """Adopt `blocks[i]` as the cached page for tokens' i-th full
        chunk.  Existing nodes keep their block (first writer wins — the
        duplicate page stays request-private and recycles normally);
        returns the newly adopted blocks."""
        bs = self.block_size
        t = self._tick()
        node, adopted = self._root, []
        for bi in range(min(len(blocks), len(tokens) // bs)):
            chunk = tuple(tokens[bi * bs:(bi + 1) * bs])
            key = self._key(node is self._root, ns, chunk)
            child = node.children.get(key)
            if child is None:
                child = _RadixNode(key, blocks[bi], node)
                node.children[key] = child
                self._by_block[blocks[bi]] = child
                adopted.append(blocks[bi])
            child.last_used = t
            node = child
        return adopted

    def drop_subtree(self, ns, refcount):
        """Invalidate EXACTLY namespace `ns`'s subtree (a hot-swapped
        adapter slot): every node under first-level children keyed
        ``(ns, ...)`` leaves the tree.  Returns the refcount-zero blocks
        (immediately reclaimable — the caller frees them); blocks a live
        request still references merely stop being cached and recycle
        normally once that request drops them."""
        freed = []
        for key in [k for k in self._root.children
                    if isinstance(k, tuple) and len(k) == 2
                    and k[0] == ns]:
            stack = [self._root.children.pop(key)]
            while stack:
                nd = stack.pop()
                stack.extend(nd.children.values())
                del self._by_block[nd.block]
                if refcount[nd.block] == 0:
                    freed.append(nd.block)
        return freed

    def evict(self, n, refcount):
        """Free up to `n` RECLAIMABLE blocks: leaves whose refcount is
        zero, oldest-LRU first.  Refcounted blocks are untouchable — a
        request is still reading those pages.  Returns the freed blocks
        (the caller returns them to its free list).  One scan + a heap:
        an interior node enters the heap the moment its last child frees,
        so the whole reclaim is O(cached log cached), not O(n * cached)."""
        import heapq

        heap = [(nd.last_used, nd.block) for nd in self._by_block.values()
                if not nd.children and refcount[nd.block] == 0]
        heapq.heapify(heap)
        freed = []
        while heap and len(freed) < n:
            _, block = heapq.heappop(heap)
            victim = self._by_block[block]
            parent = victim.parent
            del parent.children[victim.chunk]
            del self._by_block[victim.block]
            freed.append(victim.block)
            if (parent is not self._root and not parent.children
                    and refcount[parent.block] == 0):
                heapq.heappush(heap, (parent.last_used, parent.block))
        return freed


class GenerationEngine:
    """Greedy continuous-batching decode over a shared paged-KV pool.

    Usage:
        eng = GenerationEngine(model, max_batch=4, block_size=16, num_blocks=64)
        eng.add_request("a", prompt_ids_a, max_new_tokens=8)
        while eng.has_work():
            for rid, toks in eng.step().items(): ...
        eng.result("a")  # -> list of generated token ids

    step() advances one MACRO-STEP of D = decode_chunk tokens per
    dispatch (D resolves to FLAGS_decode_chunk, default 8, when the
    constructor arg is None) and returns {rid: [tokens...]}; only at
    D == 1 — an explicit decode_chunk=1 or the flag set to 1 — does it
    return the legacy per-token {rid: token} shape.  Consumers that
    stream token-by-token should pass decode_chunk=1 or iterate the
    lists; `result(rid)` is unaffected either way (docs/DECODE.md).
    """

    @startup.phase("serving.engine.build", "engine_build_seconds")
    def __init__(self, model, max_batch=4, block_size=16, num_blocks=128,
                 eos_token_id=None, mesh=None, mp_axis="mp",
                 prefill_chunk=None, draft_model=None,
                 num_speculative_tokens=4, decode_chunk=None,
                 prefix_cache=None, kv_cache_dtype=None, adapters=None,
                 prefill_chunk_blocks=None):
        """mesh: optional ProcessMesh/jax Mesh with an `mp_axis` dimension —
        the engine then serves TENSOR-PARALLEL: weights get Megatron
        placements (the contract's `shard`), the paged-KV pool is sharded
        over the KV-head dim, and the ONE compiled decode program runs
        GSPMD-partitioned over the mesh (VERDICT r3 #6; reference capability:
        analysis_predictor multi-device serving).  The WHOLE feature set
        composes with the mesh: int8 pools shard payload + quant scales
        leaf-wise on the same KV-head spec, adapter packs place their A/B
        factors on their base projections' Megatron split
        (nn.AdapterPack.place_over_mesh), speculative engines shard the
        draft model and its pools too, and token streams stay
        bit-identical to the single-device engine (docs/DECODE.md
        sharded-serving section).

        decode_chunk (None -> FLAGS_decode_chunk): macro-step width D —
        step() advances D tokens per compiled dispatch (a lax.scan over the
        single-token step with donated pools), admitting/retiring requests
        only at macro-step boundaries; rows that finish mid-chunk are
        masked onto their scratch page for the rest of the chunk (their
        K/V writes never touch the shared pool) and their surplus tokens
        are dropped on the host.  Token streams are bit-identical for
        every D.  step() returns {rid: token} when D == 1 (back-compat)
        and {rid: [tokens...]} when D > 1.  Ignored by speculative engines
        (their tick is already multi-token).

        prefix_cache (None -> FLAGS_prefix_cache): radix/prefix KV reuse —
        admission matches the longest cached token-id prefix at page
        granularity, takes REFERENCES to those pool pages instead of
        re-prefilling them, and prefills only the suffix; full prompt
        blocks written by prefill are inserted back into the tree, and
        refcount-zero leaves are evicted LRU under pool pressure.

        kv_cache_dtype (None -> FLAGS_kv_cache_dtype): 'bf16' keeps
        full-precision pools in the model's serving dtype (today's exact
        behavior); 'int8' stores quantized pools with per-block-per-head
        scales, dequantized on gather inside the jitted step — roughly
        double the resident requests at fixed pool bytes.

        adapters: multi-tenant LoRA serving (nn/lora.py, docs/LORA.md) —
        an int rank, a config dict ({"rank", "alpha", "max_adapters",
        "targets"}), or a prebuilt nn.AdapterPack.  Pre-allocates
        FLAGS_lora_max_adapters hot-swappable slots (plus reserved slot 0
        = the exact base-model identity); register_adapter/evict_adapter
        mutate slot CONTENTS only, at macro-step boundaries, so the
        compiled decode step — which gathers each batch row's A/B by its
        slot index — is reused across swaps with zero recompiles.
        Requests pick an adapter via add_request(..., adapter=name);
        mixed-adapter batches decode in ONE dispatch.  With draft_model=
        the DRAFT proposes with the base model (no per-tenant draft
        packs) while the target verifies through each row's adapter —
        emitted streams equal the plain adapter engine's; a
        heavily-shifted tenant just pays a lower acceptance rate.

        prefill_chunk_blocks (None -> FLAGS_prefill_chunk_blocks):
        INTERLEAVED chunked prefill — admission only reserves a slot and
        pool pages; the prompt then advances at most this many pool-block
        chunks per step() between decode dispatches (the PREFILLING
        state), so a long prompt never stalls resident streams' inter-
        token latency.  0 = atomic prefill at admission (legacy).
        Streams are bit-identical to atomic admission: every chunk is a
        fixed block-aligned span through the same cached forward, and
        the per-block pour writes the same bytes (and the same
        per-block quant scales) the atomic pour batches.  Ignored by
        speculative engines (their draft pour rides atomic admission)."""
        self.model = model
        # the model contract (models/contract.py): everything the engine
        # asks of the model goes through it, and every pool it holds comes
        # from the contract's cache specification
        contract = self._contract = model.serving_contract()
        spec = self._spec = contract.spec
        if prefill_chunk is not None and int(prefill_chunk) < 1:
            raise ValueError("prefill_chunk must be a positive token count")
        self.prefill_chunk = None if prefill_chunk is None else int(prefill_chunk)
        if prefill_chunk_blocks is not None and int(prefill_chunk_blocks) < 0:
            raise ValueError("prefill_chunk_blocks must be >= 0 "
                             "(0 = atomic prefill)")
        self.prefill_chunk_blocks = (None if prefill_chunk_blocks is None
                                     else int(prefill_chunk_blocks))
        self.block_size = int(block_size)
        self.max_batch = int(max_batch)
        self.eos_token_id = eos_token_id
        self._n_layers = spec.n_layers

        self._pool_sharding = self._d_pool_sharding = None
        self._mp_axis = mp_axis
        if mesh is not None:
            from paddle_tpu.distributed.auto_parallel import ProcessMesh

            self._require_kv("a mesh (tensor-parallel serving)")
            if not isinstance(mesh, ProcessMesh):
                mesh = ProcessMesh(mesh)
            if mp_axis not in mesh.dim_names:
                raise ValueError(
                    f"mesh has no {mp_axis!r} axis: {mesh.dim_names}")
            contract.shard(mesh, mp_axis)
            # pool pages sharded over KV heads: each mp rank holds its
            # heads' pages; the paged-attention gather stays local
            self._pool_sharding = self._kv_pool_sharding(
                mesh, mp_axis, spec.pools[0].heads, "")
        self.mesh = mesh

        from paddle_tpu.ops import paged_attention as pa

        # per pool of the specification and per layer, pages
        # [num_blocks, heads, bs, width], plus one dedicated scratch page
        # per slot (masked lanes write there, never the pool)
        self._num_blocks = int(num_blocks)
        total = self._num_blocks + self.max_batch
        kv_dt = (kv_cache_dtype if kv_cache_dtype is not None
                 else _flags.flag("FLAGS_kv_cache_dtype"))
        if kv_dt not in ("bf16", "int8"):
            raise ValueError(
                f"kv_cache_dtype must be 'bf16' or 'int8', got {kv_dt!r}")
        if kv_dt == "int8":
            self._require_kv("an int8 pool (kv_cache_dtype='int8')")
        self._kv_dtype = kv_dt  # resolved ONCE: pools are allocated now
        # leaf-wise placement: a QuantPool's int8 payload [blocks,Nkv,bs,H]
        # and its f32 scales [blocks,Nkv] both shard on the KV-head dim
        # (the same PartitionSpec(None, mp) covers both ranks — trailing
        # dims replicate), so int8 pools compose with the mesh engine
        self._pools = self._alloc_pools(spec, total, self._pool_sharding)
        # a window class's RINGS: slot i owns blocks i * ring .. (i + 1) *
        # ring - 1 of that class's pools from here on, whatever it serves;
        # one constant [max_batch, ring] table a class (None for a paged
        # class, whose table is the requests'): on the host for the pour's
        # page indices, on the device once for the macro-step.  A STATE
        # class's pools have one row a slot, row i slot i's: the pour finds
        # it like a ring of one, and no table goes to the device (decode's
        # row b is slot b)
        self._ring_pages = [
            None if c.paged else np.arange(
                self.max_batch * (1 if c.slot_state
                                  else c.ring_blocks(self.block_size)),
                dtype=np.int32).reshape(self.max_batch, -1)
            for c in spec.classes]
        with startup.phase("serving.engine.build.state",
                           "engine_state_alloc_seconds"):
            self._ring_tables = jax.block_until_ready([
                None if c.window is None else jnp.asarray(t)
                for c, t in zip(spec.classes, self._ring_pages)])
        self._free = list(range(self._num_blocks))
        self._ref = [0] * total  # per-block request refcounts (allocator)
        pc = (bool(prefix_cache) if prefix_cache is not None
              else bool(_flags.flag("FLAGS_prefix_cache")))
        if pc:
            self._require_kv("the prefix cache")
        if self.prefill_chunk is not None:
            self._require_kv("chunked prefill (prefill_chunk)")
        self._prefix = RadixPrefixCache(self.block_size) if pc else None
        self._pending: deque = deque()  # admission retries (pool pressure)
        self._parked: dict = {}   # rid -> parked record (preempted LOWs)
        self._submit_seq = 0      # admission tie-break within an SLO class
        self._scratch = [self._num_blocks + i for i in range(self.max_batch)]
        self._slots = [_Slot() for _ in range(self.max_batch)]
        self._results: dict = {}
        self._max_blocks_per_seq = max(2, self._num_blocks // max(1, self.max_batch))
        if decode_chunk is not None and int(decode_chunk) < 1:
            raise ValueError("decode_chunk must be >= 1")
        self._decode_chunk = None if decode_chunk is None else int(decode_chunk)
        self._step_fns: dict = {}  # macro-step executables, keyed by D
        self._logit_rows_fn = None  # next_token_logits' one program
        # admission prefill programs, keyed by (padded suffix, prefix length)
        self._prefill_fns: dict = {}
        # masked lanes' block tables (every page is the slot's scratch
        # page): constant, so committed to the device ONCE here — not
        # re-transferred on every dispatch
        with startup.phase("serving.engine.build.state",
                           "engine_state_alloc_seconds"):
            self._scratch_tables = jax.block_until_ready(jnp.asarray(np.tile(
                np.asarray(self._scratch, np.int32)[:, None],
                (1, self._max_blocks_per_seq))))
        self._req_counter = 0
        self._queued_at: dict = {}  # rid -> perf_counter when add_request queued it
        self._state = list(model.state_dict().values())
        # ---- fault-tolerance tier (serving/snapshot.py) -----------------
        self._macro_steps = 0          # boundary count; snapshot step tags
        self._last_auto_snapshot = 0   # boundary of the last periodic save
        self._snapshot_store = None    # cached EngineSnapshot (valid-cache)
        self._draining = False         # drain(): admissions closed
        self._drain_step = None        # committed handoff step (idempotence)
        self._drain_dir = None         # ...and where it committed
        self._preempt_requested = False
        self._preempt_saved = False
        self._prev_handlers: dict = {}
        _ENGINES.add(self)

        # ---- speculative tier: draft model + its own paged pools --------
        self.draft_model = draft_model
        self._prefill_chunk_blocks()   # refuses interleaved prefill by name
        self.num_speculative = int(num_speculative_tokens)
        self._draft_fn = self._verify_fn = None
        if draft_model is not None:
            if self.num_speculative < 1:
                raise ValueError("num_speculative_tokens must be >= 1")
            self._require_kv("speculative decoding (draft_model)")
            if draft_model.config.vocab_size != model.config.vocab_size:
                raise ValueError("draft and target must share a vocabulary")
            d_contract = self._d_contract = draft_model.serving_contract()
            d_spec = self._d_spec = d_contract.spec
            if not d_spec.kv_pair:
                raise NotImplementedError(
                    "GenerationEngine: a draft model must keep K/V pools; "
                    f"this one keeps {[p.name for p in d_spec.pools]}")
            if mesh is not None and draft_model is not model:
                # the draft serves the same mesh: Megatron placements on
                # its weights, its pools sharded over ITS KV-head count
                # (which may differ from the target's)
                d_contract.shard(mesh, mp_axis)
            if mesh is not None:
                self._d_pool_sharding = self._kv_pool_sharding(
                    mesh, mp_axis, d_spec.pools[0].heads, "draft ")
            self._d_pools = self._alloc_pools(d_spec, total,
                                              self._d_pool_sharding)
            self._d_state = list(draft_model.state_dict().values())
            self._spec_stats = {"ticks": 0, "proposed": 0, "accepted": 0,
                                "emitted": 0}

        # ---- multi-tenant LoRA tier: slot-stacked adapter pack ----------
        self._pack = None
        if adapters is not None:
            from paddle_tpu.nn.lora import AdapterPack

            self._require_kv("LoRA adapter slots (adapters)")

            # speculative + adapters composes with a BASE-MODEL draft:
            # the draft proposes adapter-free tokens and the target
            # verifies through each row's adapter, so the emitted stream
            # is exactly the plain adapter engine's (greedy acceptance
            # only ever keeps tokens the adapted target would decode) —
            # a heavily-shifted tenant just pays a lower acceptance rate
            if isinstance(adapters, AdapterPack):
                self._pack = adapters
            elif isinstance(adapters, int):
                self._pack = AdapterPack(model, rank=adapters)
            elif isinstance(adapters, dict):
                self._pack = AdapterPack(model, **adapters)
            else:
                raise TypeError(
                    "adapters must be an int rank, a config dict, or an "
                    f"nn.AdapterPack; got {type(adapters).__name__}")
            if mesh is not None:
                # A/B factors ride the base projections' Megatron split
                # (col targets shard B's out dim, row targets shard A's
                # in dim); recorded shardings are re-applied after every
                # slot scatter so hot swaps keep one compiled signature
                self._pack.place_over_mesh(mesh.jax_mesh, mp_axis=mp_axis)
            S = self._pack.num_slots
            self._adapter_registry: dict = {}   # name -> (arrays, alpha)
            self._slot_names = [None] * S       # slot -> installed name
            self._slot_epochs = [0] * S         # bumped per content change
            self._slot_refs = [0] * S           # in-flight request counts
            self._slot_used = [0] * S           # LRU clock marks
            self._slot_clock = 0
            _LORA_STATS["slots_total"] = S - 1
            _LORA_STATS["slots_resident"] = 0
        all_pools = [p for pools in (self._pools
                                     + getattr(self, "_d_pools", []))
                     for p in pools]
        _DECODE_STATS["pool_bytes"] = sum(pa.pool_nbytes(p)
                                          for p in all_pools)
        for k in _DECODE_STATS:
            if k.endswith("_pool_bytes"):
                _DECODE_STATS[k] = 0
        for ps, pools in zip(spec.pools, self._pools):
            _DECODE_STATS[ps.name + "_pool_bytes"] = sum(
                pa.pool_nbytes(p) for p in pools)
        _DECODE_STATS["window_ring_blocks"] = sum(
            t.shape[1] for t in self._ring_tables if t is not None)
        _DECODE_STATS["slot_state_bytes"] = sum(
            _DECODE_STATS[ps.name + "_pool_bytes"]
            for c in spec.classes if c.slot_state for ps in c.pools)
        # per-device footprint: each pool leaf's committed sharding
        # divides its bytes (== pool_bytes on single-device engines)
        _DECODE_STATS["pool_bytes_per_device"] = sum(
            pa.pool_device_nbytes(p) for p in all_pools)
        _DECODE_STATS["mesh_shape"] = "" if mesh is None else "x".join(
            f"{n}{s}" for n, s in zip(mesh.dim_names, mesh.shape))
        if _flags.flag("FLAGS_verify_sharding"):
            # mesh lint at construction: param/pool placements, pool
            # donation aliasing, per-device HBM estimate — abstract, so a
            # replicated-pool blowup or a double-donated pool buffer fails
            # loudly here, before the first decode dispatch
            from paddle_tpu.static.mesh_lint import lint_engine

            lint_engine(self, raise_on_error=True)

    # ------------------------------------------- the cache specification
    def _require_kv(self, feature):
        """The engine's optional features were built for a K pool and a V
        pool a layer; a model whose contract specifies other pools is
        refused BY NAME here, never served by K/V code on its pool."""
        if not self._spec.kv_pair:
            windows = [c.window for c in self._spec.classes
                       if c.window is not None]
            state = [p.name for c in self._spec.classes if c.slot_state
                     for p in c.pools]
            raise NotImplementedError(
                f"GenerationEngine: {feature} cannot hold this model's "
                f"cache pools {[p.name for p in self._spec.pools]} yet"
                + (f" (a window class: rings of the last {windows[0]} "
                   "positions a slot, which are not pages)" if windows else "")
                + (f" (a state class: {state} are state a slot, with no "
                   "positions and no pages)" if state else "")
                + "; it was built for a K/V pair (docs/DECODE.md, the model "
                "contract)")

    def _class_tables(self, block_table, ring_tables):
        """`decode`'s tables where it takes one a class: a paged class's is
        the block table, a window class's the slots' rings (`ring_tables`
        holds None in every other class's place), a state class's None (its
        pools' row b is row b's slot)."""
        return tuple(block_table if c.paged else t
                     for c, t in zip(self._spec.classes, ring_tables))

    def _alloc_pools(self, spec, total, sharding):
        """`pools[p][i]` of a cache specification, zeroed and placed: a
        paged class's pools `total` blocks each (the allocator's, plus a
        scratch page a slot), a window class's max_batch rings, a state
        class's max_batch rows of [heads, width].  Waited for, class by
        class, under `serving.engine.build.pools` (a paged class) or
        `.state` (what a slot owns for good: rings, rows)."""
        from paddle_tpu.ops import paged_attention as pa

        def blocks(cls):
            return (total if cls.window is None
                    else self.max_batch * cls.ring_blocks(self.block_size))

        def one(cls, ps):
            if cls.slot_state:
                return jnp.zeros((self.max_batch, ps.heads, ps.width), ps.dtype)
            return self._place_pool(pa.alloc_paged_pool(
                blocks(cls), ps.heads, self.block_size, ps.width,
                jnp.int8 if self._kv_dtype == "int8" else ps.dtype), sharding)

        def stacked(cls, ps):
            # made whole (never int8: a stacked class is not a K/V pair)
            shape = ((self.max_batch, ps.heads, ps.width) if cls.slot_state
                     else (blocks(cls), ps.heads, self.block_size, ps.width))
            return jnp.zeros((len(cls.layers),) + shape, ps.dtype)

        pools = []
        for cls in spec.classes:
            with (startup.phase("serving.engine.build.pools",
                                "engine_pool_alloc_seconds") if cls.paged
                  else startup.phase("serving.engine.build.state",
                                     "engine_state_alloc_seconds")):
                pools += jax.block_until_ready(
                    [[stacked(cls, ps)] if cls.stacked
                     else [one(cls, ps) for _ in cls.layers]
                     for ps in cls.pools])
        return pools

    # ------------------------------------------------------ pool placement
    @staticmethod
    def _kv_pool_sharding(mesh, mp_axis, nkv, who):
        """NamedSharding for a paged pool on the TP mesh: pages shard
        over the KV-head dim (axis 1) when the axis divides the head
        count; otherwise replicated with a warning.  The SAME spec covers
        a QuantPool's rank-2 scales [blocks, Nkv] — trailing dims
        replicate — so int8 pools place leaf-wise through it."""
        from jax.sharding import NamedSharding, PartitionSpec

        mp = mesh.get_dim_size(mp_axis)
        if nkv % mp == 0:
            return NamedSharding(mesh.jax_mesh,
                                 PartitionSpec(None, mp_axis))
        import warnings

        warnings.warn(
            f"num_key_value_heads={nkv} not divisible by mp={mp}; "
            f"{who}KV pool replicated", stacklevel=3)
        return NamedSharding(mesh.jax_mesh, PartitionSpec())

    @staticmethod
    def _place_pool(pool, sharding):
        """Commit a pool (plain array or QuantPool pytree) to `sharding`
        leaf-wise; identity when sharding is None (single device)."""
        if sharding is None:
            return pool
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sharding), pool)

    # ------------------------------------------------------------ requests
    def has_work(self):
        # a DRAINING engine's queued requests are not its work: they rode
        # the drain snapshot and belong to the restore target (serving
        # them here too would double-serve; counting them here would make
        # the lame-duck `while has_work(): step()` loop spin forever).
        # PREFILLING slots and the parked lot count: both finish through
        # future boundaries (drain() demotes them to the queue first).
        return any(s.active or s.prefill is not None
                   for s in self._slots) or (
            (bool(self._pending) or bool(self._parked))
            and not self._draining)

    def pending_requests(self):
        """Request ids queued for admission (pool pressure); they retry at
        the next macro-step boundary."""
        return [req["rid"] for req in self._pending]

    def parked_requests(self):
        """Request ids preempted into the host-side parking lot (their
        pool pages live host-side; they re-admit bit-identically at a
        later boundary — docs/DECODE.md preemption)."""
        return list(self._parked)

    def prefilling_requests(self):
        """Request ids in the PREFILLING state (interleaved chunked
        prefill in progress; docs/DECODE.md admission scheduler)."""
        return [s.rid for s in self._slots if s.prefill is not None]

    def result(self, rid):
        return self._results.get(rid)

    def next_token_logits(self):
        """{rid: float32 [V]}: the NEXT token's logits of every active
        row — the contract's decode step, as the macro-step scans it, over
        the pools the engine holds now (what the prefill program poured
        and the macro-steps wrote; every cache class's, a window class's
        rings through the slots' ring tables).  One compiled call that
        hands back the logits alone: pools, slots and streams are
        untouched, and a greedy row's argmax is the token the next step
        emits.  For comparisons that need more than a token (perfbench's
        logit rows, chip_smoke.py); serving a request never calls it."""
        from paddle_tpu._core.autograd import no_grad

        if self._pack is not None or self.draft_model is not None:
            raise NotImplementedError(
                "next_token_logits: the plain macro-step's decode only (no "
                "adapter pack, no draft model)")
        contract, state = self._contract, self._state
        per_class = self._spec.per_class_tables

        def logit_rows(state_vals, pools, tokens, tables, lens, active,
                       ring_tables):
            originals = [t._value for t in state]
            try:
                for t, v in zip(state, state_vals):
                    t._bind(v)
                if per_class:
                    tables = self._class_tables(tables, ring_tables)
                with no_grad():
                    h, _, _ = contract.decode(tokens, contract.pool_carry(pools),
                                              tables, lens, active=active)
                    return contract.logits(h)._value[:, -1].astype(jnp.float32)
            finally:
                for t, v in zip(state, originals):
                    t._bind(v)

        if self._logit_rows_fn is None:
            self._logit_rows_fn = jax.jit(logit_rows)
        B, W = self.max_batch, self._max_blocks_per_seq
        tokens = np.zeros((B, 1), np.int32)
        tables = np.tile(np.asarray(self._scratch, np.int32)[:, None], (1, W))
        lens = np.ones((B,), np.int32)
        for i, s in enumerate(self._slots):
            if s.active:
                tokens[i, 0] = s.last_token
                tables[i] = list(s.blocks) + [s.blocks[-1]] * (W - len(s.blocks))
                lens[i] = s.seq_len + 1
        rows = np.asarray(self._logit_rows_fn(
            [t._value for t in self._state], [list(p) for p in self._pools],
            jnp.asarray(tokens), jnp.asarray(tables), jnp.asarray(lens),
            jnp.asarray([s.active for s in self._slots]),
            list(self._ring_tables)))
        return {s.rid: rows[i] for i, s in enumerate(self._slots) if s.active}

    # ---------------------------------------------------- adapter registry
    def _require_pack(self):
        if self._pack is None:
            raise RuntimeError(
                "this engine was built without adapters=; pass "
                "GenerationEngine(adapters=rank_or_config) to serve "
                "multi-tenant LoRA (docs/LORA.md)")
        return self._pack

    def _slot_of(self, name):
        return next((s for s, n in enumerate(self._slot_names) if n == name),
                    None)

    def _touch_slot(self, slot):
        self._slot_clock += 1
        self._slot_used[slot] = self._slot_clock

    def _bump_epoch(self, slot):
        """Invalidate exactly `slot`'s prefix-cache subtree: the old
        (slot, epoch) namespace becomes unreachable and its refcount-zero
        pages return to the free list NOW."""
        if self._prefix is not None:
            freed = self._prefix.drop_subtree(
                (slot, self._slot_epochs[slot]), self._ref)
            self._free.extend(freed)
        self._slot_epochs[slot] += 1
        _LORA_STATS["cache_epochs"] += 1

    def _resident_count(self):
        return sum(1 for n in self._slot_names[1:] if n is not None)

    def register_adapter(self, name, state_dict, alpha=None):
        """Register a LoRA adapter (an adapter-only state dict — see
        nn.lora.lora_state_dict) and install it into a pack slot if one is
        free or LRU-reclaimable.  Returns the slot index, or None when
        every slot currently serves in-flight requests — the adapter stays
        registered and installs lazily when one of its requests is
        admitted at a macro-step boundary (requests never raise on slot
        exhaustion; they QUEUE, same FIFO contract as pool exhaustion).

        Installation is a pure device scatter into pre-allocated arrays:
        pack geometry (rank, slot count, targets) never changes, so the
        compiled decode step is reused — zero recompiles per swap.
        `alpha` defaults to the pack's alpha (scaling = alpha/rank is
        per-slot, so tenants may differ).

        Re-registering a RESIDENT name updates its slot in place (new
        weights scattered, epoch bumped so stale cached prefixes die) —
        refused while the adapter has in-flight ACTIVE requests, whose
        streams must not change weights mid-flight (queued requests are
        fine: they haven't started and will serve the new version)."""
        pack = self._require_pack()
        from paddle_tpu.nn.lora import parse_adapter_state_dict

        arrays = parse_adapter_state_dict(
            state_dict, pack.num_layers, pack.targets, pack.rank)
        slot = self._slot_of(name)
        if slot is not None:
            if self._slot_refs[slot] > 0:
                raise RuntimeError(
                    f"adapter {name!r} has in-flight requests; "
                    "re-registering would change their weights "
                    "mid-stream — drain them first")
            self._adapter_registry[name] = (arrays, alpha)
            self._bump_epoch(slot)
            self._pack.set_slot(slot, arrays, alpha)
            self._touch_slot(slot)
            _LORA_STATS["swaps"] += 1
            return slot
        self._adapter_registry[name] = (arrays, alpha)
        return self._try_install(name)

    def _try_install(self, name):
        """Make `name` resident: reuse its slot, take a free one, or evict
        the LRU idle slot (never one with in-flight requests).  Returns
        the slot index or None (transient exhaustion — every slot busy)."""
        slot = self._slot_of(name)
        if slot is not None:
            self._touch_slot(slot)
            return slot
        arrays, alpha = self._adapter_registry[name]
        S = self._pack.num_slots
        free = next((s for s in range(1, S) if self._slot_names[s] is None),
                    None)
        if free is None:
            idle = [s for s in range(1, S) if self._slot_refs[s] == 0]
            if not idle:
                return None
            free = min(idle, key=lambda s: self._slot_used[s])
            self._slot_names[free] = None
            _LORA_STATS["evictions"] += 1
        # the install overwrites EVERY target (omitted ones zero), so no
        # separate clear; the epoch bump strands the old contents' cached
        # prefix subtree before the new tenant can be matched against it
        self._bump_epoch(free)
        self._pack.set_slot(free, arrays, alpha)
        self._slot_names[free] = name
        self._touch_slot(free)
        _LORA_STATS["swaps"] += 1
        _LORA_STATS["slots_resident"] = self._resident_count()
        return free

    def evict_adapter(self, name):
        """Unregister `name` and vacate its slot.  REFUSES (raises) while
        the adapter has in-flight requests — active slots or queued
        admissions; retire or drain them first.  The slot's prefix-cache
        subtree is invalidated and its contents zeroed."""
        self._require_pack()
        if name not in self._adapter_registry:
            raise KeyError(f"adapter {name!r} is not registered")
        slot = self._slot_of(name)
        in_flight = (slot is not None and self._slot_refs[slot] > 0)
        if in_flight or any(r.get("adapter") == name for r in self._pending):
            raise RuntimeError(
                f"adapter {name!r} has in-flight requests "
                f"({'active' if in_flight else 'queued'}); drain them "
                "before evicting")
        del self._adapter_registry[name]
        if slot is not None:
            self._slot_names[slot] = None
            self._bump_epoch(slot)
            self._pack.clear_slot(slot)
            _LORA_STATS["evictions"] += 1
            _LORA_STATS["slots_resident"] = self._resident_count()

    def adapter_slots(self):
        """{adapter name: slot index} for currently RESIDENT adapters
        (registered-but-swapped-out adapters are absent)."""
        self._require_pack()
        return {n: s for s, n in enumerate(self._slot_names)
                if n is not None}

    def _alloc(self, n):
        """Pop n blocks (refcount 1 each).  Under pressure, reclaimable
        prefix-cache pages (refcount-zero LRU leaves) are evicted first;
        a genuine shortfall raises _PoolExhausted — admission backs out
        and queues, it never surfaces to the caller mid-submit."""
        if len(self._free) < n and self._prefix is not None:
            freed = self._prefix.evict(n - len(self._free), self._ref)
            self._free.extend(freed)
            _DECODE_STATS["prefix_evictions"] += len(freed)
        if len(self._free) < n:
            raise _PoolExhausted(
                f"paged pool exhausted: need {n} blocks, {len(self._free)} free"
            )
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._ref[b] = 1
        return out

    def _unref(self, blocks):
        """Drop one reference per block; blocks reaching refcount zero
        return to the free list UNLESS the prefix tree caches them — those
        stay resident as reclaimable pages until LRU eviction."""
        for b in blocks:
            self._ref[b] -= 1
            if self._ref[b] <= 0 and (
                    self._prefix is None or not self._prefix.holds(b)):
                self._free.append(b)

    def _back_out(self, fresh, matched):
        """Undo a failed admission attempt's allocation: prefill and pour
        only ever wrote the fresh pages, so returning them (and the prefix
        references) restores the allocator exactly."""
        for b in fresh:
            self._ref[b] = 0
            self._free.append(b)
        self._unref(matched)

    def _release(self, slot):
        self._unref(slot.blocks)
        if self._pack is not None:
            self._slot_refs[slot.adapter_slot] -= 1
            slot.adapter_slot = 0
        slot.blocks = []
        slot.active = False
        slot.rid = None
        slot.req = None
        slot.prefill = None

    def add_request(self, rid, prompt_ids, max_new_tokens=16,
                    temperature=None, seed=0, adapter=None, nonce=None,
                    priority="normal"):
        """Prefill the prompt, pour K/V into pool pages, occupy a slot.

        With the prefix cache on, the longest cached token-id prefix is
        matched at page granularity first: those pages are REFERENCED (not
        re-prefilled) and only the suffix runs through the model.

        Under pool pressure (or with no free slot) the request is QUEUED
        instead of raising: admission retries at the next macro-step
        boundary, and `add_request` returns None (the first generated
        token otherwise).  Its PRNG nonce is reserved at submit time, so a
        queued-then-admitted sampled request draws the same stream an
        immediately-admitted one would.  Requests that can NEVER fit
        (wider than the per-seq block table) still raise.

        temperature: None/0 -> greedy decode for this request;
        > 0 -> per-request temperature sampling, deterministic per
        (seed, join order) — the seed is folded with a per-request nonce so
        same-seed requests still draw distinct streams, and each request
        folds its OWN generated-token counter per step.  Requests with
        different decode configs share the ONE compiled decode program
        (the config rides in as per-slot arrays).

        adapter: name of a REGISTERED LoRA adapter (register_adapter) to
        serve this request with; None = the base model (pack slot 0).
        A request whose adapter cannot be made resident right now (every
        slot busy with in-flight requests) QUEUES exactly like pool
        exhaustion — FIFO retry at the next macro-step boundary, with the
        PRNG nonce reserved at submit so a queued-then-admitted stream
        matches immediate admission bit-for-bit.  An UNREGISTERED adapter
        name raises KeyError (nothing to wait for).

        nonce: EXPLICIT submit-time nonce (serving/cluster.py's router
        assigns these globally) instead of this engine's local counter —
        a request re-dispatched to a DIFFERENT replica after a crash
        draws exactly the stream the dead replica would have, because the
        sampling key is (seed, nonce) and both are now request identity,
        not engine state.  The local counter advances past any explicit
        nonce so mixed use can never collide.

        priority: SLO class — "high" | "normal" | "low".  Admission at
        macro-step boundaries runs in (class, submit order) — FIFO
        within a class — the deadline-pressure scheduler weights
        interleaved prefill-chunk grants by class, and LOW requests are
        PREEMPTIBLE (FLAGS_preempt_low_priority): when a higher class
        cannot be admitted, a LOW resident's pages park host-side and
        its stream resumes bit-identically on re-admission (submit-time
        nonces make the stream request identity, not engine state).

        With interleaved chunked prefill active (prefill_chunk_blocks /
        FLAGS_prefill_chunk_blocks > 0) add_request ALWAYS returns None:
        prefill spreads over future step() boundaries, and the first
        token surfaces through step()'s output as a queued admission
        would (a list-valued entry led by token #1)."""
        if self._draining:
            raise RuntimeError(
                "engine is draining (drain(): migration snapshot taken, "
                "admissions closed) — submit to the restored engine "
                "instead (docs/CHECKPOINT.md serving section)")
        if self.draft_model is not None and float(temperature or 0.0) > 0.0:
            # checked BEFORE any allocation/prefill: a rejected request
            # must not leak pool blocks or burn two prefills
            raise ValueError(
                "speculative decoding slots are greedy-only (sampled "
                "acceptance needs rejection sampling); drop temperature")
        prompt = np.asarray(prompt_ids, np.int32).reshape(1, -1)
        max_len = prompt.shape[1] + int(max_new_tokens)
        # speculative verify overshoots by up to K+1 positions past the
        # budget before lens bookkeeping rolls back — those writes must
        # land in pages the request OWNS, never in the table-padding block
        headroom = 0 if self.draft_model is None else self.num_speculative + 1
        n_blocks = -(-(max_len + headroom) // self.block_size)
        if n_blocks > self._max_blocks_per_seq:
            raise RuntimeError(
                f"request needs {n_blocks} blocks > per-seq table width "
                f"{self._max_blocks_per_seq}"
            )
        if adapter is not None:
            self._require_pack()
            if adapter not in self._adapter_registry:
                raise KeyError(
                    f"adapter {adapter!r} is not registered on this "
                    "engine; call register_adapter first")
        if priority not in _PRIORITY:
            raise ValueError(
                f"priority must be one of {sorted(_PRIORITY)}, "
                f"got {priority!r}")
        # nonce reserved at SUBMIT time: retry timing can't shift the
        # request's sampling stream
        if nonce is None:
            nonce = self._req_counter
            self._req_counter += 1
        else:
            nonce = int(nonce)
            self._req_counter = max(self._req_counter, nonce + 1)
        req = {"rid": rid, "prompt": prompt, "max_len": max_len,
               "n_blocks": n_blocks,
               "temperature": float(temperature or 0.0),
               "seed": int(seed), "nonce": nonce, "adapter": adapter,
               "pri": _PRIORITY[priority], "seq": self._submit_seq}
        self._submit_seq += 1
        if self._prefill_chunk_blocks() > 0:
            # interleaved mode: admission happens at boundaries only (the
            # chunk scheduler owns the prefill work); first tokens surface
            # through step() exactly like queued admissions
            self._pending.append(req)
            return None
        # fairness: while older same-or-higher-class requests wait,
        # newcomers queue behind (the boundary scheduler orders the queue
        # by (class, submit order); all-default-priority traffic is FIFO —
        # the original contract)
        if self._pending or not self._try_admit(req):
            # the wait _try_admit's commit reports as queue_wait_seconds
            self._queued_at[rid] = time.perf_counter()
            self._pending.append(req)
            return None
        return self._results[rid][0]

    def _prefill_chunk_blocks(self) -> int:
        """Per-macro-step prefill budget N in pool blocks (0 = atomic
        prefill at admission).  Speculative engines always resolve 0:
        their admission pours the draft pools too, and interleaving
        would desynchronize d_seq_len mid-prefill."""
        if self.draft_model is not None:
            return 0
        n = (self.prefill_chunk_blocks
             if self.prefill_chunk_blocks is not None
             else max(0, int(_flags.flag("FLAGS_prefill_chunk_blocks"))))
        if n:
            self._require_kv("interleaved prefill (prefill_chunk_blocks)")
        return n

    def _admit_pending(self):
        """Retry queued admissions — called at macro-step boundaries — in
        (priority class, submit order): parked (preempted) requests
        compete in the SAME ordering as queued ones.  When the head
        candidate is above LOW and cannot be admitted, a LOW resident may
        be preempted to make room (FLAGS_preempt_low_priority).  Returns
        the admitted request ids whose FIRST token is already available
        (atomic admissions): it surfaces through this step()'s output.
        Interleaved reservations enter the PREFILLING state instead —
        their rids surface later, when _advance_prefills finishes them —
        and re-admitted parked requests already delivered token #1, so
        neither appears in the returned list."""
        admitted = []
        interleaved = self._prefill_chunk_blocks() > 0
        while True:
            cands = [((rec["req"].get("pri", 2), rec["req"].get("seq", 0)),
                      None, rid) for rid, rec in self._parked.items()]
            cands += [((req.get("pri", 1), req.get("seq", 0)), req,
                       req["rid"]) for req in self._pending]
            if not cands:
                break
            _key, req, rid = min(cands, key=lambda c: c[0])
            if req is None:
                ok = self._try_unpark(rid)
            elif interleaved:
                ok = self._begin_prefill(req)
                if ok:
                    self._drop_pending(req)
            else:
                ok = self._try_admit(req)
                if ok:
                    self._drop_pending(req)
                    admitted.append(rid)
            if ok:
                continue
            # head-of-line blocked: a request above LOW may evict a LOW
            # resident (its pages park host-side) and retry
            if _key[0] < _PRIORITY["low"] and self._preempt_one():
                continue
            if not any(s.active or s.prefill is not None
                       for s in self._slots):
                # nothing resident to drain and still no room: the
                # engine can never make progress — be loud
                raise RuntimeError(
                    f"queued request {rid!r} cannot be admitted "
                    "with an idle engine (pool too small?)")
            break
        return admitted

    def _drop_pending(self, req):
        # remove by IDENTITY: req dicts hold numpy prompts, so deque's
        # ==-based remove could raise on a truth-ambiguous array compare
        for i, r in enumerate(self._pending):
            if r is req:
                del self._pending[i]
                return

    def _try_admit(self, req):
        """One admission attempt: prefix-match, allocate, prefill the
        suffix, pour, occupy a slot.  Returns False (with ALL state backed
        out — no leaked blocks, no occupied slot, no stolen references) on
        transient shortage; real errors back out and re-raise.

        An attempt that finds a free slot is one `serving.admit` span
        carrying the request's rid; its phases are child spans whose host
        seconds reach the admit_* counters only when the attempt COMMITS
        (as prefix_hits does): a backed-out attempt must not inflate the
        split."""
        t0 = time.perf_counter()
        slot = next((s for s in self._slots if not s.active), None)
        if slot is None:
            return False
        acc = dict.fromkeys(_ADMIT_PHASES, 0.0)
        acc.update(dict.fromkeys(_ADMIT_COUNTS, 0))
        with RecordEvent("serving.admit", rid=req["rid"],
                         prompt_len=req["prompt"].shape[1],
                         blocks=req["n_blocks"]):
            if not self._admit(req, slot, acc):
                return False
        st = _DECODE_STATS
        st["admissions"] += 1
        st["admit_seconds"] += time.perf_counter() - t0
        for name in _ADMIT_PHASES:
            st["admit_" + name + "_seconds"] += acc[name]
        for name in acc.keys() - set(_ADMIT_PHASES):
            st[name] += acc[name]
        queued_at = self._queued_at.pop(req["rid"], None)
        if queued_at is not None:
            st["queued_admissions"] += 1
            st["queue_wait_seconds"] += t0 - queued_at
        return True

    def _admit(self, req, slot, acc):
        """_try_admit's body, into the free `slot`; `acc` tallies this
        attempt's phases."""
        import paddle_tpu as paddle

        # ---- adapter residency: the request's adapter must hold a pack
        # slot before prefill (adapted projections feed the K/V it pours).
        # Transient slot exhaustion — every slot serving in-flight
        # requests — queues exactly like pool exhaustion.
        ad_slot = 0
        if self._pack is not None and req.get("adapter") is not None:
            ad_slot = self._try_install(req["adapter"])
            if ad_slot is None:
                return False
        prompt = req["prompt"]
        s0 = prompt.shape[1]
        bs = self.block_size
        # ---- prefix match: reference cached pages instead of prefilling.
        # Capped at (s0-1)//bs full blocks so at least one suffix token
        # always prefills — that forward produces the first-token logits.
        # Adapter engines namespace the walk by (slot, epoch): tenants
        # sharing a prompt under one adapter share pages, other adapters
        # (different K/V!) never cross-match, and a swapped slot's bumped
        # epoch makes its old subtree unmatchable.
        ns = ((ad_slot, self._slot_epochs[ad_slot])
              if self._pack is not None else None)
        toks = matched = None
        with _admit_phase("match", acc):
            if self._prefix is not None:
                # token list cached across retries (the prompt is
                # immutable); the match itself re-walks each attempt on
                # purpose — the LRU touch keeps a waiting request's pages
                # warm for its retry instead of letting pressure evict them
                toks = req.setdefault("toks", [int(t) for t in prompt[0]])
                matched = self._prefix.match(
                    toks, max_blocks=(s0 - 1) // bs, ns=ns)
                for b in matched:
                    self._ref[b] += 1
            matched = matched or []
            try:
                fresh = self._alloc(req["n_blocks"] - len(matched))
            except _PoolExhausted:
                self._unref(matched)
                return False
            blocks = matched + fresh
            m_len = len(matched) * bs
            model = self.model
            # the atomic base-model prefill runs as ONE compiled program
            # per (suffix bucket, prefix length); what stays eager is
            # selected on what this attempt observes: a suffix longer than
            # prefill_chunk (chunked prefill), and an adapter request (its
            # forward-post hooks close over the pack's arrays, which a
            # trace would freeze).  Interleaved prefill never comes here.
            compiled = not ad_slot and (
                self.prefill_chunk is None
                or s0 - m_len <= self.prefill_chunk)
            try:
                if not compiled:
                    caches = self._prefix_or_empty(
                        self._spec, self._pools, matched, m_len)
                elif m_len:
                    prefix = [tuple(t._value for t in layer) for layer in
                              self._gather_prefix(
                                  self._spec, self._pools, matched, m_len)]
                else:
                    prefix = None    # no 2N empty tensors for a program
            except BaseException:
                self._back_out(fresh, matched)
                raise
        try:
            # adapter requests prefill THROUGH their adapter: forward-post
            # hooks add each target projection's (x A)(B) s delta, so the
            # poured K/V matches what the adapted model would cache
            # (slot 0 installs no hooks — exact base-model prefill)
            if self._pack is not None and ad_slot:
                from paddle_tpu.nn.lora import adapter_prefill_scope

                prefill_ctx = adapter_prefill_scope(
                    self._contract.adapter_layers(), self._pack, ad_slot)
            else:
                prefill_ctx = contextlib.nullcontext()
            with prefill_ctx, paddle.no_grad():
                with _admit_phase("prefill", acc):
                    if compiled:
                        logits_last, new_blocks, aux = self._prefill_compiled(
                            prompt, prefix, m_len, acc)
                    else:
                        aux = {}
                        ops0 = _autograd.funnel_calls()
                        h, caches = self._prefill_suffix(prompt, caches,
                                                         m_len)
                        acc["admit_eager_ops"] = (_autograd.funnel_calls()
                                                  - ops0)
                        acc["prefill_eager_fallbacks"] = 1
                # the read-back is the admission's one device sync: the
                # host waits here for everything the prefill enqueued
                with _admit_phase("first_token", acc):
                    if not compiled:
                        logits_last = self._contract.logits(
                            h[:, -1:, :])._value[0, -1, :]
                    first = int(np.asarray(jnp.argmax(logits_last)))
                    # what the device counted (the contract's aux) came
                    # with the same program: no second wait
                    acc.update({k: int(v) for k, v in
                                jax.device_get(aux).items()})

            # pour the suffix's cache rows into this request's exclusive
            # pages (matched prefix pages are shared and immutable)
            with _admit_phase("pour", acc):
                if not compiled:
                    new_blocks = _cache_blocks(self._spec, caches, m_len, s0,
                                               bs)
                self._pour(self._pools, new_blocks, fresh,
                           sharding=self._pool_sharding,
                           rings=self._slot_rings(self._slots.index(slot)))
            if self.draft_model is not None:
                # draft prefill over the same suffix into the draft pools
                # (cached pages were poured to BOTH pool sets at insert
                # time, so a matched prefix covers the draft too)
                with _admit_phase("prefill", acc), paddle.no_grad():
                    d_caches = self._prefix_or_empty(
                        self._d_spec, self._d_pools, matched, m_len)
                    _, d_caches, _ = self._d_contract.forward_cached(
                        paddle.to_tensor(prompt[:, m_len:]), d_caches, m_len)
                with _admit_phase("pour", acc):
                    self._pour(self._d_pools,
                               _cache_blocks(self._d_spec, d_caches, m_len,
                                             s0, bs),
                               fresh, sharding=self._d_pool_sharding)
                slot.d_seq_len = s0
        except BaseException:
            self._back_out(fresh, matched)
            raise

        slot.rid = req["rid"]
        slot.active = True
        slot.seq_len = s0
        slot.max_len = req["max_len"]
        slot.blocks = blocks
        slot.adapter_slot = ad_slot
        slot.priority = req.get("pri", _PRIORITY["normal"])
        slot.req = req
        slot.prefill = None
        if self._pack is not None:
            # in-flight reference pins the adapter slot: LRU install and
            # evict_adapter both refuse referenced slots
            self._slot_refs[ad_slot] += 1
            self._touch_slot(ad_slot)
        slot.temperature = req["temperature"]
        # seed folded with the submit-time nonce: same-seed requests get
        # distinct streams and retries reproduce them
        slot.key = np.asarray(
            jax.random.fold_in(jax.random.PRNGKey(req["seed"]),
                               req["nonce"]))
        if slot.temperature > 0.0:
            # re-pick the FIRST token by sampling (prefill used argmax);
            # fold index 0 = this request's first generated token
            lg = logits_last.astype(jnp.float32) / slot.temperature
            key = jax.random.fold_in(jnp.asarray(slot.key), 0)
            first = int(np.asarray(jax.random.categorical(key, lg)))
        slot.last_token = first
        slot.generated = [first]
        self._results[slot.rid] = slot.generated
        if self._prefix is not None:
            # full prompt blocks become shared pages for future requests
            # (matched nodes just get LRU-touched); the partial tail block
            # stays request-private — the copy-on-write rule
            self._prefix.insert(toks, blocks[:s0 // bs], ns=ns)
            # hit/miss telemetry counts COMMITTED admissions only: a
            # queued-then-retried or prefill-errored attempt must not
            # inflate the avoided-prefill tokens
            if matched:
                _DECODE_STATS["prefix_hits"] += 1
                _DECODE_STATS["prefix_hit_tokens"] += m_len
            else:
                _DECODE_STATS["prefix_misses"] += 1
        _DECODE_STATS["resident_peak"] = max(
            _DECODE_STATS["resident_peak"],
            sum(1 for s in self._slots if s.active))
        _DECODE_STATS["admitted_" + _PRI_NAMES[slot.priority]] += 1
        if self.eos_token_id is not None and first == self.eos_token_id:
            self._finish(slot)
        elif slot.seq_len + 1 >= slot.max_len:
            self._finish(slot)
        return True

    def _prefill_suffix(self, prompt, caches, m_len):
        """The target model's eager forward over prompt[:, m_len:] on top
        of `caches` (the matched prefix, or empties): (hidden, caches)."""
        import paddle_tpu as paddle

        forward, s0 = self._contract.forward_cached, prompt.shape[1]
        if self.prefill_chunk is None or s0 - m_len <= self.prefill_chunk:
            return forward(paddle.to_tensor(prompt[:, m_len:]), caches,
                           m_len)[:2]
        # chunked prefill: fixed-size chunks through the cached forward
        # (bottom-right-aligned cross-length attention) cap the peak
        # activation footprint for long prompts
        off = m_len
        while off < s0:
            chunk = prompt[:, off:off + self.prefill_chunk]
            h, caches, _ = forward(paddle.to_tensor(chunk), caches, off)
            off += chunk.shape[1]
        return h, caches

    # ------------------------------------------- the prefill program
    def _prefill_bucket(self, s, m_len) -> int:
        """Padded length of an `s`-token suffix behind `m_len` prefix
        tokens: the next power of two, at least one pool block, clipped so
        that m_len + bucket stays inside the rope table.  One program per
        bucket serves every real length in it."""
        return min(max(self.block_size, 1 << (s - 1).bit_length()),
                   self._contract.max_positions - m_len)

    def _prefill_program(self, s_pad, m_len):
        """The compiled model work of one atomic admission, built once per
        (padded suffix length, prefix length) and kept in `_prefill_fns`:
        (state_vals, ids[1, s_pad], n_real, prefix_caches) ->
        (logits_last[vocab], blocks, aux).  The contract's forward over the
        suffix on top of the gathered prefix (None when m_len == 0), the
        logits of position n_real - 1 (a traced scalar: one program serves
        every real length of its bucket), every layer's new cache rows
        already in the pools' block layout (`blocks[p][i]`,
        [ceil(s_pad / bs), heads, bs, width]; a window class's as its ring,
        [ring_blocks, heads, bs, width], the prompt's last window only),
        and what the model counted.
        Right padding is invisible to the real positions under the causal
        bottom-right-aligned mask; positions at or past n_real are ZEROED
        before the blocks are shaped, so a partial block's tail (and every
        block past it) holds what the eager pour's jnp.pad leaves there —
        an int8 pool takes its per-block scale from the whole block.
        Weights enter as arguments and are bound under the trace, as in
        _build_step; the pools stay outside."""
        fn = self._prefill_fns.get((s_pad, m_len))
        if fn is not None:
            return fn
        from paddle_tpu._core.autograd import no_grad
        from paddle_tpu._core.tensor import Tensor

        contract, state, bs = self._contract, self._state, self.block_size
        spec = self._spec

        def prefill_program(state_vals, ids, n_real, prefix_caches):
            originals = [t._value for t in state]
            try:
                for t, v in zip(state, state_vals):
                    t._bind(v)
                with no_grad():
                    caches = (_empty_caches(spec) if not m_len
                              else [tuple(Tensor(t) for t in layer)
                                    for layer in prefix_caches])
                    h, caches, aux = contract.forward_cached(
                        Tensor(ids), caches, m_len, n_real=n_real)
                    last = jax.lax.dynamic_slice_in_dim(
                        h._value, n_real - 1, 1, axis=1)
                    logits_last = contract.logits(Tensor(last))._value[0, -1]
                real = (jnp.arange(m_len + s_pad)
                        < m_len + n_real)[None, :, None, None]
                # (a state class's row has no positions to zero)
                blocks = _cache_blocks(
                    spec, [tuple(t._value if c.slot_state
                                 else jnp.where(real, t._value, 0)
                                 for (c, _p), t in zip(spec.layer_pools(li),
                                                       layer))
                           for li, layer in enumerate(caches)],
                    m_len, m_len + s_pad, bs, end=m_len + n_real)
                return logits_last, blocks, aux
            finally:
                for t, v in zip(state, originals):
                    t._bind(v)

        # `jit_prefill_program` on the trace's XLA Modules line, beside
        # `jit_decode_macro_step`
        fn = self._prefill_fns[(s_pad, m_len)] = jax.jit(prefill_program)
        return fn

    def _prefill_compiled(self, prompt, prefix, m_len, acc):
        """Run the suffix prompt[:, m_len:] through its bucket's program:
        (logits_last, blocks, aux), the blocks of the padded bucket (all-zero
        past the real tokens).  Nothing is read back here."""
        suffix = prompt[:, m_len:]
        s = suffix.shape[1]
        s_pad = self._prefill_bucket(s, m_len)
        built = (s_pad, m_len) not in self._prefill_fns
        acc["prefill_programs_built"] = int(built)
        fn = self._prefill_program(s_pad, m_len)
        ids = np.zeros((1, s_pad), np.int32)
        ids[:, :s] = suffix
        args = ([t._value for t in self._state], ids, np.int32(s), prefix)
        if built:
            with startup.first_use("jit_prefill_program", (s_pad, m_len)):
                out = jax.block_until_ready(fn(*args))
        else:
            out = fn(*args)
        acc["prefill_program_calls"] = acc["admit_eager_ops"] = 1
        acc["prefill_pad_tokens"] = s_pad - s
        return out

    # ------------------------------------- interleaved prefill (PREFILLING)
    def _begin_prefill(self, req):
        """Interleaved admission, reservation half: claim a slot, adapter
        residency, prefix-cache pages, and fresh pool blocks NOW — then
        hand the prompt to the chunk scheduler.  The slot enters the
        PREFILLING state (`slot.prefill` set, `active` False: the decode
        dispatch masks the lane onto its scratch page exactly like an
        empty slot) and _advance_prefills forwards it one pool block per
        granted chunk.  Returns False — fully backed out, same contract
        as _try_admit — on transient shortage."""
        slot = next((s for s in self._slots
                     if not s.active and s.prefill is None), None)
        if slot is None:
            return False
        ad_slot = 0
        if self._pack is not None and req.get("adapter") is not None:
            ad_slot = self._try_install(req["adapter"])
            if ad_slot is None:
                return False
        prompt = req["prompt"]
        s0 = prompt.shape[1]
        bs = self.block_size
        ns = ((ad_slot, self._slot_epochs[ad_slot])
              if self._pack is not None else None)
        matched = None
        if self._prefix is not None:
            toks = req.setdefault("toks", [int(t) for t in prompt[0]])
            matched = self._prefix.match(toks, max_blocks=(s0 - 1) // bs,
                                         ns=ns)
            for b in matched:
                self._ref[b] += 1
        matched = matched or []
        try:
            fresh = self._alloc(req["n_blocks"] - len(matched))
        except _PoolExhausted:
            self._unref(matched)
            return False
        m_len = len(matched) * bs
        try:
            caches = self._prefix_or_empty(self._spec, self._pools, matched,
                                           m_len)
        except BaseException:
            self._back_out(fresh, matched)
            raise
        slot.rid = req["rid"]
        slot.blocks = matched + fresh
        slot.adapter_slot = ad_slot
        slot.priority = req.get("pri", _PRIORITY["normal"])
        slot.req = req
        if self._pack is not None:
            self._slot_refs[ad_slot] += 1
            self._touch_slot(ad_slot)
        slot.prefill = _PrefillState(
            req=req, caches=caches, matched=list(matched),
            fresh=list(fresh), off=m_len, poured=len(matched),
            since=self._macro_steps)
        return True

    def _pressure(self, slot) -> int:
        """Deadline pressure of a PREFILLING slot: class weight scaled by
        boundaries waited.  Deterministic in macro-steps — the budget
        math never consults wall clocks, so schedules (and therefore
        token streams) reproduce run-to-run."""
        st = slot.prefill
        waited = self._macro_steps - st.since
        return _PRI_WEIGHT[slot.priority] * (1 + waited)

    def _prefill_budget(self) -> int:
        """Prefill-chunk grants for THIS macro-step.  N =
        prefill_chunk_blocks while decode streams are resident (their
        inter-token latency is what the budget protects); 2N once the
        most-pressured prefill crosses _PRESSURE_ESCALATE (so a starved
        prefill still converges under decode load); unbounded (-1) when
        nothing is decoding — there is no ITL to protect, finish."""
        n = self._prefill_chunk_blocks()
        if not any(s.active for s in self._slots):
            return -1
        work = [s for s in self._slots if s.prefill is not None]
        peak = max(self._pressure(s) for s in work)
        return 2 * n if peak >= _PRESSURE_ESCALATE else n

    def _advance_prefills(self):
        """Run this boundary's prefill-chunk budget: grants go to the
        most-pressured PREFILLING slot first (re-ranked per grant, so one
        long prompt cannot shadow a later HIGH admission), and requests
        whose final chunk lands activate — their rids are returned and
        their first token surfaces through this step()'s output."""
        finished = []
        if not any(s.prefill is not None for s in self._slots):
            return finished
        budget = self._prefill_budget()
        while budget != 0:
            work = [s for s in self._slots if s.prefill is not None]
            if not work:
                break
            slot = max(work, key=self._pressure)
            if self._prefill_chunk_step(slot):
                finished.append(slot.rid)
            budget -= 1
        return finished

    def _prefill_chunk_step(self, slot):
        """ONE granted chunk: forward the next pool-block-sized prompt
        span through the cached prefill path, pour any block it
        completed, and publish poured full blocks to the prefix tree so
        a mid-prefill admission can already hit them on the chunk
        boundary.  The span [off, off+bs) is a function of the prompt
        alone — never of scheduling — and each chunk keeps its own
        full-chunk attention geometry, which is why the emitted stream is
        bit-identical to an atomic engine prefilling in
        prefill_chunk=block_size chunks.  Returns True when the prompt
        completed (the slot activated)."""
        import paddle_tpu as paddle

        st = slot.prefill
        prompt = st.req["prompt"]
        s0 = prompt.shape[1]
        bs = self.block_size
        contract = self._contract
        try:
            if self._pack is not None and slot.adapter_slot:
                from paddle_tpu.nn.lora import adapter_prefill_scope

                ctx = adapter_prefill_scope(contract.adapter_layers(),
                                            self._pack, slot.adapter_slot)
            else:
                ctx = contextlib.nullcontext()
            with ctx, paddle.no_grad():
                chunk = prompt[:, st.off:st.off + bs]
                st.h, st.caches, _ = contract.forward_cached(
                    paddle.to_tensor(chunk), st.caches, st.off)
                st.off += chunk.shape[1]
            _DECODE_STATS["prefill_chunks"] += 1
            # pour freshly COMPLETED blocks as we go: per-block pour
            # writes the same bytes (and the same per-block quant scales)
            # the atomic pour batches, so the boundary is pure data
            # movement
            while st.poured < st.off // bs:
                self._pour_block(slot, st.poured)
                st.poured += 1
            if self._prefix is not None and st.poured > len(st.matched):
                ns = ((slot.adapter_slot,
                       self._slot_epochs[slot.adapter_slot])
                      if self._pack is not None else None)
                toks = st.req.setdefault(
                    "toks", [int(t) for t in prompt[0]])
                self._prefix.insert(toks[:st.poured * bs],
                                    slot.blocks[:st.poured], ns=ns)
            if st.off < s0:
                return False
            self._finish_prefill(slot)
            return True
        except BaseException:
            # back out like _try_admit: the request is forfeit, the
            # allocator/slot are restored (tree-held poured pages stay
            # cached — they are complete, valid blocks)
            self._cancel_prefill(slot)
            raise

    def _pour_block(self, slot, j):
        """Pour ONE completed prompt block (tokens [j*bs, (j+1)*bs)) from
        the naive prefill caches into the slot's j-th pool page — the
        chunked entry (ops.paged_attention.paged_pour_block)."""
        from paddle_tpu.ops import paged_attention as pa

        bs = self.block_size
        st = slot.prefill
        lo = j * bs
        b = slot.blocks[j]
        for li, layer in enumerate(st.caches):
            for pools, t in zip(self._pools, layer):
                rows = jnp.moveaxis(t._value, 1, 2)[0, :, lo:lo + bs]
                pools[li] = self._place_pool(      # rows: [heads, bs, width]
                    pa.paged_pour_block(pools[li], rows, b),
                    self._pool_sharding)

    def _finish_prefill(self, slot):
        """Last chunk landed: pour the remainder (the partial tail block
        plus zero-padded future decode pages — exactly the atomic pour's
        coverage from the same offset), derive the first token from the
        final chunk's logits, and activate the slot.  Mirrors
        _try_admit's commit tail."""
        import paddle_tpu as paddle

        st = slot.prefill
        req = st.req
        prompt = req["prompt"]
        s0 = prompt.shape[1]
        bs = self.block_size
        with paddle.no_grad():
            logits_last = self._contract.logits(
                st.h[:, -1:, :])._value[0, -1, :]
        first = int(np.asarray(jnp.argmax(logits_last)))
        self._pour(self._pools,
                   _cache_blocks(self._spec, st.caches, st.poured * bs, s0,
                                 bs),
                   slot.blocks[st.poured:], sharding=self._pool_sharding)
        _DECODE_STATS["prefill_eager_fallbacks"] += 1
        slot.active = True
        slot.prefill = None
        slot.seq_len = s0
        slot.max_len = req["max_len"]
        slot.temperature = req["temperature"]
        slot.d_seq_len = 0
        slot.key = np.asarray(
            jax.random.fold_in(jax.random.PRNGKey(req["seed"]),
                               req["nonce"]))
        if slot.temperature > 0.0:
            lg = logits_last.astype(jnp.float32) / slot.temperature
            key = jax.random.fold_in(jnp.asarray(slot.key), 0)
            first = int(np.asarray(jax.random.categorical(key, lg)))
        slot.last_token = first
        slot.generated = [first]
        self._results[slot.rid] = slot.generated
        if self._prefix is not None:
            ns = ((slot.adapter_slot, self._slot_epochs[slot.adapter_slot])
                  if self._pack is not None else None)
            toks = req.setdefault("toks", [int(t) for t in prompt[0]])
            self._prefix.insert(toks, slot.blocks[:s0 // bs], ns=ns)
            if st.matched:
                _DECODE_STATS["prefix_hits"] += 1
                _DECODE_STATS["prefix_hit_tokens"] += len(st.matched) * bs
            else:
                _DECODE_STATS["prefix_misses"] += 1
        _DECODE_STATS["resident_peak"] = max(
            _DECODE_STATS["resident_peak"],
            sum(1 for s in self._slots if s.active))
        _DECODE_STATS["admitted_" + _PRI_NAMES[slot.priority]] += 1
        if self.eos_token_id is not None and first == self.eos_token_id:
            self._finish(slot)
        elif slot.seq_len + 1 >= slot.max_len:
            self._finish(slot)

    def _cancel_prefill(self, slot, requeue=False):
        """Back a PREFILLING slot out: references released through _unref
        (never a direct free — incremental inserts may have handed poured
        pages to the prefix tree, where they stay as reclaimable cached
        pages), the slot cleared.  With requeue=True the original
        submission returns to the queue — re-prefill is deterministic
        (same spans, same bytes), so demotion costs work, never
        correctness."""
        st = slot.prefill
        self._unref(st.fresh)
        self._unref(st.matched)
        if self._pack is not None:
            self._slot_refs[slot.adapter_slot] -= 1
        slot.adapter_slot = 0
        slot.blocks = []
        slot.rid = None
        slot.req = None
        slot.prefill = None
        if requeue:
            self._pending.append(st.req)

    # ------------------------------------------- preemption (parking lot)
    def _preempt_one(self):
        """Evict one LOW-priority resident to unblock a higher-class
        admission.  ACTIVE LOWs park: their pool pages ship host-side
        (serving/snapshot.py park_request_state) and the stream resumes
        bit-identically on re-admission.  PREFILLING LOWs demote back to
        the queue instead — their progress is re-derivable, their pages
        are not yet a stream.  Returns True when something was evicted."""
        if self.draft_model is not None:
            return False
        if not _flags.flag("FLAGS_preempt_low_priority"):
            return False
        if not self._spec.kv_pair:
            # the parking lot (serving/snapshot.py) holds K/V pages only:
            # on other pools a LOW resident is never preempted, the higher
            # class waits for a slot as it does with the flag off
            return False
        victims = [s for s in self._slots
                   if s.active and s.priority >= _PRIORITY["low"]
                   and s.adapter_slot == 0 and s.req is not None]
        if victims:
            # least progress lost first; slot index breaks ties so the
            # choice is deterministic
            v = min(victims,
                    key=lambda s: (len(s.generated), self._slots.index(s)))
            self._park_request(v)
            return True
        pf = [s for s in self._slots
              if s.prefill is not None and s.priority >= _PRIORITY["low"]]
        if pf:
            self._cancel_prefill(pf[0], requeue=True)
            _DECODE_STATS["preemptions"] += 1
            return True
        return False

    def _park_request(self, slot):
        """Preempt an ACTIVE request: its per-request state (slot fields,
        emitted tokens, nonce-derived key) plus its pool pages — verbatim
        pool-native bytes, the same wire face the cluster ships — move to
        the host-side parking lot, and its pool blocks free NOW."""
        from paddle_tpu.serving.snapshot import park_request_state

        rec = park_request_state(self, slot)
        self._parked[slot.rid] = rec
        self._release(slot)
        _DECODE_STATS["preemptions"] += 1
        _DECODE_STATS["parked_requests"] = len(self._parked)

    def _try_unpark(self, rid):
        """Re-admit a parked request: fresh pool blocks, pages placed
        VERBATIM (pool_set_blocks — ship-then-place is bit-exact by
        construction, never a re-quantization), slot state restored.
        The resumed stream continues token-for-token where it parked:
        the sampling key is (seed, nonce) and the per-step fold index is
        len(generated), both request identity.  Returns False on
        transient shortage (slot or pool), leaving the record parked."""
        from paddle_tpu.serving.snapshot import unpark_request_state

        rec = self._parked[rid]
        slot = next((s for s in self._slots
                     if not s.active and s.prefill is None), None)
        if slot is None:
            return False
        if not unpark_request_state(self, slot, rec):
            return False
        del self._parked[rid]
        # live streams alias their slot's generated list — the same
        # invariant _try_admit establishes
        self._results[rid] = slot.generated
        _DECODE_STATS["preempt_readmits"] += 1
        _DECODE_STATS["parked_requests"] = len(self._parked)
        _DECODE_STATS["resident_peak"] = max(
            _DECODE_STATS["resident_peak"],
            sum(1 for s in self._slots if s.active))
        return True

    def _prefix_or_empty(self, spec, pools, matched, m_len):
        """Naive-cache seed for a suffix prefill: the matched prefix
        gathered out of `pools`, or length-0 empties, shaped by the cache
        specification.  One builder for the main and draft pools so their
        prefix-gather contracts cannot drift apart."""
        if m_len:
            return self._gather_prefix(spec, pools, matched, m_len)
        return _empty_caches(spec)

    def _gather_prefix(self, spec, pools, blocks, length):
        """Materialize a matched prefix's cache rows as naive-cache
        Tensors (`caches[layer][p]`: [1, L, heads, width]): the suffix
        prefill attends these through the same cross-length path chunked
        prefill uses.  Quantized pools dequantize here — gather-side
        dequant, exactly as the decode step does."""
        from paddle_tpu._core.tensor import Tensor
        from paddle_tpu.ops import paged_attention as pa

        tables = jnp.asarray(np.asarray(blocks, np.int32)[None])
        return [tuple(
            Tensor(jnp.moveaxis(                     # [1, heads, L, width]
                pa.paged_gather(per_layer[li], tables)[:, :, :length], 1, 2
            ).astype(ps.dtype))
            for ps, per_layer in zip(spec.pools, pools))
            for li in range(spec.n_layers)]

    def _slot_rings(self, slot):
        """Per pool of the specification (`self._pools` order): the ring
        pages of slot number `slot` in a window class's pools (a state
        class's one row), None for a paged class's."""
        return [None if t is None else t[slot]
                for c, t in zip(self._spec.classes, self._ring_pages)
                for _p in c.pools]

    def _pour(self, pools, blocks, pages, sharding=None, rings=None):
        """Scatter blocks (`_cache_blocks` layout, `blocks[p][i]`) into
        `pages`, a request's exclusively owned pool pages in order: one
        `paged_pour_blocks` per pool.  Pages past the given blocks — the
        request's future decode pages — are poured with zeros, which on a
        quantized pool also resets a recycled page's stale scale.  A
        window class's blocks are its whole ring and go to `rings[p]`, the
        slot's ring pages (`_slot_rings`), never to `pages`: a ring is not
        allocated by request.  A state class's block is the slot's one row,
        poured the same way (a reused slot starts from its own prompt's
        state).  A stacked class's pool takes all its layers' blocks in one
        pour (`_pour_stacked_blocks`)."""
        # on the device ONCE, then shared by every pour (a numpy index would
        # be transferred again by each of the pools x layers calls)
        pages = jnp.asarray(pages, jnp.int32)
        rings = [None if r is None else jnp.asarray(r) for r in rings or ()]
        stacked = ([c.stacked for c in self._spec.classes for _p in c.pools]
                   if pools is self._pools else [False] * len(pools))
        for q, (per_layer, new) in enumerate(zip(pools, blocks)):
            idx = pages if not rings or rings[q] is None else rings[q]
            if stacked[q]:                 # one array, the layers in front
                per_layer[0] = _pour_stacked_blocks(per_layer[0], list(new), idx)
                continue
            for li in range(len(per_layer)):
                # placed: the pool stays committed to its head-sharded
                # layout, so the decode executable's input shardings stay
                # stable
                per_layer[li] = self._place_pool(
                    _pour_new_blocks(per_layer[li], new[li], idx), sharding)

    def _finish(self, slot):
        _DECODE_STATS["completed_" + _PRI_NAMES[slot.priority]] += 1
        self._results[slot.rid] = list(slot.generated)
        self._release(slot)

    def adopt_pages(self, prompt_ids, k_blocks, v_blocks, ns=None):
        """Adopt externally prefilled KV pages (a prefill worker's
        shipment — serving/cluster.py) as CACHED prefix pages: pool-native
        page bytes (`ops.paged_attention.pool_get_blocks` dicts, one per
        layer) land verbatim in freshly taken pool blocks, and the prompt's
        full-block chunks enter the radix prefix tree refcount-ZERO —
        resident, reclaimable, and matched by the next `add_request` for
        this prompt exactly like locally cached pages.  Shipping is
        DETERMINISTIC: a prefill worker pours through the same
        `paged_pour_blocks` math over the same full-block forward, so a
        re-dispatched request adopts byte-identical pages and its stream
        is the one the first dispatch would have produced — the cluster's
        bit-exact fail-over contract.  (Versus a purely local prefill of
        the WHOLE prompt, page bytes can differ at XLA reassociation
        level ~1e-9: the forward spans differ, so shape-dependent tiling
        may reassociate — which is why the cluster contract compares
        cluster runs to cluster runs, docs/SERVING_CLUSTER.md.)

        `ns` is the sender's (slot, epoch) adapter namespace — the pack
        slot whose weights poured these pages, pinned at SHIP time.  On
        an adapter engine the pages land in exactly that prefix-cache
        namespace, so a tenant admission under the same adapter matches
        them and other tenants (different K/V!) never cross-match.  A
        STALE epoch — the slot was re-registered/evicted between ship and
        adoption, so this engine no longer serves those weights — drops
        the shipment loudly (lora_stats()["ship_ns_drops"], return 0)
        instead of caching K/V no admission should ever match.  ns=None
        on an adapter engine means the base model: slot 0's namespace,
        whose epoch never moves (slot 0 is the reserved identity).

        Best-effort by contract: pool pressure (after LRU reclaim) or an
        already-cached prefix simply adopts fewer (possibly zero) blocks
        and returns that count — shipping is an optimization; admission
        always works without it.  Geometry mismatches raise."""
        self._require_kv("cluster page shipping (adopt_pages)")
        if self._prefix is None:
            raise RuntimeError(
                "adopt_pages needs the prefix cache: shipped pages are "
                "delivered AS cached prefixes (build the engine with "
                "prefix_cache=True; docs/SERVING_CLUSTER.md)")
        if self.draft_model is not None:
            raise RuntimeError(
                "adopt_pages on a speculative engine is not supported: "
                "shipped pages cover the target pools only, and a "
                "draft-pool-less prefix would desynchronize d_seq_len")
        if self._pack is None:
            if ns is not None:
                raise ValueError(
                    "adopt_pages got adapter namespace ns="
                    f"{tuple(ns)} but this engine was built without "
                    "adapters= — adapter-poured K/V must never enter a "
                    "base engine's un-namespaced prefix cache")
        else:
            slot, epoch = (0, self._slot_epochs[0]) if ns is None \
                else (int(ns[0]), int(ns[1]))
            if not 0 <= slot < self._pack.num_slots:
                raise ValueError(
                    f"adopt_pages namespace slot {slot} out of range "
                    f"[0, {self._pack.num_slots}) for this engine's pack")
            if epoch != self._slot_epochs[slot]:
                # pinned at ship time, stale at adoption: the slot was
                # re-registered (or its tenant evicted) in between, so
                # these pages hold K/V of weights this engine no longer
                # serves — strand them loudly, never cache them
                _LORA_STATS["ship_ns_drops"] += 1
                return 0
            ns = (slot, epoch)
        if len(k_blocks) != self._n_layers or len(v_blocks) != self._n_layers:
            raise ValueError(
                f"shipped pages cover {len(k_blocks)}/{len(v_blocks)} "
                f"layers; this engine has {self._n_layers}")
        bs = self.block_size
        n_wire = int(np.asarray(k_blocks[0]["payload"]).shape[0])
        toks = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        n = min(n_wire, len(toks) // bs)
        from paddle_tpu.ops import paged_attention as pa

        want_leaves = {name for name, _a in pa.pool_parts(self._pools[0][0])}
        for li in range(self._n_layers):
            for leaves in (k_blocks[li], v_blocks[li]):
                if set(leaves) != want_leaves:
                    # a kind mismatch (bf16 pages into an int8 pool or
                    # vice versa) must be THIS error, not a KeyError deep
                    # in pool_set_blocks — the sender quantized for the
                    # wrong pool kind and retrying cannot help
                    raise ValueError(
                        f"shipped page leaves {sorted(leaves)} != pool "
                        f"kind {sorted(want_leaves)} (layer {li}; "
                        f"kv_cache_dtype mismatch between sender and "
                        "this engine?)")
                got = tuple(np.asarray(leaves["payload"]).shape[1:])
                want = (self._spec.pools[0].heads, bs,
                        self._spec.pools[0].width)
                if got != want:
                    raise ValueError(
                        f"shipped page geometry {got} != pool {want} "
                        f"(layer {li})")
        # only the NOVEL tail needs pool blocks: chunks the tree already
        # holds keep their existing pages (and get LRU-touched)
        matched = self._prefix.match(toks[: n * bs], ns=ns)
        start = len(matched)
        if start >= n:
            return 0
        try:
            fresh = self._alloc(n - start)
        except _PoolExhausted:
            return 0
        for b in fresh:
            self._ref[b] = 0  # cached-but-unreferenced: reclaimable
        idx = jnp.asarray(fresh, jnp.int32)
        for li in range(self._n_layers):
            kb = {name: jnp.asarray(arr)[start:n]
                  for name, arr in k_blocks[li].items()}
            vb = {name: jnp.asarray(arr)[start:n]
                  for name, arr in v_blocks[li].items()}
            self._pools[0][li] = pa.pool_set_blocks(self._pools[0][li], idx, kb)
            self._pools[1][li] = pa.pool_set_blocks(self._pools[1][li], idx, vb)
            if self._pool_sharding is not None:
                self._pools[0][li] = self._place_pool(self._pools[0][li],
                                                    self._pool_sharding)
                self._pools[1][li] = self._place_pool(self._pools[1][li],
                                                    self._pool_sharding)
        self._prefix.insert(toks[: n * bs], matched + fresh, ns=ns)
        return len(fresh)

    # ------------------------------------------------- fault tolerance
    def snapshot(self, dir, step=None) -> int:
        """Commit a restorable snapshot of this LIVE engine under `dir`
        through the CheckpointManager commit protocol (atomic rename,
        checksummed manifest, SIGKILL matrix — serving/snapshot.py,
        docs/CHECKPOINT.md serving section).  Call between step()s; the
        automatic path (maybe_snapshot) runs at macro-step boundaries
        only.  Returns the committed step tag."""
        from paddle_tpu.serving.snapshot import EngineSnapshot

        self._require_kv("the engine snapshot (snapshot / drain)")
        store = self._snapshot_store
        if store is None or store.dir != str(dir):
            # one store per engine+dir: its manifest-validity cache makes
            # the per-save retention sweep mtime-cheap instead of
            # re-hashing every retained snapshot's pool bytes
            store = self._snapshot_store = EngineSnapshot(dir)
        return store.save(self, step=step)

    def install_preemption_handler(self, signals=None):
        """SIGTERM-style preemption for serving: the handler only flips a
        flag (async signal context is no place for device syncs or disk
        IO); the next maybe_snapshot() at a macro-step boundary writes
        the final snapshot — the CheckpointManager flag-flip design on
        the serving loop.  Check `preemption_saved` to exit cleanly."""
        import signal as _signal

        if signals is None:
            signals = (_signal.SIGTERM,)

        def _handler(signum, frame):
            self._preempt_requested = True

        for s in signals:
            prev = _signal.signal(s, _handler)
            # re-install keeps the ORIGINAL disposition: recording our
            # own handler as "previous" would make uninstall a no-op and
            # strand SIGTERM on a detached engine forever
            self._prev_handlers.setdefault(s, prev)

    def uninstall_preemption_handler(self):
        import signal as _signal

        for s, prev in self._prev_handlers.items():
            _signal.signal(s, prev)
        self._prev_handlers.clear()

    @property
    def preemption_requested(self) -> bool:
        return self._preempt_requested

    @property
    def preemption_saved(self) -> bool:
        """True once a preemption-triggered snapshot has been committed."""
        return self._preempt_saved

    def maybe_snapshot(self, dir=None, step=None):
        """Snapshot when due — a pending preemption flag, or the periodic
        FLAGS_engine_snapshot_interval macro-step boundary.  step() calls
        this at the END of every macro-step when FLAGS_engine_snapshot_dir
        is set, so snapshots land at boundaries and never mid-dispatch.
        Returns the committed step tag, or None when nothing was due."""
        if self._draining:
            # the drain snapshot IS the handoff state: lame-duck stepping
            # after drain() must not overwrite it (or worse, push it out
            # of retention) with post-handoff boundaries
            return None
        d = dir if dir is not None else _flags.flag("FLAGS_engine_snapshot_dir")
        if not d:
            return None
        due = self._preempt_requested and not self._preempt_saved
        if not due:
            # N boundaries since the last periodic save (not a modulo of
            # the counter: idle boundaries call in without advancing it,
            # and must not re-save the same state every call)
            interval = int(_flags.flag("FLAGS_engine_snapshot_interval"))
            due = (interval > 0 and self._macro_steps > 0
                   and self._macro_steps - self._last_auto_snapshot
                   >= interval)
        if not due:
            return None
        st = self.snapshot(d, step=step)
        self._last_auto_snapshot = self._macro_steps
        if self._preempt_requested:
            self._preempt_saved = True
        return st

    def drain(self, dir=None, step=None) -> int:
        """The migration / elastic-scale-down primitive: commit a final
        snapshot (resident requests, queued admissions, caches, adapter
        state — everything) and CLOSE admissions on this engine.  Returns
        the snapshot step to hand off; `restore_engine` rebuilds a fully
        open engine from it on another process/host/topology.  The
        drained engine may keep stepping its RESIDENTS to completion —
        it never admits again (add_request raises, and the queued
        requests in the snapshot are the restore target's to serve, so
        the lame duck neither admits nor counts them as work; automatic
        maybe_snapshot is disarmed too, so post-handoff boundaries can
        never overwrite or age out the handoff snapshot)."""
        if self._draining and self._drain_step is not None:
            # idempotent: a re-drain (an orchestrator retrying a timed-out
            # handoff) returns the ALREADY-committed handoff step — a
            # second snapshot here would capture lame-duck progress and
            # hand the restore target different state per retry.  Only
            # for the SAME directory: returning a step that does not
            # exist under a new dir would send the restore target to a
            # missing snapshot while the caller believes it committed.
            if dir is not None and str(dir) != self._drain_dir:
                raise ValueError(
                    f"engine already drained to {self._drain_dir!r} "
                    f"(step {self._drain_step}); a re-drain to {dir!r} "
                    "cannot re-capture the handoff state — restore from "
                    "the original directory")
            return self._drain_step
        d = dir if dir is not None else _flags.flag("FLAGS_engine_snapshot_dir")
        if not d:
            raise ValueError(
                "drain() needs a snapshot directory: pass dir= or set "
                "FLAGS_engine_snapshot_dir")
        # in-flight overload-discipline state is the restore target's to
        # serve: PREFILLING slots and parked (preempted) requests demote
        # to queued submissions BEFORE the snapshot — they ride it as
        # pending and replay deterministically from (seed, nonce) on the
        # restored engine (re-prefill spans and pours are identical)
        for s in self._slots:
            if s.prefill is not None:
                self._cancel_prefill(s, requeue=True)
        for rid in list(self._parked):
            self._pending.append(self._parked.pop(rid)["req"])
        _DECODE_STATS["parked_requests"] = len(self._parked)
        self._draining = True
        self._drain_dir = str(d)
        st = self._drain_step = self.snapshot(d, step=step)
        from paddle_tpu.serving.snapshot import _SNAPSHOT_STATS

        _SNAPSHOT_STATS["drains"] += 1
        return st

    # -------------------------------------------------------------- decode
    def _effective_chunk(self) -> int:
        if self._decode_chunk is not None:
            return self._decode_chunk
        return max(1, int(_flags.flag("FLAGS_decode_chunk")))

    def _build_step(self, chunk: int):
        """One macro-step executable: `chunk` decode tokens per dispatch.

        The single-token step rides a lax.scan INSIDE the jit (pools
        donated), emitting [B, chunk] tokens per dispatch — one host
        round-trip and one device sync amortize over the whole chunk.
        Rows that hit a stop condition mid-chunk flip a `done` mask: their
        remaining writes land on their scratch page (never the shared
        pool) and their lens/fold counters freeze, so the live rows'
        streams stay bit-identical to the per-token path while the host
        discards the masked tail after the dispatch.

        On adapter engines the step takes three extra arguments — the
        per-row slot vector and the pack's A/B + scaling arrays — and
        every decoder layer adds the gathered per-row LoRA delta, so a
        batch mixing tenants (and base rows at slot 0) decodes in this
        one program; swaps change argument VALUES only, never shapes, so
        the executable is reused across them."""
        from paddle_tpu._core.autograd import no_grad

        contract = self._contract
        state = self._state
        eos = self.eos_token_id
        has_pack = self._pack is not None

        per_class = self._spec.per_class_tables

        def decode_macro_step(state_vals, pools, tokens, tables,
                              scratch_tables, lens, max_lens, done0, temps,
                              keys, steps, *more):
            # a specification of several cache classes, or of a window
            # class, brings one table a class after the block table's own
            # arguments (None in a paged class's place): the model's
            # `tables` is then a tuple, and a ring is never read as pages
            class_tables, lora_args = ((more[:1], more[1:]) if per_class
                                       else ((), more))
            kv_only = {}
            if has_pack:
                ad_slots, pack_ab, pack_scaling = lora_args
                kv_only.update(adapters=pack_ab, slots=ad_slots,
                               scaling=jnp.take(pack_scaling, ad_slots))
            originals = [t._value for t in state]
            try:
                for t, v in zip(state, state_vals):
                    t._bind(v)
                # carry form ONCE per dispatch: a LayerStack's pools scan
                # as one stacked [N, ...] buffer each — the N-pool concat
                # is paid per dispatch, never per decoded token
                pools = contract.pool_carry(pools)

                # the body is defined INSIDE the traced step: lax.scan
                # caches body jaxprs by the body's identity, and a shared
                # body would leak one trace's bound-weight tracers into
                # the next trace
                def one(carry, _):
                    tok, pools_c, lens_c, steps_c, done = carry
                    # finished/inactive lanes park on their scratch page
                    # with lens 1 — same geometry the host gives inactive
                    # slots, so their writes never touch the shared pool
                    tables_eff = jnp.where(done[:, None], scratch_tables,
                                           tables)
                    if class_tables:
                        # a window class's table is the slots' own rings
                        # for every row: a finished or empty lane writes
                        # position 0 of a ring nobody reads until its
                        # slot's next admission pours it anew
                        tables_eff = self._class_tables(tables_eff,
                                                        class_tables[0])
                    lens_eff = jnp.where(done, jnp.int32(1), lens_c)
                    with no_grad():
                        h, pools_c, aux = contract.decode(
                            tok, pools_c, tables_eff, lens_eff,
                            active=~done, **kv_only)
                        logits = contract.logits(h)
                    lg = logits._value[:, -1, :]
                    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                    # per-slot temperature sampling inside the SAME
                    # program: fold the slot's generated-token counter
                    # into its key, sample per row, select by the mask
                    safe_t = jnp.where(temps > 0, temps, 1.0)[:, None]
                    skeys = jax.vmap(jax.random.fold_in)(keys, steps_c)
                    sampled = jax.vmap(jax.random.categorical)(
                        skeys, lg.astype(jnp.float32) / safe_t
                    ).astype(jnp.int32)
                    nxt = jnp.where(temps > 0, sampled, greedy)
                    # mirror of the host stop conditions: EOS, or the
                    # sequence (now lens_c long) leaving no room for one
                    # more token within max_len
                    fin = ((nxt == eos) if eos is not None
                           else jnp.zeros_like(done))
                    new_done = done | fin | (lens_c + 1 >= max_lens)
                    lens_n = jnp.where(done, lens_c, lens_c + 1)
                    steps_n = jnp.where(done, steps_c, steps_c + 1)
                    return (nxt[:, None], pools_c, lens_n, steps_n,
                            new_done), (nxt, aux)

                (tok, pools, *_), (toks, aux) = jax.lax.scan(
                    one, (tokens, pools, lens, steps, done0),
                    None, length=chunk)
                # what the model counted on the device (the contract's
                # aux; {} for most), summed over the chunk: it rides back
                # with the tokens, read at the step's one sync
                aux = {k: jnp.sum(v) for k, v in aux.items()}
                return (jnp.moveaxis(toks, 0, 1),
                        contract.pool_unpack(pools), aux)
            finally:
                for t, v in zip(state, originals):
                    t._bind(v)

        # the function's name is the program's: `jit_decode_macro_step` on
        # the trace's XLA Modules line and in PjitFunction(...) host events
        return jax.jit(decode_macro_step, donate_argnums=(1,))

    def _step_avals(self):
        """ShapeDtypeStruct mirror of step()'s exact dispatch signature,
        in argument order.  Device-resident inputs (weights, pools, the
        scratch tables, adapter pack arrays) carry their live shardings so
        an AOT-compiled executable accepts the real committed arrays;
        host-built inputs (tokens/tables/lens/...) are plain avals.  The
        signature is geometry-pure — max_batch, blocks-per-seq, pool
        shapes, pack shape — so two engines built from the same recorded
        geometry produce identical avals (what lets a warm standby carry
        its compiled steps onto a snapshot-restored engine)."""
        def arr_aval(v):
            return jax.ShapeDtypeStruct(v.shape, v.dtype,
                                        sharding=getattr(v, "sharding",
                                                         None))

        B, W = self.max_batch, self._max_blocks_per_seq
        avals = (
            [arr_aval(t._value) for t in self._state],
            jax.tree_util.tree_map(arr_aval, [list(p) for p in self._pools]),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),     # tokens
            jax.ShapeDtypeStruct((B, W), jnp.int32),     # tables
            arr_aval(self._scratch_tables),
            jax.ShapeDtypeStruct((B,), jnp.int32),       # lens
            jax.ShapeDtypeStruct((B,), jnp.int32),       # max_lens
            jax.ShapeDtypeStruct((B,), jnp.bool_),       # done0
            jax.ShapeDtypeStruct((B,), jnp.float32),     # temps
            jax.ShapeDtypeStruct((B, 2), jnp.uint32),    # keys
            jax.ShapeDtypeStruct((B,), jnp.uint32),      # steps
        )
        if self._spec.per_class_tables:
            avals += ([None if t is None else arr_aval(t)
                       for t in self._ring_tables],)
        if self._pack is not None:
            avals += (jax.ShapeDtypeStruct((B,), jnp.int32),
                      jax.tree_util.tree_map(arr_aval, self._pack.ab),
                      jax.tree_util.tree_map(arr_aval, self._pack.scaling))
        return avals

    def warmup(self, chunks=None, *, prefill=True, adopt=True):
        """Pay trace + XLA compile for this engine's hot executables
        before traffic — the serving analogue of jit.TrainStep.warmup.
        No step runs: the macro-step is lowered from ShapeDtypeStructs
        (state, pools, and host inputs as avals), compiled, and stored in
        the same `_step_fns` table step() consults, so the first real
        dispatch runs a ready executable instead of compiling on the
        serving critical path.  With FLAGS_compilation_cache_dir set the
        compile itself deserializes from the persistent cache — a
        respawned cluster worker warms up in cache-hit time before
        announcing readiness (serving/cluster_worker.py).

        `chunks` lists decode chunk widths to compile (default: the
        effective chunk).  There is no separate tail program to warm: the
        macro-step's done-mask design parks rows that finish mid-chunk on
        their scratch pages in-device, so the one D-token executable IS
        the tail executable.  `prefill=True` additionally builds (and
        runs once, on a dummy prompt) the admission prefill program of a
        single-block prompt over no prefix: programs are keyed by the
        suffix length rounded up to a power of two (at least one block)
        and the matched prefix length, so this readies every admission of
        at most block_size tokens; longer buckets still compile lazily,
        once each, at their first admission.  `adopt=True` round-trips
        one scratch page through pool_get_blocks/pool_set_blocks — the
        page-shipping adopt path's gather/scatter programs.

        Speculative engines skip the macro-step warm (they dispatch
        draft/verify programs, not `_step_fns`); prefill/adopt warming
        still applies where supported.  Returns
        {"chunks": [warmed widths], "seconds": wall}."""
        t0 = time.perf_counter()
        warmed: list = []
        if self.draft_model is None:
            todo = sorted({int(c) for c in (
                chunks if chunks is not None else [self._effective_chunk()])})
            for D in todo:
                if D < 1:
                    raise ValueError("decode chunk widths must be >= 1")
                if D not in self._step_fns:
                    with startup.first_use("jit_decode_macro_step", D):
                        self._step_fns[D] = (self._build_step(D)
                                             .lower(*self._step_avals())
                                             .compile())
                warmed.append(D)
        if prefill:
            s_pad = self._prefill_bucket(self.block_size, 0)
            built = (s_pad, 0) not in self._prefill_fns
            with (startup.first_use("jit_prefill_program", (s_pad, 0))
                  if built else contextlib.nullcontext()):
                jax.block_until_ready(self._prefill_program(s_pad, 0)(
                    [t._value for t in self._state],
                    np.zeros((1, s_pad), np.int32), np.int32(self.block_size),
                    None))
        if adopt and self._prefix is not None and self.draft_model is None \
                and self._pack is None:
            from paddle_tpu.ops import paged_attention as pa

            # one scratch page through the ship-adoption gather/scatter:
            # scratch contents are garbage by design (masked lanes write
            # there), and the poured-back pool is DISCARDED — only the
            # compiled programs persist
            idx = jnp.asarray([self._scratch[0]], jnp.int32)
            for pool in (pools[0] for pools in self._pools):
                leaves = pa.pool_get_blocks(pool, idx)
                pa.pool_set_blocks(pool, idx, dict(leaves))
        return {"chunks": warmed, "seconds": time.perf_counter() - t0}

    def _build_draft_step(self):
        from paddle_tpu._core.autograd import no_grad

        contract = self._d_contract
        state = self._d_state

        def draft_step(state_vals, kpools, vpools, tokens, tables, lens):
            originals = [t._value for t in state]
            try:
                for t, v in zip(state, state_vals):
                    t._bind(v)
                with no_grad():
                    h, (new_k, new_v), _ = contract.decode(
                        tokens, [kpools, vpools], tables, lens)
                    logits = contract.logits(h)
                return (jnp.argmax(logits._value[:, -1, :], axis=-1)
                        .astype(jnp.int32), new_k, new_v)
            finally:
                for t, v in zip(state, originals):
                    t._bind(v)

        return jax.jit(draft_step)

    def _build_verify(self):
        from paddle_tpu._core.autograd import no_grad

        contract = self._contract
        state = self._state
        has_pack = self._pack is not None

        def verify_step(state_vals, kpools, vpools, tokens, tables, lens,
                        *lora_args):
            """tokens [B, K+1]; lens INCLUDING the whole chunk; returns
            preds [B, K+1] (greedy next token after each chunk position)
            plus the written pools.  On adapter engines the extra args
            are the per-row slot vector + the pack's A/B and scaling
            (same contract as the plain macro-step): the TARGET verifies
            through each row's adapter even though the draft proposed
            with the base model, so acceptance only ever keeps tokens
            the adapted model would decode."""
            kv_only = {"chunk": True}
            if has_pack:
                ad_slots, pack_ab, pack_scaling = lora_args
                kv_only.update(adapters=pack_ab, slots=ad_slots,
                               scaling=jnp.take(pack_scaling, ad_slots))
            originals = [t._value for t in state]
            try:
                for t, v in zip(state, state_vals):
                    t._bind(v)
                with no_grad():
                    h, (new_k, new_v), _ = contract.decode(
                        tokens, [kpools, vpools], tables, lens, **kv_only)
                    logits = contract.logits(h)
                return (jnp.argmax(logits._value, axis=-1).astype(jnp.int32),
                        new_k, new_v)
            finally:
                for t, v in zip(state, originals):
                    t._bind(v)

        return jax.jit(verify_step)

    def _spec_step(self):
        """One speculative tick: the draft proposes K tokens per live slot
        (K compiled single-token draft steps, batched over slots), the
        target verifies every chunk in ONE compiled multi-token step, and
        per-slot greedy acceptance emits 1..K+1 tokens.  Rejected tail
        entries in the pools die by lens bookkeeping — pages are
        positional, so rollback costs nothing."""
        if self._draft_fn is None:
            self._draft_fn = self._build_draft_step()
            self._verify_fn = self._build_verify()
        K = self.num_speculative
        B, W = self.max_batch, self._max_blocks_per_seq
        tables = np.zeros((B, W), np.int32)
        last = np.zeros((B, 1), np.int32)
        seq0 = np.zeros((B,), np.int32)
        d0 = np.zeros((B,), np.int32)
        ad_slots = np.zeros((B,), np.int32)
        for i, sl in enumerate(self._slots):
            if sl.active:
                row = list(sl.blocks) + [sl.blocks[-1]] * (W - len(sl.blocks))
                tables[i] = row
                last[i, 0] = sl.last_token
                seq0[i] = sl.seq_len
                d0[i] = sl.d_seq_len
                ad_slots[i] = sl.adapter_slot
            else:
                tables[i] = self._scratch[i]
        tables_j = jnp.asarray(tables)

        # ---- draft proposes K tokens (inactive lanes ride scratch) -----
        # K+1 draft steps: the extra step feeds the LAST proposal so the
        # draft pool always covers its own proposals — acceptance then
        # never needs a per-slot catch-up pass, whatever gets accepted
        d_state = [t._value for t in self._d_state]
        prop_dev = []
        tok = jnp.asarray(last)
        for j in range(K + 1):
            lens_d = jnp.asarray(d0 + 1 + j)
            tok1, dk, dv = self._draft_fn(
                d_state, list(self._d_pools[0]), list(self._d_pools[1]),
                tok, tables_j, lens_d)
            self._d_pools[0], self._d_pools[1] = list(dk), list(dv)
            if j < K:
                prop_dev.append(tok1)
                tok = tok1[:, None]  # stays on device: steps pipeline
        _DECODE_STATS["dispatches"] += K + 1
        t_sync = time.perf_counter()
        proposals = np.stack([np.asarray(t) for t in prop_dev], axis=1)
        _DECODE_STATS["sync_seconds"] += time.perf_counter() - t_sync

        # ---- target verifies the whole chunk in one step ---------------
        chunk = np.concatenate([last, proposals], axis=1)  # [B, K+1]
        lens_v = jnp.asarray(seq0 + K + 1)
        lora_args = ()
        if self._pack is not None:
            # the draft proposed base-model tokens; the target verifies
            # through each row's adapter (pack as ARGUMENTS — hot swaps
            # change values, never shapes, like the plain macro-step)
            lora_args = (jnp.asarray(ad_slots), self._pack.ab,
                         self._pack.scaling)
            _LORA_STATS["gather_dispatches"] += 1
        preds, nk, nv = self._verify_fn(
            [t._value for t in self._state],
            list(self._pools[0]), list(self._pools[1]),
            jnp.asarray(chunk), tables_j, lens_v, *lora_args)
        self._pools[0], self._pools[1] = list(nk), list(nv)
        _DECODE_STATS["dispatches"] += 1
        t_sync = time.perf_counter()
        preds = np.asarray(preds)  # [B, K+1]
        _DECODE_STATS["sync_seconds"] += time.perf_counter() - t_sync

        # ---- per-slot acceptance + emission ----------------------------
        self._spec_stats["ticks"] += 1
        out = {}
        for i, sl in enumerate(self._slots):
            if not sl.active:
                continue
            accepted = 0
            while accepted < K and preds[i, accepted] == proposals[i, accepted]:
                accepted += 1
            self._spec_stats["proposed"] += K
            self._spec_stats["accepted"] += accepted
            new_toks = [int(t) for t in proposals[i, :accepted]]
            new_toks.append(int(preds[i, accepted]))
            base_seq = sl.seq_len  # pre-round trusted pool coverage
            emitted = []
            finish = False
            for t in new_toks:
                emitted.append(t)
                sl.generated.append(t)
                if self.eos_token_id is not None and t == self.eos_token_id:
                    finish = True
                    break
                # total = prompt + generated = base_seq + 1 + emitted
                if base_seq + 1 + len(emitted) >= sl.max_len:
                    finish = True
                    break
            # trusted pool coverage = prompt + generated[:-1]; the draft
            # pool covers the same prefix (its stale tail dies positionally)
            sl.seq_len = base_seq + len(emitted)
            sl.d_seq_len = sl.seq_len
            sl.last_token = emitted[-1]
            out[sl.rid] = emitted
            self._spec_stats["emitted"] += len(emitted)
            if finish:
                self._finish(sl)
        return out

    def spec_stats(self):
        """Speculative acceptance counters (None on plain engines):
        mean acceptance = accepted/proposed sizes num_speculative_tokens;
        emitted/ticks is the per-tick speedup over plain decode."""
        return None if self.draft_model is None else dict(self._spec_stats)

    def step(self):
        """One macro-step for every live request: D = decode_chunk tokens
        advance in ONE compiled dispatch; requests are admitted/retired
        only here, at macro-step boundaries (stop conditions re-checked on
        the host after the dispatch; a row that stopped mid-chunk had its
        surplus lanes masked onto its scratch page in-device and its
        surplus tokens dropped now).

        Plain engines return {rid: token} when D == 1 and
        {rid: [tok, ...]} when D > 1; SPECULATIVE engines always emit a
        LIST of tokens per request per tick — one accepted run plus the
        target's correction/bonus token.  A request admitted from the
        PENDING QUEUE this step always maps to a list, led by its
        prefill-produced first token (the one add_request returned None
        instead of)."""
        with RecordEvent("serving.step"):
            return self._step()

    def _step(self):
        if not self.has_work():
            # an idle engine is still at a boundary: a pending SIGTERM
            # preemption (or an overdue interval) must commit its final
            # snapshot HERE, or a drained-empty serving loop would spin
            # until the orchestrator escalates to SIGKILL
            self.maybe_snapshot()
            return {}
        # macro-step boundary: queued admissions (pool pressure at
        # add_request time) retry before this dispatch; their prefill
        # first tokens (add_request returned None) surface in THIS
        # step's output — always as a list for those rids, even at D=1.
        # A draining engine admits NOTHING: its queue was handed off in
        # the drain snapshot and will be served by the restore target.
        with RecordEvent("serving.step.schedule"):
            admitted = [] if self._draining else self._admit_pending()
            # interleaved chunked prefill: grant this boundary's budget of
            # block-sized chunks (deadline pressure orders the PREFILLING
            # slots); prompts whose final chunk landed activate NOW and
            # their first token joins this step's output like any queued
            # admission (drain() demoted prefilling slots, so this is a
            # no-op on a lame duck)
            admitted.extend(self._advance_prefills())
        if not any(s.active for s in self._slots):
            # an admitted request may have finished AT admission
            # (EOS / max_new_tokens=1): its first token still surfaces.
            # This IS a macro-step boundary — allocator/results/pending
            # all mutated — so the counter advances and the periodic
            # snapshot interval keeps accruing across such steps
            out = {rid: list(self._results[rid]) for rid in admitted}
            self._macro_steps += 1
            self.maybe_snapshot()
            return out
        t_start = time.perf_counter()
        if self.draft_model is not None:
            if self._draft_fn is None:
                # the tick that builds and first runs BOTH speculative
                # programs (it ends in its own read-backs): one span, named
                # for the pair
                with startup.first_use("jit_draft_step+jit_verify_step",
                                       self.num_speculative):
                    out = self._spec_step()
            else:
                out = self._spec_step()
            _DECODE_STATS["tokens"] += sum(len(v) for v in out.values())
            _DECODE_STATS["macro_steps"] += 1
            _DECODE_STATS["step_seconds"] += time.perf_counter() - t_start
            # prepend AFTER the stats: prefill firsts aren't decode tokens
            self._merge_admitted(out, admitted)
            self._macro_steps += 1
            self.maybe_snapshot()  # boundary: no-op without a snapshot dir
            return out
        with RecordEvent("serving.step.dispatch"):
            D = self._effective_chunk()
            step_fn = self._step_fns.get(D)
            built = step_fn is None
            if built:
                step_fn = self._step_fns[D] = self._build_step(D)

            B, W = self.max_batch, self._max_blocks_per_seq
            tokens = np.zeros((B, 1), np.int32)
            tables = np.zeros((B, W), np.int32)
            lens = np.ones((B,), np.int32)
            max_lens = np.zeros((B,), np.int32)
            done0 = np.ones((B,), bool)
            temps = np.zeros((B,), np.float32)
            keys = np.zeros((B, 2), np.uint32)
            steps = np.zeros((B,), np.uint32)
            ad_slots = np.zeros((B,), np.int32)
            for i, s in enumerate(self._slots):
                if s.active:
                    tokens[i, 0] = s.last_token
                    row = list(s.blocks) + [s.blocks[-1]] * (W - len(s.blocks))
                    tables[i] = row
                    lens[i] = s.seq_len + 1  # includes the token being decoded
                    max_lens[i] = s.max_len
                    done0[i] = False
                    temps[i] = s.temperature
                    keys[i] = s.key
                    steps[i] = len(s.generated)  # fold index for this request
                    ad_slots[i] = s.adapter_slot
                else:
                    tables[i] = self._scratch[i]  # park masked lanes off-pool
                    lens[i] = 1

            lora_args = ()
            if self._pack is not None:
                # pack contents ride as ARGUMENTS (not closed-over constants):
                # register_adapter's scatter produces new arrays of identical
                # shape, so a swap changes values only and this same compiled
                # step serves every tenant mix
                lora_args = (jnp.asarray(ad_slots), self._pack.ab,
                             self._pack.scaling)
                _LORA_STATS["gather_dispatches"] += 1
            class_args = ((list(self._ring_tables),)
                          if self._spec.per_class_tables else ())
            args = (
                [t._value for t in self._state],
                [list(p) for p in self._pools],
                jnp.asarray(tokens), jnp.asarray(tables),
                self._scratch_tables, jnp.asarray(lens),
                jnp.asarray(max_lens), jnp.asarray(done0),
                jnp.asarray(temps), jnp.asarray(keys), jnp.asarray(steps),
                *class_args, *lora_args,
            )
            if built:
                with startup.first_use("jit_decode_macro_step", D):
                    nxt, new_pools, aux = jax.block_until_ready(
                        step_fn(*args))
            else:
                nxt, new_pools, aux = step_fn(*args)
        self._pools = [list(p) for p in new_pools]
        t_sync = time.perf_counter()
        with RecordEvent("serving.step.sync"):
            nxt = np.asarray(nxt)  # [B, D] — the one device sync per chunk
            for k, v in jax.device_get(aux).items():   # same program
                _DECODE_STATS[k] += int(v)
        _DECODE_STATS["dispatches"] += 1
        _DECODE_STATS["macro_steps"] += 1
        _DECODE_STATS["last_chunk"] = D
        _DECODE_STATS["sync_seconds"] += time.perf_counter() - t_sync

        with RecordEvent("serving.step.retire"):
            out = {}
            for i, s in enumerate(self._slots):
                if not s.active:
                    continue
                rid = s.rid  # _finish() clears the slot's rid on retirement
                emitted = []
                for j in range(D):
                    tok = int(nxt[i, j])
                    s.seq_len += 1
                    s.last_token = tok
                    s.generated.append(tok)
                    emitted.append(tok)
                    if (self.eos_token_id is not None
                            and tok == self.eos_token_id) or (
                                s.seq_len + 1 >= s.max_len):
                        self._finish(s)
                        break
                out[rid] = emitted if D > 1 else emitted[0]
                _DECODE_STATS["tokens"] += len(emitted)
        _DECODE_STATS["step_seconds"] += time.perf_counter() - t_start
        self._merge_admitted(out, admitted)
        self._macro_steps += 1
        self.maybe_snapshot()  # boundary: no-op without a snapshot dir
        return out

    def _merge_admitted(self, out, admitted):
        """Prepend queue-admitted requests' prefill first tokens to this
        step's output.  Those rids always map to a LIST (even at D=1):
        the queued-admission case is new surface, so no existing caller
        sees the shape change."""
        for rid in admitted:
            first = self._results[rid][0]
            got = out.get(rid)
            if got is None:
                out[rid] = [first]
            elif isinstance(got, list):
                out[rid] = [first] + got
            else:
                out[rid] = [first, got]


from .snapshot import (EngineSnapshot, restore_engine,  # noqa: E402
                       reset_snapshot_stats, snapshot_stats)
