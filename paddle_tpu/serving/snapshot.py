"""Live-engine snapshots: serving-tier fault tolerance (ROADMAP item 5).

Training survives SIGKILL bit-exactly (CheckpointManager); this module
gives the SERVING tier the same property.  `EngineSnapshot` captures a
live `GenerationEngine` — paged K/V pools (bf16 and int8 payload +
scales), block tables and per-block refcounts, the radix prefix-cache
tree (namespaces, epochs, LRU order), the adapter pack with slot/epoch
state, in-flight request state (emitted tokens, per-request PRNG keys,
block lists), the FIFO pending queue, and the submit-time nonce counter —
so a restored engine continues every greedy AND seeded-sampled stream
bit-identically from where the killed engine left off.

The commit rides the SAME atomic protocol as CheckpointManager
(`distributed.checkpoint.manager.commit_dir`: temp dir -> fsynced payload
-> checksummed MANIFEST.json -> one atomic rename), including the
FLAGS_checkpoint_kill_point SIGKILL matrix — crash consistency of engine
snapshots is proven mechanically by the same four kill points
(tests/test_engine_snapshot_crash.py).

Restore builds a FRESH engine from the snapshot's recorded geometry and
pours state back in.  Pool tensors load through the sharded checkpoint
store's shard records (`_assemble_region` — the reshard-on-load path), so
a snapshot taken on a single device restores onto a TP mesh and vice
versa; the mesh lint (FLAGS_verify_sharding) validates placements at
restore-time construction exactly as at normal construction.

`engine.drain()` (snapshot + stop admitting) is the migration /
elastic-scale-down primitive: the returned step restores on another host
or topology with queued requests intact (docs/CHECKPOINT.md).
"""

from __future__ import annotations

import os
import pickle
import shutil
import time

import numpy as np
import jax.numpy as jnp

from paddle_tpu.distributed.checkpoint import (_META_FILE, Metadata,
                                               build_shard_snapshot)
from paddle_tpu.distributed.checkpoint import _assemble_region, _LazyFiles
from paddle_tpu.distributed.checkpoint import manager as _ckpt

__all__ = ["EngineSnapshot", "restore_engine", "snapshot_stats",
           "reset_snapshot_stats", "park_request_state",
           "unpark_request_state"]

_UNSET = object()


# ---------------------------------------------------------------- counters
# Serving-owned (profiler.snapshot_stats() reads them — same contract as
# decode_stats): saves/restores of live engines, committed bytes, wall
# seconds spent capturing+committing, torn snapshots skipped during
# latest_step scans, and drain() calls (the migration primitive).
_SNAPSHOT_STATS = {
    "saves": 0,
    "restores": 0,
    "bytes": 0,
    "snapshot_seconds": 0.0,
    "corrupt_skipped": 0,
    "drains": 0,
}


def snapshot_stats(reset: bool = False) -> dict:
    """Live-engine snapshot counters (docs/CHECKPOINT.md serving section):
    snapshots saved and restored, bytes committed, seconds spent in
    save() (device→host capture + atomic commit), torn/corrupt snapshot
    dirs skipped while resolving latest_step, and engine drains.  Zeros
    when no engine snapshot activity this process."""
    out = dict(_SNAPSHOT_STATS)
    if reset:
        reset_snapshot_stats()
    return out


def reset_snapshot_stats():
    for k in _SNAPSHOT_STATS:
        _SNAPSHOT_STATS[k] = 0.0 if isinstance(_SNAPSHOT_STATS[k], float) else 0


# Torn dirs already counted in corrupt_skipped — PROCESS-wide, because
# engine.snapshot()/restore_engine() construct fresh EngineSnapshot
# instances per call and a kept-for-post-mortem torn dir must not bump
# the health counter again on every later resolve.
_SKIP_COUNTED: set = set()


# ----------------------------------------------------- radix tree state
def _radix_state(tree):
    """Serialize a RadixPrefixCache: DFS node list with parent indices
    (parents always precede their children), preserving each node's key —
    plain chunk tuples and adapter-namespaced ``((slot, epoch), chunk)``
    first-level keys alike — pool block, and LRU clock mark."""
    if tree is None:
        return None
    nodes = []
    stack = [(tree._root, -1)]
    while stack:
        node, pidx = stack.pop()
        if node is tree._root:
            idx = -1
        else:
            nodes.append((pidx, node.chunk, node.block, node.last_used))
            idx = len(nodes) - 1
        for child in node.children.values():
            stack.append((child, idx))
    return {"block_size": tree.block_size, "clock": tree._clock,
            "nodes": nodes}


def _radix_from_state(state):
    from paddle_tpu.serving import RadixPrefixCache, _RadixNode

    tree = RadixPrefixCache(state["block_size"])
    tree._clock = state["clock"]
    built = []
    for pidx, key, block, last_used in state["nodes"]:
        parent = tree._root if pidx < 0 else built[pidx]
        node = _RadixNode(key, block, parent)
        node.last_used = last_used
        parent.children[key] = node
        tree._by_block[block] = node
        built.append(node)
    return tree


# ------------------------------------------------------- host state capture
def _model_record(cfg):
    return {
        "num_hidden_layers": cfg.num_hidden_layers,
        "num_attention_heads": cfg.num_attention_heads,
        "num_key_value_heads": cfg.num_key_value_heads,
        "hidden_size": cfg.hidden_size,
        "vocab_size": cfg.vocab_size,
        "dtype": cfg.dtype,
    }


def _check_model(model, saved, who):
    got = _model_record(model.config)
    if got != saved:
        diff = {k: (saved[k], got[k]) for k in saved if got.get(k) != saved[k]}
        raise ValueError(
            f"{who} does not match the snapshot's geometry — the poured "
            f"K/V was computed by different weights/config: {diff} "
            "(saved, got).  Restore needs the SAME model the snapshot "
            "was taken from; weights themselves ride the training "
            "checkpoint tier, not the engine snapshot.")


def park_request_state(eng, slot):
    """Extract ONE resident request's restorable state — the
    single-request face of the engine snapshot (preemption parking,
    docs/DECODE.md): the slot's host fields plus its pool pages as
    verbatim pool-native bytes (`pool_get_blocks` dicts per layer, the
    same wire face the cluster ships).  The caller releases the slot;
    `unpark_request_state` places the bytes back untouched, so
    park→unpark is bit-exact by construction — never a re-quantization,
    and the (seed, nonce) sampling key plus the len(generated) fold
    index resume the stream token-for-token."""
    from paddle_tpu.ops import paged_attention as pa

    def host(blocks):
        return {name: np.asarray(a) for name, a in blocks.items()}

    pages_k = [host(pa.pool_get_blocks(p, slot.blocks))
               for p in eng._pools[0]]
    pages_v = [host(pa.pool_get_blocks(p, slot.blocks))
               for p in eng._pools[1]]
    return {
        "req": slot.req, "seq_len": slot.seq_len, "max_len": slot.max_len,
        "n_blocks": len(slot.blocks), "last_token": slot.last_token,
        "generated": list(slot.generated), "temperature": slot.temperature,
        "key": None if slot.key is None else np.asarray(slot.key),
        "priority": slot.priority,
        "pages_k": pages_k, "pages_v": pages_v,
    }


def unpark_request_state(eng, slot, rec):
    """Re-admit a parked request into `slot`: fresh pool blocks, parked
    pages placed VERBATIM (`pool_set_blocks`), slot state restored.
    Returns False — nothing mutated — when the pool cannot supply the
    blocks right now (the record stays parked for a later boundary)."""
    from paddle_tpu.serving import _PoolExhausted
    from paddle_tpu.ops import paged_attention as pa

    try:
        blocks = eng._alloc(rec["n_blocks"])
    except _PoolExhausted:
        return False
    idx = jnp.asarray(blocks, jnp.int32)
    for li in range(eng._n_layers):
        eng._pools[0][li] = pa.pool_set_blocks(eng._pools[0][li], idx,
                                             rec["pages_k"][li])
        eng._pools[1][li] = pa.pool_set_blocks(eng._pools[1][li], idx,
                                             rec["pages_v"][li])
        if eng._pool_sharding is not None:
            eng._pools[0][li] = eng._place_pool(eng._pools[0][li],
                                              eng._pool_sharding)
            eng._pools[1][li] = eng._place_pool(eng._pools[1][li],
                                              eng._pool_sharding)
    slot.rid = rec["req"]["rid"]
    slot.active = True
    slot.prefill = None
    slot.seq_len = rec["seq_len"]
    slot.max_len = rec["max_len"]
    slot.blocks = blocks
    slot.last_token = rec["last_token"]
    slot.generated = list(rec["generated"])
    slot.temperature = rec["temperature"]
    slot.key = None if rec["key"] is None else np.asarray(rec["key"])
    slot.d_seq_len = 0
    slot.adapter_slot = 0
    slot.priority = rec.get("priority", 2)
    slot.req = rec["req"]
    return True


def _capture_host_state(eng):
    """Everything but the pool tensors, as picklable host values.  Called
    between macro-steps (the engine is single-threaded host-side), so the
    captured view is a consistent boundary state.

    Overload-discipline state rides as RE-QUEUED submissions: PREFILLING
    slots and parked (preempted) requests both append their original req
    dicts to the captured pending queue — the restored engine replays
    them from (seed, nonce), deterministically — and a prefilling slot's
    reserved blocks are virtually released in the captured allocator
    (mirroring _unref: pages the prefix tree holds stay resident as
    reclaimable cached pages, so mid-prefill poured work survives as
    cache hits)."""
    cfg = {
        "format": 1,
        "max_batch": eng.max_batch,
        "block_size": eng.block_size,
        "num_blocks": eng._num_blocks,
        "eos_token_id": eng.eos_token_id,
        "kv_cache_dtype": eng._kv_dtype,
        "prefill_chunk": eng.prefill_chunk,
        "prefill_chunk_blocks": eng.prefill_chunk_blocks,
        "decode_chunk": eng._decode_chunk,  # ctor value; None = flag-driven
        "prefix_cache": eng._prefix is not None,
        "has_draft": eng.draft_model is not None,
        "num_speculative": eng.num_speculative,
        "model": _model_record(eng.model.config),
        "draft": (_model_record(eng.draft_model.config)
                  if eng.draft_model is not None else None),
        "adapters": (None if eng._pack is None else {
            "rank": eng._pack.rank,
            "alpha": eng._pack.alpha,
            "max_adapters": eng._pack.num_slots - 1,
            "targets": tuple(eng._pack.targets),
        }),
    }
    free = list(eng._free)
    ref = list(eng._ref)
    pending = [dict(req) for req in eng._pending]
    slots = []
    for s in eng._slots:
        if getattr(s, "prefill", None) is not None:
            # PREFILLING: demote to a queued submission and virtually
            # release its reserved blocks in the CAPTURED allocator
            # (mirror _unref — tree-held poured pages stay out of free)
            st = s.prefill
            for b in st.fresh + st.matched:
                ref[b] -= 1
                if ref[b] <= 0 and (eng._prefix is None
                                    or not eng._prefix.holds(b)):
                    free.append(b)
            pending.append(dict(st.req))
            slots.append({
                "rid": None, "active": False, "seq_len": 0, "max_len": 0,
                "blocks": [], "last_token": 0, "generated": [],
                "temperature": 0.0, "key": None, "d_seq_len": 0,
                "adapter_slot": 0, "priority": 1, "req": None,
            })
            continue
        slots.append({
            "rid": s.rid, "active": s.active, "seq_len": s.seq_len,
            "max_len": s.max_len, "blocks": list(s.blocks),
            "last_token": s.last_token, "generated": list(s.generated),
            "temperature": s.temperature,
            "key": None if s.key is None else np.asarray(s.key),
            "d_seq_len": s.d_seq_len, "adapter_slot": s.adapter_slot,
            "priority": getattr(s, "priority", 1),
            "req": getattr(s, "req", None),
        })
    for rec in getattr(eng, "_parked", {}).values():
        pending.append(dict(rec["req"]))
    pack = None
    if eng._pack is not None:
        registry = {}
        for name, (arrays, alpha) in eng._adapter_registry.items():
            registry[name] = ({t: (np.asarray(a), np.asarray(b))
                               for t, (a, b) in arrays.items()}, alpha)
        pack = {
            "registry": registry,
            "slot_names": list(eng._slot_names),
            "slot_epochs": list(eng._slot_epochs),
            "slot_used": list(eng._slot_used),
            "slot_clock": eng._slot_clock,
        }
    return {
        "config": cfg,
        "alloc": {"free": free, "ref": ref},
        "slots": slots,
        "results": {rid: list(v) for rid, v in eng._results.items()},
        "pending": pending,
        "req_counter": eng._req_counter,
        "macro_steps": eng._macro_steps,
        "radix": _radix_state(eng._prefix),
        "pack": pack,
        "spec_stats": (dict(eng._spec_stats)
                       if eng.draft_model is not None else None),
    }


class EngineSnapshot:
    """Step-tagged live-engine snapshot store under `dir` — the serving
    analog of CheckpointManager's policy layer: atomic commits through the
    shared protocol, retention of the newest `max_to_keep` VALID steps,
    corruption skip on resolve, stale-temp sweep.

        store = EngineSnapshot("snaps")
        store.save(engine)                    # step-tagged atomic commit
        eng = store.restore(model)            # newest valid, fresh engine
        eng = store.restore(model, mesh=mesh) # ...onto a different topology
    """

    def __init__(self, dir, max_to_keep=2):
        if max_to_keep is not None and max_to_keep < 1:
            raise ValueError("max_to_keep must be >= 1 (or None for unlimited)")
        self.dir = str(dir)
        self.max_to_keep = max_to_keep
        os.makedirs(self.dir, exist_ok=True)
        self._valid_cache: dict = {}  # step dir -> (manifest mtime, bool)

    # ------------------------------------------------------------- layout
    def _step_dir(self, step: int) -> str:
        return os.path.join(self.dir, f"step_{int(step):08d}")

    def all_steps(self) -> list:
        """Committed step numbers, ascending (validity not checked)."""
        out = []
        try:
            names = os.listdir(self.dir)
        except OSError:
            return out
        for name in names:
            m = _ckpt._STEP_RE.match(name)
            if m and os.path.isdir(os.path.join(self.dir, name)):
                out.append(int(m.group(1)))
        return sorted(out)

    def _valid(self, path: str) -> bool:
        """Checksum validity with the manager's (mtime, ok) caching: the
        per-save retention sweep and restore-time re-checks must not
        re-hash every retained snapshot's pool bytes — that sha256 wall
        would land inside the very save_ms the bench gate budgets."""
        mpath = os.path.join(path, _ckpt._MANIFEST)
        try:
            mtime = os.stat(mpath).st_mtime_ns
        except OSError:
            return False
        cached = self._valid_cache.get(path)
        if cached is not None and cached[0] == mtime:
            return cached[1]
        ok = _ckpt.CheckpointManager._verify_manifest(path, mpath)
        self._valid_cache[path] = (mtime, ok)
        return ok

    def latest_step(self):
        """Newest step whose snapshot passes checksum verification, or
        None.  Torn/corrupt directories (a SIGKILL mid-commit, bit rot)
        are skipped and counted in snapshot_stats()['corrupt_skipped'] —
        restore always lands on the newest LOADABLE engine state."""
        for step in reversed(self.all_steps()):
            path = self._step_dir(step)
            if self._valid(path):
                return step
            if path not in _SKIP_COUNTED:  # count each torn dir once
                _SKIP_COUNTED.add(path)
                _SNAPSHOT_STATS["corrupt_skipped"] += 1
        return None

    # --------------------------------------------------------------- save
    def save(self, engine, step=None) -> int:
        """Commit a snapshot of `engine` (default step tag: its macro-step
        count).  Call between step()s — a macro-step boundary; the engine
        never snapshots itself mid-dispatch (maybe_snapshot runs at the
        END of step()).  Returns the committed step number.  The commit
        is the CheckpointManager protocol verbatim, kill points included:
        a crash at any point leaves the previous snapshot restorable."""
        t0 = time.perf_counter()
        from paddle_tpu.ops import paged_attention as pa

        pools = {}
        for li, p in enumerate(engine._pools[0]):
            pools.update(pa.pool_state_dict(f"pool.k{li}", p))
        for li, p in enumerate(engine._pools[1]):
            pools.update(pa.pool_state_dict(f"pool.v{li}", p))
        if engine.draft_model is not None:
            for li, p in enumerate(engine._d_pools[0]):
                pools.update(pa.pool_state_dict(f"pool.dk{li}", p))
            for li, p in enumerate(engine._d_pools[1]):
                pools.update(pa.pool_state_dict(f"pool.dv{li}", p))
        # device->host sync happens HERE (shard-wise for TP engines: each
        # pool leaf's unique shards + global offsets enter the metadata,
        # which is what lets restore reshard onto any topology)
        arrays, md, fname = build_shard_snapshot(pools)
        extras_blob = pickle.dumps(_capture_host_state(engine), protocol=4)
        step = int(step if step is not None else engine._macro_steps)

        def writer(tmp):
            # the ONE payload-writer body (npz + metadata + extras, each
            # fsynced, kill points included) shared with
            # CheckpointManager._commit
            return _ckpt.write_payload(tmp, arrays, fname, md.to_json(),
                                       extras_blob)

        _final, written = _ckpt.commit_dir(
            self.dir, f"step_{step:08d}", writer,
            manifest_extra={"step": step, "kind": "engine_snapshot"})
        # every byte was hashed moments ago while writing the manifest —
        # seed the verify cache so the retention sweep below (and any
        # restore) need not read it all back
        self._valid_cache[_final] = (
            os.stat(os.path.join(_final, _ckpt._MANIFEST)).st_mtime_ns, True)
        _SNAPSHOT_STATS["saves"] += 1
        _SNAPSHOT_STATS["bytes"] += written
        _SNAPSHOT_STATS["snapshot_seconds"] += time.perf_counter() - t0
        self._gc()
        return step

    # ----------------------------------------------------------------- gc
    def _gc(self):
        """Retention: newest `max_to_keep` VALID steps kept; a torn dir
        newer than every valid snapshot is kept for post-mortem (restore
        skips it anyway); stale temp dirs of dead processes are swept —
        the CheckpointManager rules, on the snapshot store."""
        steps = self.all_steps()
        valid = [s for s in steps if self._valid(self._step_dir(s))]
        keep = set(valid if self.max_to_keep is None
                   else valid[-self.max_to_keep:])
        newest_valid = valid[-1] if valid else None
        for s in steps:
            if s in keep:
                continue
            if s not in valid and (newest_valid is None or s > newest_valid):
                continue
            path = self._step_dir(s)
            shutil.rmtree(path, ignore_errors=True)
            # evict bookkeeping with the dir: a long-lived serving
            # process commits snapshots indefinitely, and undropped
            # entries would grow without bound (a re-torn future dir of
            # the same name must also count afresh)
            self._valid_cache.pop(path, None)
            _SKIP_COUNTED.discard(path)
        _ckpt.sweep_stale_tmp(self.dir)

    def config(self, step=None) -> dict:
        """The recorded engine geometry of snapshot `step` (default:
        newest valid) — the `_capture_host_state` config dict (max_batch,
        block_size, num_blocks, kv_cache_dtype, decode_chunk, model
        record, ...), WITHOUT loading any pool bytes.  This is what lets
        a warm standby decide whether its AOT-compiled executables carry
        onto the restored engine (identical geometry => identical step
        signature) and what a respawned worker warms up against before
        announcing readiness (serving/cluster_worker.py)."""
        if step is None:
            step = self.latest_step()
            if step is None:
                raise RuntimeError(
                    f"no valid engine snapshot under {self.dir!r}")
        path = self._step_dir(step)
        if not self._valid(path):
            raise RuntimeError(f"engine snapshot {path} is missing or corrupt")
        with open(os.path.join(path, _ckpt._EXTRAS), "rb") as f:
            extras = pickle.load(f)
        return dict(extras["config"])

    # -------------------------------------------------------------- restore
    def restore(self, model, step=None, *, mesh=None, mp_axis="mp",
                draft_model=None, decode_chunk=_UNSET):
        """Rebuild a live engine from snapshot `step` (default: newest
        valid).  `model` (and `draft_model` for speculative snapshots)
        must be the SAME model the snapshot was taken from — geometry is
        validated loudly; weights ride the training checkpoint tier.

        `mesh`/`mp_axis` may DIFFER from the save-time topology: the
        fresh engine is constructed for the target mesh (weights get
        Megatron placements, the mesh lint validates at construction when
        FLAGS_verify_sharding is on) and every pool tensor loads through
        the shard-record assembly path — reshard-on-load, single-device
        ↔ TP in either direction.  `decode_chunk` defaults to the saved
        constructor value; streams are bit-identical for every D, so a
        restore under different FLAGS_decode_chunk stays correct (the
        compiled steps simply rebuild).

        Returns the restored `GenerationEngine`, admitting (a snapshot
        taken by drain() restores OPEN — that is the migration target)."""
        if step is None:
            step = self.latest_step()
            if step is None:
                raise RuntimeError(
                    f"no valid engine snapshot under {self.dir!r}")
        path = self._step_dir(step)
        if not self._valid(path):
            raise RuntimeError(f"engine snapshot {path} is missing or corrupt")
        with open(os.path.join(path, _ckpt._EXTRAS), "rb") as f:
            extras = pickle.load(f)
        cfg = extras["config"]
        _check_model(model, cfg["model"], "model")
        if cfg["has_draft"] and draft_model is None:
            raise ValueError(
                "snapshot was taken from a speculative engine; pass the "
                "same draft_model=")
        if not cfg["has_draft"] and draft_model is not None:
            raise ValueError(
                "snapshot engine had no draft model; drop draft_model=")
        if cfg["has_draft"]:
            _check_model(draft_model, cfg["draft"], "draft model")

        from paddle_tpu.serving import GenerationEngine
        from collections import deque

        eng = GenerationEngine(
            model,
            max_batch=cfg["max_batch"], block_size=cfg["block_size"],
            num_blocks=cfg["num_blocks"], eos_token_id=cfg["eos_token_id"],
            mesh=mesh, mp_axis=mp_axis, prefill_chunk=cfg["prefill_chunk"],
            draft_model=draft_model,
            num_speculative_tokens=cfg["num_speculative"],
            decode_chunk=(cfg["decode_chunk"] if decode_chunk is _UNSET
                          else decode_chunk),
            prefix_cache=cfg["prefix_cache"],
            kv_cache_dtype=cfg["kv_cache_dtype"],
            adapters=(dict(cfg["adapters"]) if cfg["adapters"] else None),
            # absent in pre-overload snapshots: restore atomic (None ->
            # flag-driven, the constructor default)
            prefill_chunk_blocks=cfg.get("prefill_chunk_blocks"),
        )

        # ---- pools: shard records -> assembled host arrays -> the fresh
        # engine's placement (reshard-on-load; `_place_pool` commits the
        # target sharding so the compiled step's input shardings are the
        # constructed engine's, whatever topology saved the bytes)
        from paddle_tpu.ops import paged_attention as pa

        with open(os.path.join(path, _META_FILE)) as f:
            md = Metadata.from_json(f.read())
        files = _LazyFiles(path)

        def fetch(name, _tmpl):
            tm = md.tensors[name]
            full = tuple(slice(0, d) for d in tm.global_shape)
            # jnp.array COPIES (jnp.asarray zero-copy-aliases the host
            # buffer on CPU): these pools flow into the compiled step's
            # donate_argnums slots, and donating a buffer XLA merely
            # borrows from numpy corrupts the heap — an intermittent
            # SIGSEGV/abort at the next executable teardown, reproduced
            # under loaded tier-1 shards before this copy existed
            return jnp.array(_assemble_region(tm, files, full))

        def load(prefix, template, sharding):
            pool = pa.pool_from_state(template, fetch, prefix)
            return eng._place_pool(pool, sharding)

        eng._pools[0] = [load(f"pool.k{li}", p, eng._pool_sharding)
                         for li, p in enumerate(eng._pools[0])]
        eng._pools[1] = [load(f"pool.v{li}", p, eng._pool_sharding)
                         for li, p in enumerate(eng._pools[1])]
        if draft_model is not None:
            eng._d_pools[0] = [load(f"pool.dk{li}", p, eng._d_pool_sharding)
                               for li, p in enumerate(eng._d_pools[0])]
            eng._d_pools[1] = [load(f"pool.dv{li}", p, eng._d_pool_sharding)
                               for li, p in enumerate(eng._d_pools[1])]

        # ---- allocator + requests
        eng._free = list(extras["alloc"]["free"])
        eng._ref = list(extras["alloc"]["ref"])
        eng._pending = deque(extras["pending"])
        eng._req_counter = extras["req_counter"]
        eng._macro_steps = extras["macro_steps"]
        for sd, slot in zip(extras["slots"], eng._slots):
            slot.rid = sd["rid"]
            slot.active = sd["active"]
            slot.seq_len = sd["seq_len"]
            slot.max_len = sd["max_len"]
            slot.blocks = list(sd["blocks"])
            slot.last_token = sd["last_token"]
            slot.generated = list(sd["generated"])
            slot.temperature = sd["temperature"]
            slot.key = None if sd["key"] is None else np.asarray(sd["key"])
            slot.d_seq_len = sd["d_seq_len"]
            slot.adapter_slot = sd["adapter_slot"]
            slot.priority = sd.get("priority", 1)
            slot.req = sd.get("req")
        # the submit-sequence tie-break resumes past every captured
        # request so post-restore submissions keep FIFO-within-class
        eng._submit_seq = 1 + max(
            [r.get("seq", -1) for r in extras["pending"]]
            + [sd.get("req", {}).get("seq", -1) if sd.get("req") else -1
               for sd in extras["slots"]] + [-1])
        eng._results = {rid: list(v) for rid, v in extras["results"].items()}
        for slot in eng._slots:
            if slot.active:
                # live streams alias their slot's generated list — the
                # same invariant _try_admit establishes
                eng._results[slot.rid] = slot.generated

        # ---- prefix cache (namespaces, epochs, LRU order)
        if cfg["prefix_cache"] and extras["radix"] is not None:
            eng._prefix = _radix_from_state(extras["radix"])

        # ---- adapter pack: registry replayed into slots via the normal
        # scatter (zero-recompile contract intact), epochs restored so a
        # post-restore hot swap strands exactly the right cached subtree
        if extras["pack"] is not None:
            pk = extras["pack"]
            registry = {}
            for name, (arrays, alpha) in pk["registry"].items():
                registry[name] = (
                    {t: (jnp.asarray(a), jnp.asarray(b))
                     for t, (a, b) in arrays.items()}, alpha)
            eng._adapter_registry = registry
            eng._slot_names = list(pk["slot_names"])
            eng._slot_used = list(pk["slot_used"])
            eng._slot_clock = pk["slot_clock"]
            for s, name in enumerate(eng._slot_names):
                if s and name is not None:
                    eng._pack.set_slot(s, *registry[name])
            eng._slot_epochs = list(pk["slot_epochs"])
            refs = [0] * eng._pack.num_slots
            for slot in eng._slots:
                if slot.active:
                    refs[slot.adapter_slot] += 1
            eng._slot_refs = refs
            import paddle_tpu.serving as _serving

            _serving._LORA_STATS["slots_total"] = eng._pack.num_slots - 1
            _serving._LORA_STATS["slots_resident"] = eng._resident_count()

        if eng.draft_model is not None and extras["spec_stats"] is not None:
            eng._spec_stats = dict(extras["spec_stats"])
        _SNAPSHOT_STATS["restores"] += 1
        return eng


def restore_engine(model, dir, step=None, *, mesh=None, mp_axis="mp",
                   draft_model=None, decode_chunk=_UNSET):
    """Restore a live engine from the newest valid snapshot under `dir`
    (or an explicit `step`) — `EngineSnapshot(dir).restore(...)`; see
    that method for the topology-migration and bit-exact-resume
    contract."""
    return EngineSnapshot(dir).restore(
        model, step=step, mesh=mesh, mp_axis=mp_axis,
        draft_model=draft_model, decode_chunk=decode_chunk)
