"""Cluster worker process entry (`python -m paddle_tpu.serving.cluster_worker`).

Spawned by `serving.cluster.EngineCluster` with a JSON spec in
PADDLE_CLUSTER_SPEC.  Two roles:

- **decode**: owns ONE `GenerationEngine` (prefix cache forced on — it is
  both the page-adoption surface for shipped KV and the substrate of the
  cluster prefix index).  Pops router messages from its inbound ShmRing,
  steps the engine, and pushes per-position token events + completion
  reports.  With a snapshot dir + interval the engine auto-snapshots at
  macro-step boundaries (serving/snapshot.py), and a respawned worker
  RESTORES from the newest valid boundary, re-emitting each resident
  stream from position 0 — the router's per-position merge dedups and
  verifies the overlap, so fail-over is bit-exact.
- **prefill**: builds the model once, computes K/V for a prompt's full
  blocks through the SAME `paged_pour_blocks` math the engine uses, and
  ships the pool-native page bytes (`pool_get_blocks` leaves — int8
  payload + f32 scales for int8 pools, about half the bf16 wire bytes)
  back through the router to the target decode replica, block by block.
- **standby**: the warm-start tier (docs/SERVING_CLUSTER.md).  Builds an
  engine with the cluster's geometry, AOT-warms its macro-step
  executables (`GenerationEngine.warmup` — persistent-cache-served
  compiles), announces `ready` with `warmed=True`, then parks on its
  ring.  A `promote` message hands it a dead replica's snapshot dir: it
  restores the boundary state, carries its warm executables onto the
  restored engine (identical recorded geometry means an identical step
  signature), reports the claimed residents via `resume`, and serves as
  the replica — compile-free on the recovery critical path.

A decode/standby worker spawned with spec["warmup"] warms up BEFORE
pushing its readiness report, so its first heartbeat means "already
compiled" — the router drops the boot-grace carve-out for it
(FailureDetector.mark_warmed) and judges it on the steady-state miss
budget immediately.

Heartbeats ride a background thread bumping a TCPStore counter every
heartbeat_ms/2 — SIGKILL stops the bumps, which is the router's
miss-threshold failure signal.  A worker whose store connection dies
(the router is gone) exits rather than serving into the void.
Crash injection: spec["kill"] = "point:nth" SIGKILLs this process at the
named protocol point (tests/test_serving_cluster_crash.py).
"""

from __future__ import annotations

import json
import os
import pickle
import sys
import threading


def _bootstrap_jax():
    """Same pinning as tests/conftest.py / run_tier1's worker bootstrap:
    CPU platform, exact matmuls, shared persistent compile cache.  The
    cache comes up through _core/compile_cache.enable — NOT raw
    jax.config.update calls — so worker processes find the directory every
    other process of the repo uses and get the shared helper's exact
    semantics: gate-zeroing (every small CPU-smoke compile persists), the
    jax.monitoring hit/miss counters the readiness report carries, and the
    FLAGS_compilation_cache_dir listener."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_default_matmul_precision", "highest")
    from paddle_tpu._core import compile_cache

    compile_cache.enable()


def _load_factory(spec: str):
    """'module:fn' or 'path/to/file.py:fn' -> the model factory."""
    mod, fn = spec.rsplit(":", 1)
    if mod.endswith(".py"):
        import importlib.util

        s = importlib.util.spec_from_file_location("_cluster_model_def", mod)
        m = importlib.util.module_from_spec(s)
        s.loader.exec_module(m)
    else:
        import importlib

        m = importlib.import_module(mod)
    return getattr(m, fn)


def _heartbeat_loop(store, key, period_s):
    while True:
        try:
            store.add(key, 1)
        except OSError:
            os._exit(4)  # the router (store host) is gone: stop serving
        if _HB_STOP.wait(period_s):
            return


_HB_STOP = threading.Event()


class _Out:
    def __init__(self, ring):
        self.ring = ring

    def push(self, msg):
        self.ring.push(pickle.dumps(msg, protocol=4), timeout_ms=30_000)


# ----------------------------------------------------------- cluster adapters
def _cluster_adapter_state(model, rank, seed):
    """Deterministic LoRA weights for one cluster adapter spec: every
    worker derives the SAME state dict from (model geometry, rank, seed)
    — numpy RandomState, host-side, platform-stable — so adapter weights
    never ride the wire and every engine's registration installs
    identical contents (the model-factory construction-identity story
    applied to adapters; router.cluster_adapter_table)."""
    import numpy as np

    from paddle_tpu.nn.lora import LLAMA_TARGETS, _resolve_sublayer

    rng = np.random.RandomState(int(seed))
    layers = model.model.layers
    sd = {}
    for li in range(len(layers)):
        blk = layers[li]
        for t in LLAMA_TARGETS:
            lin = _resolve_sublayer(blk, t)
            a = rng.standard_normal((lin.in_features, int(rank))) * 0.02
            b = rng.standard_normal((int(rank), lin.out_features)) * 0.02
            sd[f"model.layers.{li}.{t}.lora_A"] = a.astype(np.float32)
            sd[f"model.layers.{li}.{t}.lora_B"] = b.astype(np.float32)
    return sd


def _register_cluster_adapters(eng, spec):
    """Register spec["adapters"] IN ORDER on a freshly built engine:
    first-fit slots from 1 + one epoch bump per install lands adapter i
    at (slot i+1, epoch 1) on every worker — the fleet-wide namespace
    cluster_adapter_table promises.  A snapshot-RESTORED engine already
    carries its adapters (the snapshot records registry + slots +
    epochs); re-registering a resident name would bump its epoch out of
    fleet lockstep, so resident names are left untouched."""
    for name, rank, alpha, seed in (spec.get("adapters") or []):
        if name in eng._adapter_registry:
            continue
        eng.register_adapter(
            name, _cluster_adapter_state(eng.model, rank, seed), alpha=alpha)


# --------------------------------------------------------------- decode role
def _warm_report(warm):
    """Readiness-report fields describing this process's warm state: did
    it AOT-warm (and how long that took), and how the persistent compile
    cache served its compiles (process-lifetime jax.monitoring counters —
    zero at exec, so absolute values ARE this boot's counts)."""
    from paddle_tpu._core.compile_cache import compile_stats

    cs = compile_stats()
    return {"warmed": warm is not None,
            "warmup_s": float(warm["seconds"]) if warm else 0.0,
            "cache_hits": int(cs["persistent_cache_hits"]),
            "cache_misses": int(cs["persistent_cache_misses"])}


def _claimed_rids(eng) -> set:
    """The rids a restored engine resurrects: resident slots, the queued
    backlog, and finished-but-undelivered results — the boundary may have
    caught a request between completion and the router's read."""
    tracked = {s.rid for s in eng._slots if s.active}
    tracked.update(eng.pending_requests())
    tracked.update(eng._results)
    return tracked


def _build_decode_engine(spec, model):
    import paddle_tpu as paddle
    from paddle_tpu.serving import GenerationEngine, restore_engine
    from paddle_tpu.serving.snapshot import EngineSnapshot

    snap_dir = spec["snapshot_dir"]
    if snap_dir and spec["snapshot_interval"] > 0:
        paddle.set_flags({
            "FLAGS_engine_snapshot_dir": snap_dir,
            "FLAGS_engine_snapshot_interval": spec["snapshot_interval"]})

    kw = dict(spec["engine"])
    kw["prefix_cache"] = True
    if spec["restore"] and snap_dir and \
            EngineSnapshot(snap_dir).latest_step() is not None:
        eng = restore_engine(model, snap_dir)
        return eng, _claimed_rids(eng)
    return GenerationEngine(model, **kw), set()


def _decode_loop(spec, model, ring_in, out, killer):
    eng, tracked = _build_decode_engine(spec, model)
    _register_cluster_adapters(eng, spec)
    # AOT warm BEFORE the readiness report: the resume push is the claim
    # of this replica's requests, and announcing it with compiles still
    # owed would put trace+compile back on the serving critical path
    warm = eng.warmup() if spec.get("warmup") else None
    out.push({"t": "resume", "rids": sorted(tracked, key=str),
              **_warm_report(warm)})
    _decode_serve(spec, eng, tracked, ring_in, out, killer)


class _DecodeCtx:
    """Mutable decode-serve state threaded through the table-driven
    message handlers (what the pre-PR-19 handle() closure captured)."""

    __slots__ = ("spec", "eng", "tracked", "staging", "sent", "out",
                 "killer", "draining", "snap_dir", "hit_toks_reported")

    def __init__(self, spec, eng, tracked, out, killer):
        self.spec = spec
        self.eng = eng
        self.tracked = tracked
        self.staging: dict = {}
        self.sent: dict = {}
        self.out = out
        self.killer = killer
        self.draining = eng._draining
        self.snap_dir = spec["snapshot_dir"]
        # prefix_hit_tokens watermark already RELAYED to the router in
        # `done` messages (the engine counter is process-global; deltas
        # keep the router's cluster-wide aggregate double-count-free)
        self.hit_toks_reported = 0


# Decode-role message handlers.  One `_decode_msg_<message>` per spec
# message with dst=decode — handler_tables() binds them through
# serving/protocol.py with BOTH directions asserted (a spec message
# without a handler, or a handler the spec no longer names, fails at
# EngineCluster construction, before any fork).
def _decode_msg_submit(ctx, msg):
    if ctx.draining:
        ctx.out.push({"t": "requeue", "rid": msg["rid"]})
        return None
    ctx.eng.add_request(msg["rid"], msg["prompt"],
                        max_new_tokens=msg["max_new"],
                        temperature=msg["temperature"] or None,
                        seed=msg["seed"], nonce=msg["nonce"],
                        adapter=msg.get("adapter"),
                        priority=msg.get("priority", "normal"))
    ctx.killer.hit("decode-after-accept")
    ctx.tracked.add(msg["rid"])
    return None


def _decode_msg_ship_begin(ctx, msg):
    ctx.staging[msg["sid"]] = {"tokens": msg["tokens"],
                               "n": msg["n_blocks"], "k": [], "v": [],
                               "ns": msg.get("ns")}
    return None


def _decode_msg_ship_block(ctx, msg):
    st = ctx.staging.get(msg["sid"])
    if st is not None:
        st["k"].append(msg["k"])
        st["v"].append(msg["v"])
    return None


def _decode_msg_ship_end(ctx, msg):
    import numpy as np

    st = ctx.staging.pop(msg["sid"], None)
    if st is not None and len(st["k"]) == st["n"]:
        n_layers = len(st["k"][0])
        k_blocks = [
            {leaf: np.concatenate(
                [blk[li][leaf] for blk in st["k"]], axis=0)
             for leaf in st["k"][0][li]}
            for li in range(n_layers)]
        v_blocks = [
            {leaf: np.concatenate(
                [blk[li][leaf] for blk in st["v"]], axis=0)
             for leaf in st["v"][0][li]}
            for li in range(n_layers)]
        ctx.eng.adopt_pages(st["tokens"], k_blocks, v_blocks,
                            ns=st.get("ns"))
        ctx.killer.hit("decode-after-adopt")
    # an incomplete ship (a killed prefill worker) just drops:
    # admission falls back to local prefill, nothing is lost
    return None


def _decode_msg_ship_abort(ctx, msg):
    ctx.staging.pop(msg["sid"], None)
    return None


def _decode_msg_drain(ctx, msg):
    ctx.eng.drain(ctx.snap_dir)  # decode specs always carry a snapshot dir
    ctx.draining = True
    ctx.out.push({"t": "drained",
                  "queued": list(ctx.eng.pending_requests())})
    return None


def _decode_msg_stop(ctx, msg):
    return "stop"


def _decode_serve(spec, eng, tracked, ring_in, out, killer):
    handlers, _, _ = handler_tables()
    ctx = _DecodeCtx(spec, eng, tracked, out, killer)

    def emit_progress():
        active = {s.rid for s in eng._slots if s.active}
        queued = set(eng.pending_requests())
        for rid in sorted(tracked, key=str):
            lst = eng.result(rid)
            if lst is None:
                continue
            n0 = ctx.sent.get(rid, 0)
            if len(lst) > n0:
                out.push({"t": "tokens", "rid": rid, "start": n0,
                          "toks": [int(x) for x in lst[n0:]]})
                ctx.sent[rid] = len(lst)
                killer.hit("decode-mid-stream")
            if rid not in active and rid not in queued:
                from paddle_tpu.serving import decode_stats
                hits = int(decode_stats()["prefix_hit_tokens"])
                out.push({"t": "done", "rid": rid,
                          "n": ctx.sent.get(rid, 0),
                          "hit_toks": hits - ctx.hit_toks_reported})
                ctx.hit_toks_reported = hits
                tracked.discard(rid)

    # a restored engine may hold claimed results that finished before its
    # snapshot (the old router died unread): nothing will step for them,
    # so deliver them now, behind the resume report that claimed them
    emit_progress()
    while True:
        busy = eng.has_work()
        try:
            data = ring_in.pop(timeout_ms=1 if busy else 50)
        except TimeoutError:
            data = None
        except BrokenPipeError:
            os._exit(3)
        if data is not None:
            # a message outside the spec raises KeyError -> the fatal
            # path: protocol violations die loudly, never drop silently
            msg = pickle.loads(data)
            if handlers[msg["t"]](ctx, msg) == "stop":
                break
            continue  # drain the inbox before paying for a macro-step
        if busy:
            eng.step()
            emit_progress()
        elif ctx.draining:
            break  # residents finished; queued rids migrated via drained
    out.push({"t": "bye"})


# -------------------------------------------------------------- standby role
class _ParkedCtx:
    """A parked standby's handler context: nothing but the outbound ring
    (its engine is already warm; the handlers only steer the park loop)."""

    __slots__ = ("out",)

    def __init__(self, out):
        self.out = out


def _standby_msg_stop(ctx, msg):
    ctx.out.push({"t": "bye"})
    return "stop"


def _standby_msg_promote(ctx, msg):
    # the park loop breaks out and runs the restore/claim sequence with
    # this message's snapshot_dir/snapshot_interval payload
    return "promote"


def _carries_executables(eng, cfg) -> bool:
    """Whether the standby engine's AOT-compiled macro-steps are valid on
    an engine restored from recorded geometry `cfg` (EngineSnapshot
    .config()): the step signature is geometry-pure — batch, table width,
    pool shapes/dtype — and the compiled executable closes over nothing
    engine-local, so identical geometry means the executables carry.
    Adapter/speculative snapshots never carry (their signatures differ)."""
    return (cfg["max_batch"] == eng.max_batch
            and cfg["block_size"] == eng.block_size
            and cfg["num_blocks"] == eng._num_blocks
            and cfg["kv_cache_dtype"] == eng._kv_dtype
            and not cfg["has_draft"] and cfg["adapters"] is None
            and eng.draft_model is None and eng._pack is None)


def _standby_loop(spec, model, ring_in, out, killer):
    """Warm standby: pay import + trace + (persistent-cache-served)
    compile NOW, against the cluster's engine geometry, then park until a
    `promote` message hands over a dead replica's snapshot dir.  On
    promotion the standby restores the replica's boundary state, carries
    its warm executables onto the restored engine when the recorded
    geometry matches, claims the residents via `resume`, and becomes the
    decode replica — the respawn path's jax import + trace + compile wall
    never lands on the recovery critical path."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import GenerationEngine
    from paddle_tpu.serving.snapshot import EngineSnapshot

    kw = dict(spec["engine"])
    kw["prefix_cache"] = True
    eng = GenerationEngine(model, **kw)
    _register_cluster_adapters(eng, spec)
    killer.hit("standby-mid-warmup")
    warm = eng.warmup() if spec.get("warmup", True) else None
    out.push({"t": "ready", **_warm_report(warm)})

    _, _, handlers = handler_tables()
    ctx = _ParkedCtx(out)
    while True:
        try:
            data = ring_in.pop(timeout_ms=100)
        except TimeoutError:
            continue
        except BrokenPipeError:
            os._exit(3)
        if data is None:
            continue
        msg = pickle.loads(data)
        verdict = handlers[msg["t"]](ctx, msg)
        if verdict == "stop":
            return
        if verdict == "promote":
            break

    snap_dir = msg["snapshot_dir"]
    interval = int(msg.get("snapshot_interval", 0))
    spec = dict(spec)
    spec["snapshot_dir"], spec["snapshot_interval"] = snap_dir, interval
    tracked: set = set()
    if snap_dir and interval > 0:
        # the flags listener clears EVERY engine's compiled steps on ANY
        # set_flags — hold the warm executables across the snapshot-dir
        # arm and reinstall them
        step_fns, prefill_fns = dict(eng._step_fns), dict(eng._prefill_fns)
        paddle.set_flags({
            "FLAGS_engine_snapshot_dir": snap_dir,
            "FLAGS_engine_snapshot_interval": interval})
        eng._step_fns.update(step_fns)
        eng._prefill_fns.update(prefill_fns)
    store = EngineSnapshot(snap_dir) if snap_dir else None
    if store is not None and store.latest_step() is not None:
        restored = store.restore(model)
        if _carries_executables(eng, store.config()):
            restored._step_fns.update(eng._step_fns)
            # prefill programs close over the model alone (bucket, prefix
            # length and block size are their key and the geometry)
            restored._prefill_fns.update(eng._prefill_fns)
        eng = restored
        tracked = _claimed_rids(eng)
    out.push({"t": "resume", "rids": sorted(tracked, key=str),
              **_warm_report(warm)})
    _decode_serve(spec, eng, tracked, ring_in, out, killer)


# -------------------------------------------------------------- prefill role
def _prefill_pages(model, prompt, n_blocks, block_size, kv_dtype,
                   scope=None):
    """K/V pages for the prompt's first `n_blocks` FULL blocks, poured
    through the engine's own quantize/pour math into a staging pool and
    extracted as pool-native leaves.  Deterministic: the same prompt
    always ships the same bytes (the bit-exact re-ship contract), int8
    quantization included.  `scope` wraps the forward (an adapter
    request's nn.lora.adapter_prefill_scope: the poured K/V must be the
    ADAPTED model's, exactly what the decode engine's own admission would
    pour for that tenant)."""
    import contextlib

    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import _model_forward_cached
    from paddle_tpu.ops import paged_attention as pa

    scope = scope if scope is not None else contextlib.nullcontext()
    cfg = model.config
    nkv = cfg.num_key_value_heads
    hd = cfg.hidden_size // cfg.num_attention_heads
    dt = (jnp.int8 if kv_dtype == "int8"
          else jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)
    span = n_blocks * block_size
    toks = [int(t) for t in prompt[:span]]
    caches = [
        (paddle.zeros([1, 0, nkv, hd], dtype=cfg.dtype),
         paddle.zeros([1, 0, nkv, hd], dtype=cfg.dtype))
        for _ in range(cfg.num_hidden_layers)]
    arr = np.asarray(toks, np.int32).reshape(1, -1)
    with scope, paddle.no_grad():
        _h, caches = _model_forward_cached(
            model.model, paddle.to_tensor(arr), caches, 0)
    idx = jnp.arange(n_blocks, dtype=jnp.int32)

    def pour_and_extract(pool, tensor):
        kv = jnp.moveaxis(tensor._value, 1, 2)  # [1, Nkv, S, H]
        kv = kv.reshape(nkv, n_blocks, block_size, hd).swapaxes(0, 1)
        pool = pa.paged_pour_blocks(pool, kv, idx)
        return {name: np.asarray(a)
                for name, a in pa.pool_get_blocks(pool, idx).items()}

    k_layers, v_layers = [], []
    for k, v in caches:
        kp, vp = pa.alloc_paged_cache(n_blocks, nkv, block_size, hd, dt)
        k_layers.append(pour_and_extract(kp, k))
        v_layers.append(pour_and_extract(vp, v))
    return toks, k_layers, v_layers


class _PrefillCtx:
    """Prefill-role handler context: the shared model plus the resolved
    page geometry every shipment uses.  `pack` holds the cluster's
    deterministic adapters (same construction as every decode engine's
    registration — slot i+1 in spec order) so adapter requests prefill
    through their tenant's weights."""

    __slots__ = ("model", "out", "killer", "block_size", "kv_dtype",
                 "pack")

    def __init__(self, model, out, killer, block_size, kv_dtype,
                 pack=None):
        self.model = model
        self.out = out
        self.killer = killer
        self.block_size = block_size
        self.kv_dtype = kv_dtype
        self.pack = pack


def _build_prefill_pack(model, spec):
    """The prefill worker's AdapterPack: cluster adapter i installed at
    slot i+1 — the same slots cluster_adapter_table names and every
    decode engine's in-order registration lands on.  None without
    cluster adapters."""
    specs = spec.get("adapters") or []
    if not specs:
        return None
    from paddle_tpu.nn.lora import AdapterPack, parse_adapter_state_dict

    pack = AdapterPack(model, rank=int(specs[0][1]),
                       max_adapters=len(specs))
    for i, (name, rank, alpha, seed) in enumerate(specs):
        arrays = parse_adapter_state_dict(
            _cluster_adapter_state(model, rank, seed),
            pack.num_layers, pack.targets, pack.rank)
        pack.set_slot(i + 1, arrays, alpha)
    return pack


def _prefill_msg_stop(ctx, msg):
    return "stop"


def _prefill_msg_prefill(ctx, msg):
    n = int(msg["n_blocks"])
    ns = msg.get("ns")
    scope = None
    if msg.get("adapter") is not None:
        if ctx.pack is None or ns is None:
            raise RuntimeError(
                f"prefill for adapter {msg['adapter']!r} without a "
                "cluster adapter pack/namespace — the router and worker "
                "specs disagree on adapters= (serving/cluster.py)")
        from paddle_tpu.nn.lora import adapter_prefill_scope

        # the wire namespace names the slot whose weights pour this K/V
        scope = adapter_prefill_scope(ctx.model.model.layers, ctx.pack,
                                      int(ns[0]))
    toks, k_layers, v_layers = _prefill_pages(
        ctx.model, msg["prompt"], n, ctx.block_size, ctx.kv_dtype,
        scope=scope)
    ctx.killer.hit("prefill-before-ship")
    sid = msg["sid"]
    ctx.out.push({"t": "page_begin", "sid": sid, "rid": msg["rid"],
                  "tokens": toks, "n_blocks": n,
                  "n_layers": len(k_layers), "ns": ns})
    for bi in range(n):
        ctx.out.push({"t": "page_block", "sid": sid, "i": bi,
                      "k": [{leaf: a[bi:bi + 1] for leaf, a in lay.items()}
                            for lay in k_layers],
                      "v": [{leaf: a[bi:bi + 1] for leaf, a in lay.items()}
                            for lay in v_layers]})
        if bi == n // 2:
            ctx.killer.hit("prefill-mid-ship")
    ctx.out.push({"t": "page_end", "sid": sid})
    ctx.killer.hit("prefill-after-ship")
    ctx.out.push({"t": "shipped", "rid": msg["rid"], "n_blocks": n})
    return None


def _prefill_loop(spec, model, ring_in, out, killer):
    from paddle_tpu._core import flags as _flags

    block_size = int(spec["engine"].get("block_size", 16))
    # resolve EXACTLY like GenerationEngine.__init__: an unset engine
    # kwarg falls back to FLAGS_kv_cache_dtype — a 'bf16' literal here
    # would ship scale-less pages into decode replicas whose env-flagged
    # int8 pools expect payload + scales
    kv_dtype = (spec["engine"].get("kv_cache_dtype")
                or _flags.flag("FLAGS_kv_cache_dtype"))
    _, handlers, _ = handler_tables()
    ctx = _PrefillCtx(model, out, killer, block_size, kv_dtype,
                      pack=_build_prefill_pack(model, spec))
    while True:
        try:
            data = ring_in.pop(timeout_ms=100)
        except TimeoutError:
            continue
        except BrokenPipeError:
            os._exit(3)
        if data is None:
            break
        msg = pickle.loads(data)
        if handlers[msg["t"]](ctx, msg) == "stop":
            break
    out.push({"t": "bye"})


# --------------------------------------------------------------------- main
def main():
    spec = json.loads(os.environ["PADDLE_CLUSTER_SPEC"])
    _bootstrap_jax()

    from paddle_tpu import _native
    from paddle_tpu._core import flags as _flags
    from paddle_tpu.serving.cluster import _KillSpec
    from paddle_tpu.serving.transport import get_transport

    killer = _KillSpec(spec.get("kill") or "")
    # One attach deadline (FLAGS_cluster_attach_timeout_ms) covers every
    # boot-time channel: the store connect, both ring attaches, and — for
    # transport="tcp" — the endpoint-key wait + dial inside attach()
    attach_ms = int(_flags.flag("FLAGS_cluster_attach_timeout_ms"))
    store = _native.TCPStoreClient(port=spec["store_port"],
                                   timeout_ms=attach_ms)
    transport = get_transport(spec.get("transport") or "shm", store=store)
    ring_in = transport.attach(spec["ring_in"], attach_timeout_ms=attach_ms)
    ring_out = transport.attach(spec["ring_out"], attach_timeout_ms=attach_ms)
    hb = threading.Thread(
        target=_heartbeat_loop,
        args=(store, spec["hb_key"], spec["heartbeat_ms"] / 2000.0),
        daemon=True)
    hb.start()

    model = _load_factory(spec["model"])()
    out = _Out(ring_out)
    try:
        if spec["role"] == "decode":
            _decode_loop(spec, model, ring_in, out, killer)
        elif spec["role"] == "standby":
            _standby_loop(spec, model, ring_in, out, killer)
        else:
            _prefill_loop(spec, model, ring_in, out, killer)
    except BrokenPipeError:
        os._exit(3)
    except Exception as e:  # noqa: BLE001 — report, then die loudly
        import traceback

        traceback.print_exc()
        try:
            out.push({"t": "fatal", "err": f"{type(e).__name__}: {e}"})
        except Exception:
            pass
        os._exit(5)
    finally:
        _HB_STOP.set()
    os._exit(0)


_TABLES = None


def handler_tables():
    """(decode, prefill, standby) dispatch tables, bound lazily.

    Lazy so this module's top level stays stdlib-only (the worker entry
    point must not drag numpy/jax in before the role is even known).
    EngineCluster calls this at construction — before any fork — so a
    spec message without a handler, or a stray ``_<role>_msg_*`` handler
    without a spec row, fails loudly in the parent process.
    """
    global _TABLES
    if _TABLES is None:
        from paddle_tpu.serving import protocol

        g = globals()
        _TABLES = (
            protocol.bind_handlers("decode", g, prefix="_decode_msg_",
                                   label="cluster_worker decode loop"),
            protocol.bind_handlers("prefill", g, prefix="_prefill_msg_",
                                   label="cluster_worker prefill loop"),
            protocol.bind_handlers("standby", g, prefix="_standby_msg_",
                                   label="cluster_worker standby park loop"))
    return _TABLES


if __name__ == "__main__":
    main()
