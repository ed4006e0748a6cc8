"""Eager dispatch fast path (FLAGS_eager_op_jit, _core/dispatch.py).

The cache must be observationally invisible: every covered behavior is
checked bit-identical against the flag-off slow path — forward, backward,
AMP auto_cast, tensor hooks, create_graph double backward, RNG streams —
while the counters prove the fast path actually serves hits.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu._core import autograd, dispatch


@pytest.fixture(autouse=True)
def _fresh_cache():
    paddle.set_flags({"FLAGS_eager_op_jit": True})
    dispatch.cache.clear()
    dispatch.cache.reset_stats()
    yield
    paddle.set_flags({"FLAGS_eager_op_jit": True})


def _stats():
    return dispatch.cache.stats()


def _x(shape=(3, 4), seed=0, grad=False):
    rng = np.random.default_rng(seed)
    return paddle.to_tensor(rng.standard_normal(shape).astype(np.float32),
                            stop_gradient=not grad)


# ------------------------------------------------------------------ counters


def test_hit_miss_counters_across_signatures():
    x = _x(grad=True)
    w = _x((4, 4), seed=1, grad=True)

    def step():
        x.clear_grad(); w.clear_grad()
        paddle.matmul(x, w).sum().backward()

    step()
    s0 = _stats()
    assert s0["misses"] >= 1
    # hits count compiled-path serves only: the hotness ramp (2 eager-served
    # repeats) shows up as bypasses, then call 4+ hits the jitted trace
    for _ in range(4):
        step()
    s1 = _stats()
    assert s1["hits"] > s0["hits"]
    assert s1["bypasses"] > s0["bypasses"]

    # new shape => new signature => miss, not a wrong-shape hit
    x8 = _x((8, 4), seed=2, grad=True)
    x8.clear_grad(); w.clear_grad()
    paddle.matmul(x8, w).sum().backward()
    assert _stats()["misses"] > s1["misses"]

    # new dtype => new signature
    before = _stats()["misses"]
    a16 = paddle.to_tensor(np.ones((3, 4), np.float32)).astype("bfloat16")
    b16 = paddle.to_tensor(np.ones((4, 4), np.float32)).astype("bfloat16")
    paddle.matmul(a16, b16)
    assert _stats()["misses"] > before

    # changed static closure value (transpose_y) => new signature
    before = _stats()["misses"]
    paddle.matmul(x, w, transpose_y=True)
    assert _stats()["misses"] > before


def test_grad_path_traces_amortized():
    x = _x(grad=True)
    for _ in range(6):
        x.clear_grad()
        paddle.tanh(x).sum().backward()
    s = _stats()
    # tanh fwd+vjp traced once, backward application traced once; the
    # remaining five iterations are hits without retraces
    assert s["hits"] >= 5
    assert s["traces"] <= 4, s


# ------------------------------------------------------- numerics parity


def _train_trace(steps=4):
    paddle.seed(0)
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as opt

    m = nn.Sequential(nn.Linear(6, 8), nn.Tanh(), nn.Linear(8, 2))
    o = opt.SGD(learning_rate=0.05, parameters=m.parameters())
    x = paddle.to_tensor(np.arange(12, dtype=np.float32).reshape(2, 6) / 12.0)
    y = paddle.to_tensor(np.ones((2, 2), np.float32))
    losses = []
    for _ in range(steps):
        loss = ((m(x) - y) ** 2).mean()
        loss.backward()
        o.step()
        o.clear_grad()
        losses.append(np.asarray(loss._value).item())
    return losses


def test_forward_backward_bit_identical_on_off():
    paddle.set_flags({"FLAGS_eager_op_jit": True})
    _train_trace()  # populate: a signature's fourth call compiles it
    on = _train_trace()
    on2 = _train_trace()  # both runs: all cache hits
    paddle.set_flags({"FLAGS_eager_op_jit": False})
    off = _train_trace()
    # one compiled program run twice: bit-identical
    assert on == on2
    # jitted op against eager op: two XLA programs of the same arithmetic,
    # which may fuse (and so round) differently.  4 ulp of float32
    np.testing.assert_array_max_ulp(np.asarray(on, np.float32),
                                    np.asarray(off, np.float32), maxulp=4)


def test_amp_auto_cast_bit_identical_on_off():
    def run():
        with paddle.amp.auto_cast():
            a = _x((4, 8), grad=True)
            b = _x((8, 8), seed=1, grad=True)
            out = paddle.matmul(a, paddle.exp(b) * 0.1)
            out2 = paddle.matmul(a, paddle.exp(b) * 0.1)  # cached on 2nd run
            loss = out.astype("float32").sum() + out2.astype("float32").sum()
            loss.backward()
        return (np.asarray(out._value).copy(), np.asarray(a.grad._value).copy(),
                np.asarray(b.grad._value).copy(), str(out.dtype))

    paddle.set_flags({"FLAGS_eager_op_jit": True})
    run()  # populate
    on = run()
    paddle.set_flags({"FLAGS_eager_op_jit": False})
    off = run()
    assert on[3] == off[3]
    for a, b in zip(on[:3], off[:3]):
        np.testing.assert_array_equal(a, b)


def test_tensor_hooks_bit_identical_on_off():
    def run():
        x = _x((5,), grad=True)
        x.register_hook(lambda g: g * 3)
        (x * 2.0).sum().backward()
        return np.asarray(x.grad._value).copy()

    paddle.set_flags({"FLAGS_eager_op_jit": True})
    run()
    on = run()
    paddle.set_flags({"FLAGS_eager_op_jit": False})
    off = run()
    np.testing.assert_array_equal(on, off)
    np.testing.assert_array_equal(on, np.full(5, 6.0, np.float32))


def test_create_graph_double_backward_bypasses_cache():
    def run():
        x = paddle.to_tensor(np.array([2.0, 3.0], np.float32), stop_gradient=False)
        y = (x * x * x).sum()
        (g,) = paddle.grad(y, x, create_graph=True)
        g.sum().backward()
        return np.asarray(x.grad._value).copy()

    paddle.set_flags({"FLAGS_eager_op_jit": True})
    run()
    before = _stats()
    on = run()
    after = _stats()
    # the _vjp_through_tape rebuild closes over the GradNode — uncacheable,
    # so the second-order walk bypasses rather than hitting a frozen trace
    assert after["bypasses"] > before["bypasses"]
    paddle.set_flags({"FLAGS_eager_op_jit": False})
    off = run()
    np.testing.assert_array_equal(on, off)
    np.testing.assert_array_equal(on, np.array([12.0, 18.0], np.float32))


def test_rng_stream_identical_on_off():
    """Stateful RNG inside op bodies must neither freeze nor drift: the
    cached-trace guard aborts such traces before a counter tick."""
    x = _x((16, 16))

    def run():
        paddle.seed(42)
        a = np.asarray(F.dropout(x, 0.5, training=True)._value).copy()
        b = np.asarray(F.rrelu(-x, training=True)._value).copy()
        c = np.asarray(F.dropout(x, 0.5, training=True)._value).copy()
        return a, b, c

    paddle.set_flags({"FLAGS_eager_op_jit": True})
    run()  # populate / mark bypasses
    on = run()
    assert not np.array_equal(on[0], on[2])  # randomness advances
    paddle.set_flags({"FLAGS_eager_op_jit": False})
    off = run()
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------------------ fn identity


def test_no_crosstalk_between_equal_code_different_closures():
    x = _x()

    def make(c):
        return lambda v: v * c

    a = autograd.apply("xtalk_scale", make(2.0), x)
    b = autograd.apply("xtalk_scale", make(3.0), x)
    a2 = autograd.apply("xtalk_scale", make(2.0), x)  # hits a's entry
    np.testing.assert_array_equal(np.asarray(a._value), np.asarray(x._value) * 2.0)
    np.testing.assert_array_equal(np.asarray(b._value), np.asarray(x._value) * 3.0)
    np.testing.assert_array_equal(np.asarray(a2._value), np.asarray(a._value))


def test_mutated_recording_closure_does_not_poison_cache():
    """The jit must be built from the fn of the call that crosses the
    hotness ramp, not the recording call's pinned fn: mutating a container
    the first closure referenced must not leak into later equal-keyed
    calls."""
    x = _x()

    def make(lst):
        return lambda v: v * lst[0]

    shared = [2.0]
    autograd.apply("mut_close", make(shared), x)  # records with value 2.0
    shared[0] = 5.0  # caller mutates the recorded closure's list
    for _ in range(4):  # fresh equal-valued closures: ramp then compile
        r = autograd.apply("mut_close", make([2.0]), x)
    np.testing.assert_array_equal(np.asarray(r._value), np.asarray(x._value) * 2.0)


def test_no_crosstalk_between_ops_sharing_fn():
    x = _x()
    import jax.numpy as jnp

    r1 = autograd.apply("op_one", jnp.negative, x)
    r2 = autograd.apply("op_two", jnp.negative, x)  # same fn, different name
    np.testing.assert_array_equal(np.asarray(r1._value), np.asarray(r2._value))
    assert _stats()["misses"] >= 2  # separate entries per op name


# ------------------------------------------------------- flags / lifecycle


def test_set_flags_clears_cache_and_restores_slow_path():
    x = _x(grad=True)
    for _ in range(2):
        x.clear_grad()
        paddle.tanh(x).sum().backward()
    assert _stats()["size"] > 0
    paddle.set_flags({"FLAGS_eager_op_jit": False})
    assert _stats()["size"] == 0  # invalidated
    dispatch.cache.reset_stats()
    x.clear_grad()
    paddle.tanh(x).sum().backward()
    s = _stats()
    # flag off: the funnel never consults the cache — exact pre-PR dispatch
    assert s["hits"] == s["misses"] == s["bypasses"] == 0 and not s["enabled"]
    np.testing.assert_allclose(np.asarray(x.grad._value),
                               1.0 - np.tanh(np.asarray(x._value)) ** 2,
                               rtol=1e-6)


def test_noop_set_flags_does_not_invalidate():
    x = _x(grad=True)
    for _ in range(4):
        x.clear_grad()
        paddle.tanh(x).sum().backward()
    size = _stats()["size"]
    assert size > 0
    # re-setting a flag to its current value must NOT wipe compiled traces
    paddle.set_flags({"FLAGS_check_nan_inf": False})
    paddle.set_flags({"FLAGS_eager_op_jit": True})
    assert _stats()["size"] == size


def test_cache_size_flag_bounds_entries_with_lru_eviction():
    paddle.set_flags({"FLAGS_eager_op_cache_size": 3})
    try:
        dispatch.cache.reset_stats()
        for n in range(2, 10):
            w = paddle.to_tensor(np.ones((n,), np.float32), stop_gradient=False)
            paddle.tanh(w).sum().backward()
        s = _stats()
        assert s["size"] <= 3
        assert s["evictions"] > 0
        assert s["capacity"] == 3
    finally:
        paddle.set_flags({"FLAGS_eager_op_cache_size": 1024})


def test_profiler_exposes_cache_stats():
    from paddle_tpu import profiler

    x = _x()
    for _ in range(3):
        F.softmax(x, axis=-1)
    s = profiler.dispatch_cache_stats()
    for key in ("hits", "misses", "traces", "evictions", "bypasses", "size",
                "capacity", "enabled"):
        assert key in s
    assert s["misses"] >= 1

    p = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU])
    with p:
        F.softmax(x, axis=-1)
    table = p.summary()
    assert "Eager dispatch cache" in table

    profiler.reset_dispatch_cache()
    s2 = profiler.dispatch_cache_stats()
    assert s2["size"] == 0 and s2["hits"] == 0


# ----------------------------------------------------- transparency edges


def test_data_dependent_shape_op_falls_back():
    x = paddle.to_tensor(np.array([[1.0, 0.0], [0.0, 2.0]], np.float32))

    def masked(v):
        import jax.numpy as jnp

        return v[np.asarray(v) > 0]  # numpy peek: untraceable, eager-only

    # call enough times to cross the hotness threshold so the jit attempt
    # actually fires (and fails -> entry marked eager-only)
    rs = [autograd.apply("data_dep", masked, x) for _ in range(5)]
    for r in rs[1:]:
        np.testing.assert_array_equal(np.asarray(rs[0]._value), np.asarray(r._value))


def test_pytree_roundtrip_restores_dist_slots():
    """_unflatten must initialize process_mesh/placements: a Tensor coming
    back from a jit/tree_map round-trip supports is_dist()."""
    import jax

    t = paddle.ones([2, 2])
    (rt,) = jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(lambda v: v, t))
    t2 = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(t), [rt])
    assert t2.is_dist() is False
    assert t2.process_mesh is None and t2.placements is None

    p = paddle.create_parameter([2, 2], "float32")
    flat, treedef = jax.tree_util.tree_flatten(p)
    p2 = jax.tree_util.tree_unflatten(treedef, flat)
    assert p2.is_dist() is False

    @jax.jit
    def ident(x):
        return x

    t3 = ident(t)
    assert t3.is_dist() is False


# ---------------------------------------------------- scan-body identity guard


def test_scan_body_guard_warns_on_body_shared_across_jit_traces():
    """FLAGS_scan_body_guard: the same lax.scan body function object traced
    under two distinct jit entries poisons jax's scan-jaxpr cache (PR 3,
    docs/SCAN_LAYERS.md) — the dev-mode guard must warn."""
    import warnings

    import jax
    import jax.numpy as jnp

    paddle.set_flags({"FLAGS_scan_body_guard": True})
    try:
        def shared_body(c, x):  # ONE body object, reused across traces
            return c + x, c

        def run(xs):
            return jax.lax.scan(shared_body, jnp.zeros(()), xs)[0]

        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            jax.jit(run)(jnp.ones(4))  # first trace: no warning
            assert not any(isinstance(w.message, dispatch.ScanBodyReuseWarning)
                           for w in caught)
            jax.jit(lambda xs: run(xs) * 2)(jnp.ones(4))  # second trace
        assert any(isinstance(w.message, dispatch.ScanBodyReuseWarning)
                   for w in caught), "shared scan body not flagged"
    finally:
        paddle.set_flags({"FLAGS_scan_body_guard": False})


def test_scan_body_guard_quiet_for_fresh_bodies_and_when_off():
    import warnings

    import jax
    import jax.numpy as jnp

    paddle.set_flags({"FLAGS_scan_body_guard": True})
    try:
        def run(xs):
            def body(c, x):  # defined INSIDE the traced fn — the fix
                return c + x, c

            return jax.lax.scan(body, jnp.zeros(()), xs)[0]

        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            jax.jit(run)(jnp.ones(4))
            jax.jit(lambda xs: run(xs) * 2)(jnp.ones(4))
        assert not any(isinstance(w.message, dispatch.ScanBodyReuseWarning)
                       for w in caught)
    finally:
        paddle.set_flags({"FLAGS_scan_body_guard": False})

    # flag off: a shared body stays silent (guard is dev-mode only)
    def shared(c, x):
        return c + x, c

    def run2(xs):
        return jax.lax.scan(shared, jnp.zeros(()), xs)[0]

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        jax.jit(run2)(jnp.ones(4))
        jax.jit(lambda xs: run2(xs) * 3)(jnp.ones(4))
    assert not any(isinstance(w.message, dispatch.ScanBodyReuseWarning)
                   for w in caught)
