"""Numerics for the round-2 nn-surface closure: losses, pooling masks,
spatial transformers, beam search, LBFGS, saved-tensor hooks.

Reference parity targets cited per test (python/paddle/nn/...).
"""

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F


def test_submodule_surfaces_complete(reference_source):
    import importlib
    import re

    pairs = [
        ("nn", "nn/__init__.py"),
        ("nn.functional", "nn/functional/__init__.py"),
        ("nn.initializer", "nn/initializer/__init__.py"),
        ("static", "static/__init__.py"),
        ("jit", "jit/__init__.py"),
        ("autograd", "autograd/__init__.py"),
        ("optimizer", "optimizer/__init__.py"),
        ("amp", "amp/__init__.py"),
        ("vision.ops", "vision/ops.py"),
        ("incubate.nn.functional", "incubate/nn/functional/__init__.py"),
    ]
    for name, path in pairs:
        src = reference_source(path)
        m = re.search(r"__all__ = \[(.*?)\]", src, re.S)
        if not m:
            continue
        ref = set(re.findall(r"'([^']+)'", m.group(1)))
        mod = importlib.import_module(f"paddle_tpu.{name}")
        missing = sorted(n for n in ref if not hasattr(mod, n))
        assert not missing, f"paddle.{name} missing {missing}"


def test_max_pool_mask_and_unpool_roundtrip():
    rng = np.random.default_rng(3)
    x = paddle.to_tensor(rng.standard_normal((2, 3, 8, 8)).astype(np.float32))
    out, mask = F.max_pool2d(x, 2, return_mask=True)
    ref = np.asarray(x._value).reshape(2, 3, 4, 2, 4, 2).max(axis=(3, 5))
    np.testing.assert_allclose(np.asarray(out._value), ref, rtol=1e-6)
    # indices address the original map
    flat = np.asarray(x._value).reshape(2, 3, -1)
    gathered = np.take_along_axis(flat, np.asarray(mask._value).reshape(2, 3, -1), axis=2)
    np.testing.assert_allclose(gathered.reshape(ref.shape), ref, rtol=1e-6)
    unp = F.max_unpool2d(out, mask, 2)
    assert unp.shape == [2, 3, 8, 8]
    np.testing.assert_allclose(np.asarray(unp._value).sum(), ref.sum(), rtol=1e-5)
    # layer forms
    o1, m1 = F.max_pool1d(paddle.to_tensor(rng.standard_normal((2, 3, 8)).astype(np.float32)), 2, return_mask=True)
    assert paddle.nn.MaxUnPool1D(2)(o1, m1).shape == [2, 3, 8]


def test_affine_grid_sample_shift():
    # translation by one pixel in x (align_corners grid step = 2/(W-1))
    x = paddle.to_tensor(np.arange(16).reshape(1, 1, 4, 4).astype(np.float32))
    shift = 2.0 / 3.0
    theta = paddle.to_tensor(np.array([[[1, 0, shift], [0, 1, 0]]], np.float32))
    grid = F.affine_grid(theta, [1, 1, 4, 4], align_corners=True)
    y = np.asarray(F.grid_sample(x, grid, align_corners=True)._value)
    ref = np.asarray(x._value)
    np.testing.assert_allclose(y[0, 0, :, :3], ref[0, 0, :, 1:], atol=1e-4)
    np.testing.assert_allclose(y[0, 0, :, 3], 0.0, atol=1e-5)  # zeros padding


def test_multi_margin_and_triplet_with_distance():
    logits = paddle.to_tensor(np.array([[0.1, 0.9, 0.2]], np.float32))
    label = paddle.to_tensor(np.array([1], np.int64))
    loss = float(F.multi_margin_loss(logits, label)._value)
    ref = (max(0, 1 - 0.9 + 0.1) + max(0, 1 - 0.9 + 0.2)) / 3
    assert abs(loss - ref) < 1e-6
    a = paddle.to_tensor(np.zeros((2, 4), np.float32))
    p = paddle.to_tensor(np.ones((2, 4), np.float32) * 0.1)
    n = paddle.to_tensor(np.ones((2, 4), np.float32))
    # d_pos=0.2, d_neg=2, margin=1 -> max(0, 0.2-2+1)=0
    assert float(F.triplet_margin_with_distance_loss(a, p, n)._value) == 0.0
    # swapped roles: d_pos=2, d_neg=0.2 -> 2-0.2+1=2.8
    l1 = float(F.triplet_margin_with_distance_loss(a, n, p)._value)
    assert abs(l1 - 2.8) < 1e-5
    layer = paddle.nn.TripletMarginWithDistanceLoss()
    assert abs(float(layer(a, n, p)._value) - l1) < 1e-6


def test_hsigmoid_loss_decreases_under_training():
    paddle.seed(0)
    B, D, C = 8, 6, 5
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((B, D)).astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, C, (B,)).astype(np.int64))
    layer = paddle.nn.HSigmoidLoss(D, C)
    opt = paddle.optimizer.SGD(0.5, parameters=layer.parameters())
    losses = []
    for _ in range(30):
        loss = layer(x, y).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7, losses[:3] + losses[-3:]


def test_rnnt_loss_gradient_and_value():
    paddle.seed(0)
    B, T, U, D = 2, 4, 2, 5
    rng = np.random.default_rng(1)
    logits = paddle.to_tensor(rng.standard_normal((B, T, U + 1, D)).astype(np.float32), stop_gradient=False)
    label = paddle.to_tensor(rng.integers(1, D, (B, U)).astype(np.int32))
    tl = paddle.to_tensor(np.array([T, T], np.int32))
    ul = paddle.to_tensor(np.array([U, U], np.int32))
    loss = F.rnnt_loss(logits, label, tl, ul, blank=0, fastemit_lambda=0.0)
    assert float(loss) > 0
    loss.backward()
    g = np.asarray(logits.grad._value)
    assert np.isfinite(g).all() and np.abs(g).sum() > 0
    # degenerate exact check: T=1, U=0 -> loss = -log softmax(blank)
    lg = paddle.to_tensor(rng.standard_normal((1, 1, 1, 3)).astype(np.float32))
    l2 = F.rnnt_loss(lg, paddle.to_tensor(np.zeros((1, 0), np.int32)),
                     paddle.to_tensor(np.array([1], np.int32)),
                     paddle.to_tensor(np.array([0], np.int32)), blank=0, fastemit_lambda=0.0)
    lv = np.asarray(lg._value)[0, 0, 0]
    ref = -(lv[0] - np.log(np.exp(lv).sum()))
    assert abs(float(l2) - ref) < 1e-5


def test_npair_and_margin_cross_entropy():
    rng = np.random.default_rng(2)
    a = paddle.to_tensor(rng.standard_normal((4, 8)).astype(np.float32))
    p = paddle.to_tensor(rng.standard_normal((4, 8)).astype(np.float32))
    y = paddle.to_tensor(np.array([0, 1, 2, 3], np.int64))
    assert float(F.npair_loss(a, p, y)._value) > 0
    # margin CE with zero margins == scaled softmax CE
    cosines = paddle.to_tensor((rng.standard_normal((4, 10)) * 0.3).astype(np.float32))
    loss = F.margin_cross_entropy(cosines, y, margin1=1.0, margin2=0.0, margin3=0.0, scale=4.0)
    lv = np.asarray(cosines._value) * 4.0
    ref = -(lv[np.arange(4), [0, 1, 2, 3]] - np.log(np.exp(lv).sum(1)))
    assert abs(float(loss) - ref.mean()) < 1e-5


def test_class_center_sample():
    y = paddle.to_tensor(np.array([3, 7, 3, 1], np.int64))
    remapped, sampled = F.class_center_sample(y, 20, 6)
    sv = np.asarray(sampled._value)
    rv = np.asarray(remapped._value)
    assert len(sv) == 6 and len(set(sv.tolist())) == 6
    for orig, rm in zip([3, 7, 3, 1], rv):
        assert sv[rm] == orig


def test_beam_search_decoder_greedy_consistency():
    """Beam width 1 must equal greedy argmax decoding."""
    paddle.seed(0)
    V, E, H = 12, 8, 16
    emb = paddle.nn.Embedding(V, E)
    cell = paddle.nn.GRUCell(E, H)
    proj = paddle.nn.Linear(H, V)
    dec = paddle.nn.BeamSearchDecoder(cell, start_token=0, end_token=1, beam_size=1,
                                      embedding_fn=emb, output_fn=proj)
    h0 = paddle.zeros([2, H])
    seqs, _ = paddle.nn.dynamic_decode(dec, inits=h0, max_step_num=5)
    out = np.asarray(seqs._value)
    # greedy reference
    ids = np.zeros(2, np.int64)
    h = h0
    toks = []
    for _ in range(out.shape[1]):  # [batch, time, beam]
        x = emb(paddle.to_tensor(ids.astype(np.int64)))
        o, h = cell(x, h)
        logits = np.asarray(proj(o)._value)
        ids = logits.argmax(-1)
        toks.append(ids)
    ref = np.stack(toks, -1)
    # reference layout: [batch, time, beam]
    np.testing.assert_array_equal(out[:, :, 0], ref)


def test_gather_tree():
    # the reference's documented example (python/paddle/nn/functional/
    # extension.py gather_tree docstring)
    ids = paddle.to_tensor(np.array([[[2, 2], [6, 1]], [[3, 9], [6, 1]], [[0, 1], [9, 0]]], np.int64))
    parents = paddle.to_tensor(np.array([[[0, 0], [1, 1]], [[1, 0], [1, 0]], [[0, 0], [0, 1]]], np.int64))
    out = np.asarray(F.gather_tree(ids, parents)._value)
    ref = np.array([[[2, 2], [1, 6]], [[3, 3], [6, 1]], [[0, 1], [9, 0]]])
    np.testing.assert_array_equal(out, ref)


def test_sparse_attention_matches_masked_dense():
    rng = np.random.default_rng(5)
    B, H, S, D = 1, 1, 4, 8
    q = paddle.to_tensor(rng.standard_normal((B, H, S, D)).astype(np.float32))
    k = paddle.to_tensor(rng.standard_normal((B, H, S, D)).astype(np.float32))
    v = paddle.to_tensor(rng.standard_normal((B, H, S, D)).astype(np.float32))
    # causal CSR pattern
    cols, offs = [], [0]
    for i in range(S):
        cols.extend(range(i + 1))
        offs.append(len(cols))
    off = paddle.to_tensor(np.array([[offs]], np.int32))
    col = paddle.to_tensor(np.array([[cols]], np.int32))
    out = np.asarray(F.sparse_attention(q, k, v, off, col)._value)
    ref = np.asarray(F.scaled_dot_product_attention(
        paddle.transpose(q, [0, 2, 1, 3]), paddle.transpose(k, [0, 2, 1, 3]),
        paddle.transpose(v, [0, 2, 1, 3]), is_causal=True)._value).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_saved_tensors_hooks_pack_unpack():
    calls = {"pack": 0, "unpack": 0}

    def pack(t):
        calls["pack"] += 1
        return np.asarray(t._value)  # "offload to host"

    def unpack(h):
        calls["unpack"] += 1
        return paddle.to_tensor(h)

    class Sq(paddle.autograd.PyLayer):
        @staticmethod
        def forward(ctx, x):
            ctx.save_for_backward(x)
            return x * x

        @staticmethod
        def backward(ctx, g):
            (x,) = ctx.saved_tensor
            return g * 2 * x

    x = paddle.to_tensor(np.array([3.0], np.float32), stop_gradient=False)
    with paddle.autograd.saved_tensors_hooks(pack, unpack):
        y = Sq.apply(x)
    y.backward()
    assert calls["pack"] == 1 and calls["unpack"] == 1
    np.testing.assert_allclose(np.asarray(x.grad._value), [6.0])


def test_lbfgs_converges_to_lstsq():
    paddle.seed(0)
    A = paddle.to_tensor(np.random.default_rng(0).standard_normal((10, 5)).astype(np.float32))
    b = paddle.to_tensor(np.random.default_rng(1).standard_normal((10,)).astype(np.float32))
    x = paddle.create_parameter([5], "float32")
    opt = paddle.optimizer.LBFGS(parameters=[x], line_search_fn="strong_wolfe")

    def closure():
        r = paddle.matmul(A, x) - b
        loss = (r * r).sum()
        loss.backward()
        return loss

    for _ in range(5):
        loss = opt.step(closure)
    ref = np.linalg.lstsq(np.asarray(A._value), np.asarray(b._value), rcond=None)[0]
    np.testing.assert_allclose(np.asarray(x._value), ref, atol=1e-3)


def test_static_compat_surface():
    bs = paddle.static.BuildStrategy()
    bs.fuse_bn_act_ops = True  # settable
    es = paddle.static.ExecutionStrategy()
    assert es.num_threads == 1
    places = paddle.static.cuda_places()
    assert len(places) >= 1
    gv = paddle.static.create_global_var([2, 2], 1.5, "float32")
    np.testing.assert_allclose(np.asarray(gv._value), np.full((2, 2), 1.5))
    with pytest.raises(RuntimeError):
        paddle.static.IpuStrategy()
    # EMA swap/restore
    p = paddle.create_parameter([2], "float32", default_initializer=paddle.nn.initializer.Constant(1.0))
    ema = paddle.static.ExponentialMovingAverage(decay=0.5)
    ema.update([p])
    p._bind((p._value * 0 + 3.0))
    ema.update([p])
    before = np.asarray(p._value).copy()
    ema.apply(need_restore=False)
    np.testing.assert_allclose(np.asarray(p._value), [2.0, 2.0])  # 0.5*1 + 0.5*3
    ema.restore()
    np.testing.assert_allclose(np.asarray(p._value), before)


def test_py_func_and_print():
    x = paddle.to_tensor(np.array([1.0, 2.0], np.float32))
    out = paddle.static.py_func(lambda a: a * 3.0, x, paddle.zeros([2]))
    np.testing.assert_allclose(np.asarray(out._value), [3.0, 6.0])
    y = paddle.static.Print(x, message="dbg")
    np.testing.assert_allclose(np.asarray(y._value), np.asarray(x._value))


def test_py_func_custom_backward():
    # backward_func receives (x, out, out_grad) and returns dx; the custom
    # rule deliberately disagrees with the analytic grad (returns 10*g)
    # so the test proves backward_func is actually used.
    x = paddle.to_tensor(np.array([1.0, 2.0], np.float32), stop_gradient=False)
    out = paddle.static.py_func(
        lambda a: a * 3.0,
        x,
        paddle.zeros([2]),
        backward_func=lambda a, o, g: 10.0 * g,
    )
    out.sum().backward()
    np.testing.assert_allclose(np.asarray(x.grad._value), [10.0, 10.0])


def test_bilinear_and_global_initializer():
    init = paddle.nn.initializer.Bilinear()
    w = init._init_value((1, 1, 4, 4), np.float32)
    assert float(np.asarray(w).max()) <= 1.0 and np.asarray(w)[0, 0, 1, 1] > 0.5
    paddle.nn.initializer.set_global_initializer(paddle.nn.initializer.Constant(0.25))
    try:
        lin = paddle.nn.Linear(3, 3)
        np.testing.assert_allclose(np.asarray(lin.weight._value), np.full((3, 3), 0.25))
    finally:
        paddle.nn.initializer.set_global_initializer(None)


def test_temporal_shift_and_unflatten_layer():
    x = paddle.to_tensor(np.arange(2 * 4 * 2 * 2, dtype=np.float32).reshape(2, 4, 2, 2))
    out = F.temporal_shift(x, seg_num=2, shift_ratio=0.25)
    assert out.shape == [2, 4, 2, 2]
    u = paddle.nn.Unflatten(1, [2, 2])
    assert u(x).shape == [2, 2, 2, 2, 2]


def test_remaining_submodule_surfaces_complete():
    """Every remaining reference submodule __all__ resolves (incubate tier,
    utils, audio, vision incl. transforms, profiler, device, fleet)."""
    import importlib
    import os
    import re

    pairs = [
        ("incubate", "incubate/__init__.py"),
        ("incubate.nn", "incubate/nn/__init__.py"),
        ("incubate.optimizer", "incubate/optimizer/__init__.py"),
        ("incubate.autograd", "incubate/autograd/__init__.py"),
        ("utils", "utils/__init__.py"),
        ("audio", "audio/__init__.py"),
        ("vision", "vision/__init__.py"),
        ("vision.transforms", "vision/transforms/__init__.py"),
        ("profiler", "profiler/__init__.py"),
        ("device", "device/__init__.py"),
        ("distributed.fleet", "distributed/fleet/__init__.py"),
    ]
    for name, path in pairs:
        fp = f"/root/reference/python/paddle/{path}"
        if not os.path.exists(fp):
            continue
        m = re.search(r"__all__ = \[(.*?)\]", open(fp).read(), re.S)
        if not m:
            continue
        ref = set(re.findall(r'"([^"]+)"', m.group(1))) | set(re.findall(r"'([^']+)'", m.group(1)))
        mod = importlib.import_module(f"paddle_tpu.{name}")
        missing = sorted(n for n in ref if not hasattr(mod, n))
        assert not missing, f"paddle.{name} missing {missing}"


def test_vision_transform_numerics():
    from paddle_tpu.vision import transforms as T

    img = (np.random.default_rng(0).random((16, 16, 3)) * 255).astype(np.uint8)
    np.testing.assert_allclose(T.rotate(img, 90), np.rot90(img, 1, axes=(0, 1)))
    assert np.abs(T.adjust_hue(img, 0.0).astype(np.float32) - img).max() < 1e-2
    # hue shift by 1/3 permutes pure channels: red -> green
    red = np.zeros((2, 2, 3), np.float32)
    red[..., 0] = 1.0
    shifted = T.adjust_hue(red, 1.0 / 3.0)
    np.testing.assert_allclose(shifted[..., 1], 1.0, atol=1e-5)
    a = T.affine(img, 0, (2, 0), 1.0, (0, 0))
    assert np.array_equal(a[:, 2:], img[:, :-2])
    e = T.erase(img, 2, 3, 4, 5, 0)
    assert (e[2:6, 3:8] == 0).all() and np.array_equal(e[10:], img[10:])
    b = T.adjust_brightness(img, 2.0)
    assert b.max() <= 255.0 and b.mean() >= img.mean()
    out = T.RandomErasing(prob=1.0)(img)
    assert out.shape == img.shape
    rp = T.perspective(img, [(0, 0), (15, 0), (15, 15), (0, 15)], [(0, 0), (15, 0), (15, 15), (0, 15)])
    np.testing.assert_allclose(rp, img)  # identity homography


def test_incubate_autograd_jvp_vjp():
    import paddle_tpu.incubate.autograd as ag

    x = paddle.to_tensor(np.array([2.0], np.float32))

    def f(a):
        return a * a

    primal, tangent = ag.jvp(f, [x], [paddle.to_tensor(np.array([1.0], np.float32))])
    np.testing.assert_allclose(np.asarray(primal[0]._value), [4.0])
    np.testing.assert_allclose(np.asarray(tangent[0]._value), [4.0])  # 2x
    primal, grads = ag.vjp(f, [x])
    np.testing.assert_allclose(np.asarray(grads[0]._value), [4.0])
    assert ag.prim_enabled()


def test_incubate_top_level_ops():
    import paddle_tpu.incubate as inc

    x = paddle.to_tensor(np.random.default_rng(0).standard_normal((2, 4, 4)).astype(np.float32))
    mask = paddle.zeros([2, 4, 4])
    out = inc.softmax_mask_fuse(x, mask)
    np.testing.assert_allclose(np.asarray(out._value).sum(-1), 1.0, rtol=1e-5)
    tri = inc.softmax_mask_fuse_upper_triangle(x)
    tv = np.asarray(tri._value)
    assert tv[0, 0, 1] == 0.0 and abs(tv[0, 0, 0] - 1.0) < 1e-6  # causal row 0
    data = paddle.to_tensor(np.array([[1.0], [2.0], [3.0]], np.float32))
    seg = paddle.to_tensor(np.array([0, 0, 1], np.int32))
    np.testing.assert_allclose(np.asarray(inc.segment_sum(data, seg)._value), [[3.0], [3.0]])
    assert float(inc.identity_loss(x, "sum")._value) == pytest.approx(float(np.asarray(x._value).sum()), rel=1e-5)


def test_fused_layer_classes():
    import paddle_tpu.incubate.nn as inn

    paddle.seed(0)
    lin = inn.FusedLinear(8, 16)
    y = lin(paddle.ones([2, 8]))
    assert y.shape == [2, 16]
    da = inn.FusedDropoutAdd(p=0.0)
    z = da(paddle.ones([2, 4]), paddle.ones([2, 4]))
    np.testing.assert_allclose(np.asarray(z._value), 2.0)
    bd = inn.FusedBiasDropoutResidualLayerNorm(8, dropout_rate=0.0)
    o = bd(paddle.ones([2, 3, 8]), paddle.ones([2, 3, 8]))
    assert np.abs(np.asarray(o._value).mean()) < 1e-5  # LN zero-means
    # FusedEcMoe: reference forward contract is per-token gate logits
    moe = inn.FusedEcMoe(8, 16, 4)
    x = paddle.to_tensor(np.random.default_rng(0).standard_normal((2, 3, 8)).astype(np.float32))
    gate = paddle.to_tensor(np.random.default_rng(1).standard_normal((2, 3, 4)).astype(np.float32))
    out = moe(x, gate)
    assert out.shape == [2, 3, 8] and np.isfinite(np.asarray(out._value)).all()


def test_device_predicates_and_fleet_util():
    import paddle_tpu.device as dev

    assert dev.is_compiled_with_cuda() is False
    assert dev.is_compiled_with_distribute() is True
    assert dev.get_cudnn_version() is None
    with pytest.raises(RuntimeError):
        dev.XPUPlace(0)
    import paddle_tpu.distributed.fleet as fleet

    assert fleet.util.get_file_shard(["a", "b", "c"]) == ["a", "b", "c"]  # world 1
    f = fleet.Fleet()
    assert callable(f.init)


def test_utils_trio():
    import paddle_tpu.utils as U

    assert U.try_import("math") is not None
    with pytest.raises(ImportError):
        U.try_import("definitely_not_a_module_xyz")
    assert U.require_version("0.1.0")
    with pytest.raises(Exception):
        U.require_version("99.0.0")
    calls = []

    @U.deprecated(update_to="new_fn", since="0.2")
    def old_fn():
        calls.append(1)
        return 7

    import warnings

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert old_fn() == 7
        assert any("deprecated" in str(x.message) for x in w)
