"""PP-YOLOE-class detector + PP-OCR-class recognizer (BASELINE.json rows).

Reference lineage: the PP-YOLO family (yolo_box decode,
paddle/phi/kernels/gpu/yolo_box_kernel.cu) and the PP-OCR recognition
pipeline (CRNN + warpctc, paddle/phi/kernels/gpu/warpctc_kernel.cu).
"""

import numpy as np
import pytest
import jax

import paddle_tpu as paddle
from paddle_tpu.vision.models import (
    CRNN, PPYoloDet, ctc_greedy_decode, ppocr_rec_tiny, ppyolo_tiny,
)


def test_detector_forward_shapes_and_decode():
    paddle.seed(0)
    model = ppyolo_tiny(num_classes=4)
    model.eval()
    B, H = 2, 64
    x = paddle.randn([B, 3, H, H])
    with paddle.no_grad():
        outs = model(x)
    assert len(outs) == 3
    per_anchor = 3
    for out, ds in zip(outs, model.downsample_ratios):
        assert tuple(out.shape) == (B, per_anchor * (5 + 4), H // ds, H // ds)
    boxes, scores = model.decode(outs, H)
    n = sum(per_anchor * (H // d) ** 2 for d in model.downsample_ratios)
    assert tuple(boxes.shape) == (B, n, 4)
    assert tuple(scores.shape) == (B, n, 4)  # [B, N, num_classes]
    assert np.isfinite(np.asarray(boxes._value)).all()


def test_detector_trains_and_jits():
    """A dense regression objective over the head maps decreases under the
    compiled TrainStep (detection-loss plumbing is model-external, like the
    reference's separate loss modules)."""
    from paddle_tpu.jit import TrainStep

    paddle.seed(1)
    model = ppyolo_tiny(num_classes=2)
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())

    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.normal(size=(2, 3, 64, 64)).astype(np.float32))

    def loss_fn(m, xb):
        outs = m(xb)
        return sum((o ** 2).mean() for o in outs)

    step = TrainStep(model, opt, loss_fn)
    losses = [float(step(x)._value) for _ in range(4)]
    assert losses[-1] < losses[0], losses


def test_crnn_shapes_ctc_loss_and_decode():
    paddle.seed(3)
    model = ppocr_rec_tiny(num_classes=10)
    model.eval()
    B, W = 2, 64
    x = paddle.randn([B, 3, 32, W])
    with paddle.no_grad():
        logp = model(x)
    assert tuple(logp.shape) == (B, W // 4, 11)
    # log-softmax rows sum to 1
    np.testing.assert_allclose(
        np.exp(np.asarray(logp._value)).sum(-1), 1.0, rtol=1e-4)

    labels = paddle.to_tensor(np.array([[1, 2, 3], [4, 5, 0]], np.int64))
    lens = paddle.to_tensor(np.array([3, 2], np.int64))
    loss = model.loss(logp, labels, lens)
    assert np.isfinite(float(loss._value)) and float(loss._value) > 0

    decoded = ctc_greedy_decode(logp)
    assert len(decoded) == B and all(isinstance(s, list) for s in decoded)


@pytest.mark.slow  # 121s: 60 eager train iterations to convergence — the
# heaviest single test in the fast tier (--durations); CRNN shape/CTC-loss/
# decode coverage stays fast via the two sibling tests below
def test_crnn_overfits_one_sample():
    """CTC training drives the greedy decode to the target sequence on a
    single fixed input — end-to-end recognition learning."""
    paddle.seed(5)
    model = ppocr_rec_tiny(num_classes=6)
    opt = paddle.optimizer.AdamW(5e-3, parameters=model.parameters())
    rng = np.random.default_rng(2)
    x = paddle.to_tensor(rng.normal(size=(1, 3, 32, 48)).astype(np.float32))
    target = [2, 4, 1]
    labels = paddle.to_tensor(np.array([target], np.int64))
    lens = paddle.to_tensor(np.array([3], np.int64))

    losses = []
    for _ in range(60):
        logp = model(x)
        loss = model.loss(logp, labels, lens)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss._value))
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])
    model.eval()
    with paddle.no_grad():
        decoded = ctc_greedy_decode(model(x))
    assert decoded[0] == target, (decoded, target)


def test_ctc_loss_matches_torch_oracle():
    """ctc_loss forward AND gradient against torch.nn.functional.ctc_loss
    (reference kernel lineage: warpctc)."""
    import torch
    import jax.numpy as jnp
    import paddle_tpu.nn.functional as F

    rng = np.random.default_rng(7)
    T, B, C = 10, 4, 6
    logits = rng.normal(size=(T, B, C)).astype(np.float32)
    labels = np.array([[2, 4, 1], [3, 3, 0], [5, 0, 0], [0, 0, 0]], np.int64)
    llens = np.array([3, 2, 1, 0], np.int64)   # incl. an EMPTY target
    ilens = np.array([10, 8, 10, 6], np.int64)

    lp_t = torch.log_softmax(torch.tensor(logits, requires_grad=True), dim=-1)
    lp_t.retain_grad()
    ref = torch.nn.functional.ctc_loss(
        lp_t, torch.tensor(labels), torch.tensor(ilens), torch.tensor(llens),
        blank=0, reduction="mean", zero_infinity=False)
    ref.backward()

    def ours(lg):
        lp = jax.nn.log_softmax(lg, axis=-1)
        with paddle.no_grad():
            return F.ctc_loss(
                paddle.Tensor(lp), paddle.to_tensor(labels),
                paddle.to_tensor(ilens), paddle.to_tensor(llens), blank=0,
                reduction="mean")._value

    got = float(ours(jnp.asarray(logits)))
    np.testing.assert_allclose(got, float(ref), rtol=1e-4, atol=1e-5)
    g = jax.grad(lambda lg: ours(lg))(jnp.asarray(logits))
    assert np.isfinite(np.asarray(g)).all()
    # torch grads flow to raw logits through its own log_softmax; compare
    # against torch's logits-gradient for the full chain
    torch_logits = torch.tensor(logits, requires_grad=True)
    ref2 = torch.nn.functional.ctc_loss(
        torch.log_softmax(torch_logits, dim=-1), torch.tensor(labels),
        torch.tensor(ilens), torch.tensor(llens), blank=0, reduction="mean")
    ref2.backward()
    np.testing.assert_allclose(np.asarray(g), torch_logits.grad.numpy(),
                               rtol=2e-3, atol=2e-4)
