"""PP-YOLO-class detection + PP-OCR-class recognition walkthrough.

Run (CPU): python examples/detect_and_ocr.py
Shows the BASELINE.json row-4 model families end to end: a detector forward
with yolo_box decode, and a CRNN recognizer trained with CTC until its
greedy decode emits the target sequence.
"""

import sys
import os

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.vision.models import ctc_greedy_decode, ppocr_rec_tiny, ppyolo_tiny


def detect():
    paddle.seed(0)
    model = ppyolo_tiny(num_classes=4)
    model.eval()
    x = paddle.randn([1, 3, 64, 64])
    with paddle.no_grad():
        outs = model(x)                       # 3 FPN levels of head maps
        boxes, scores = model.decode(outs, 64)
    print(f"detector: {len(outs)} levels -> boxes {tuple(boxes.shape)}, "
          f"scores {tuple(scores.shape)}")


def recognize():
    paddle.seed(5)
    model = ppocr_rec_tiny(num_classes=6)
    opt = paddle.optimizer.AdamW(5e-3, parameters=model.parameters())
    rng = np.random.default_rng(2)
    x = paddle.to_tensor(rng.normal(size=(1, 3, 32, 48)).astype(np.float32))
    target = [2, 4, 1]
    labels = paddle.to_tensor(np.array([target], np.int64))
    lens = paddle.to_tensor(np.array([3], np.int64))

    for i in range(60):
        loss = model.loss(model(x), labels, lens)
        loss.backward()
        opt.step()
        opt.clear_grad()
        if i % 20 == 0:
            print(f"ocr ctc loss[{i}] = {float(loss._value):.4f}")
    model.eval()
    with paddle.no_grad():
        decoded = ctc_greedy_decode(model(x))
    print(f"ocr: target {target} -> decoded {decoded[0]}")
    assert decoded[0] == target


if __name__ == "__main__":
    detect()
    recognize()
    print("ok")
