"""PP-OCR-class text recognizer (BASELINE.json row: PP-OCRv4).

Reference lineage: the PP-OCR recognition pipeline served from the
reference's vision/text stack — a conv feature extractor squeezed to a
sequence, a bidirectional LSTM encoder, and a CTC head trained with
`ctc_loss` (python/paddle/nn/functional/loss.py warpctc lineage;
paddle/phi/kernels/gpu/warpctc_kernel.cu).

TPU-native notes: static [B, 3, 32, W] inputs, the height axis fully
collapsed by stride-(2,1) convs so the sequence length is W/4 at trace
time (no dynamic shapes), the BiLSTM is the framework's lax.scan-based
nn.LSTM, and greedy CTC decode is a jit-friendly argmax + host-side
collapse.
"""

from __future__ import annotations

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn

__all__ = ["CRNN", "ppocr_rec_tiny", "ctc_greedy_decode"]


from .detection import ConvBNLayer


def _ConvBlock(cin, cout, stride):
    return ConvBNLayer(cin, cout, k=3, stride=stride, act="relu")


class CRNN(nn.Layer):
    """conv stack -> [B, T, C] sequence -> BiLSTM -> CTC logits.

    forward(x[B, 3, 32, W]) -> log-probs [B, T=W/4, num_classes+1]
    (class 0 is the CTC blank, matching nn.functional.ctc_loss)."""

    def __init__(self, num_classes=96, hidden=64, widths=(32, 64, 128)):
        super().__init__()
        w = list(widths)
        self.convs = nn.Sequential(
            _ConvBlock(3, w[0], stride=2),          # 32 -> 16, W -> W/2
            _ConvBlock(w[0], w[1], stride=(2, 2)),  # 16 -> 8,  W/2 -> W/4
            _ConvBlock(w[1], w[2], stride=(2, 1)),  # 8 -> 4,   keep W/4
            _ConvBlock(w[2], w[2], stride=(4, 1)),  # 4 -> 1,   keep W/4
        )
        self.rnn = nn.LSTM(w[2], hidden, direction="bidirect")
        self.head = nn.Linear(2 * hidden, num_classes + 1)
        self.num_classes = num_classes

    def forward(self, x):
        import paddle_tpu.nn.functional as F

        h = self.convs(x)                      # [B, C, 1, T]
        h = h.squeeze(2).transpose([0, 2, 1])  # [B, T, C]
        h, _ = self.rnn(h)
        logits = self.head(h)                  # [B, T, K+1]
        return F.log_softmax(logits, axis=-1)

    def loss(self, log_probs, labels, label_lengths):
        """CTC loss over the full (static) time axis."""
        import paddle_tpu.nn.functional as F

        B, T = log_probs.shape[0], log_probs.shape[1]
        input_lengths = paddle.full([B], T, dtype="int64")
        return F.ctc_loss(log_probs.transpose([1, 0, 2]), labels,
                          input_lengths, label_lengths, blank=0)


def ctc_greedy_decode(log_probs, blank=0):
    """[B, T, K] log-probs -> list of decoded id lists (collapse repeats,
    drop blanks) — host-side, like the reference's ctc_align op."""
    ids = np.asarray(paddle.argmax(log_probs, axis=-1)._value)
    out = []
    for row in ids:
        seq, prev = [], blank
        for t in row:
            t = int(t)
            if t != blank and t != prev:
                seq.append(t)
            prev = t
        out.append(seq)
    return out


def ppocr_rec_tiny(num_classes=96, **kw):
    return CRNN(num_classes=num_classes, hidden=48, widths=(16, 32, 64), **kw)
