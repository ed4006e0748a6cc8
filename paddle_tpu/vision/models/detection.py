"""PP-YOLOE-class single-stage detector (BASELINE.json row: PP-YOLOE).

Reference lineage: the PP-YOLO family served from the reference's vision
stack — CSP backbone blocks + FPN neck + per-level heads decoded by the
`yolo_box` operator (python/paddle/vision/ops.py yolo_box; CUDA kernel
paddle/phi/kernels/gpu/yolo_box_kernel.cu).

TPU-native design notes: everything is static-shaped dense conv compute
(MXU-friendly NCHW convs XLA lays out itself); the decode is the already-
verified `paddle_tpu.vision.ops.yolo_box` running inside the same jit —
no dynamic-shape NMS in the compiled path (candidate filtering is a
host-side post-step, like the reference's multiclass_nms living outside
the TensorRT-compiled subgraph).
"""

from __future__ import annotations

import paddle_tpu as paddle
import paddle_tpu.nn as nn

__all__ = ["PPYoloDet", "ppyolo_tiny", "ppyolo_s"]


class ConvBNLayer(nn.Layer):
    """conv + BN + activation (shared by the detection and OCR families)."""

    def __init__(self, cin, cout, k=3, stride=1, groups=1, act="silu"):
        super().__init__()
        self.conv = nn.Conv2D(cin, cout, k, stride=stride,
                              padding=(k - 1) // 2, groups=groups, bias_attr=False)
        self.bn = nn.BatchNorm2D(cout)
        self.act = {"silu": nn.Silu, "relu": nn.ReLU}[act]()

    def forward(self, x):
        return self.act(self.bn(self.conv(x)))


class CSPResBlock(nn.Layer):
    """CSP residual block: split, residual-conv half, concat, fuse."""

    def __init__(self, ch, n=1):
        super().__init__()
        half = ch // 2
        self.left = ConvBNLayer(ch, half, k=1)
        self.right = ConvBNLayer(ch, half, k=1)
        self.blocks = nn.LayerList([
            nn.Sequential(ConvBNLayer(half, half, 1), ConvBNLayer(half, half, 3))
            for _ in range(n)
        ])
        self.fuse = ConvBNLayer(ch, ch, k=1)

    def forward(self, x):
        left = self.left(x)
        right = self.right(x)
        for blk in self.blocks:
            right = right + blk(right)
        return self.fuse(paddle.concat([left, right], axis=1))


class PPYoloDet(nn.Layer):
    """Backbone (stem + CSP stages) -> top-down FPN -> per-level anchor
    heads.  forward(x) returns per-level raw head maps
    [B, A*(5+C), H, W] for training; `decode(outputs, img_size)` runs
    yolo_box per level and concatenates boxes/scores."""

    def __init__(self, num_classes=80, widths=(32, 64, 128, 256, 256),
                 depth=1, anchors=None, downsample_ratios=(8, 16, 32)):
        super().__init__()
        self.num_classes = num_classes
        # one anchor set per FPN level (PP-YOLO tiny defaults, px)
        self.anchors = anchors or [
            [10, 15, 24, 36, 72, 42],
            [35, 87, 102, 96, 60, 170],
            [220, 125, 128, 222, 264, 266],
        ]
        self.downsample_ratios = list(downsample_ratios)

        w = list(widths)
        self.stem = ConvBNLayer(3, w[0], 3, stride=2)
        stages = []
        for i in range(1, len(w)):
            stages.append(nn.Sequential(
                ConvBNLayer(w[i - 1], w[i], 3, stride=2),
                CSPResBlock(w[i], n=depth),
            ))
        self.stages = nn.LayerList(stages)

        # top-down neck over the last 3 stages
        c3, c4, c5 = w[-3], w[-2], w[-1]
        self.lat5 = ConvBNLayer(c5, c4, 1)
        self.lat4 = ConvBNLayer(c4 + c4, c3, 1)
        self.lat3 = ConvBNLayer(c3 + c3, c3, 1)
        self.up = nn.Upsample(scale_factor=2, mode="nearest")

        per_anchor = len(self.anchors[0]) // 2
        out_ch = per_anchor * (5 + num_classes)
        self.heads = nn.LayerList([
            nn.Conv2D(c, out_ch, 1) for c in (c3, c3, c4)
        ])

    def forward(self, x):
        feats = []
        h = self.stem(x)
        for st in self.stages:
            h = st(h)
            feats.append(h)
        c3, c4, c5 = feats[-3], feats[-2], feats[-1]
        p5 = self.lat5(c5)                                  # [B, c4, H/32]
        p4 = self.lat4(paddle.concat([self.up(p5), c4], 1))  # [B, c3, H/16]
        p3 = self.lat3(paddle.concat([self.up(p4), c3], 1))  # [B, c3, H/8]
        return [self.heads[0](p3), self.heads[1](p4), self.heads[2](p5)]

    def decode(self, outputs, img_size, conf_thresh=0.01):
        """Per-level yolo_box decode -> (boxes [B, N, 4], scores [B, N, C])."""
        from paddle_tpu.vision import ops as V

        boxes, scores = [], []
        imgsz = paddle.to_tensor(
            [[int(img_size), int(img_size)]] * outputs[0].shape[0], dtype="int32"
        )
        for out, anchors, ds in zip(outputs, self.anchors,
                                    self.downsample_ratios):
            b, s = V.yolo_box(out, imgsz, anchors, self.num_classes,
                              conf_thresh, ds)
            boxes.append(b)
            scores.append(s)
        return paddle.concat(boxes, axis=1), paddle.concat(scores, axis=1)


def ppyolo_tiny(num_classes=80, **kw):
    return PPYoloDet(num_classes, widths=(16, 32, 64, 128, 128), depth=1, **kw)


def ppyolo_s(num_classes=80, **kw):
    return PPYoloDet(num_classes, widths=(32, 64, 128, 256, 256), depth=2, **kw)
