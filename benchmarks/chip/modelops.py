"""Operations of one image through the float model, from its shapes.

A multiply and an add per MAC of every convolution, attention product and
the classifier; norms, activations and pooling are left out.  This is the
work an implementation has to do whatever its precision, so ``mfu``
divides it by the step's device time and the chip's peak.
"""
from __future__ import annotations

EXPAND = 4
HEAD_EXPAND = 4


def ops_per_image(cfg: dict) -> int:
    widths, depths = cfg["widths"], cfg["depths"]
    hd = cfg["head_dim"]
    h = -(-cfg["img_res"] // 2)                     # after the stride-2 stem
    ops = 2 * h * h * 9 * 3 * widths[0]
    cin = widths[0]
    for si, (w, d) in enumerate(zip(widths, depths)):
        for bi in range(d):
            s = 2 if (bi == 0 and si > 0) else 1
            mid = cin * EXPAND
            ho = -(-h // s)
            ops += 2 * (h * h * cin * mid + ho * ho * mid * 9
                        + ho * ho * mid * w)
            h, cin = ho, w
            if si >= len(widths) - 2:
                n, heads = h * h, w // hd
                ops += 2 * n * w * 3 * w             # qkv
                ops += 2 * n * 3 * w * 25            # 5x5 aggregation
                # per token scale: k'^T v, q' (k'^T v), q' . sum k'
                ops += 2 * 2 * (2 * n * heads * hd * hd + n * heads * hd)
                ops += 2 * n * 2 * w * w             # projection
    ops += 2 * h * h * cin * cin * HEAD_EXPAND
    ops += 2 * cin * HEAD_EXPAND * cfg["n_classes"]
    return ops
