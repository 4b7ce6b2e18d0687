"""Depthwise conv with packed 4-bit weights, NHWC, SAME padding.

Operations: a multiply and an add per tap per output element.  Minimal
bytes: the input map read once and the output map written once in the
configuration's activation dtype (the layer's inputs are not quantized),
and kh x kw x C weights at half a byte.  Padding is not traffic.
"""

PEAK = "bf16_flops_per_s"


def work(call, dtype_bytes):
    m = call["meta"]
    B, H, W, C = m["B"], m["H"], m["W"], m["C"]
    kh, kw, s = m["kh"], m["kw"], m["stride"]
    HO, WO = -(-H // s), -(-W // s)
    ops = 2 * B * HO * WO * C * kh * kw
    nbytes = (B * H * W * C + B * HO * WO * C) * dtype_bytes \
        + (kh * kw * C + 1) // 2
    return ops, nbytes
