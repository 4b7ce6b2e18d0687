"""Fused int8 ReLU linear attention, one token scale of an MSA block:
q, k, v (B, N, H, D) -> out (B, N, H, D).

Operations: per batch row and head, a multiply and an add per MAC of
k^T v (N x D x D) and of q (k^T v) (N x D x D), and of the k-sum (N x D)
and q . k-sum (N x D): 2 * B * H * (2 * N * D^2 + 2 * N * D).  Minimal
bytes: q, k and v read once and the output written once, in the
configuration's activation dtype: 4 * B * N * H * D elements (the
per-tensor scales are scalars and left out).
"""

PEAK = "int8_ops_per_s"


def work(call, dtype_bytes):
    m = call["meta"]
    B, N, H, D = m["B"], m["N"], m["H"], m["D"]
    ops = 2 * B * H * (2 * N * D * D + 2 * N * D)
    return ops, 4 * B * N * H * D * dtype_bytes
