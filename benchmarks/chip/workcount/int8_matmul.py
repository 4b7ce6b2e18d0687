"""Uniform int8 x int8 matmul: y (M, N) = x (M, K) @ w (K, N).

Operations: a multiply and an add per MAC.  Minimal bytes: int8
activations and weights read once, the output written once in the
configuration's activation dtype (per-filter scales are N-sized and left
out).
"""

PEAK = "int8_ops_per_s"


def work(call, dtype_bytes):
    M, N, K = call["M"], call["N"], call["K"]
    return 2 * M * N * K, M * K + K * N + M * N * dtype_bytes
