"""Fused int8 ReLU linear-attention kernel (EfficientViT MSA, paper Sec. II-A).

The f32 path in ``nn.attention.relu_linear_attention`` materializes three
(B,N,H,D) einsum operands plus the (B,H,D,D) kv tensor in HBM.  This kernel
runs the whole token-mixer for one (batch, head) pair inside VMEM:

* prologue — q/k/v arrive in FLOAT with scalar max-abs act scales (the PR 1
  fused-rounding convention: the int8 payloads never exist as HBM arrays);
  ReLU is applied to q/k before rounding so the scales are computed on the
  post-ReLU range.
* body — the (D,D) kv and (D,) ksum contractions accumulate in int32 on the
  int8 operands (MPMA merged-mode analogue), then kv is requantized to int8
  in VMEM (the same trick ``decode_attention_int8`` applies to its softmax
  weights) so the per-token numerator/denominator contractions are ALSO
  integer dots — the compiled module carries no f32 dot for any MSA
  contraction.
* epilogue — the numerator/denominator normalization ``num / (den + eps)``
  runs on the f32-rescaled accumulators and writes the output tile once.

Grid: (B, N/bn) — per batch row, kv/ksum/skv build once for every head on
the first N-step (scratch persists across the sequential "arbitrary" dim,
exactly like the matmul kernels' accumulators) and every step streams one
bn-row block of q through them.  Blocks are (1, rows, H, D): the last two
dims are whole, as the TPU's (8, 128) tiling rule asks, and the heads are a
static loop inside the kernel.  N is padded by the ops.py wrapper; padded k
rows quantize to zero and padded q rows emit zeros that are sliced away.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.quant import quantize_act


def _kernel(q_ref, k_ref, v_ref, sq_ref, sk_ref, sv_ref, o_ref,
            kv_ref, ksum_ref, skv_ref, *, eps: float, H: int):
    sk = sk_ref[0, 0]
    sv = sv_ref[0, 0]

    @pl.when(pl.program_id(1) == 0)
    def _build_kv():
        for h in range(H):
            # prologue: ReLU + fused int8 rounding on the VMEM tiles (shared
            # quantize_act definition with the XLA/ref paths)
            k8 = quantize_act(
                jax.nn.relu(k_ref[0, :, h, :].astype(jnp.float32)), sk)
            v8 = quantize_act(v_ref[0, :, h, :].astype(jnp.float32), sv)
            kv32 = jax.lax.dot_general(
                k8, v8, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)                 # (D, D)
            ksum_ref[h] = jnp.sum(k8.astype(jnp.int32), axis=0,
                                  keepdims=True)
            # requantize kv to int8 range so the numerator dot stays integer
            # (int8 x int32_kv would overflow int32 at vision token counts)
            kv_f = kv32.astype(jnp.float32) * (sk * sv)
            skv = jnp.maximum(
                jnp.max(jnp.abs(kv_f), keepdims=True) / 127.0, 1e-8)  # (1,1)
            skv_ref[h] = skv
            kv_ref[h] = jnp.clip(jnp.round(kv_f / skv), -127, 127
                                 ).astype(jnp.int8)

    sq = sq_ref[0, 0]
    for h in range(H):
        q8 = quantize_act(jax.nn.relu(q_ref[0, :, h, :].astype(jnp.float32)),
                          sq)
        num = jax.lax.dot_general(q8, kv_ref[h], (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)  # (bn, D)
        den = jnp.sum(q8.astype(jnp.int32) * ksum_ref[h], axis=-1,
                      keepdims=True)                                 # (bn, 1)
        num_f = num.astype(jnp.float32) * (sq * skv_ref[h])
        den_f = den.astype(jnp.float32) * (sq * sk)
        o_ref[0, :, h, :] = num_f / (den_f + eps)


def relu_attn(q: jax.Array, k: jax.Array, v: jax.Array,
              sq: jax.Array, sk: jax.Array, sv: jax.Array,
              *, bn: int = 128, eps: float = 1e-6,
              interpret: bool = False) -> jax.Array:
    """q/k/v (B,N,H,D) float; sq/sk/sv scalar f32 act scales -> (B,N,H,D) f32.

    N must be pre-padded to a ``bn`` multiple (ops.py does this); zero pad
    rows are inert (ReLU(0) quantizes to 0 in every contraction).
    """
    B, N, H, D = q.shape
    grid = (B, N // bn)
    qkv_spec = pl.BlockSpec((1, N, H, D), lambda b, n: (b, 0, 0, 0))
    scalar = pl.BlockSpec((1, 1), lambda b, n: (0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, eps=eps, H=H),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bn, H, D), lambda b, n: (b, n, 0, 0)),
            qkv_spec,
            qkv_spec,
            scalar,
            scalar,
            scalar,
        ],
        out_specs=pl.BlockSpec((1, bn, H, D), lambda b, n: (b, n, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, N, H, D), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((H, D, D), jnp.int8),     # requantized kv per head
            pltpu.VMEM((H, 1, D), jnp.int32),    # ksum per head
            pltpu.VMEM((H, 1, 1), jnp.float32),  # kv requantization scales
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="relu_attn",
    )(q, k, v, sq.reshape(1, 1), sk.reshape(1, 1), sv.reshape(1, 1))
