"""W8A8 integer matmul kernel (the MPMA *merged mode*, paper Sec. IV-1b).

Grid (M/bm, N/bn, K/bk); int32 accumulation in a VMEM scratch; the
activation row-sum (for the asymmetric-weight zero-point fold) accumulates
alongside; the float epilogue (zero-point correction + act*weight scales)
runs on the last K step so the integer tiles never round-trip to HBM.

Fused activation quantization: x arrives in FLOAT, the layer-wise max-abs
scale is a scalar operand, and the int8 rounding runs in the prologue on
the VMEM tile — the quantized activation never exists as a separate HBM
array (the XLA quantize pass this kernel used to depend on is gone).

MXU alignment: block shapes default to 128x128x128 (int8 MXU-native on
v5e); the ops.py wrapper pads inputs to block multiples.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.quant import quantize_act


def _kernel(x_ref, w_ref, ascale_ref, wscale_ref, zp_ref, o_ref,
            acc_ref, xsum_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xsum_ref[...] = jnp.zeros_like(xsum_ref)

    sa = ascale_ref[0, 0]
    # fused activation quantization: float tile -> int8 in VMEM (pure-jnp
    # quantize_act runs inside the kernel body, so kernel and XLA/ref paths
    # share one rounding definition)
    xq = quantize_act(x_ref[...].astype(jnp.float32), sa)
    acc_ref[...] += jax.lax.dot_general(
        xq, w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    xsum_ref[...] += jnp.sum(xq.astype(jnp.int32), axis=-1, keepdims=True)

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        corr = xsum_ref[...].astype(jnp.float32) * zp_ref[...]
        o_ref[...] = (acc - corr) * (sa * wscale_ref[...])


def int8_matmul(x: jax.Array, wq: jax.Array, act_scale: jax.Array,
                scale: jax.Array, zero_point: jax.Array,
                *, bm: int = 128, bn: int = 128, bk: int = 128,
                interpret: bool = False) -> jax.Array:
    """x (M,K) float; wq (K,N) int8; scale/zp (N,) f32 -> y (M,N) f32.

    Shapes must be pre-padded to block multiples (ops.py does this).
    """
    M, K = x.shape
    N = wq.shape[1]
    nk = K // bk
    grid = (M // bm, N // bn, nk)
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.int32),
            pltpu.VMEM((bm, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="int8_matmul",
    )(x, wq, act_scale.reshape(1, 1), scale.reshape(1, -1),
      zero_point.reshape(1, -1))
