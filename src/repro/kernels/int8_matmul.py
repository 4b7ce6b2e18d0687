"""W8A8 integer matmul kernel (the MPMA *merged mode*, paper Sec. IV-1b).

Grid (M/bm, N/bn, K/bk); int32 accumulation; the activation row-sum (for
the asymmetric-weight zero-point fold) accumulates alongside; the float
epilogue (zero-point correction + act*weight scales) runs on the last K
step so the integer tiles never round-trip to HBM.

Fused activation quantization: x arrives in FLOAT, in the layer's own
dtype, the layer-wise max-abs scale is a scalar operand, and the int8
rounding runs in the prologue on the VMEM tile (cast to f32 there) — the
quantized activation never exists as a separate HBM array.  The output is
written in x's dtype: the epilogue's f32 result is rounded once, in VMEM.

Launch geometry (``ops.int8_tile_plan``): where the weight and a row slab
fit the VMEM budget, each grid step takes a row slab of whole K and whole
N (grid (M/bm, 1, 1), one K step, no scratch accumulator); larger weights
tile N, then K, in multiples of 128 and accumulate over K steps in VMEM
scratch.  The ops.py wrapper pads inputs to block multiples.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.quant import quantize_act


def _kernel(x_ref, w_ref, ascale_ref, wscale_ref, zp_ref, o_ref, *scratch,
            nk: int):
    sa = ascale_ref[0, 0]
    # fused activation quantization: float tile -> int8 in VMEM (pure-jnp
    # quantize_act runs inside the kernel body, so kernel and XLA/ref paths
    # share one rounding definition)
    xq = quantize_act(x_ref[...].astype(jnp.float32), sa)
    acc = jax.lax.dot_general(xq, w_ref[...], (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    xsum = jnp.sum(xq.astype(jnp.int32), axis=-1, keepdims=True)

    def epilogue(acc, xsum):
        corr = xsum.astype(jnp.float32) * zp_ref[...]
        y = (acc.astype(jnp.float32) - corr) * (sa * wscale_ref[...])
        o_ref[...] = y.astype(o_ref.dtype)

    if nk == 1:
        epilogue(acc, xsum)
        return
    acc_ref, xsum_ref = scratch

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xsum_ref[...] = jnp.zeros_like(xsum_ref)

    acc_ref[...] += acc
    xsum_ref[...] += xsum

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        epilogue(acc_ref[...], xsum_ref[...])


def int8_matmul(x: jax.Array, wq: jax.Array, act_scale: jax.Array,
                scale: jax.Array, zero_point: jax.Array,
                *, bm: int = 128, bn: int = 128, bk: int = 128,
                interpret: bool = False) -> jax.Array:
    """x (M,K) float; wq (K,N) int8; scale/zp (N,) f32 -> y (M,N) in
    x's dtype.

    Shapes must be pre-padded to block multiples (ops.py does this).
    """
    M, K = x.shape
    N = wq.shape[1]
    nk = K // bk
    grid = (M // bm, N // bn, nk)
    scratch = [] if nk == 1 else [pltpu.VMEM((bm, bn), jnp.int32),
                                  pltpu.VMEM((bm, 1), jnp.int32)]
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
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="int8_matmul",
    )(x, wq, act_scale.reshape(1, 1), scale.reshape(1, -1),
      zero_point.reshape(1, -1))
