"""APoT-coded matmul kernel (the SAT engine, paper Sec. IV-2, on TPU).

Each weight byte is (zero<<7 | sign<<6 | e1<<3 | e2); the ASIC decodes this
with two shifters + an adder (Eq. 4).  The TPU-native equivalent performed
here: decode the byte tile *in VMEM* with exponent arithmetic
(2^-e = exp2), then feed the MXU.  Weights cross HBM as 1-byte codes and the
decoded bf16/f32 tile exists only in VMEM — the fused-dequant bandwidth win
recorded in DESIGN.md §3.

The per-filter scale stays in the epilogue (the decoded operand is the
unscaled codebook value), matching QAPoT.matmul and ref.apot_matmul_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def decode_apot_tile(codes: jax.Array) -> jax.Array:
    """code bytes (bk,bn) -> f32 values s*(2^-e1 + 2^-e2), zero-aware.

    Accepts uint8 codes OR an int8 view of the same bytes (the merged M2Q
    payload stores both engines' bytes in one int8 array): widening to int32
    and masking with 0xFF recovers the unsigned bit pattern on two's-
    complement hardware.  Bit masks are python ints (pallas kernels may not
    capture traced constants).
    """
    c = codes.astype(jnp.int32) & 0xFF
    e1 = ((c >> 3) & 0x07).astype(jnp.float32)
    e2 = (c & 0x07).astype(jnp.float32)
    mag = jnp.exp2(-e1) + jnp.exp2(-e2)
    sign = jnp.where((c & 0x40) != 0, -1.0, 1.0)
    return jnp.where((c & 0x80) != 0, 0.0, sign * mag)


def _kernel(x_ref, c_ref, scale_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = decode_apot_tile(c_ref[...])
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        o_ref[...] = acc_ref[...] * scale_ref[...]


def apot_matmul(x: jax.Array, codes: jax.Array, scale: jax.Array,
                *, bm: int = 128, bn: int = 128, bk: int = 128,
                interpret: bool = False) -> jax.Array:
    """x (M,K); codes (K,N) uint8; scale (N,) -> y (M,N) f32."""
    M, K = x.shape
    N = codes.shape[1]
    nk = K // bk
    grid = (M // bm, N // bn, nk)
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="apot_matmul",
    )(x, codes, scale.reshape(1, -1))
