"""W4 (nibble-packed) weights-only matmul kernel — the MPMA *single mode*
path generalized to memory-intensive dense layers (embeddings / decode-shape
matmuls).

The 4-bit payload stays packed in HBM and through the BlockSpec pipeline;
nibbles are unpacked *in VMEM* right before the MXU dot — the HBM win the
paper's 4-bit weight buffers target (Table VI).  Activations stay bf16/f32
(weights-only quantization: the memory-intensive layers are bandwidth-, not
compute-, limited).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _unpack_nibbles(packed: jax.Array) -> jax.Array:
    """(bk, bn/2) uint8 -> (bk, bn) f32 codes in 0..15 (even idx = low),
    through int32: the chip has no direct uint8 -> f32 convert."""
    p = packed.astype(jnp.int32)
    lo = p & 0x0F
    hi = (p >> 4) & 0x0F
    bk, half = packed.shape
    out = jnp.stack([lo, hi], axis=-1)  # (bk, bn/2, 2)
    return out.reshape(bk, 2 * half).astype(jnp.float32)


def _kernel(x_ref, wp_ref, wscale_ref, zp_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = _unpack_nibbles(wp_ref[...])
    w = (q - zp_ref[...]) * wscale_ref[...]
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        o_ref[...] = acc_ref[...]


def int4_matmul(x: jax.Array, packed: jax.Array, scale: jax.Array,
                zero_point: jax.Array,
                *, bm: int = 128, bn: int = 128, bk: int = 128,
                interpret: bool = False) -> jax.Array:
    """x (M,K) f32/bf16; packed (K,N/2) uint8; scale/zp (N,) -> (M,N) f32.

    Shapes must be pre-padded to block multiples, with blocks the TPU can
    tile: the packed block's bn/2 lanes a multiple of 128 or all of N/2
    (ops.py does both)."""
    M, K = x.shape
    N = packed.shape[1] * 2
    nk = K // bk
    grid = (M // bm, N // bn, nk)
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn // 2), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="int4_matmul",
    )(x, packed, scale.reshape(1, -1), zero_point.reshape(1, -1))
