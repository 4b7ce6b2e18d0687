"""Fused two-level mixed-quantization matmul — the flagship M2-ViT kernel.

The paper pipelines its two engines (MPMA for the uniform filter half, SAT
for the APoT half) over the same activation stream (Sec. IV "Execution
Flow").  The TPU equivalent: ONE kernel invocation whose grid walks the
activation tile once; per (m, k) step it feeds the int8 MXU dot for the
uniform engine AND the decode+dot for the SAT engine from the *same* x tile
in VMEM.

Permutation-free layout (see core.qtensor): the weight arrives as a single
merged byte array in ORIGINAL filter order — each column holds either an
offset-folded int8 uniform payload or an APoT code byte, with per-column
scales zero-masked on the columns the other engine owns.  The epilogue sums
the two engine accumulators and writes ONE output tile directly in filter
order: no concatenate, no inverse-permutation gather, ever.

Fused activation quantization: x arrives in float; the max-abs scale is a
scalar operand and the int8 rounding happens in the kernel prologue on the
VMEM tile, so the quantized activation never round-trips through HBM as a
separate XLA pass.

Tradeoff (deliberate): with interleaved per-filter scheme assignment, both
engines sweep all N columns and the zero-masked scales cancel the half each
does not own — 2x the MAC count of two half-width dots.  What it buys: the
weight stays 1 byte/weight, HBM traffic is unchanged IN THIS KERNEL (the
decode lives in VMEM; the XLA fallback in core.qtensor does materialize
the decoded operand — see _merged_matmul's note), and the O(M*N) concat +
inverse-permutation gather epilogue (plus its round-trips) is gone.  The
decode/serving shapes this kernel exists for are bandwidth-bound (small
M), where bytes moved — not MACs — set the wall-clock; layers
whose consumer can absorb the reorder offline avoid even that via the
fold_perm path (apply.py FFN groups), which keeps the halves contiguous.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.quant import quantize_act
from .apot_matmul import decode_apot_tile


def _kernel(x_ref, p_ref, uscale_ref, uzp_ref, ascale_ref, act_scale_ref,
            y_ref, uacc_ref, xsum_ref, aacc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        uacc_ref[...] = jnp.zeros_like(uacc_ref)
        xsum_ref[...] = jnp.zeros_like(xsum_ref)
        aacc_ref[...] = jnp.zeros_like(aacc_ref)

    sa = act_scale_ref[0, 0]
    # fused activation quantization: float tile -> int8 in VMEM (shared
    # rounding definition with the XLA/ref paths)
    xq = quantize_act(x_ref[...].astype(jnp.float32), sa)
    p = p_ref[...]
    # uniform engine: int8 x int8 -> int32 (MPMA merged mode; 2x MXU rate)
    uacc_ref[...] += jax.lax.dot_general(
        xq, p, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    xsum_ref[...] += jnp.sum(xq.astype(jnp.int32), axis=-1, keepdims=True)
    # SAT engine: decode the SAME byte tile as APoT codes, f32 dot.  On
    # uniform columns the decode is garbage — cancelled by a_scale == 0.
    w = decode_apot_tile(p)
    aacc_ref[...] += jnp.dot(xq.astype(jnp.float32), w,
                             preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        u = uacc_ref[...].astype(jnp.float32)
        corr = xsum_ref[...].astype(jnp.float32) * uzp_ref[...]
        yu = (u - corr) * uscale_ref[...]
        ya = aacc_ref[...] * ascale_ref[...]
        y_ref[...] = (yu + ya) * sa


def m2q_matmul(x: jax.Array, act_scale: jax.Array, payload: jax.Array,
               u_scale: jax.Array, u_zp: jax.Array, a_scale: jax.Array,
               *, bm: int = 128, bn: int = 128, bk: int = 128,
               interpret: bool = False) -> jax.Array:
    """x (M,K) float; merged payload (K,N) int8; scales (N,) zero-masked.

    Returns y (M,N) f32 in original filter order (ops.py pads/unpads).
    """
    M, K = x.shape
    N = payload.shape[1]
    nk = K // bk
    grid = (M // bm, N // bn, nk)
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.int32),
            pltpu.VMEM((bm, 1), jnp.int32),
            pltpu.VMEM((bm, bn), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="m2q_matmul",
    )(x, payload, u_scale.reshape(1, -1), u_zp.reshape(1, -1),
      a_scale.reshape(1, -1), act_scale.reshape(1, 1))
