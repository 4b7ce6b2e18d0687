"""4-bit depthwise conv kernel (the MPMA *single mode*, paper Sec. IV-1a).

DWConv is the paper's memory-intensive class: one weight channel per filter,
no cross-filter input reuse — so the win is bandwidth, exactly what 4-bit
weights buy (Table II shows 4-bit is accuracy-free).  The packed nibbles
(kh*kw, C/2) stay packed across HBM; decode happens once per channel tile in
VMEM; the tap accumulation mirrors the paper's output-parallel dataflow
(partial sums accumulate across taps in registers, never leaving VMEM).

The kernel is parameterized over the kernel window (kh, kw) and stride so it
serves BOTH EfficientViT depthwise shapes: the MBConv 3x3 (stride 1 and the
stride-2 stage-entry downsamplers) and the MSA 5x5 multi-scale aggregation.

Grid: (B, H-tiles, C/bc) — channels are the parallel dim (the paper's
"blocks within a PE tile compute different channels") and the output H axis
is tiled in blocks of ``bh`` rows.  Each input block carries its halo: the
``bh`` output rows of tile ``t`` consume input rows
``[t*bh*stride, t*bh*stride + (bh-1)*stride + kh)``, so consecutive input
blocks OVERLAP by ``kh - stride`` rows.  Overlap is expressed with
``pl.Element`` element-offset indexing (a blocked BlockSpec can only step
by whole blocks); the per-block VMEM footprint is bounded by the tile, not
the feature map, so arbitrary-resolution maps (R256/R384/R512, detection
sizes) run the packed-w4 kernel.

The wrapper materializes XLA SAME padding once (asymmetric for even windows
under stride, matching ``lax.conv_general_dilated``), so the kernel body only
sees padded tiles and every tap is a plain strided read of the VMEM block.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def same_padding(size: int, k: int, stride: int) -> Tuple[int, int]:
    """XLA SAME padding (lo, hi) for one spatial dim."""
    out = -(-size // stride)  # ceil
    total = max((out - 1) * stride + k - size, 0)
    lo = total // 2
    return lo, total - lo


def _decode_w4(wp_ref, scale_ref, zp_ref, KH: int, KW: int) -> jax.Array:
    """Unpack the (kh*kw, bc/2) nibble tile to (kh*kw, bc) f32 weights —
    once per grid step, in VMEM (through int32: the chip has no direct
    uint8 -> f32 convert)."""
    p = wp_ref[0].astype(jnp.int32)
    lo = p & 0x0F
    hi = (p >> 4) & 0x0F
    q = jnp.stack([lo, hi], axis=-1).reshape(KH * KW, -1)
    return (q.astype(jnp.float32) - zp_ref[0]) * scale_ref[0]


def _kernel(x_ref, wp_ref, scale_ref, zp_ref, o_ref, *, KH: int, KW: int,
            BH: int, WO: int, stride: int):
    """The block is SAME-padded rows; tap (i, j) of every output pixel in
    the tile is one strided read of the block."""
    w = _decode_w4(wp_ref, scale_ref, zp_ref, KH, KW)
    acc = jnp.zeros((BH, WO, w.shape[-1]), jnp.float32)
    for i in range(KH):
        for j in range(KW):
            if stride == 1:
                tap = x_ref[0, pl.ds(i, BH), pl.ds(j, WO), :]
            else:
                tap = x_ref[0, pl.ds(i, BH, stride=stride),
                            pl.ds(j, WO, stride=stride), :]
            acc = acc + tap.astype(jnp.float32) * w[KW * i + j]
    o_ref[0] = acc


def dwconv_w4(x: jax.Array, packed: jax.Array, scale: jax.Array,
              zero_point: jax.Array, *, kh: int = 3, kw: int = 3,
              stride: int = 1, bh: Optional[int] = None, bc: int = 128,
              interpret: bool = False) -> jax.Array:
    """x (B,H,W,C) (unpadded); packed (kh*kw, C/2) uint8; scale/zp (C,) f32.

    Returns (B,HO,WO,C) f32 — depthwise kh x kw, SAME padding, stride >= 1.
    ``bh``: output rows per H-tile (None = whole map in one tile); ``bc``:
    channels per tile — all C, or a multiple of 128 lanes.
    """
    B, H, W, C = x.shape
    assert packed.shape[0] == kh * kw, (packed.shape, kh, kw)
    bc = min(bc, C)
    assert C % bc == 0 and bc % 2 == 0
    assert bc == C or bc % 128 == 0, (bc, C)  # lane-dim tiling rule
    ph = same_padding(H, kh, stride)
    pw = same_padding(W, kw, stride)
    HO = -(-H // stride)
    WO = -(-W // stride)
    bh = HO if bh is None else max(1, min(bh, HO))
    T = -(-HO // bh)                      # H-tiles
    step = bh * stride                    # input rows consumed per tile
    bh_in = (bh - 1) * stride + kh        # input rows read per tile (halo'd)
    WI = W + pw[0] + pw[1]
    # rows the LAST tile reads, in padded coordinates; pad the bottom so
    # every element-offset read stays in bounds (zero rows only ever feed
    # output rows >= HO, which are sliced away)
    hi_need = (T - 1) * step + bh_in
    xp = jnp.pad(x, ((0, 0), (ph[0], max(ph[1], hi_need - ph[0] - H)), pw,
                     (0, 0)))
    nc = C // bc
    grid = (B, T, nc)
    # a single channel block gets a literal 0 lane offset: Mosaic must
    # prove every lane offset is 128-aligned, and c * bc is not when bc < 128
    in_spec = pl.BlockSpec(
        (pl.Element(1), pl.Element(bh_in), pl.Element(WI), pl.Element(bc)),
        lambda b, t, c: (b, t * step, 0, 0 if nc == 1 else c * bc))
    # per-channel-block weight slabs: (C/bc, taps, bc/2) keeps every block's
    # last two dims whole, so any bc is legal for the packed nibbles
    wp = packed.reshape(kh * kw, nc, bc // 2).transpose(1, 0, 2)
    y = pl.pallas_call(
        functools.partial(_kernel, KH=kh, KW=kw, BH=bh, WO=WO, stride=stride),
        grid=grid,
        in_specs=[
            in_spec,
            pl.BlockSpec((1, kh * kw, bc // 2), lambda b, t, c: (c, 0, 0)),
            pl.BlockSpec((1, 1, bc), lambda b, t, c: (c, 0, 0)),
            pl.BlockSpec((1, 1, bc), lambda b, t, c: (c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bh, WO, bc), lambda b, t, c: (b, t, 0, c)),
        out_shape=jax.ShapeDtypeStruct((B, T * bh, WO, C), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="dwconv_w4",
    )(xp, wp, scale.reshape(nc, 1, bc), zero_point.reshape(nc, 1, bc))
    return y[:, :HO]
