"""EfficientViT (the paper's backbone, Fig. 1): Convolution-Transformer
hybrid with MBConvs + lightweight multi-scale ReLU linear attention (MSA).

Layer taxonomy matches the paper's Sec. III-A exactly:
  * PWConvs (1x1) and the MSA MatMuls -> computation-intensive -> mixed
    uniform8/APoT (KIND_DENSE);
  * DWConvs -> memory-intensive -> 4-bit uniform (KIND_DWCONV).

B1: widths (16,32,64,128,256), depths (1,2,3,3,4); B2: widths
(24,48,96,192,384), depths (1,3,4,4,6).  Norms are channel LayerNorms
(functional stand-in for BN; noted in DESIGN.md), activation is Hardswish->
we use SiLU (same family).  NHWC layout throughout.

The block graph is not upstream's ``EfficientViTBackbone``
(mit-han-lab/efficientvit): there stage 0 is DSConv blocks (expand 1),
stages 3-4 are one stride-2 MBConv then ``d`` EfficientViT blocks (LiteMLA,
then an MBConv), LiteMLA's aggregation is a 5x5 depthwise conv followed by
a grouped 1x1 conv (groups = 3 x heads), and no norm precedes qkv.  Here
every stage is MBConv at expand 4, stages 3-4 are ``d`` (MBConv, MSA)
pairs, the aggregation is the depthwise conv alone, and an RMS norm
precedes qkv.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import nn
from ..core import policy as pol
from .config import ArchConfig

QUANT_RULES = [
    (r"(ln|norm|gamma|bias|b$)", pol.KIND_SKIP),
    # every (kh,kw,1,C) depthwise filter is memory-intensive (Sec. III-A):
    # the MBConv 3x3 (w_dw) AND the MSA 5x5 aggregation (w_agg) — w_agg was
    # historically mis-filed under KIND_DENSE despite its depthwise shape
    (r"(w_dw|w_agg)", pol.KIND_DWCONV),
    (r"(w_pw\d?|w_in|w_out|w_qkv|w_proj)", pol.KIND_DENSE),
    (r"head/w", pol.KIND_DENSE),
]

# Per-arch recipe defaults (see repro.recipe): the paper's Sec. III-A split
# is STRUCTURAL — PWConv/MatMul are computation-intensive, DWConv memory-
# intensive — independent of deployment shape, so pin every dense-kind path
# to the mixed decision instead of steering intensity_threshold (the
# reduced proxy's widths sit far below any MXU ridge point and would
# otherwise classify memory-bound).  DWConv/embedding stay structurally
# low-bit in policy.decide regardless of these overrides.
QUANT_OVERRIDES = (
    (r"(w_pw\d?|w_in|w_out|w_qkv|w_proj|head/w)",
     pol.PathOverride(decision=pol.DECISION_MIXED)),
)

# Opt-in int8 stem (ROADMAP item): the 3x3 cin=3 stem stays f32 by default
# (QUANT_RULES does not match it), but a recipe may quantize it and run it
# as an im2col + int8 matmul (nn.layers routes non-1x1 quantized filters
# through patch extraction + the PWConv matmul hot path):
#
#     rec = PRESETS["m2q-w8a8"].replace(
#         rules=tuple(QUANT_RULES) + (STEM_RULE,),
#         overrides=(STEM_OVERRIDE,))
#
# The override pins uniform-8 W8A8 (the stem's 27-row filter is too small
# for the intensity classifier to place reliably, and mixed-scheme buys
# nothing at cin=3); recipe-level overrides precede QUANT_OVERRIDES, so the
# taxonomy pins above are unaffected.
STEM_RULE = (r"stem/w$", pol.KIND_DENSE)
STEM_OVERRIDE = (r"stem/w$", pol.PathOverride(decision=pol.DECISION_MIXED,
                                              scheme="uniform8"))


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _conv(key, kh, kw, cin, cout):
    return nn.lecun_normal(key, (kh, kw, cin, cout))


def _init_mbconv(key, cin, cout, expand=4):
    ks = jax.random.split(key, 3)
    mid = cin * expand
    return {
        "w_pw1": _conv(ks[0], 1, 1, cin, mid),
        "w_dw": nn.lecun_normal(ks[1], (3, 3, 1, mid)),
        "w_pw2": _conv(ks[2], 1, 1, mid, cout),
        "ln1": jnp.ones((mid,), jnp.float32),
        "ln2": jnp.ones((cout,), jnp.float32),
    }


def _init_msa(key, c, dim_per_head=16):
    """Lite multi-scale attention: qkv pwconv, a 5x5 depthwise aggregation
    producing a second token scale, ReLU linear attention, output proj."""
    ks = jax.random.split(key, 4)
    d = 3 * c
    return {
        "w_qkv": _conv(ks[0], 1, 1, c, d),
        "w_agg": nn.lecun_normal(ks[1], (5, 5, 1, d)),  # depthwise multi-scale
        "w_proj": _conv(ks[2], 1, 1, 2 * c, c),
        "ln": jnp.ones((c,), jnp.float32),
    }


def init(cfg: ArchConfig, key) -> dict:
    widths, depths = cfg.widths, cfg.depths
    keys = jax.random.split(key, 64)
    ki = iter(range(64))
    params = {
        "stem": {"w": _conv(keys[next(ki)], 3, 3, 3, widths[0]),
                 "ln": jnp.ones((widths[0],), jnp.float32)},
        "stages": [],
        "head": {},
    }
    cin = widths[0]
    stages = []
    for si, (w, d) in enumerate(zip(widths, depths)):
        blocks = []
        for bi in range(d):
            # stage-entry blocks (bi==0, si>0) run their depthwise conv at
            # stride 2 — decided in forward(); _init_mbconv is stride-
            # agnostic because only w_dw sees the stride and the residual
            # is gated on stride==1 AND matching channels in _mbconv
            blk = {"mb": _init_mbconv(keys[next(ki)], cin, w)}
            if si >= len(widths) - 2:  # last two stages get MSA (transformer)
                blk["msa"] = _init_msa(keys[next(ki)], w, cfg.dim_per_head)
            blocks.append(blk)
            cin = w
        stages.append(blocks)
    params["stages"] = stages
    params["head"] = {
        "w_in": _conv(keys[next(ki)], 1, 1, cin, cin * 4),
        "ln": jnp.ones((cin * 4,), jnp.float32),
        "w": nn.lecun_normal(keys[next(ki)], (cin * 4, cfg.n_classes)),
    }
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _cln(x, g):  # channel layernorm (BN stand-in)
    return nn.rms_norm(x, g)


def _mbconv(p, x, stride=1):
    h = nn.conv2d(x, p["w_pw1"])
    h = nn.silu(_cln(h, p["ln1"]))
    h = nn.dwconv2d(h, p["w_dw"], stride=stride)
    h = nn.silu(h)
    h = nn.conv2d(h, p["w_pw2"])
    h = _cln(h, p["ln2"])
    if stride == 1 and x.shape[-1] == h.shape[-1]:
        h = h + x
    return h


def _msa(p, x, dim_per_head=16):
    B, H, W, C = x.shape
    qkv = nn.conv2d(_cln(x, p["ln"]), p["w_qkv"])  # (B,H,W,3C)
    qkv2 = nn.dwconv2d(qkv, p["w_agg"])  # second scale (5x5 aggregation)
    outs = []
    # both token scales run through nn.relu_linear_attention, which routes
    # to the fused int8 Pallas kernel under kernels.ops dispatch (the attn
    # axis) — the accelerator's low-precision engines cover the MSA
    # MatMuls, not just the conv halves
    for t in (qkv, qkv2):
        q, k, v = jnp.split(t.reshape(B, H * W, 3 * C), 3, axis=-1)
        nh = C // dim_per_head
        q = q.reshape(B, H * W, nh, dim_per_head)
        k = k.reshape(B, H * W, nh, dim_per_head)
        v = v.reshape(B, H * W, nh, dim_per_head)
        o = nn.relu_linear_attention(q, k, v)
        outs.append(o.reshape(B, H, W, C))
    o = jnp.concatenate(outs, axis=-1)  # (B,H,W,2C)
    return x + nn.conv2d(o, p["w_proj"])


def forward(cfg: ArchConfig, params, images, unroll: bool = False,
            remat: bool = False):
    """images: (B, res, res, 3) -> logits (B, n_classes).

    Each part runs under a ``jax.named_scope`` (``stem``, ``stage0``...,
    ``head``), and inside a stage each block under ``mbconv`` or ``msa``;
    the compiled program keeps them in every instruction's ``op_name``
    metadata (``stage3/msa/...``): a profile's device ops map back to their
    stage and block kind.
    """
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("stem"):
        x = images.astype(dtype)
        x = nn.conv2d(x, params["stem"]["w"], stride=2)
        x = nn.silu(_cln(x, params["stem"]["ln"]))
    for si, blocks in enumerate(params["stages"]):
        with jax.named_scope(f"stage{si}"):
            for bi, blk in enumerate(blocks):
                stride = 2 if (bi == 0 and si > 0) else 1
                with jax.named_scope("mbconv"):
                    x = _mbconv(blk["mb"], x, stride=stride)
                if "msa" in blk:
                    with jax.named_scope("msa"):
                        x = _msa(blk["msa"], x, cfg.dim_per_head)
    with jax.named_scope("head"):
        x = nn.conv2d(x, params["head"]["w_in"])
        x = nn.silu(_cln(x, params["head"]["ln"]))
        x = jnp.mean(x, axis=(1, 2))  # global pool
        return nn.dense(x, params["head"]["w"])
