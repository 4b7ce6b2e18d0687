"""Plain float32 reference of the served EfficientViT classifier.

This is the yardstick that decides `correct`.  It imports nothing of the
system under test and uses no kernel, cache or batching.

The model follows the published description (Cai et al., arXiv:2205.14756;
mit-han-lab/efficientvit ``efficientvit_backbone_b1/b2``) with the
departures each configuration file lists: RMS channel norm for BatchNorm,
SiLU for Hardswish, and a head of a 1x1 conv to 4C, a norm, global pooling
and a linear layer to the classes.

The configuration serves the model under the paper's two-level mixed
quantization (M2-ViT, arXiv:2410.09113, Eqs. 1-6), so the reference computes
that arithmetic in float32 from the float weights alone:

* compute-heavy layers (1x1 convs, MSA qkv/proj, head): half the filters,
  those with the least APoT penalty (Eq. 6, 1:1 split), take APoT weights
  (Eq. 5: sign x (2^-a + 2^-b) x (max - min), a <= b <= 7, the code
  a = b = 7 standing for zero); the other half
  asymmetric uniform 8-bit per filter (Eqs. 1-2); the layer input is
  symmetric int8 with one static scale, max|x| / 127 over the calibration
  images (run through the float model here);
* depthwise convs: asymmetric uniform 4-bit per channel, float inputs;
* stem, norms, activations and the ReLU linear attention: float.

``bits=4`` is the control, one step below the int8 that the configuration
states: every compute-heavy filter uniform 4-bit and every such input int4
(max|x| / 7); the depthwise layers keep their 4 bits.  A comparison that
cannot tell the control from the served model decides nothing.

The parameter tree has the layout the served model takes (nested dicts of
float32 arrays), and :func:`make_params` fills it from a seed on the device
in one jitted call, so the same weights go to the system and to this file.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
EPS_NORM = 1e-6
EPS_ATTN = 1e-6
EXPAND = 4          # MBConv expansion
HEAD_EXPAND = 4     # head 1x1 conv to 4C
APOT_EMAX = 7       # 3-bit exponent fields
DW_BITS = 4
MIXED = ("w_pw1", "w_pw2", "w_qkv", "w_proj", "w_in", "w")
DEPTHWISE = ("w_dw", "w_agg")


def prng_key(seed: int):
    """A key for any non-negative whole ``seed``, also past 32 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def param_shapes(cfg: dict) -> dict:
    """Nested dict of leaf shapes for a configuration file's sizes."""
    widths, depths = cfg["widths"], cfg["depths"]
    w0 = widths[0]
    tree = {"stem": {"w": (3, 3, 3, w0), "ln": (w0,)}, "stages": []}
    cin = w0
    for si, (w, d) in enumerate(zip(widths, depths)):
        blocks = []
        for _ in range(d):
            mid = cin * EXPAND
            blk = {"mb": {"w_pw1": (1, 1, cin, mid), "w_dw": (3, 3, 1, mid),
                          "w_pw2": (1, 1, mid, w), "ln1": (mid,),
                          "ln2": (w,)}}
            if si >= len(widths) - 2:   # the last two stages carry MSA
                blk["msa"] = {"w_qkv": (1, 1, w, 3 * w),
                              "w_agg": (5, 5, 1, 3 * w),
                              "w_proj": (1, 1, 2 * w, w), "ln": (w,)}
            blocks.append(blk)
            cin = w
        tree["stages"].append(blocks)
    tree["head"] = {"w_in": (1, 1, cin, cin * HEAD_EXPAND),
                    "ln": (cin * HEAD_EXPAND,),
                    "w": (cin * HEAD_EXPAND, cfg["n_classes"])}
    return tree


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(d, int) for d in x)


def _init_leaf(key, shape):
    """Norm gains are ones; filters are LeCun-normal over their fan-in."""
    if len(shape) == 1:
        return jnp.ones(shape, jnp.float32)
    return (jax.random.normal(key, shape, jnp.float32)
            / math.sqrt(math.prod(shape[:-1])))


def make_params(cfg: dict, seed: int) -> dict:
    """The parameter tree for ``cfg``, drawn from ``seed`` on the default
    device in one jitted call."""
    leaves, treedef = jax.tree.flatten(param_shapes(cfg), is_leaf=_is_shape)

    def build(key):
        keys = jax.random.split(key, len(leaves))
        return [_init_leaf(k, s) for k, s in zip(keys, leaves)]

    return jax.tree.unflatten(treedef, jax.jit(build)(prng_key(seed)))


# ---------------------------------------------------------------------------
# the quantizers (float32 in, dequantized float32 out)
# ---------------------------------------------------------------------------


def uniform_weights(w2, bits):
    """Asymmetric uniform per filter (Eqs. 1-2), zero representable; w2 is
    (K, N) with filters on the last axis."""
    lo = jnp.minimum(jnp.min(w2, 0, keepdims=True), 0.0)
    hi = jnp.maximum(jnp.max(w2, 0, keepdims=True), 0.0)
    qmax = 2.0 ** bits - 1
    scale = jnp.maximum((hi - lo) / qmax, 1e-8)
    zp = jnp.clip(jnp.round(-lo / scale), 0, qmax)
    q = jnp.clip(jnp.round(w2 / scale) + zp, 0, qmax)
    return (q - zp) * scale


def _apot_magnitudes():
    mags = {2.0 ** -a + 2.0 ** -b for a in range(APOT_EMAX + 1)
            for b in range(a, APOT_EMAX + 1)}
    return jnp.asarray(sorted(mags), jnp.float32)


def apot_weights(w2):
    """APoT per filter (Eq. 5): the nearest of sign x (2^-a + 2^-b) x S,
    with S = max - min of the filter.  The code of the smallest magnitude,
    a = b = 7, stands for zero, so a weight nearest to it becomes 0."""
    scale = jnp.maximum(jnp.max(w2, 0, keepdims=True)
                        - jnp.min(w2, 0, keepdims=True), 1e-8)
    mags = _apot_magnitudes()
    idx = jnp.argmin(jnp.abs(jnp.abs(w2 / scale)[..., None] - mags), -1)
    mag = jnp.where(idx == 0, 0.0, mags[idx])
    return jnp.where(w2 < 0, -1.0, 1.0) * mag * scale


def mixed_weights(w2):
    """Eq. 6 at a 1:1 split: the N // 2 filters whose APoT error exceeds
    their uniform 8-bit error least go to APoT, the rest to uniform 8-bit."""
    wu, wa = uniform_weights(w2, 8), apot_weights(w2)
    penalty = (jnp.mean((w2 - wa) ** 2, 0)
               - jnp.mean((w2 - wu) ** 2, 0))
    n = w2.shape[-1]
    order = jnp.argsort(penalty, stable=True)
    is_apot = jnp.zeros((n,), bool).at[order[: n // 2]].set(True)
    return jnp.where(is_apot, wa, wu)


def quantize_weights(params: dict, compute: str = "m2q",
                     bits: int = 8) -> dict:
    """The dequantized weight tree the quantized forward runs on.
    ``compute``: "m2q" (the 1:1 APoT/uniform split) or "uniform" for the
    compute-heavy filters; ``bits`` below 8 makes them all uniform at that
    width (the control)."""
    if compute not in ("m2q", "uniform"):
        raise ValueError(f"unknown compute scheme {compute!r}")

    def visit(path, w):
        name = path[-1].key
        w2 = w.reshape(-1, w.shape[-1])
        if name in DEPTHWISE:
            return uniform_weights(w2, DW_BITS).reshape(w.shape)
        if name in MIXED and path[0].key != "stem":
            if compute == "m2q" and bits == 8:
                return mixed_weights(w2).reshape(w.shape)
            return uniform_weights(w2, bits).reshape(w.shape)
        return w
    return jax.tree_util.tree_map_with_path(visit, params)


def _fake_quant_act(x, max_abs, bits):
    qmax = 2.0 ** (bits - 1) - 1
    scale = jnp.maximum(max_abs / qmax, 1e-8)
    return jnp.clip(jnp.round(x / scale), -qmax, qmax) * scale


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


class _Layers:
    """Layer arithmetic of one forward: float, recording the input max|x|
    of each compute-heavy layer (calibration), or quantized."""

    def __init__(self, act_max=None, bits=8):
        self.act_max = act_max      # None: float inputs
        self.bits = bits
        self.seen = {}              # layer path -> max|x| of its input

    def mixed_input(self, x, path):
        self.seen[path] = jnp.max(jnp.abs(x))
        if self.act_max is None:
            return x
        return _fake_quant_act(x, self.act_max[path], self.bits)

    def conv(self, x, w, path=None, stride=1, groups=1):
        if path is not None:
            x = self.mixed_input(x, path)
        return jax.lax.conv_general_dilated(
            x, w, window_strides=(stride, stride), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups, precision=HIGHEST)


def _norm(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + EPS_NORM) * g


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _attention(q, k, v):
    """ReLU linear attention; q, k, v (B, N, heads, D)."""
    qr, kr = jax.nn.relu(q), jax.nn.relu(k)
    kv = jnp.einsum("bnhd,bnhe->bhde", kr, v, precision=HIGHEST)
    num = jnp.einsum("bnhd,bhde->bnhe", qr, kv, precision=HIGHEST)
    den = jnp.einsum("bnhd,bhd->bnh", qr, jnp.sum(kr, axis=1),
                     precision=HIGHEST)[..., None]
    return num / (den + EPS_ATTN)


def _mbconv(L, p, x, stride, at):
    h = _silu(_norm(L.conv(x, p["w_pw1"], f"{at}/w_pw1"), p["ln1"]))
    h = _silu(L.conv(h, p["w_dw"], stride=stride, groups=h.shape[-1]))
    h = _norm(L.conv(h, p["w_pw2"], f"{at}/w_pw2"), p["ln2"])
    if stride == 1 and x.shape[-1] == h.shape[-1]:
        h = h + x
    return h


def _msa(L, p, x, head_dim, at):
    B, H, W, C = x.shape
    qkv = L.conv(_norm(x, p["ln"]), p["w_qkv"], f"{at}/w_qkv")
    agg = L.conv(qkv, p["w_agg"], groups=qkv.shape[-1])
    outs = []
    for t in (qkv, agg):                      # the two token scales
        q, k, v = (a.reshape(B, H * W, C // head_dim, head_dim)
                   for a in jnp.split(t.reshape(B, H * W, 3 * C), 3, -1))
        outs.append(_attention(q, k, v).reshape(B, H, W, C))
    return x + L.conv(jnp.concatenate(outs, -1), p["w_proj"],
                      f"{at}/w_proj")


def _forward(cfg, params, images, L):
    x = jnp.asarray(images, jnp.float32)
    x = L.conv(x, params["stem"]["w"], stride=2)    # the stem stays float
    x = _silu(_norm(x, params["stem"]["ln"]))
    for si, blocks in enumerate(params["stages"]):
        for bi, blk in enumerate(blocks):
            at = f"stages/{si}/{bi}"
            x = _mbconv(L, blk["mb"], x, 2 if (bi == 0 and si > 0) else 1,
                        f"{at}/mb")
            if "msa" in blk:
                x = _msa(L, blk["msa"], x, cfg["head_dim"], f"{at}/msa")
    head = params["head"]
    x = _silu(_norm(L.conv(x, head["w_in"], "head/w_in"), head["ln"]))
    x = jnp.mean(x, axis=(1, 2))
    return jnp.dot(L.mixed_input(x, "head/w"), head["w"], precision=HIGHEST)


def float_forward(cfg: dict, params: dict, images):
    """(B, R, R, 3) images -> (B, n_classes) logits of the float model."""
    return _forward(cfg, params, images, _Layers())


def calibrate(cfg: dict, params: dict, batches) -> dict:
    """max|x| at the input of every compute-heavy layer over ``batches``
    of images, through the float model."""
    run = jax.jit(lambda p, x: _layer_maxima(cfg, p, x))
    out = None
    for b in batches:
        m = run(params, b)
        out = m if out is None else jax.tree.map(jnp.maximum, out, m)
    return out


def _layer_maxima(cfg, params, images):
    L = _Layers()
    _forward(cfg, params, images, L)
    return L.seen


def quantized_forward(cfg: dict, qweights: dict, act_max: dict, images,
                      bits: int = 8):
    """Logits of the quantized model: ``qweights`` from
    :func:`quantize_weights`, ``act_max`` from :func:`calibrate`."""
    return _forward(cfg, qweights, images, _Layers(act_max, bits))
