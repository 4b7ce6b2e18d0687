"""Quantized convolutions as first-class citizens of the M2Q hot path.

Covers: the paper-taxonomy (kind-by-shape) regression on QUANT_RULES, real
QTensor production for conv leaves in quantize_model, PWConv/DWConv parity
(fused Pallas dispatch vs pure-XLA QTensor path vs dequantized float
reference), kernel routing counts on a full quantized EfficientViT forward,
the HLO proof that no f32 dequantized-weight convolution survives on the
quantized hot path, and the MBConv stride/residual assumptions.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import nn
from repro.configs.registry import REDUCED
from repro.core import (M2QPolicy, QM2Q, QUniform, ShapeCtx, fake_quant_act,
                        quantize_model, select_schemes)
from repro.core import policy as pol
from repro.core.apply import match_kind
from repro.core.calibrate import (rule_matcher, run_calibration,
                                  wrap_for_calibration)
from repro.core.calibrate import path_str
from repro.kernels import ops
from repro.models import efficientvit as evit
from repro.models import get_model


def _rng(seed=0):
    return np.random.default_rng(seed)


def _qconv_m2q(w4, act_max_abs=None):
    """Quantize an HWIO conv filter the way core.apply does: flattened 2-D
    payload, original shape in aux."""
    w2 = jnp.asarray(w4).reshape(-1, w4.shape[-1])
    asn = select_schemes(w2, ratio=0.5)
    qt = QM2Q.quantize(w2, asn.apot_idx, asn.uniform_idx,
                       act_max_abs=act_max_abs)
    return dataclasses.replace(qt, shape=tuple(w4.shape))


def _qconv_u4(w4):
    w2 = jnp.asarray(w4).reshape(-1, w4.shape[-1])
    qt = QUniform.quantize(w2, bits=4)
    return dataclasses.replace(qt, shape=tuple(w4.shape))


# ---------------------------------------------------------------------------
# taxonomy: kind follows shape (paper Sec. III-A)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["efficientvit-b1-r224",
                                  "efficientvit-b2-r224"])
def test_quant_rules_kind_agrees_with_shape(arch):
    """Walk the param tree: every (kh,kw,1,C) depthwise filter must map to
    KIND_DWCONV (the 5x5 w_agg aggregation was historically mis-filed as
    KIND_DENSE), every 1x1 conv and 2-D matmul to KIND_DENSE."""
    cfg = REDUCED[arch]
    model = get_model(cfg)
    params = model.init(cfg, jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    seen_agg = seen_dw = 0
    for path, leaf in leaves:
        key = path_str(path)
        kind = match_kind(model.QUANT_RULES, key)
        if kind in (None, pol.KIND_SKIP) or leaf.ndim < 2:
            continue
        if leaf.ndim == 4 and leaf.shape[2] == 1 and leaf.shape[0] > 1:
            assert kind == pol.KIND_DWCONV, (key, leaf.shape, kind)
            seen_dw += 1
            seen_agg += key.endswith("w_agg")
        elif leaf.ndim == 4 and leaf.shape[:2] == (1, 1):
            assert kind == pol.KIND_DENSE, (key, leaf.shape, kind)
        elif leaf.ndim == 2:
            assert kind == pol.KIND_DENSE, (key, leaf.shape, kind)
    assert seen_dw >= 2 and seen_agg >= 1  # both w_dw and w_agg exercised


# ---------------------------------------------------------------------------
# quantize_model produces real QTensors for conv leaves
# ---------------------------------------------------------------------------


def test_quantize_model_conv_leaves_are_qtensors():
    cfg = REDUCED["efficientvit-b1-r224"]
    model = get_model(cfg)
    params = model.init(cfg, jax.random.PRNGKey(0))
    ctx = ShapeCtx(tokens_per_step=32 * cfg.img_res * cfg.img_res)
    qp, report = quantize_model(params, model.QUANT_RULES, ctx,
                                M2QPolicy(intensity_threshold=1.0))
    flat = {path_str(p): l for p, l in
            jax.tree_util.tree_flatten_with_path(
                qp, is_leaf=lambda x: isinstance(x, (QM2Q, QUniform)))[0]}
    n_pw = n_dw = 0
    for key, leaf in flat.items():
        if key.endswith(("w_pw1", "w_pw2", "w_qkv", "w_proj", "w_in")):
            assert isinstance(leaf, QM2Q), (key, type(leaf))
            assert leaf.payload.ndim == 2 and len(leaf.shape) == 4, key
            # HWIO-aware reduction: one scale column per Cout filter
            assert leaf.u_scale.shape == (1, leaf.shape[-1]), key
            n_pw += 1
        elif key.endswith(("w_dw", "w_agg")):
            assert isinstance(leaf, QUniform) and leaf.bits == 4, key
            kh, kw, one, c = leaf.shape
            assert one == 1
            assert leaf.payload.shape == (kh * kw, c // 2), key
            assert leaf.scale.shape == (1, c), key
            n_dw += 1
    assert n_pw >= 8 and n_dw >= 4
    # the report covers every quantized leaf with a real decision
    assert all(r.decision in ("mixed", "lowbit") for r in report)
    # dequant reshapes back through the HWIO aux shape for the XLA fallback
    for key, leaf in flat.items():
        if isinstance(leaf, (QM2Q, QUniform)) and len(leaf.shape) == 4:
            assert leaf.dequant().reshape(leaf.shape).shape == leaf.shape


# ---------------------------------------------------------------------------
# PWConv parity: fused kernels vs XLA QTensor path vs float reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cin,cout", [(16, 24), (32, 130)])
def test_pwconv_m2q_parity(cin, cout, monkeypatch):
    rng = _rng(cin + cout)
    w4 = rng.normal(0, 0.05, (1, 1, cin, cout)).astype(np.float32)
    x = jnp.asarray(rng.normal(0, 1, (2, 6, 7, cin)).astype(np.float32))
    amax = jnp.float32(np.abs(np.asarray(x)).max())
    qt = _qconv_m2q(w4, act_max_abs=amax)
    assert ops.kernel_supported(qt)
    monkeypatch.setenv("REPRO_PALLAS_DISPATCH", "0")
    y_xla = nn.conv2d(x, qt)
    monkeypatch.setenv("REPRO_PALLAS_DISPATCH", "1")
    y_ker = nn.conv2d(x, qt)
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_xla),
                               rtol=1e-4, atol=1e-4)
    # float reference: dequantized weights + fake-quantized activations;
    # the error is quantization-level, not path-level
    y_ref = jax.lax.conv_general_dilated(
        fake_quant_act(x, qt.act_scale),
        qt.dequant().reshape(qt.shape), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    rel = float(jnp.linalg.norm(y_ker - y_ref) / jnp.linalg.norm(y_ref))
    assert rel < 5e-3, rel


@pytest.mark.parametrize("bits", [8, 4])
def test_pwconv_uniform_parity(bits, monkeypatch):
    rng = _rng(11 * bits)
    cin, cout = 24, 40
    w4 = rng.normal(0, 0.05, (1, 1, cin, cout)).astype(np.float32)
    x = jnp.asarray(rng.normal(0, 1, (3, 5, 5, cin)).astype(np.float32))
    w2 = jnp.asarray(w4).reshape(cin, cout)
    if bits == 8:
        qt = QUniform.quantize(w2, bits=8,
                               act_max_abs=jnp.max(jnp.abs(x)))
    else:
        qt = QUniform.quantize(w2, bits=4)
    qt = dataclasses.replace(qt, shape=tuple(w4.shape))
    assert ops.kernel_supported(qt)
    monkeypatch.setenv("REPRO_PALLAS_DISPATCH", "0")
    y_xla = nn.conv2d(x, qt)
    monkeypatch.setenv("REPRO_PALLAS_DISPATCH", "1")
    y_ker = nn.conv2d(x, qt)
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_xla),
                               rtol=1e-4, atol=1e-4)
    y_ref = jax.lax.conv_general_dilated(
        x if bits == 4 else fake_quant_act(x, qt.act_scale),
        qt.dequant().reshape(qt.shape), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    rel = float(jnp.linalg.norm(y_ker - y_ref) / jnp.linalg.norm(y_ref))
    assert rel < 5e-3, rel


# ---------------------------------------------------------------------------
# DWConv parity: packed-w4 kernel vs dequantized XLA conv
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kh,stride", [(3, 1), (3, 2), (5, 1), (5, 2)])
def test_dwconv_parity_vs_dequant_reference(kh, stride, monkeypatch):
    rng = _rng(kh * 10 + stride)
    C = 48
    w4 = rng.normal(0, 0.2, (kh, kh, 1, C)).astype(np.float32)
    x = jnp.asarray(rng.normal(0, 1, (2, 9, 9, C)).astype(np.float32))
    qt = _qconv_u4(w4)
    assert ops.dwconv_kernel_supported(qt, x, stride, C, "SAME")
    monkeypatch.setenv("REPRO_PALLAS_DISPATCH", "0")
    y_xla = nn.dwconv2d(x, qt, stride=stride)  # dequantized XLA fallback
    monkeypatch.setenv("REPRO_PALLAS_DISPATCH", "1")
    y_ker = nn.dwconv2d(x, qt, stride=stride)  # packed-w4 Pallas kernel
    assert y_ker.shape == y_xla.shape == (2, -(-9 // stride),
                                          -(-9 // stride), C)
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_xla),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# full-model routing + HLO cleanliness
# ---------------------------------------------------------------------------


def _calibrated_quantized_reduced(batch=1):
    cfg = REDUCED["efficientvit-b1-r224"]
    model = get_model(cfg)
    params = model.init(cfg, jax.random.PRNGKey(0))
    rng = _rng(0)
    imgs = jnp.asarray(rng.normal(
        0, 1, (batch, cfg.img_res, cfg.img_res, 3)).astype(np.float32))
    wrapped, stats = wrap_for_calibration(params,
                                          rule_matcher(model.QUANT_RULES))
    run_calibration(lambda p, x: model.forward(cfg, p, x), wrapped, [imgs])
    ctx = ShapeCtx(tokens_per_step=batch * cfg.img_res * cfg.img_res)
    qp, _ = quantize_model(params, model.QUANT_RULES, ctx,
                           M2QPolicy(intensity_threshold=1.0),
                           act_stats=stats)
    return cfg, model, qp, imgs


def test_quantized_forward_routes_convs_through_kernels(monkeypatch):
    """Acceptance: with dispatch on, EVERY stride-1 1x1 PWConv runs the
    fused m2q matmul and EVERY depthwise conv (3x3 + 5x5) runs dwconv_w4;
    the result matches the pure-XLA QTensor path.  The attn axis is pinned
    OFF via its env var: the int8 attention kernel shifts MSA numerics by
    quantization error, and this test's 2e-3 parity is about CONV routing
    (attention parity lives in test_attn_dispatch.py)."""
    cfg, model, qp, imgs = _calibrated_quantized_reduced()
    monkeypatch.setenv("REPRO_PALLAS_ATTN_DISPATCH", "0")
    monkeypatch.setenv("REPRO_PALLAS_DISPATCH", "0")
    y_xla = model.forward(cfg, qp, imgs)
    calls = {"mm": 0, "dw": 0}
    orig_mm, orig_dw = ops.qtensor_matmul, ops.qtensor_dwconv

    def count_mm(*a, **k):
        calls["mm"] += 1
        return orig_mm(*a, **k)

    def count_dw(*a, **k):
        calls["dw"] += 1
        return orig_dw(*a, **k)

    monkeypatch.setattr(ops, "qtensor_matmul", count_mm)
    monkeypatch.setattr(ops, "qtensor_dwconv", count_dw)
    monkeypatch.setenv("REPRO_PALLAS_DISPATCH", "1")
    y_ker = model.forward(cfg, qp, imgs)
    # REDUCED b1: 7 depthwise sites (4 MBConv 3x3 + 3 MSA 5x5 w_agg); every
    # quantized 1x1 PWConv (+ the 2-D head via nn.dense) hits the matmul
    # kernels
    assert calls["dw"] == 7, calls
    assert calls["mm"] >= 15, calls
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_xla),
                               rtol=2e-3, atol=2e-3)
    assert bool(jnp.all(jnp.isfinite(y_ker)))


def test_hlo_quantized_forward_has_no_f32_weight_conv():
    """Acceptance (qlint conv-budget rule): the compiled quantized forward
    emits NO dequantized-weight convolution for quantized conv leaves.
    Dispatch on: the only convolution left is the (unquantized) stem.
    Dispatch off: PWConvs STILL lower to quantized matmuls (no f32 conv);
    only the stem and the 7 weights-only depthwise fallbacks convolve."""
    from repro.analysis import lint
    from repro.analysis.traces import trace_fn
    cfg, model, qp, imgs = _calibrated_quantized_reduced()
    tr = trace_fn(lambda p, x: model.forward(cfg, p, x), (qp, imgs),
                  name="evit/m2q/forward", dispatch=True,
                  meta={"conv_budget": 1})
    assert lint(tr, "conv-budget") == []
    tr0 = trace_fn(lambda p, x: model.forward(cfg, p, x), (qp, imgs),
                   name="evit/m2q/forward-xla", dispatch=False,
                   meta={"conv_budget": 1 + 7})
    assert lint(tr0, "conv-budget") == []
    # seeded violation: a wrong budget must FIRE the rule (non-vacuous)
    tr0.meta["conv_budget"] = 1
    vs = lint(tr0, "conv-budget")
    assert [v.rule for v in vs] == ["conv-budget"] and "8 conv" in \
        vs[0].message


# ---------------------------------------------------------------------------
# dwconv_w4 H-tiled high-resolution path (the old whole-map guard is gone)
# ---------------------------------------------------------------------------


def test_dwconv_high_res_maps_stay_on_kernel():
    """ISSUE 9 satellite: with the H-tiled grid the VMEM bound is the
    TILE, so 256x256 and 384x384 maps take the kernel path — no more
    whole-map budget fallback — and the guard derives its answer from
    dwconv_tile_plan (rejecting only maps the tiler cannot block)."""
    rng = _rng(77)
    C = 4
    w4 = rng.normal(0, 0.2, (3, 3, 1, C)).astype(np.float32)
    qt = _qconv_u4(w4)
    for res in (224, 256, 384):
        x = jnp.zeros((1, res, res, C), jnp.float32)
        assert ops.dwconv_kernel_supported(qt, x, 1, C, "SAME"), res
        assert ops.dwconv_kernel_supported(qt, x, 2, C, "SAME"), res
    # 5x5 MSA window at high resolution too
    w5 = rng.normal(0, 0.2, (5, 5, 1, C)).astype(np.float32)
    x384 = jnp.zeros((1, 384, 384, C), jnp.float32)
    assert ops.dwconv_kernel_supported(_qconv_u4(w5), x384, 1, C, "SAME")
    # the tile plan itself fits under the budget at these resolutions...
    for res in (256, 384, 512):
        plan = ops.dwconv_tile_plan(res, res, 3, 3, 1)
        assert plan is not None and 1 <= plan[0] <= res
        assert ops._dwconv_tile_bytes(res, 3, 3, 1, *plan) <= \
            ops._DWCONV_VMEM_BYTES
    # ...and only a genuinely untileable map (a row too wide for even the
    # minimal 1-row 2-channel tile) is refused
    assert ops.dwconv_tile_plan(2, 2 ** 21, 3, 3, 1) is None
    assert not ops.dwconv_kernel_supported(
        qt, jnp.zeros((1, 2, 2 ** 21, C), jnp.float32), 1, C, "SAME")


@pytest.mark.parametrize("res,stride", [(256, 1), (256, 2),
                                        (384, 1), (384, 2)])
def test_dwconv_high_res_kernel_matches_xla_reference(res, stride,
                                                      monkeypatch):
    """ISSUE 9 acceptance: R256/R384 depthwise maps execute on the Pallas
    w4 kernel (dispatch-on nn.dwconv2d routes there, the H-tiled grid) and
    match the dequantized-weight XLA conv — triangulated over stride-1 and
    the stride-2 downsampler path."""
    rng = _rng(res + stride)
    C = 4
    w4 = rng.normal(0, 0.2, (3, 3, 1, C)).astype(np.float32)
    qt = _qconv_u4(w4)
    x = jnp.asarray(rng.normal(0, 1, (1, res, res, C)).astype(np.float32))
    calls = {"dw": 0}
    orig = ops.qtensor_dwconv

    def spy(*a, **k):
        calls["dw"] += 1
        return orig(*a, **k)

    monkeypatch.setattr(ops, "qtensor_dwconv", spy)
    with ops.dispatch(conv=True):
        y = nn.dwconv2d(x, qt, stride=stride)
    assert calls["dw"] == 1, "high-res map did not take the kernel path"
    y_ref = jax.lax.conv_general_dilated(
        x, qt.dequant().reshape(qt.shape), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# opt-in int8 im2col stem (ISSUE 5 satellite; ROADMAP stem item)
# ---------------------------------------------------------------------------


def test_stem_im2col_int8_matmul_parity():
    """A quantized KxK stride-2 conv leaf lowers to im2col + the quantized
    matmul path (kernel and XLA variants agree), tracking the fake-quant
    f32 conv to quantization tolerance."""
    rng = _rng(88)
    w4 = rng.normal(0, 0.1, (3, 3, 3, 8)).astype(np.float32)
    x = jnp.asarray(rng.normal(0, 1, (2, 9, 9, 3)).astype(np.float32))
    w2 = jnp.asarray(w4).reshape(27, 8)
    qt = QUniform.quantize(w2, bits=8, act_max_abs=jnp.max(jnp.abs(x)))
    qt = dataclasses.replace(qt, shape=tuple(w4.shape))
    with ops.dispatch(dense=False, conv=False):
        y_xla = nn.conv2d(x, qt, stride=2)
    with ops.dispatch(dense=True, conv=True):
        y_ker = nn.conv2d(x, qt, stride=2)
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_xla),
                               rtol=1e-4, atol=1e-4)
    y_ref = jax.lax.conv_general_dilated(
        fake_quant_act(x, qt.act_scale), qt.dequant().reshape(qt.shape),
        (2, 2), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    rel = float(jnp.linalg.norm(y_ker - y_ref) / jnp.linalg.norm(y_ref))
    assert rel < 5e-3, rel


def test_stem_opt_in_recipe_quantizes_and_removes_last_conv():
    """The stem is f32 by DEFAULT; a recipe appending evit.STEM_RULE +
    evit.STEM_OVERRIDE quantizes it to uniform-8 W8A8, the forward stays
    close to the default artifact's, and the dispatch-on HLO drops to ZERO
    convolutions (the stem was the only one left)."""
    from repro.recipe import PRESETS, quantize
    cfg = REDUCED["efficientvit-b1-r224"]
    model = get_model(cfg)
    params = model.init(cfg, jax.random.PRNGKey(0))
    imgs = jnp.asarray(_rng(4).normal(
        0, 1, (2, cfg.img_res, cfg.img_res, 3)).astype(np.float32))
    qm_default = quantize(cfg, params, "m2q-w8a8", calib_batches=[imgs])
    assert isinstance(qm_default.params["stem"]["w"], jax.Array)  # f32 stem
    rec = PRESETS["m2q-w8a8"].replace(
        rules=tuple(evit.QUANT_RULES) + (evit.STEM_RULE,),
        overrides=(evit.STEM_OVERRIDE,))
    qm = quantize(cfg, params, rec, calib_batches=[imgs])
    stem = qm.params["stem"]["w"]
    assert isinstance(stem, QUniform) and stem.bits == 8
    assert stem.act_scale is not None  # calibrated -> true int8 path
    assert stem.payload.shape == (27, cfg.widths[0])
    # numerics: the int8 stem moves logits by bounded quantization error
    # (a RANDOM-INIT reduced net amplifies first-layer noise — the tight
    # per-layer guard is test_stem_im2col_int8_matmul_parity; the trained
    # proxy in examples/quantize_efficientvit loses no top-1)
    y_def = qm_default.forward(imgs)
    y_stem = qm.forward(imgs)
    assert bool(jnp.all(jnp.isfinite(y_stem)))
    rel = float(jnp.linalg.norm(y_stem - y_def) / jnp.linalg.norm(y_def))
    assert rel < 0.25, rel
    # the paper-taxonomy pins are unaffected by the extra override
    by_path = {r.path: r for r in qm.report}
    assert by_path["stem/w"].decision == "mixed"
    assert all(r.decision == qr.decision for r, qr in
               zip(qm_default.report, (by_path[r.path] for r in
                                       qm_default.report)))
    # HLO (qlint conv-budget rule): with conv dispatch on the stem's conv
    # is gone -> zero convolutions in the whole module
    def fwd(p, x):
        with ops.dispatch(dense=True, conv=True, attn=False):
            return model.forward(cfg, p, x)
    from repro.analysis import lint
    from repro.analysis.traces import trace_fn
    tr = trace_fn(fwd, (qm.params, imgs), name="evit/stem-q/forward",
                  dispatch=False, meta={"conv_budget": 0})
    assert lint(tr, "conv-budget") == []


# ---------------------------------------------------------------------------
# MBConv stride/residual assumptions (stride_block cleanup)
# ---------------------------------------------------------------------------


def test_mbconv_stride_and_residual_assumptions():
    """_init_mbconv is stride-agnostic: only w_dw sees the stride (1x1
    PWConvs never downsample) and the residual is gated on stride==1 AND
    matching channels.  Zeroed conv weights make the residual observable:
    the conv branch collapses to exactly 0."""
    key = jax.random.PRNGKey(0)
    x = jnp.asarray(_rng(1).normal(0, 1, (1, 8, 8, 16)).astype(np.float32))
    p_same = jax.tree.map(jnp.zeros_like, evit._init_mbconv(key, 16, 16))
    # stride 1, cin == cout: residual survives -> output IS the input
    np.testing.assert_array_equal(np.asarray(evit._mbconv(p_same, x)),
                                  np.asarray(x))
    # stride 2: spatial halves, residual must NOT be applied
    y2 = evit._mbconv(p_same, x, stride=2)
    assert y2.shape == (1, 4, 4, 16)
    np.testing.assert_array_equal(np.asarray(y2), np.zeros((1, 4, 4, 16)))
    # channel change at stride 1: no residual either
    p_wide = jax.tree.map(jnp.zeros_like, evit._init_mbconv(key, 16, 24))
    y3 = evit._mbconv(p_wide, x)
    assert y3.shape == (1, 8, 8, 24)
    np.testing.assert_array_equal(np.asarray(y3), np.zeros((1, 8, 8, 24)))


def test_stage_entry_blocks_downsample_in_forward():
    """Stage-entry blocks (bi==0, si>0) run stride 2: feature maps halve
    exactly once per stage after the stride-2 stem."""
    cfg = REDUCED["efficientvit-b1-r224"]
    model = get_model(cfg)
    params = model.init(cfg, jax.random.PRNGKey(0))
    x = jnp.zeros((1, cfg.img_res, cfg.img_res, 3), jnp.float32)
    x = nn.conv2d(x, params["stem"]["w"], stride=2)
    res = cfg.img_res // 2
    for si, blocks in enumerate(params["stages"]):
        for bi, blk in enumerate(blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            x = evit._mbconv(blk["mb"], x, stride=stride)
            if stride == 2:
                res //= 2
            assert x.shape[1] == x.shape[2] == res, (si, bi, x.shape)
