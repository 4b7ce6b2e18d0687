"""Per-kernel allclose sweeps: Pallas (interpret=True on CPU) vs the ref.py
pure-jnp oracles, across shapes (aligned, ragged, tiny) and dtypes; plus
triangulation against the QTensor XLA paths, parity of the permutation-free
merged M2Q layout against the legacy concat+gather epilogue and the float
reference, HLO cleanliness of the fused path, and the block autotuner."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import QAPoT, QM2Q, QUniform, quantize_act, select_schemes
from repro.core.packing import apot_decode_values, apot_encode, pack_int4
from repro.core.quant import apot_quantize, fake_quant_act, uniform_quantize
from repro.kernels import autotune, ops, ref

SHAPES = [(128, 128, 128), (256, 384, 512), (96, 72, 136), (8, 16, 32),
          (130, 258, 514)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _rng(seed=0):
    return np.random.default_rng(seed)


def _mk_int8_weights(rng, K, N):
    w = rng.normal(0, 0.05, (K, N)).astype(np.float32)
    qt = QUniform.quantize(jnp.asarray(w), bits=8)
    return qt


@pytest.mark.parametrize("M,K,N", SHAPES)
def test_int8_matmul_vs_ref(M, K, N):
    rng = _rng(M + K + N)
    qt = _mk_int8_weights(rng, K, N)
    x = rng.normal(0, 1, (M, K)).astype(np.float32)
    sa = jnp.float32(np.abs(x).max() / 127.0)
    # the kernel quantizes the float tile in its prologue; the oracle takes
    # the pre-quantized activation — identical rounding by construction
    y_ker = ops.int8_matmul_op(jnp.asarray(x), qt.payload, sa,
                               qt.scale.reshape(-1),
                               qt.zero_point.reshape(-1), interpret=True)
    xq = quantize_act(jnp.asarray(x), sa)
    y_ref = ref.int8_matmul_ref(xq, qt.payload, sa, qt.scale.reshape(-1),
                                qt.zero_point.reshape(-1))
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    # triangulate vs QTensor serving path
    qt.act_scale = sa
    y_qt = qt.matmul(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_qt),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("M,K,N", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_int4_matmul_vs_ref(M, K, N, dtype):
    N = N + (N % 2)  # packing needs even N
    rng = _rng(M + K + N + 1)
    w = rng.normal(0, 0.05, (K, N)).astype(np.float32)
    qt = QUniform.quantize(jnp.asarray(w), bits=4)
    x = jnp.asarray(rng.normal(0, 1, (M, K)).astype(np.float32), dtype)
    y_ker = ops.int4_matmul_op(x.astype(jnp.float32), qt.payload,
                               qt.scale.reshape(-1),
                               qt.zero_point.reshape(-1), interpret=True)
    y_ref = ref.int4_matmul_ref(x.astype(jnp.float32), qt.payload,
                                qt.scale.reshape(-1),
                                qt.zero_point.reshape(-1))
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-5)
    y_qt = qt.matmul(x.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_qt),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("M,K,N", SHAPES)
def test_apot_matmul_vs_ref(M, K, N):
    rng = _rng(M * 3 + K + N)
    w = rng.normal(0, 0.05, (K, N)).astype(np.float32)
    qt = QAPoT.quantize(jnp.asarray(w))
    x = rng.normal(0, 1, (M, K)).astype(np.float32)
    y_ker = ops.apot_matmul_op(jnp.asarray(x), qt.codes, qt.scale.reshape(-1),
                               interpret=True)
    y_ref = ref.apot_matmul_ref(jnp.asarray(x), qt.codes,
                                qt.scale.reshape(-1))
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    y_qt = qt.matmul(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_qt),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("M,K,N", [(128, 128, 128), (64, 96, 200),
                                   (16, 32, 48), (130, 514, 254)])
def test_m2q_matmul_vs_ref_and_qtensor(M, K, N):
    rng = _rng(M + 7 * K + N)
    w = rng.normal(0, 0.05, (K, N)).astype(np.float32)
    asn = select_schemes(jnp.asarray(w), ratio=0.5)
    x = rng.normal(0, 1, (M, K)).astype(np.float32)
    qt = QM2Q.quantize(jnp.asarray(w), asn.apot_idx, asn.uniform_idx,
                       act_max_abs=jnp.float32(np.abs(x).max()))
    y_ker = ops.m2q_matmul_op(
        jnp.asarray(x), qt.act_scale, qt.payload, qt.u_scale.reshape(-1),
        qt.u_zp.reshape(-1), qt.a_scale.reshape(-1), interpret=True)
    y_ref = ref.m2q_merged_ref(
        jnp.asarray(x), qt.act_scale, qt.payload, qt.u_scale.reshape(-1),
        qt.u_zp.reshape(-1), qt.a_scale.reshape(-1))
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    # full fused dispatch vs QTensor XLA path (both permutation-free)
    y_full = ops.qtensor_matmul(jnp.asarray(x), qt, interpret=True)
    y_qt = qt.matmul(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(y_full), np.asarray(y_qt),
                               rtol=5e-3, atol=5e-3)


def _legacy_m2q(w, asn, x, act_scale):
    """Pre-refactor oracle: quantize the halves separately, run both engine
    matmuls, CONCATENATE, then inverse-permutation GATHER — the epilogue the
    merged layout deleted."""
    ui = jnp.asarray(asn.uniform_idx, jnp.int32)
    ai = jnp.asarray(asn.apot_idx, jnp.int32)
    inv_perm = jnp.argsort(jnp.concatenate([ui, ai]))
    xq = quantize_act(x, act_scale)
    qu = QUniform.quantize(w[:, ui], bits=8)
    yu = ref.int8_matmul_ref(xq, qu.payload, act_scale,
                             qu.scale.reshape(-1), qu.zero_point.reshape(-1))
    t = apot_quantize(w[:, ai], axis=-1)
    ya = ref.apot_matmul_ref(xq.astype(jnp.float32) * act_scale,
                             apot_encode(t), t.scale.reshape(-1))
    y = jnp.concatenate([yu, ya], axis=-1)
    return jnp.take(y, inv_perm, axis=-1)


@pytest.mark.parametrize("M,K,N", [(32, 64, 48), (16, 96, 130)])
def test_m2q_permutation_free_parity_vs_legacy_and_float(M, K, N):
    """The permutation-free merged path must match (a) the legacy
    concat+gather path bit-for-bit and (b) the float reference to
    quantization tolerance."""
    rng = _rng(11 * M + K + N)
    w = jnp.asarray(rng.normal(0, 0.05, (K, N)).astype(np.float32))
    asn = select_schemes(w, ratio=0.5)
    x = jnp.asarray(rng.normal(0, 1, (M, K)).astype(np.float32))
    amax = jnp.float32(np.abs(np.asarray(x)).max())
    qt = QM2Q.quantize(w, asn.apot_idx, asn.uniform_idx, act_max_abs=amax)

    y_legacy = _legacy_m2q(w, asn, x, qt.act_scale)
    y_merged = qt.matmul(x)
    np.testing.assert_allclose(np.asarray(y_merged), np.asarray(y_legacy),
                               rtol=1e-5, atol=1e-5)
    y_fused = ops.m2q_matmul_op(x, qt.act_scale, qt.payload,
                                qt.u_scale.reshape(-1), qt.u_zp.reshape(-1),
                                qt.a_scale.reshape(-1), interpret=True)
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_legacy),
                               rtol=1e-5, atol=1e-5)
    # float reference: error is quantization-level, not path-level
    y_float = fake_quant_act(x, qt.act_scale) @ qt.dequant()
    rel = float(jnp.linalg.norm(y_merged - y_float)
                / jnp.linalg.norm(y_float))
    assert rel < 5e-3, rel


def test_m2q_hlo_emits_no_gather_or_concat():
    """Acceptance (qlint no-gather-concat rule): zero gather/concatenate
    reachable from the quantized payloads before their contraction, on
    BOTH serving paths (XLA QTensor matmul and the fused Pallas dispatch),
    counting fusion interiors too.  The QTensor is passed as a jit
    ARGUMENT so its payloads are entry parameters the rule can seed from."""
    from repro.analysis import lint
    from repro.analysis.traces import trace_fn
    from repro.launch.hlo_analysis import op_histogram
    rng = _rng(21)
    w = jnp.asarray(rng.normal(0, 0.05, (128, 96)).astype(np.float32))
    asn = select_schemes(w, ratio=0.5)
    qt = QM2Q.quantize(w, asn.apot_idx, asn.uniform_idx,
                       act_max_abs=jnp.float32(3.0))
    x = jnp.zeros((8, 128), jnp.float32)
    for tag, fn in (("xla", lambda q, v: q.matmul(v)),
                    ("fused", lambda q, v: ops.qtensor_matmul(
                        v, q, interpret=True))):
        tr = trace_fn(fn, (qt, x), name=f"m2q/matmul/{tag}",
                      dispatch=False, meta={"quantized": True})
        assert lint(tr, "no-gather-concat") == []
    # the legacy epilogue DOES emit them (guards against a vacuous check;
    # op_histogram, not the rule — the legacy path contracts a FLOAT
    # weight, so there is no quantized entry param for the rule to seed
    # from, which is exactly why the merged layout exists)
    txt = jax.jit(
        lambda v: _legacy_m2q(w, asn, v, jnp.float32(3.0) / 127.0)
    ).lower(x).compile().as_text()
    hist = op_histogram(txt, include_fused=True)
    assert hist.get("gather", 0) >= 1 and hist.get("concatenate", 0) >= 1
    # seeded rule violation: a weight-side permutation gather BEFORE the
    # contraction — the epilogue shape the rule exists to catch
    def permuted(q, v):
        return v @ q.dequant()[jnp.argsort(jnp.argsort(w[:, 0]))]

    trv = trace_fn(permuted, (qt, x), name="m2q/matmul/permuted",
                   dispatch=False, meta={"quantized": True})
    vs = lint(trv, "no-gather-concat")
    assert vs and all(v.rule == "no-gather-concat" for v in vs)


@pytest.mark.parametrize("B,H,W,C", [(2, 8, 8, 32), (1, 14, 14, 64),
                                     (3, 7, 9, 16), (1, 16, 16, 130)])
@pytest.mark.parametrize("kh,kw,stride", [(3, 3, 1), (5, 5, 1), (3, 3, 2),
                                          (5, 5, 2), (3, 5, 1)])
def test_dwconv_w4_vs_ref(B, H, W, C, kh, kw, stride):
    """Generalized window/stride sweep (MBConv 3x3 incl. stride-2 stage
    entries, MSA 5x5 aggregation), triangulated kernel == ref == XLA conv."""
    C = C + (C % 2)
    rng = _rng(B + H + W + C + 7 * kh + stride)
    w = rng.normal(0, 0.2, (kh, kw, C)).astype(np.float32)
    u = uniform_quantize(jnp.asarray(w), bits=4, axis=-1)
    packed = pack_int4(u.q.reshape(kh * kw, C))
    scale = u.scale.reshape(-1)
    zp = u.zero_point.reshape(-1)
    x = rng.normal(0, 1, (B, H, W, C)).astype(np.float32)
    y_ker = ops.dwconv_w4_op(jnp.asarray(x), packed, scale, zp, kh=kh, kw=kw,
                             stride=stride, interpret=True)
    y_ref = ref.dwconv_w4_ref(jnp.asarray(x), packed, scale, zp, kh=kh,
                              kw=kw, stride=stride)
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    # triangulate against the dequantized-weight XLA conv (SAME semantics)
    wd = ((u.q.astype(np.float32) - np.asarray(u.zero_point))
          * np.asarray(u.scale)).reshape(kh, kw, 1, C)
    y_xla = jax.lax.conv_general_dilated(
        jnp.asarray(x), jnp.asarray(wd), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=C)
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_xla),
                               rtol=1e-4, atol=1e-4)


def test_qtensor_matmul_dispatch_uniform4_apot():
    rng = _rng(99)
    w = rng.normal(0, 0.05, (64, 48)).astype(np.float32)
    x = jnp.asarray(rng.normal(0, 1, (3, 5, 64)).astype(np.float32))
    q4 = QUniform.quantize(jnp.asarray(w), bits=4)
    np.testing.assert_allclose(
        np.asarray(ops.qtensor_matmul(x, q4, interpret=True)),
        np.asarray(q4.matmul(x)), rtol=1e-4, atol=1e-4)
    qa = QAPoT.quantize(jnp.asarray(w))
    np.testing.assert_allclose(
        np.asarray(ops.qtensor_matmul(x, qa, interpret=True)),
        np.asarray(qa.matmul(x)), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# nn.dense kernel dispatch wiring
# ---------------------------------------------------------------------------


def test_dense_routes_qtensors_through_kernels_when_enabled(monkeypatch):
    """With dispatch forced on, the model-facing nn.dense runs the fused
    Pallas path for supported leaves and matches the XLA QTensor path; the
    CPU default leaves dispatch off."""
    from repro import nn
    from repro.core import qmatmul

    monkeypatch.setenv("REPRO_PALLAS_DISPATCH", "0")
    assert not ops.dispatch_enabled()  # forced off -> XLA path
    monkeypatch.setenv("REPRO_PALLAS_DISPATCH", "1")
    assert ops.dispatch_enabled()

    rng = _rng(31)
    w = jnp.asarray(rng.normal(0, 0.05, (64, 48)).astype(np.float32))
    x = jnp.asarray(rng.normal(0, 1, (4, 64)).astype(np.float32))
    amax = jnp.float32(np.abs(np.asarray(x)).max())

    asn = select_schemes(w, ratio=0.5)
    qm = QM2Q.quantize(w, asn.apot_idx, asn.uniform_idx, act_max_abs=amax)
    assert ops.kernel_supported(qm)
    np.testing.assert_allclose(np.asarray(nn.dense(x, qm)),
                               np.asarray(qmatmul(x, qm)),
                               rtol=1e-4, atol=1e-4)
    q8 = QUniform.quantize(w, bits=8, act_max_abs=amax)
    assert ops.kernel_supported(q8)
    np.testing.assert_allclose(np.asarray(nn.dense(x, q8)),
                               np.asarray(qmatmul(x, q8)),
                               rtol=1e-4, atol=1e-4)
    # uncalibrated leaves stay on the XLA path (kernel would quantize
    # activations the XLA dequant path does not)
    assert not ops.kernel_supported(QM2Q.quantize(w, asn.apot_idx,
                                                  asn.uniform_idx))
    assert not ops.kernel_supported(QUniform.quantize(w, bits=8))
    # embeddings (axis=0 per-row scales) never dispatch
    assert not ops.kernel_supported(QUniform.quantize(w, bits=8, axis=0))


# ---------------------------------------------------------------------------
# int8_matmul launch geometry: row slabs of whole K and N, bf16 at HBM
# ---------------------------------------------------------------------------

# EfficientViT-B2's 1x1 convs (K, N) at reduced M, a row count with no
# 16-row divisor (B2 stage 4 at batch 15: the padded plan), the int8 head,
# and a qwen1.5-0.5b decode FFN at batch 4
SLAB_CASES = [(1024, 24, 96), (1024, 96, 24), (1024, 48, 192),
              (1024, 192, 48), (1024, 384, 1152), (1024, 768, 384),
              (1024, 384, 1536), (735, 384, 1536), (16, 1536, 1000),
              (4, 1024, 2816)]


@pytest.mark.parametrize("M,K,N", SLAB_CASES)
def test_int8_slab_path_is_bit_identical_to_the_padded_f32_path(M, K, N):
    """The served 8-bit path (bf16 into and out of the kernel, blocks from
    int8_tile_plan) gives the same bits as the path it replaced: x cast to
    f32, padded 128-wide blocks, an f32 output, then the cast to bf16."""
    rng = _rng(M * 7 + K + N)
    qt = _mk_int8_weights(rng, K, N)
    x = jnp.asarray(rng.normal(0, 1, (M, K)), jnp.bfloat16)
    qt.act_scale = jnp.max(jnp.abs(x.astype(jnp.float32))) / 127.0
    bm, bn, bk = ops.int8_tile_plan(M, K, N, 2)
    assert (bn, bk) == (N, K)
    new = ops.qtensor_matmul(x, qt, interpret=True)
    old = ops.int8_matmul_op(
        x.astype(jnp.float32), qt.payload, qt.act_scale,
        qt.scale.reshape(-1), qt.zero_point.reshape(-1), interpret=True,
        blocks=(128, 128, 128)).astype(jnp.bfloat16)
    assert new.dtype == jnp.bfloat16 and new.shape == (M, N)
    np.testing.assert_array_equal(np.asarray(new.view(jnp.uint16)),
                                  np.asarray(old.view(jnp.uint16)))


def _pointwise_launches(arch):
    """Every int8_matmul launch (M, N, K) of the quantized forward of
    ``arch`` at one image, as the served trace requests it, and the
    int8_plan_counts() the trace added."""
    import dataclasses

    from repro.configs.registry import ARCHS
    from repro.models import get_model
    from repro.recipe import PRESETS, abstract_quantize

    cfg = ARCHS[arch]
    base = PRESETS["m2q-w8a8"]
    recipe = base.replace(policy=dataclasses.replace(
        base.policy, compute_scheme="uniform8"))
    qp = abstract_quantize(cfg, recipe=recipe, tokens_per_step=16)
    x = jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.float32)
    reqs, before = [], ops.int8_plan_counts()
    with autotune.record_requests(reqs), \
            ops.dispatch(dense=True, conv=True, attn=True):
        jax.eval_shape(lambda p, im: get_model(cfg).forward(cfg, p, im),
                       qp, x)
    after = ops.int8_plan_counts()
    return ([(r.M, r.N, r.K) for r in reqs if r.kernel == "int8_matmul"],
            {k: after[k] - before[k] for k in after})


@pytest.fixture(scope="module")
def pointwise():
    return {arch: _pointwise_launches(arch)
            for arch in ("efficientvit-b1-r224", "efficientvit-b2-r224")}


def test_int8_plan_takes_whole_k_and_n_at_every_bucket(pointwise):
    """Every B1 and B2 1x1 conv (and head) at buckets 1-16 gets one weight
    block of whole K and N and a row slab that fits the VMEM budget as
    counted.  Rows divide M at bucket 16, the offline cells' batch; where
    M = b x 196 or b x 49 has no 16-row divisor (odd b), M is padded by
    under one sublane tile a step."""
    budget = ops._DWCONV_VMEM_BYTES
    for arch, (launches, _) in pointwise.items():
        steps16 = 0
        for M1, N, K in launches:
            for b in range(1, 17):
                M = b * M1
                bm, bn, bk = ops.int8_tile_plan(M, K, N, 2)
                assert (bn, bk) == (N, K), (arch, M, K, N)
                assert ops._int8_tile_bytes(bm, bn, bk, 2) <= budget
                assert bm == M or bm % 16 == 0, (arch, M, K, N, bm)
                steps = -(-M // bm)
                assert steps * bm - M < 16 * steps, (arch, M, K, N, bm)
                if b == 16:
                    assert M % bm == 0, (arch, M, K, N, bm)
                    steps16 += steps
        # 19,908 grid steps a B2 forward at 128-row blocks
        assert steps16 < {"efficientvit-b1-r224": 400,
                          "efficientvit-b2-r224": 600}[arch]


def test_int8_plan_tiles_n_then_k_when_the_weight_is_too_large():
    budget = ops._DWCONV_VMEM_BYTES
    # a 4096 x 11008 weight (45 MB) cannot sit whole in VMEM: N tiles in
    # multiples of 128 that divide it, K stays whole while it fits
    bm, bn, bk = ops.int8_tile_plan(64, 4096, 11008, 2)
    assert bk == 4096 and bn < 11008 and bn % 128 == 0 and 11008 % bn == 0
    assert bm == 64
    assert ops._int8_tile_bytes(bm, bn, bk, 2) <= budget
    # K too long for even a 128-wide N block: K tiles as well
    bm, bn, bk = ops.int8_tile_plan(8, 50000, 50000, 4)
    assert bn == 128 and bk < 50000 and bk % 128 == 0
    assert ops._int8_tile_bytes(bm, bn, bk, 4) <= budget
    # a smaller budget tiles what the default keeps whole
    assert ops.int8_tile_plan(784, 384, 1536, 2)[1:] == (1536, 384)
    small = ops.int8_tile_plan(784, 384, 1536, 2, budget=1 << 20)
    assert small[1] < 1536 and small[1] % 128 == 0
    assert ops._int8_tile_bytes(*small, 2) <= 1 << 20


def test_int8_tiled_plan_runs_the_multi_k_path():
    """A plan that tiles K accumulates over K steps in VMEM scratch and
    matches the slab launch of the same product bit for bit."""
    rng = _rng(5)
    M, K, N = 32, 512, 384
    qt = _mk_int8_weights(rng, K, N)
    x = jnp.asarray(rng.normal(0, 1, (M, K)), jnp.float32)
    sa = jnp.max(jnp.abs(x)) / 127.0
    args = (x, qt.payload, sa, qt.scale.reshape(-1),
            qt.zero_point.reshape(-1))
    before = ops.int8_plan_counts()
    slab = ops.int8_matmul_op(*args, interpret=True)
    tiled = ops.int8_matmul_op(*args, interpret=True, blocks=(16, 128, 128))
    after = ops.int8_plan_counts()
    assert {k: after[k] - before[k] for k in after} == {"slab": 1,
                                                        "tiled": 1}
    np.testing.assert_array_equal(np.asarray(slab), np.asarray(tiled))


def test_int8_plan_counts_every_b2_pointwise_conv_as_a_slab(pointwise):
    launches, counts = pointwise["efficientvit-b2-r224"]
    # 18 MBConvs x 2, 10 MSA blocks x 2 (qkv, proj), 2 in the head
    assert len(launches) == 58
    assert counts == {"slab": 58, "tiled": 0}


# ---------------------------------------------------------------------------
# autotuner
# ---------------------------------------------------------------------------


def test_autotune_interpret_falls_back_to_heuristic():
    assert autotune.blocks_for("m2q_matmul", 130, 258, 514,
                               interpret=True) == \
        autotune.heuristic_blocks(130, 258, 514)
    # no bench_fn -> heuristic even when "tunable"
    assert autotune.blocks_for("m2q_matmul", 128, 128, 128,
                               interpret=False) == (128, 128, 128)
    # int8_matmul falls back to its VMEM slab plan instead (f32 unless the
    # request says otherwise): whole K and N, all 130 rows
    assert autotune.blocks_for("int8_matmul", 130, 258, 514,
                               interpret=True) == (130, 258, 514) == \
        ops.int8_tile_plan(130, 514, 258, 4)
    assert autotune.blocks_for("int8_matmul", 128, 128, 128,
                               interpret=False) == (128, 128, 128)


def test_autotune_cache_roundtrip(tmp_path):
    path = str(tmp_path / "tune.json")
    key = autotune.cache_key("k", 1, 2, 3)
    cache = autotune.AutotuneCache(path)
    assert cache.get(key) is None
    cache.put(key, (8, 16, 32))
    reloaded = autotune.AutotuneCache(path).load()
    assert reloaded.get(key) == (8, 16, 32)
    assert len(reloaded) == 1
    # corrupt file degrades to empty, not an exception
    with open(path, "w") as f:
        f.write("{not json")
    with pytest.warns(RuntimeWarning):
        assert autotune.AutotuneCache(path).load().get(key) is None


def test_autotune_cache_key_salts_backend_and_version():
    """The committed-cache contract: a key names kernel version AND
    backend, so caches can never leak block choices across either."""
    k_cpu = autotune.cache_key("int8_matmul", 8, 16, 32, backend="cpu")
    k_tpu = autotune.cache_key("int8_matmul", 8, 16, 32, backend="tpu")
    assert k_cpu != k_tpu
    assert k_cpu == "int8_matmul@v2:8x16x32:cpu"
    # dwconv_w4 was re-gridded (H-tiling) — its salt must be bumped so
    # whole-map-era caches orphan instead of mis-steering the new grid
    assert autotune.KERNEL_VERSIONS["dwconv_w4"] >= 2
    assert "@v2" in autotune.cache_key("dwconv_w4", 8, 16, 32)


def test_autotune_cache_drops_foreign_and_legacy_keys(tmp_path):
    """Old-format (unsalted) and foreign entries are dropped through the
    RuntimeWarning salvage path; valid salted entries survive."""
    import json

    path = str(tmp_path / "tune.json")
    good = autotune.cache_key("k", 1, 2, 3)
    with open(path, "w") as f:
        json.dump({good: [8, 16, 32],
                   "k:1x2x3:cpu": [8, 8, 8],         # legacy unsalted
                   "not a key at all": [8, 8, 8],    # foreign junk
                   autotune.cache_key("k", 9, 9, 9): [8, "x", 8]}, f)
    with pytest.warns(RuntimeWarning, match="3 corrupt"):
        cache = autotune.AutotuneCache(path).load()
    assert cache.get(good) == (8, 16, 32)
    assert len(cache) == 1


def test_autotune_never_benches_inside_a_trace(tmp_path):
    """Benching under jit tracing would 'time' tracer construction and
    poison the persistent cache; inside a trace the tuner must return the
    heuristic (or a warm cache hit) without calling bench_fn."""
    path = str(tmp_path / "tune.json")
    calls = []

    def bench(blocks):
        calls.append(blocks)
        return np.zeros(())

    def traced(x):
        blocks = autotune.blocks_for("fake_traced", 64, 64, 64,
                                     interpret=False, bench_fn=bench,
                                     cache_path=path, force_tune=True,
                                     operands=(x,))
        assert blocks == autotune.heuristic_blocks(64, 64, 64)
        return x

    jax.jit(traced)(jnp.zeros((2,)))
    assert calls == []
    assert autotune.AutotuneCache(path).load().get(
        autotune.cache_key("fake_traced", 64, 64, 64)) is None


def test_autotune_all_failures_do_not_poison_cache(tmp_path):
    path = str(tmp_path / "tune.json")

    def bench(blocks):
        raise RuntimeError("kernel launch failed")

    best = autotune.blocks_for("fake_broken", 64, 64, 64, interpret=False,
                               bench_fn=bench, cache_path=path,
                               candidates=[(8, 8, 8)], force_tune=True)
    assert best == autotune.heuristic_blocks(64, 64, 64)
    # the untuned fallback must NOT be persisted under the tuned key
    assert autotune.AutotuneCache(path).load().get(
        autotune.cache_key("fake_broken", 64, 64, 64)) is None


def test_autotune_times_candidates_and_persists(tmp_path):
    path = str(tmp_path / "tune.json")
    import time
    calls = []
    cands = [(8, 8, 8), (16, 16, 16), (32, 32, 32)]
    times = {(8, 8, 8): 3.0, (16, 16, 16): 1.0, (32, 32, 32): 2.0}

    def bench(blocks):
        calls.append(blocks)
        time.sleep(times[blocks] / 1000.0)
        return np.zeros(())

    autotune.reset_probe_count()
    best = autotune.blocks_for("fake_kernel", 64, 64, 64, interpret=False,
                               bench_fn=bench, cache_path=path,
                               candidates=cands, force_tune=True)
    assert best == (16, 16, 16)
    assert set(calls) == set(cands)
    assert autotune.tuning_probe_count() == len(cands)
    # second call (no force): served from the persisted cache — no
    # re-benchmarking, no new probes
    calls.clear()
    again = autotune.blocks_for("fake_kernel", 64, 64, 64, interpret=False,
                                bench_fn=bench, cache_path=path,
                                candidates=cands)
    assert again == (16, 16, 16) and calls == []
    assert autotune.tuning_probe_count() == len(cands)
    # and it survives a fresh cache object reading the JSON file
    fresh = autotune.AutotuneCache(path).load()
    assert fresh.get(autotune.cache_key("fake_kernel", 64, 64, 64)) == \
        (16, 16, 16)
