"""Compile every Pallas kernel of the serving path for a TPU v5e, without one.

Each case lowers a kernel entry point at the shapes EfficientViT-B1 or -B2
R224 (bucket 16) or qwen1.5-0.5b (decode batch 4) really produce, compiles it
against a described ``v5e:2x2`` topology with ``interpret=False``, and asserts
the compiled HLO launches the kernel (``tpu_custom_call``).  This is what the
chip's compiler would refuse: blocks that break the (8, 128) tiling rule,
unsupported casts, VMEM overflow.  Nothing runs, so nothing here says anything
about results or speed.

The topology is described inside a fixture (never at import time), so every
pytest-xdist worker collects the same tests and only the one given this file
loads the TPU compiler.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.launch.hlo_analysis import kernel_counts


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off around these."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


f32, i8, u8, i32 = jnp.float32, jnp.int8, jnp.uint8, jnp.int32

# (id, kernel, entry point, operand shapes) — B1 R224 at bucket 16 and
# qwen1.5-0.5b decode at batch 4 / max_len 128, as the served traces launch
CASES = [
    ("b1-pwconv-stage3", "m2q_matmul",
     lambda x, s, p, a, b, c: ops.m2q_matmul_op(x, s, p, a, b, c,
                                                interpret=False),
     [((3136, 128), f32), ((), f32), ((128, 512), i8), ((512,), f32),
      ((512,), f32), ((512,), f32)]),
    ("b1-head", "m2q_matmul",
     lambda x, s, p, a, b, c: ops.m2q_matmul_op(x, s, p, a, b, c,
                                                interpret=False),
     [((16, 1024), f32), ((), f32), ((1024, 1000), i8), ((1000,), f32),
      ((1000,), f32), ((1000,), f32)]),
    ("qwen-ffn-w8a8", "int8_matmul",
     lambda x, w, s, a, b: ops.int8_matmul_op(x, w, s, a, b,
                                              interpret=False),
     [((4, 1024), f32), ((1024, 2816), i8), ((), f32), ((2816,), f32),
      ((2816,), f32)]),
    ("qwen-lm-head-w4", "int4_matmul",
     lambda x, p, a, b: ops.int4_matmul_op(x, p, a, b, interpret=False),
     [((4, 1024), f32), ((1024, 151936 // 2), u8), ((151936,), f32),
      ((151936,), f32)]),
    ("b1-dwconv-3x3-s2", "dwconv_w4",
     lambda x, p, a, b: ops.dwconv_w4_op(x, p, a, b, kh=3, kw=3, stride=2,
                                         interpret=False),
     [((16, 112, 112, 64), f32), ((9, 32), u8), ((64,), f32),
      ((64,), f32)]),
    ("b1-dwconv-5x5-agg", "dwconv_w4",
     lambda x, p, a, b: ops.dwconv_w4_op(x, p, a, b, kh=5, kw=5, stride=1,
                                         interpret=False),
     [((16, 14, 14, 384), f32), ((25, 192), u8), ((384,), f32),
      ((384,), f32)]),
    ("b1-msa-stage3", "relu_attn",
     lambda q, k, v: ops.relu_attn_op(q, k, v, interpret=False),
     [((16, 196, 8, 16), f32)] * 3),
    ("b1-msa-stage4", "relu_attn",
     lambda q, k, v: ops.relu_attn_op(q, k, v, interpret=False),
     [((16, 49, 16, 16), f32)] * 3),
    ("qwen-decode-int8kv", "decode_attn_int8",
     lambda q, k, v, ks, vs, n: ops.decode_attn_int8_op(
         q, k, v, ks, vs, n, interpret=False),
     [((4, 1, 16, 64), f32), ((4, 128, 16, 64), i8), ((4, 128, 16, 64), i8),
      ((4, 128, 16), f32), ((4, 128, 16), f32), ((4,), i32)]),
]


@pytest.mark.parametrize("kernel,fn,shapes",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_kernel_compiles_for_v5e(kernel, fn, shapes, one_chip,
                                 no_persistent_cache):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert kernel_counts(text) == {kernel: 1}


# EfficientViT-B2 R224's int8 1x1 convs and head at bucket 16, (M, K, N):
# the row slabs of whole K and N that int8_tile_plan gives, bf16 in and out
# — where a VMEM overflow of the plan would show
B2_POINTWISE = [(200704, 24, 96), (200704, 96, 24), (50176, 48, 192),
                (50176, 192, 48), (50176, 48, 96), (12544, 96, 384),
                (12544, 384, 96), (12544, 96, 192), (3136, 192, 768),
                (3136, 192, 576), (3136, 768, 192), (3136, 384, 192),
                (784, 384, 1536), (784, 384, 1152), (784, 1536, 384),
                (784, 768, 384), (16, 1536, 1000)]


@pytest.mark.parametrize("M,K,N", B2_POINTWISE,
                         ids=[f"{m}x{k}x{n}" for m, k, n in B2_POINTWISE])
def test_b2_int8_slab_plan_compiles_for_v5e(M, K, N, one_chip,
                                            no_persistent_cache):
    bm, bn, bk = ops.int8_tile_plan(M, K, N, 2)
    assert (bn, bk) == (N, K) and M % bm == 0
    bf16 = jnp.bfloat16
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in [((M, K), bf16), ((K, N), i8), ((), f32),
                          ((N,), f32), ((N,), f32)]]
    out = jax.eval_shape(lambda *a: ops.int8_matmul_op(*a, interpret=False),
                         *args)
    assert out.dtype == bf16 and out.shape == (M, N)
    text = jax.jit(lambda *a: ops.int8_matmul_op(*a, interpret=False)).lower(
        *args).compile().as_text()
    assert "tpu_custom_call" in text
    assert kernel_counts(text) == {"int8_matmul": 1}
    # the rows divide into whole slabs and K, N are whole: nothing padded
    assert " pad(" not in text


def test_b1_served_forward_compiles_with_every_kernel(one_chip,
                                                      no_persistent_cache,
                                                      monkeypatch):
    """The whole quantized B1 R224 forward at bucket 16, as VisionEngine
    traces it on a chip: every depthwise conv, PWConv/matmul and MSA token
    mixer is a kernel launch in the compiled program."""
    from repro.configs.registry import ARCHS
    from repro.kernels import autotune
    from repro.models import get_model
    from repro.recipe import abstract_quantize

    # steer the backend-derived default in the test: the described chip is
    # not this process's backend, so kernels would otherwise interpret
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    cfg = ARCHS["efficientvit-b1-r224"]
    model = get_model(cfg)
    qp = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        abstract_quantize(cfg, recipe="m2q-w8a8", tokens_per_step=16))
    x = jax.ShapeDtypeStruct((16, 224, 224, 3), f32, sharding=one_chip)
    reqs = []
    with autotune.record_requests(reqs), \
            ops.dispatch(dense=True, conv=True, attn=True):
        text = jax.jit(lambda p, im: model.forward(cfg, p, im)).lower(
            qp, x).compile().as_text()
    counts = kernel_counts(text)
    dispatched = {r.kernel for r in reqs}
    assert dispatched == {"m2q_matmul", "dwconv_w4", "relu_attn"}
    assert set(counts) == dispatched
    # one launch per traced call site (13 blocks: 42 matmuls, 20 depthwise
    # convs, 2 token scales x 7 MSA blocks)
    assert counts == {k: int(np.sum([r.kernel == k for r in reqs]))
                      for k in dispatched}


def test_b2_served_forward_compiles_and_block_scopes_change_nothing(
        one_chip, no_persistent_cache, monkeypatch):
    """The quantized B2 R224 forward at bucket 16, as the b2-int8-offline
    cell serves it (uniform int8 compute layers): 32-wide heads and
    1,536-channel expansions compile with every kernel, and the forward's
    ``jax.named_scope``s (stage, then ``mbconv`` / ``msa``) change no
    instruction and no kernel launch, only the metadata."""
    import contextlib
    import dataclasses
    import re

    from repro.configs.registry import ARCHS
    from repro.models import get_model
    from repro.recipe import PRESETS, abstract_quantize

    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    cfg = ARCHS["efficientvit-b2-r224"]
    model = get_model(cfg)
    base = PRESETS["m2q-w8a8"]
    recipe = base.replace(policy=dataclasses.replace(
        base.policy, compute_scheme="uniform8"))
    qp = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        abstract_quantize(cfg, recipe=recipe, tokens_per_step=16))
    x = jax.ShapeDtypeStruct((16, 224, 224, 3), f32, sharding=one_chip)

    def compiled():
        with ops.dispatch(dense=True, conv=True, attn=True):
            return jax.jit(lambda p, im: model.forward(cfg, p, im)).lower(
                qp, x).compile().as_text()

    def instructions(text):
        return re.findall(r"^\s*(?:ROOT\s+)?%(\S+)\s*=\s*(\S+ \S+?)\(",
                          text, re.M)

    scoped = compiled()
    # 18 MBConvs (two matmuls, one depthwise conv), 10 MSA blocks (qkv and
    # proj matmuls, the 5x5 depthwise conv, two token scales), two in the
    # head
    assert kernel_counts(scoped) == {"int8_matmul": 58, "dwconv_w4": 28,
                                     "relu_attn": 20}
    assert "stage3/msa/" in scoped and "stage4/mbconv/" in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = compiled()
    assert "stage3/" not in bare
    assert instructions(scoped) == instructions(bare)
    assert kernel_counts(bare) == kernel_counts(scoped)
