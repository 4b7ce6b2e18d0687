"""Sharding rules + small-mesh end-to-end dry-runs (subprocess: the device
count must be fixed before jax initializes), including sharded serving of a
QuantizedModel through both engines."""
import json
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P


class FakeMesh:
    """Shape-only mesh stand-in for spec-level tests (no devices needed)."""
    axis_names = ("data", "model")
    shape = {"data": 4, "model": 4}


def test_spec_rules_divisibility_and_paths():
    import jax
    from repro.dist.sharding import spec_for_param

    m = FakeMesh()
    # column-parallel qkv
    assert spec_for_param("layers/attn/wq", (24, 64, 128), np.dtype("float32"),
                          m) == P(None, None, "model")
    # QTensor child path suffixes are stripped before rule matching
    assert spec_for_param("layers/attn/wq/0/0", (24, 64, 128),
                          np.dtype("int8"), m) == P(None, None, "model")
    # indivisible dim falls back to replication, not an error
    assert spec_for_param("layers/attn/wq", (24, 64, 126),
                          np.dtype("float32"), m) == P(None, None, None)
    # permutation indices always replicate
    assert spec_for_param("layers/attn/wq/2", (24, 128), np.dtype("int32"),
                          m) == P()
    # expert weights: EP on the (stacked) expert axis 1 of (L, E, D, F)
    assert spec_for_param("layers/moe/experts/w1", (8, 16, 64, 128),
                          np.dtype("float32"), m) == P(None, "model", None,
                                                       None)
    # fsdp adds a data axis on the first free divisible dim of big tensors
    s = spec_for_param("layers/mlp/w1", (24, 512, 256), np.dtype("float32"),
                       m, fsdp=True)
    assert s == P("data", None, "model") or s == P(("data",), None, "model")


def _leaves_with_specs(tree, specs):
    """[(path_str, leaf, spec)] — QTensor leaves flatten through."""
    import jax
    from repro.core.calibrate import path_str
    lp, treedef = jax.tree_util.tree_flatten_with_path(tree)
    sp = jax.tree_util.tree_leaves(specs,
                                   is_leaf=lambda x: isinstance(x, P))
    assert len(lp) == len(sp)
    return [(path_str(p), leaf, spec) for (p, leaf), spec in zip(lp, sp)]


def test_param_specs_over_quantized_model_artifact():
    """ISSUE 4 satellite: QTensor children of a QuantizedModel co-shard —
    the merged-byte QM2Q payload and its per-column scales all split on the
    filter (last) axis, act scales and any integer index leaves replicate."""
    import jax
    from repro.configs.registry import REDUCED
    from repro.dist.sharding import param_specs, spec_for_param
    from repro.models import get_model
    from repro.recipe import quantize

    cfg = REDUCED["qwen1.5-0.5b"]
    model = get_model(cfg)
    params = model.init(cfg, jax.random.PRNGKey(0))
    qm = quantize(cfg, params, "m2q-w8a8")  # synthesized calibration
    specs = param_specs(qm.params, FakeMesh())

    rows = _leaves_with_specs(qm.params, specs)
    # mixed-decision attn projection: QM2Q children 0..3 (payload, u_scale,
    # u_zp, a_scale) must CO-shard on the filter axis
    wq = {path: (leaf, spec) for path, leaf, spec in rows
          if "attn/wq" in path}
    assert wq, "expected QM2Q children under layers/attn/wq"
    payload = [v for p, v in wq.items() if p.endswith("/0")]
    assert payload and payload[0][0].dtype == np.int8  # merged byte array
    co = {p: v for p, v in wq.items()
          if p.split("/")[-1] in ("0", "1", "2", "3")}
    assert len(co) == 4
    for path, (leaf, spec) in co.items():
        assert spec[-1] == "model", (path, spec)     # filter-axis co-shard
    # column-parallel consumer pairs with row-parallel wo (Megatron sandwich)
    wo = [(leaf, spec) for path, leaf, spec in rows
          if "attn/wo" in path and path.endswith("/0")]
    assert wo and wo[0][1][-2] == "model"
    # int32 index leaves would replicate (the merged layout has none left —
    # assert the rule directly, and that no index leaf survived)
    assert spec_for_param("layers/attn/wq/5", (2, 64), np.dtype("int32"),
                          FakeMesh()) == P()
    for path, leaf, spec in rows:
        if np.dtype(leaf.dtype).kind in "iu" and leaf.dtype.itemsize >= 4:
            assert spec == P(), (path, spec)


def test_cache_specs_cover_every_cache_family():
    """cache_specs on each family's init_cache: batch rows over 'data'
    wherever divisible (axis 0 for per-slot vectors, axis 1 under the
    stacked layer dim), attention heads over 'model' when asked."""
    import jax
    from repro.configs.registry import REDUCED
    from repro.dist.sharding import cache_specs
    from repro.models import get_model

    m = FakeMesh()
    for name in ("qwen1.5-0.5b", "rwkv6-3b", "recurrentgemma-9b"):
        cfg = REDUCED[name]
        model = get_model(cfg)
        cache = model.init_cache(cfg, 8, 16)
        specs = cache_specs(cache, m, shard_model=True)
        checked = 0
        for path, leaf, spec in _leaves_with_specs(cache, specs):
            nd = len(leaf.shape)
            if nd == 0:
                continue
            bdim = 0 if nd == 1 else 1
            want = "data" if leaf.shape[bdim] % 4 == 0 else None
            assert spec[bdim] == want, (name, path, leaf.shape, spec)
            if nd >= 5:  # (L, B, T, H, Dh) attention cache: heads axis
                want_h = "model" if leaf.shape[3] % 4 == 0 else None
                assert spec[3] == want_h, (name, path, leaf.shape, spec)
            checked += 1
        assert checked >= 2, name  # every family exposes >= 2 state leaves


_SMALL_DRYRUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import json
    import jax, jax.numpy as jnp
    from repro.configs.registry import REDUCED
    from repro.dist import sharding as shd
    from repro.models import get_model
    from repro.optim.adamw import AdamW
    from repro.train.step import make_train_step, make_serve_step

    mesh = jax.make_mesh((4, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = REDUCED["qwen3-14b"].replace(dtype="bfloat16", act_sharding="data",
                                       attn_bf16_mm=True, causal_skip=True)
    model = get_model(cfg)
    with mesh:
        # train step compiles AND runs on 16 virtual devices
        params = model.init(cfg, jax.random.PRNGKey(0))
        opt = AdamW(lr=1e-3)
        opt_state = opt.init(params)
        step = make_train_step(cfg, model, opt)
        batch = {"tokens": jnp.zeros((8, 32), jnp.int32),
                 "labels": jnp.zeros((8, 32), jnp.int32)}
        pspec = shd.param_specs(params, mesh, fsdp=True)
        in_specs = (pspec, type(opt_state)(count=jax.sharding.PartitionSpec(),
                                           m=pspec, v=pspec),
                    shd.batch_specs(batch, mesh))
        fn = jax.jit(step, in_shardings=shd.shardings_from_specs(in_specs, mesh),
                     donate_argnums=(0, 1))
        params2, opt2, metrics = fn(params, opt_state, batch)
        loss = float(metrics["loss"])
        # quantized decode also compiles + runs sharded
        from repro.core import M2QPolicy, ShapeCtx, quantize_model
        qp, _ = quantize_model(model.init(cfg, jax.random.PRNGKey(0)),
                               model.QUANT_RULES, ShapeCtx(tokens_per_step=8),
                               M2QPolicy(intensity_threshold=0.5))
        cache = model.init_cache(cfg, 8, 16)
        serve = make_serve_step(cfg, model)
        qspec = shd.param_specs(qp, mesh)
        sfn = jax.jit(serve, in_shardings=shd.shardings_from_specs(
            (qspec, shd.cache_specs(cache, mesh, shard_model=True),
             shd.batch_specs(jnp.zeros((8, 1), jnp.int32), mesh)), mesh),
            donate_argnums=(1,))
        logits, cache = sfn(qp, cache, jnp.zeros((8, 1), jnp.int32))
        print(json.dumps({"loss": loss,
                          "finite": bool(jnp.isfinite(loss)),
                          "logits_finite": bool(jnp.all(jnp.isfinite(
                              logits.astype(jnp.float32))))}))
""")


@pytest.mark.slow
def test_small_mesh_end_to_end():
    out = subprocess.run([sys.executable, "-c", _SMALL_DRYRUN],
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["finite"] and rec["logits_finite"]


_SERVE_SHARDED_DRYRUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import json
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.registry import REDUCED
    from repro.dist import sharding as shd
    from repro.models import get_model
    from repro.recipe import quantize

    mesh = jax.make_mesh((4, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)

    def assert_on_spec(tree, specs, what):
        leaves = jax.tree_util.tree_leaves(tree)
        specl = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        assert len(leaves) == len(specl), what
        for leaf, spec in zip(leaves, specl):
            want = NamedSharding(mesh, spec)
            # is_equivalent_to: spec-level equality modulo trailing-None
            # normalization (a decode-step sharding constraint round-trip
            # drops trailing Nones from the spec)
            assert leaf.sharding.is_equivalent_to(want, leaf.ndim), (
                what, leaf.shape, leaf.sharding, want)

    # ---- token engine: sharded decode over a QuantizedModel -------------
    cfg = REDUCED["qwen1.5-0.5b"]
    model = get_model(cfg)
    params = model.init(cfg, jax.random.PRNGKey(0))
    qm = quantize(cfg, params, "m2q-w8a8")
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, int(n), dtype=np.int32)
               for n in rng.integers(3, 9, 5)]

    eng = qm.serve(max_batch=8, max_len=32, mesh=mesh)
    # placements match dist.sharding specs EXACTLY (params + decode cache)
    assert_on_spec(eng.params, shd.param_specs(qm.params, mesh), "qparams")
    cspecs = shd.cache_specs(eng.cache, mesh, shard_model=True)
    assert_on_spec(eng.cache, cspecs, "cache@init")
    sharded = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.run()
    assert all(r.done for r in sharded)
    # the decode loop kept the cache pinned to spec through every step
    assert_on_spec(eng.cache, cspecs, "cache@end")

    ref_eng = qm.serve(max_batch=8, max_len=32)  # single-placement ref
    ref = [ref_eng.submit(p, max_new_tokens=4) for p in prompts]
    ref_eng.run()
    token_match = all(a.out_tokens == b.out_tokens
                      for a, b in zip(sharded, ref))

    # ---- vision engine: data-parallel sharded batches -------------------
    vcfg = REDUCED["efficientvit-b1-r224"]
    vmodel = get_model(vcfg)
    vparams = vmodel.init(vcfg, jax.random.PRNGKey(1))
    imgs = rng.normal(0, 1, (5, vcfg.img_res, vcfg.img_res, 3)).astype(
        np.float32)
    vqm = quantize(vcfg, vparams, "m2q-w8a8", calib_batches=[imgs[:2]])
    veng = vqm.serve(max_batch=8, mesh=mesh)
    assert veng.min_bucket == 4  # bucket floor = data axis: even shards
    assert_on_spec(veng.params, shd.param_specs(vqm.params, mesh),
                   "vision qparams")
    handles = [veng.submit(im) for im in imgs]
    out = veng.flush()
    assert veng.stats.buckets_used == {8}  # 5 -> pow2 8, 2 rows/device
    ref_logits = np.asarray(vqm.forward(jnp.asarray(imgs)))
    vision_close = bool(np.allclose(out, ref_logits, rtol=1e-3, atol=1e-3))
    handle_rows = bool(np.allclose(
        np.stack([h.result() for h in handles]), out))

    print(json.dumps({"token_match": token_match,
                      "vision_close": vision_close,
                      "handle_rows": handle_rows,
                      "devices": len(jax.devices())}))
""")


@pytest.mark.slow
def test_sharded_serving_quantized_model_both_engines():
    """ISSUE 4 acceptance: a 16-virtual-device dry-run serves a
    QuantizedModel through BOTH engines with ``mesh=`` — param and cache
    placements equal the dist.sharding specs (asserted in-subprocess), the
    sharded token decode reproduces the unsharded greedy tokens, and the
    sharded vision logits match the direct quantized forward."""
    out = subprocess.run([sys.executable, "-c", _SERVE_SHARDED_DRYRUN],
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["devices"] == 16
    assert rec["token_match"] and rec["vision_close"] and rec["handle_rows"]


_KERNEL_MESH_DRYRUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core.packing import pack_int4
    from repro.kernels import ops
    from repro.launch.serve import parse_mesh

    rng = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    i8 = lambda *s: jnp.asarray(rng.integers(-127, 128, s), jnp.int8)
    u4 = lambda *s: pack_int4(jnp.asarray(rng.integers(0, 16, s), jnp.uint8))
    s, zp = jnp.asarray(rng.uniform(.01, .02, 128), jnp.float32), f(128)
    # M = 196 rows: padded to one 128-row block per device, not 2 blocks
    # split 4 ways (which would leave each device a partial block)
    cases = {
        "int8": (lambda x, w: ops.int8_matmul_op(
            x, w, jnp.float32(.05), s, zp, interpret=True),
            (f(196, 256), i8(256, 128))),
        "int4": (lambda x, w: ops.int4_matmul_op(x, w, s, zp,
                                                 interpret=True),
                 (f(196, 256), u4(256, 128))),
        "dwconv": (lambda x, w: ops.dwconv_w4_op(x, w, s[:8], zp[:8],
                                                 stride=2, interpret=True),
                   (f(4, 10, 10, 8), u4(9, 8))),
        "relu_attn": (lambda q, k, v: ops.relu_attn_op(q, k, v,
                                                       interpret=True),
                      (f(4, 20, 4, 8), f(4, 20, 4, 8), f(4, 20, 4, 8))),
        "decode": (lambda q, k, v, ks, vs: ops.decode_attn_int8_op(
            q, k, v, ks, vs, jnp.asarray([3, 16, 1, 9]), interpret=True),
            (f(4, 1, 4, 8), i8(4, 16, 4, 8), i8(4, 16, 4, 8),
             jnp.abs(f(4, 16, 4)), jnp.abs(f(4, 16, 4)))),
    }
    err = {}
    for name, (fn, args) in cases.items():
        ref = fn(*args)
        for spec in ("4x1", "2x2", "1x4"):
            with ops.kernel_mesh(parse_mesh(spec)):
                # a fresh function per mesh: jit's trace cache does not
                # see the kernel_mesh scope
                got = jax.jit(lambda *a: fn(*a))(*args)
            err[f"{name}@{spec}"] = float(jnp.max(jnp.abs(got - ref)))
    print(json.dumps(err))
""")


def test_kernel_launches_under_a_mesh_match_one_device():
    """Under ``ops.kernel_mesh`` every kernel launch runs per device in a
    ``shard_map`` (Mosaic kernels cannot be SPMD-partitioned): batch rows
    split over ``data``, heads over ``model``.  On 4 virtual devices each
    kernel reproduces its unsharded result exactly, for data-, mixed- and
    model-parallel meshes."""
    out = subprocess.run([sys.executable, "-c", _KERNEL_MESH_DRYRUN],
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    err = json.loads(out.stdout.strip().splitlines()[-1])
    assert len(err) == 15
    assert all(e == 0.0 for e in err.values()), err
