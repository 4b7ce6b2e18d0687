"""Offline autotune sweep: shape discovery, offline-vs-lazy equivalence,
zero-probe warmed traces, backend/version cache salting, and the CI smoke
gate's missing-shape failure mode (ISSUE 9)."""
import json

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import autotune
from repro.launch import autotune_sweep as sw


def _req(kernel, m, n, k, tunable=True, **meta):
    return autotune.ShapeRequest(
        kernel, m, n, k, tunable,
        tuple(sorted((key, int(v)) for key, v in meta.items())))


REQS = [
    _req("m2q_matmul", 130, 258, 514),
    _req("int8_matmul", 8, 16, 32),
    _req("int4_matmul", 64, 64, 64),
    _req("apot_matmul", 16, 8, 8),
    _req("dwconv_w4", 64, 4, 9, B=1, H=8, W=8, C=4, kh=3, kw=3, stride=1),
    _req("relu_attn", 8, 8, 2, B=1, N=8, H=2, D=8),
    _req("decode_attn_int8", 1, 2, 8, tunable=False, Hkv=2, T=4, window=0),
]


# ---------------------------------------------------------------------------
# offline warm == lazy choices, byte-identical through the JSON round trip
# ---------------------------------------------------------------------------


def test_offline_warm_matches_lazy_choices(tmp_path):
    """Satellite: a warmed cache holds exactly the block triples lazy
    tuning would have chosen for the same shapes on this backend — so
    committing the offline sweep's output changes WHEN tuning happens,
    never WHAT executes."""
    offline = str(tmp_path / "offline.json")
    lazy_path = str(tmp_path / "lazy.json")
    wrote, skipped = sw.warm(REQS, offline, progress=lambda *a: None)
    assert wrote == sum(r.tunable for r in REQS) and skipped == 0
    cache = autotune.AutotuneCache(offline).load()
    for r in REQS:
        if not r.tunable:
            assert cache.get(r.key()) is None
            continue
        lazy = autotune.blocks_for(r.kernel, r.M, r.N, r.K,
                                   interpret=True, cache_path=lazy_path)
        assert cache.get(r.key()) == lazy, r
    # idempotent: a re-run skips every already-cached shape
    wrote2, skipped2 = sw.warm(REQS, offline, progress=lambda *a: None)
    assert wrote2 == 0 and skipped2 == sum(r.tunable for r in REQS)


def test_committed_tuned_cache_overrides_heuristic(tmp_path):
    """Cache-FIRST lookup: a committed entry (e.g. tuned on a real
    accelerator of this backend name) serves its block choice verbatim
    even where live tuning is disabled."""
    path = str(tmp_path / "c.json")
    key = autotune.cache_key("m2q_matmul", 128, 128, 128)
    autotune.AutotuneCache(path).put(key, (8, 8, 8))
    got = autotune.blocks_for("m2q_matmul", 128, 128, 128,
                              interpret=True, cache_path=path)
    assert got == (8, 8, 8)
    assert got != autotune.heuristic_blocks(128, 128, 128)


def test_foreign_backend_entries_never_serve(tmp_path):
    """Backend salt: a cache committed for another backend misses here
    (its entries are valid-format, so they survive load — they just can
    never be looked up under this backend's keys)."""
    path = str(tmp_path / "tpu.json")
    foreign = autotune.cache_key("m2q_matmul", 128, 128, 128, backend="tpu")
    autotune.AutotuneCache(path).put(foreign, (8, 8, 8))
    assert jax.default_backend() != "tpu"
    got = autotune.blocks_for("m2q_matmul", 128, 128, 128,
                              interpret=True, cache_path=path)
    assert got == autotune.heuristic_blocks(128, 128, 128)
    assert autotune.AutotuneCache(path).load().get(foreign) == (8, 8, 8)


def test_default_cache_path_is_the_committed_file_in_the_checkout(
        monkeypatch):
    """With no REPRO_AUTOTUNE_CACHE the lookup reads the per-backend file
    the sweep writes and the repo commits — inside the checkout, never
    under $HOME — so a compiled program depends only on committed files."""
    from repro.checkout import ROOT
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE", raising=False)
    path = autotune.default_cache_path()
    assert path == str(ROOT / "results" / "autotune"
                       / f"{jax.default_backend()}.json")
    assert path == autotune.committed_cache_path()
    assert autotune.default_cache_path("tpu").endswith(
        "results/autotune/tpu.json")
    assert (ROOT / "src" / "repro" / "checkout.py").is_file()
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", "/elsewhere/cache.json")
    assert autotune.default_cache_path() == "/elsewhere/cache.json"


def test_compile_cache_defaults_into_the_checkout(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` set: nothing is set in code.  Unset:
    the persistent compile cache is ``<checkout>/.jax_cache``."""
    from repro.checkout import ROOT, enable_compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jax")
        assert enable_compile_cache() == "/elsewhere/jax"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT /
                                                           ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


# ---------------------------------------------------------------------------
# zero tuning probes at trace time against a warmed cache
# ---------------------------------------------------------------------------


def test_trace_against_warmed_cache_zero_probes(tmp_path, monkeypatch):
    """Satellite: with the default cache pointed at a warmed file, an
    in-trace block request is a pure cache hit — the cached triple is
    served (not the heuristic) and the probe counter stays at zero."""
    path = str(tmp_path / "warm.json")
    key = autotune.cache_key("int8_matmul", 64, 32, 16)
    autotune.AutotuneCache(path).put(key, (32, 16, 8))
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
    autotune.reset_probe_count()
    served = []

    def traced(x):
        served.append(autotune.blocks_for("int8_matmul", 64, 32, 16,
                                          interpret=True))
        return x

    jax.jit(traced).lower(jax.ShapeDtypeStruct((2,), jnp.float32))
    assert served == [(32, 16, 8)]
    assert autotune.tuning_probe_count() == 0


def test_probe_counter_counts_live_tuning(tmp_path):
    """The counter the zero-probe assertions rely on actually counts:
    cold-cache force-tuning probes once per candidate; the warmed second
    call probes zero more times and returns the identical choice."""
    path = str(tmp_path / "t.json")
    cands = [(8, 8, 8), (16, 16, 16)]
    autotune.reset_probe_count()
    first = autotune.blocks_for("fake_probe", 32, 32, 32, interpret=False,
                                bench_fn=lambda b: jnp.zeros(()),
                                cache_path=path, candidates=cands,
                                force_tune=True)
    assert autotune.tuning_probe_count() == len(cands)
    second = autotune.blocks_for("fake_probe", 32, 32, 32, interpret=False,
                                 bench_fn=lambda b: jnp.zeros(()),
                                 cache_path=path, candidates=cands)
    assert second == first
    assert autotune.tuning_probe_count() == len(cands)


# ---------------------------------------------------------------------------
# synthetic launch reconstruction (the accelerator tuning path)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("req", [r for r in REQS if r.tunable],
                         ids=lambda r: r.kernel)
def test_bench_fn_reconstructs_real_launches(req):
    """Every tunable kernel's recorded request rebuilds an executable
    launch from synthetic operands (what offline tuning times on a real
    backend) — here executed once in interpret mode for correctness."""
    fn = sw._bench_fn(req, interpret=True)
    assert fn is not None, req
    out = fn(autotune.heuristic_blocks(req.M, req.N, req.K))
    assert jax.block_until_ready(out) is not None


def test_bench_fn_skips_note_only_requests():
    assert sw._bench_fn(next(r for r in REQS if not r.tunable),
                        interpret=True) is None


# ---------------------------------------------------------------------------
# end-to-end: discover -> warm -> smoke (real model, reduced shapes)
# ---------------------------------------------------------------------------


def test_sweep_discovers_warms_and_smokes(tmp_path, monkeypatch):
    """The CI gate end to end on one reduced vision config: discovery
    finds dwconv/matmul/attention shapes, warming covers them all, the
    smoke passes — and deleting one committed entry makes it FAIL (a
    missing shape must never silently re-tune at serving time)."""
    from repro.analysis.traces import shape_requests

    path = str(tmp_path / "cpu.json")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
    cfg, rec = ["efficientvit-b1-r224"], ("m2q-w8a8",)
    reqs, per_trace = shape_requests(cfg, recipes=rec, hires=())
    assert per_trace and all(n > 0 for n in per_trace.values())
    kinds = {r.kernel for r in reqs}
    assert {"dwconv_w4", "m2q_matmul", "relu_attn"} <= kinds
    sw.warm(reqs, path, progress=lambda *a: None)
    assert sw.smoke(cfg, rec, path, hires=(),
                    progress=lambda *a: None) == 0
    # drop one tunable entry -> the gate must fail loudly
    data = json.loads(open(path).read())
    victim = next(r.key() for r in reqs if r.tunable)
    del data[victim]
    with open(path, "w") as f:
        json.dump(data, f)
    autotune._CACHES.pop(path, None)  # drop the warmed in-process view
    assert sw.smoke(cfg, rec, path, hires=(),
                    progress=lambda *a: None) == 1


def test_int8_launches_against_a_warmed_v2_cache_run_zero_probes(
        tmp_path, monkeypatch):
    """After the int8_matmul version bump a cache warmed from the served
    requests covers them under v2 keys: the traced launch serves the slab
    plan from it and runs zero probes; a v1 entry for the shape is never
    looked up."""
    from repro.kernels import ops

    path = str(tmp_path / "cpu.json")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
    M, K, N = 64, 96, 24
    x = jax.ShapeDtypeStruct((M, K), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((K, N), jnp.int8)
    v = jax.ShapeDtypeStruct((N,), jnp.float32)

    def launch(x, w, v):
        return ops.int8_matmul_op(x, w, jnp.float32(1.0), v, v)

    reqs = []
    with autotune.record_requests(reqs):
        jax.eval_shape(launch, x, w, v)
    assert [(r.kernel, r.M, r.N, r.K) for r in reqs] == \
        [("int8_matmul", M, N, K)]
    assert dict(reqs[0].meta)["x_bytes"] == 2
    autotune.AutotuneCache(path).put(
        f"int8_matmul@v1:{M}x{N}x{K}:{jax.default_backend()}", (8, 8, 8))
    sw.warm(reqs, path, progress=lambda *a: None)
    cache = autotune.AutotuneCache(path).load()
    assert cache.get(reqs[0].key()) == ops.int8_tile_plan(M, K, N, 2)
    assert "@v2:" in reqs[0].key()
    autotune._CACHES.pop(path, None)
    autotune.reset_probe_count()
    served = []
    with autotune.record_requests(served):  # a fresh trace, not eval_shape's
        jax.jit(lambda *a: launch(*a)).lower(x, w, v)
    assert autotune.tuning_probe_count() == 0
    assert [r.key() for r in served] == [reqs[0].key()]
