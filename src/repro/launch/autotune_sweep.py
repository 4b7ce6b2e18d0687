"""autotune_sweep: warm the kernel autotune cache OFFLINE for a
deployment's shape set, so serving-time traces are pure cache hits.

Lazy-at-trace tuning (the PR 1 posture) re-pays candidate timing on the
first request per shape — at serving scale that is a real p99 tail.  This
CLI enumerates every kernel-launch shape a deployment's hot paths request
(registry configs x recipes x resolutions, via
``analysis.traces.shape_requests`` — block choices resolve at Python trace
time, so LOWERING alone walks every ``blocks_for``/``note_shape`` call
site), tunes each shape for the current backend, and writes the per-backend
cache file that ``kernels.autotune`` consults FIRST on every launch.

On an accelerator each shape is tuned against synthetic operands (the
request's recorded geometry rebuilds a real launch); on CPU/interpret —
where timing the Python interpreter is meaningless — the fallback triple
is committed instead, which is byte-identical to what lazy tuning would
have chosen there (the offline-vs-lazy equivalence tests pin this).

``--smoke`` is the CI gate: re-enumerate the pinned CI shape set against
the COMMITTED cache and FAIL on any missing key (a missing shape must fail
loudly, never silently re-tune at serving time), asserting zero tuning
probes ran during the trace walk.  ``--bench`` appends per-shape wall-clock
rows to the kernel bench report, making the sweep double as the
kernel-regression harness.

Usage:
  PYTHONPATH=src python -m repro.launch.autotune_sweep \
      --cache results/autotune/cpu.json          # warm the committed cache
  PYTHONPATH=src python -m repro.launch.autotune_sweep --smoke
  PYTHONPATH=src python -m repro.launch.autotune_sweep \
      --configs efficientvit-b1-r224 --bench benchmarks/BENCH_kernels.json

Exit codes: 0 ok; 1 smoke found missing shapes / tuning probes; 2 usage.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path
from typing import Callable, List, Optional, Sequence, Tuple

# the CI shape set: pinned small configs whose committed-cache completeness
# the --smoke stage asserts (one vision config exercising the H-tiled
# dwconv shapes incl. the R384/R512 hi-res traces, one token config
# exercising prefill/decode matmul + attention shapes)
CI_CONFIGS: Tuple[str, ...] = ("efficientvit-b1-r224", "qwen1.5-0.5b")
CI_RECIPES: Tuple[str, ...] = ("m2q-w8a8", "uniform8")


def _bench_fn(req, interpret: bool) -> Optional[Callable]:
    """Rebuild a real launch of the request's shape from synthetic operands
    (values are irrelevant to timing; dtypes/shapes are not).  Returns a
    ``blocks -> result`` closure for the tuner, or None when the request
    cannot be reconstructed (missing geometry, non-tunable kernel)."""
    import jax.numpy as jnp

    from ..kernels import ops

    M, N, K = req.M, req.N, req.K
    meta = dict(req.meta)
    if req.kernel == "m2q_matmul":
        x = jnp.ones((M, K), jnp.float32)
        payload = jnp.zeros((K, N), jnp.int8)
        v1 = jnp.ones((N,), jnp.float32)
        v0 = jnp.zeros((N,), jnp.float32)
        return lambda b: ops.m2q_matmul_op(
            x, jnp.float32(1.0), payload, v1, v0, v1,
            interpret=interpret, blocks=b)
    if req.kernel == "int8_matmul":
        x = jnp.ones((M, K), jnp.bfloat16 if meta.get("x_bytes") == 2
                     else jnp.float32)
        wq = jnp.zeros((K, N), jnp.int8)
        v1 = jnp.ones((N,), jnp.float32)
        v0 = jnp.zeros((N,), jnp.float32)
        return lambda b: ops.int8_matmul_op(
            x, wq, jnp.float32(1.0), v1, v0, interpret=interpret, blocks=b)
    if req.kernel == "int4_matmul" and N % 2 == 0:
        x = jnp.ones((M, K), jnp.float32)
        packed = jnp.zeros((K, N // 2), jnp.uint8)
        v1 = jnp.ones((N,), jnp.float32)
        v0 = jnp.zeros((N,), jnp.float32)
        return lambda b: ops.int4_matmul_op(
            x, packed, v1, v0, interpret=interpret, blocks=b)
    if req.kernel == "apot_matmul":
        x = jnp.ones((M, K), jnp.float32)
        codes = jnp.full((K, N), 0x80, jnp.uint8)  # zero-flag byte
        return lambda b: ops.apot_matmul_op(
            x, codes, jnp.ones((N,), jnp.float32),
            interpret=interpret, blocks=b)
    if req.kernel == "dwconv_w4" and {"B", "H", "W", "C", "kh", "kw",
                                      "stride"} <= meta.keys():
        B, H, W, C = meta["B"], meta["H"], meta["W"], meta["C"]
        kh, kw, stride = meta["kh"], meta["kw"], meta["stride"]
        if C % 2:
            return None
        x = jnp.ones((B, H, W, C), jnp.float32)
        packed = jnp.zeros((kh * kw, C // 2), jnp.uint8)
        scale = jnp.ones((C,), jnp.float32)
        zp = jnp.zeros((C,), jnp.float32)
        return lambda b: ops.dwconv_w4_op(
            x, packed, scale, zp, kh=kh, kw=kw, stride=stride,
            interpret=interpret, blocks=b)
    if req.kernel == "relu_attn" and {"B", "N", "H", "D"} <= meta.keys():
        q = jnp.ones((meta["B"], meta["N"], meta["H"], meta["D"]),
                     jnp.float32)
        return lambda b: ops.relu_attn_op(q, q, q, interpret=interpret,
                                          blocks=b)
    return None


def discover(configs: Sequence[str], recipes: Sequence[str],
             hires: Optional[Sequence[int]] = None, progress=print):
    """Enumerate the deployment's shape set (lower-only trace walk).
    ``hires`` overrides the default high-resolution vision trace set
    (tests pass ``()`` to skip the slow R384/R512 lowerings)."""
    from ..analysis.traces import VISION_HIRES, shape_requests
    t0 = time.time()
    reqs, per_trace = shape_requests(
        configs, recipes=recipes,
        hires=VISION_HIRES if hires is None else hires)
    for name, n in per_trace.items():
        progress(f"  {name:<44} {n} request(s)")
    progress(f"  {len(reqs)} unique shape(s) across {len(per_trace)} "
             f"trace(s) ({time.time() - t0:.1f}s)")
    return reqs


def warm(requests, cache_path: str, *, force_tune: bool = False,
         progress=print) -> Tuple[int, int]:
    """Tune (accelerator) or seed with the fallback (CPU) every tunable
    request into ``cache_path``.  Returns (written, skipped-as-cached)."""
    import jax

    from ..kernels import autotune

    cache = autotune.AutotuneCache(cache_path).load()
    interpret = jax.default_backend() != "tpu"
    live = force_tune or jax.default_backend() != "cpu"
    wrote = skipped = 0
    for req in requests:
        if not req.tunable:
            continue
        key = req.key()
        if not force_tune and cache.get(key) is not None:
            skipped += 1
            continue
        if live:
            blocks = autotune.blocks_for(
                req.kernel, req.M, req.N, req.K, interpret=interpret,
                bench_fn=_bench_fn(req, interpret), cache_path=cache_path,
                force_tune=force_tune)
        else:
            # CPU: candidate timing measures the Python interpreter, so
            # commit what lazy tuning would have chosen here — the
            # fallback (byte-identical by the equivalence tests)
            blocks = autotune.fallback_blocks(req.kernel, req.M, req.N,
                                              req.K, dict(req.meta))
        cache.put(key, blocks, save=False)
        wrote += 1
        progress(f"  {key:<52} -> {tuple(blocks)}")
    cache.save()
    return wrote, skipped


def smoke(configs: Sequence[str], recipes: Sequence[str],
          cache_path: str, hires: Optional[Sequence[int]] = None,
          progress=print) -> int:
    """CI gate: the committed cache must cover every tunable shape of the
    pinned CI set, and walking the traces must run ZERO tuning probes."""
    from ..kernels import autotune

    autotune.reset_probe_count()
    reqs = discover(configs, recipes, hires=hires, progress=progress)
    cache = autotune.AutotuneCache(cache_path).load()
    tunable = [r for r in reqs if r.tunable]
    missing = [r for r in tunable if cache.get(r.key()) is None]
    probes = autotune.tuning_probe_count()
    if missing:
        progress(f"autotune_sweep: FAIL — {len(missing)} shape(s) missing "
                 f"from {cache_path} (run the sweep and commit the cache; "
                 f"a missing shape must not silently re-tune at serving "
                 f"time):")
        for r in missing:
            progress(f"  MISSING {r.key()}")
        return 1
    if probes:
        progress(f"autotune_sweep: FAIL — {probes} tuning probe(s) ran "
                 f"during the trace walk; a warmed cache must make traces "
                 f"pure cache hits")
        return 1
    progress(f"autotune_sweep: smoke ok — {len(tunable)} tunable shape(s) "
             f"all present in {cache_path} "
             f"({len(reqs) - len(tunable)} note-only), 0 tuning probes")
    return 0


def bench_rows(requests, cache_path: str, limit: int,
               progress=print) -> List[dict]:
    """Per-shape wall-clock rows at the cached block choice — the sweep's
    kernel-regression output."""
    import jax

    from ..kernels import autotune

    cache = autotune.AutotuneCache(cache_path).load()
    interpret = jax.default_backend() != "tpu"
    rows: List[dict] = []
    for req in requests:
        if len(rows) >= limit > 0:
            progress(f"  (bench limit {limit} reached; "
                     f"{len(requests) - len(rows)} request(s) not timed)")
            break
        fn = _bench_fn(req, interpret)
        if fn is None:
            continue
        blocks = (cache.get(req.key())
                  or autotune.fallback_blocks(req.kernel, req.M, req.N,
                                              req.K, dict(req.meta)))
        t = autotune.measure(fn, tuple(blocks), reps=2)
        rows.append({"name": f"{req.kernel}:{req.M}x{req.N}x{req.K}",
                     "kernel": req.kernel, "blocks": list(blocks),
                     "backend": jax.default_backend(),
                     "interpret": interpret, "time_s": t})
        progress(f"  {rows[-1]['name']:<40} {t * 1e3:9.3f} ms "
                 f"blocks={tuple(blocks)}")
    return rows


def append_bench(path: str, rows: List[dict]) -> None:
    p = Path(path)
    report = json.loads(p.read_text()) if p.exists() else {}
    report["autotune_sweep"] = rows
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps(report, indent=1))


def main(argv=None) -> int:
    from ..analysis.traces import DEFAULT_SWEEP

    ap = argparse.ArgumentParser(
        prog="autotune_sweep",
        description="offline kernel autotune: warm the per-backend cache "
                    "for a deployment's shape set")
    ap.add_argument("--configs", default=",".join(DEFAULT_SWEEP),
                    help="comma-joined registry config names (reduced "
                         "shapes are used)")
    ap.add_argument("--recipes", default="m2q-w8a8,uniform8",
                    help="comma-joined quantization recipes")
    ap.add_argument("--cache", default=None,
                    help="cache file to warm/check (default "
                         "<checkout>/results/autotune/<backend>.json)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: assert the committed cache covers the "
                         "pinned CI shape set (no warming; missing shapes "
                         "FAIL)")
    ap.add_argument("--force-tune", action="store_true",
                    help="re-tune shapes already cached (and tune even on "
                         "CPU, timing interpret-mode bodies — tests only)")
    ap.add_argument("--bench", default=None,
                    help="append per-shape wall-clock rows to this bench "
                         "report (e.g. benchmarks/BENCH_kernels.json)")
    ap.add_argument("--bench-limit", type=int, default=12,
                    help="max shapes to time for --bench (interpret-mode "
                         "rows are slow); <=0 means no limit")
    args = ap.parse_args(argv)

    from ..kernels import autotune

    cache_path = args.cache or autotune.committed_cache_path()
    # point trace-time lookups at the same file we warm/check, so the walk
    # exercises exactly the committed serving posture
    os.environ["REPRO_AUTOTUNE_CACHE"] = cache_path
    configs = [c.strip() for c in args.configs.split(",") if c.strip()]
    recipes = [r.strip() for r in args.recipes.split(",") if r.strip()]
    if not configs or not recipes:
        ap.error("--configs / --recipes must be non-empty")

    if args.smoke:
        return smoke(CI_CONFIGS, CI_RECIPES, cache_path)

    print(f"autotune_sweep: discovering shapes for {len(configs)} "
          f"config(s) x {len(recipes)} recipe(s)...")
    reqs = discover(configs, recipes)
    wrote, skipped = warm(reqs, cache_path, force_tune=args.force_tune)
    print(f"autotune_sweep: {wrote} shape(s) warmed, {skipped} already "
          f"cached -> {cache_path}")
    if args.bench:
        rows = bench_rows([r for r in reqs if r.tunable], cache_path,
                          args.bench_limit)
        append_bench(args.bench, rows)
        print(f"autotune_sweep: {len(rows)} bench row(s) -> {args.bench}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
