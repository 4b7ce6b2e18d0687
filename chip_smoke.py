"""Chip smoke test: the quantized serving path, end to end, on one TPU.

    python chip_smoke.py              # one chip: vision phase, token phase
    python chip_smoke.py --chips 4    # four chips: mesh-sharded serving only

The script runs the entry points a user calls — ``recipe.quantize()`` ->
``QuantizedModel.serve()`` -> ``VisionEngine`` / ``Engine`` — at the full
widths of the registry configs, with weights made from ``--seed``, in one
process.  It prints the device line before any work and exits non-zero
without a TPU.

* Vision (the paper's model): EfficientViT-B1 R224 under the ``m2q-w8a8``
  recipe, 21 images through a ``max_batch=16`` engine (buckets 16 and 8).
  Every delivered row must be finite, and the kernel path must agree with
  the XLA QTensor path of the same artifact within ``PARITY_BOUND``.  The
  compiled served forward must hold a ``tpu_custom_call`` for every kernel
  kind the model dispatches.  The error against a float32 reference
  forward is printed.
* Tokens: qwen1.5-0.5b quantized the way ``launch/serve.py`` does it, 4
  requests of 8 new tokens; every request must complete.
* ``--chips 4``: ``VisionEngine`` on a 4x1 (data x model) mesh and the
  token ``Engine`` on a 1x4 mesh, each compared with its one-chip result.

Any tripped ``FallbackGuard`` (a kernel that raised and was retried on XLA)
fails the run.  Times printed here are host wall-clock set-up times
(compilation included), not device metrics.  On success the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
import warnings

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

VISION_ARCH = "efficientvit-b1-r224"
TOKEN_ARCH = "qwen1.5-0.5b"
MAX_BATCH = 16
N_IMAGES = 21           # one full bucket of 16, then a flushed bucket of 8
BUCKETS = [8, 16]
N_REQUESTS = 4
MAX_NEW = 8
TOKEN_MAX_BATCH = 4
TOKEN_MAX_LEN = 128

# Relative L2 error allowed between the kernel path and the XLA QTensor path
# of the same artifact.  The matmul and depthwise kernels compute the XLA
# path's integer math (interpret-mode parity 1e-4/1e-5); the int8 MSA
# attention kernel quantizes activations that the XLA path keeps in f32, and
# the interpret-mode tests bound that at 0.05 (tests/test_attn_dispatch.py:
# relu_attn vs f32, and the whole MSA block vs its f32-attention twin).
PARITY_BOUND = 0.05
# A mesh program rounds differently from the one-chip program, and on this
# random-weight B1 int8 activation quantization turns rounding into whole
# quantization steps: a 1e-7 relative input perturbation moves the logits
# by rel L2 0.018, 1e-5 by 0.033 (CPU), and the 4x1 mesh on a v5e landed
# 0.041 from one chip.  Distinct images land about 0.22 apart, so each
# sharded row must also be nearest its own one-chip row.
MESH_BOUND = 0.1
# The token mesh reassociates the model-axis reductions, which can flip a
# greedy near-tie and send the two decodes down different paths.  So the
# 1x4 tokens are scored by the one-chip model on their own prefix: each
# must rank in its top MESH_TOP_K of the 151936-token vocabulary.
MESH_TOP_K = 8


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def rel_l2(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _images(cfg, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    return rng.normal(0, 1, (N_IMAGES, cfg.img_res, cfg.img_res, 3)
                      ).astype(np.float32)


def _prompts(cfg, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, int(rng.integers(4, 17)),
                         dtype=np.int32) for _ in range(N_REQUESTS)]


def _trips(engine) -> dict:
    from repro.kernels import ops
    return {"latch": ops.trip_counts(),
            "guard": engine.fallback_guard.stats()["trips"]}


def _served_rows(handles):
    """Delivered logits rows in submit order; a failed handle's error
    stands in for its row."""
    rows, errors = [], []
    for h in handles:
        try:
            rows.append(h.result())
        except Exception as e:  # noqa: BLE001 — reported as a failure
            errors.append(repr(e))
    return rows, errors


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_vision(cfg, seed: int = 0, dispatch=None, log=print) -> dict:
    """Quantize and serve ``cfg``; compare the kernel path with the XLA path
    of the same artifact and with a float32 reference forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import autotune, ops
    from repro.launch.hlo_analysis import kernel_counts
    from repro.models import get_model
    from repro.recipe import quantize

    t0 = time.perf_counter()
    model = get_model(cfg)
    params = model.init(cfg, jax.random.PRNGKey(seed))
    qm = quantize(cfg, params, "m2q-w8a8")
    quantize_s = time.perf_counter() - t0
    eng = qm.serve(max_batch=MAX_BATCH, dispatch=dispatch)
    images = _images(cfg, seed)

    # compile the served forward at the full bucket, and read back which
    # kernels the trace dispatched and which the compiled program launches
    x = jnp.zeros((MAX_BATCH,) + images.shape[1:], jnp.float32)
    reqs = []
    t0 = time.perf_counter()
    with autotune.record_requests(reqs), eng._dispatch_scope():
        compiled = eng._fwd.lower(eng.params, x).compile()
    compile_s = time.perf_counter() - t0
    dispatched = sorted({r.kernel for r in reqs})
    counts = kernel_counts(compiled.as_text())
    log(f"[vision] {cfg.name}: {len(qm.report)} quantized layers; kernels "
        f"dispatched {dispatched}")
    log(f"[vision] tpu_custom_call counts in the compiled bucket-"
        f"{MAX_BATCH} forward: {counts}")

    handles = []
    t0 = time.perf_counter()
    first_batch_s = None
    for im in images:
        handles.append(eng.submit(im))
        if len(handles) == MAX_BATCH:   # the full bucket ran inline
            first_batch_s = time.perf_counter() - t0
    eng.flush()
    rows, errors = _served_rows(handles)
    served = np.stack(rows) if rows else np.zeros((0, cfg.n_classes))
    log(f"[vision] set-up (host wall clock, not a device metric): init + "
        f"quantize {quantize_s:.3f} s, compile {compile_s:.3f} s, first "
        f"batch of {MAX_BATCH} {first_batch_s} s")

    xla = qm.serve(max_batch=MAX_BATCH,
                   dispatch=ops.DispatchConfig(False, False, False)
                   ).classify(images)
    # the reference is plain XLA in float32: the activation-side attention
    # kernel would otherwise serve float params too (its int8 dots cannot
    # take the "highest" matmul precision)
    cfg32 = cfg.replace(dtype="float32")
    with jax.default_matmul_precision("highest"), \
            ops.dispatch(dense=False, conv=False, attn=False):
        ref = np.asarray(jax.jit(lambda p, im: model.forward(cfg32, p, im))(
            params, jnp.asarray(images)))
    res = {
        "dispatched": dispatched, "counts": counts,
        "compile_s": compile_s, "first_batch_s": first_batch_s,
        "delivered": len(rows), "errors": errors,
        "buckets": sorted(eng.stats.buckets_used),
        "finite": bool(np.all(np.isfinite(served))),
        "parity_rel": rel_l2(served, xla) if len(rows) == N_IMAGES
        else float("nan"),
        "ref_rel": rel_l2(served, ref) if len(rows) == N_IMAGES
        else float("nan"),
        "trips": _trips(eng),
    }
    log(f"[vision] delivered {res['delivered']}/{N_IMAGES} rows, buckets "
        f"{res['buckets']}, all finite: {res['finite']}")
    log(f"[vision] kernel path vs XLA path rel L2 = {res['parity_rel']!r} "
        f"(bound {PARITY_BOUND})")
    log(f"[vision] kernel path vs float32 reference (highest precision) "
        f"rel L2 = {res['ref_rel']!r}")
    log(f"[vision] trips: {res['trips']}")
    return res


def vision_failures(res: dict) -> list:
    out = []
    if res["delivered"] != N_IMAGES or res["errors"]:
        out.append(f"vision: {res['delivered']}/{N_IMAGES} rows delivered; "
                   f"errors {res['errors']}")
    if not res["finite"]:
        out.append("vision: non-finite logits delivered")
    if not res["parity_rel"] < PARITY_BOUND:
        out.append(f"vision: kernel vs XLA rel L2 {res['parity_rel']!r} "
                   f"not under {PARITY_BOUND}")
    if res["buckets"] != BUCKETS:
        out.append(f"vision: buckets {res['buckets']}, expected {BUCKETS}")
    out += _trip_failures("vision", res["trips"])
    if not res["dispatched"]:
        out.append("vision: the served forward dispatched no kernel")
    for kind in res["dispatched"]:
        if not res["counts"].get(kind):
            out.append(f"vision: no tpu_custom_call for dispatched kernel "
                       f"{kind}")
    return out


def phase_tokens(cfg, seed: int = 0, dispatch=None, log=print) -> dict:
    """Quantize ``cfg`` as ``launch/serve.py`` does and decode
    ``N_REQUESTS`` requests of ``MAX_NEW`` tokens."""
    import jax

    from repro.kernels import autotune
    from repro.launch.serve import quantize_for_serving
    from repro.models import get_model

    t0 = time.perf_counter()
    model = get_model(cfg)
    params = model.init(cfg, jax.random.PRNGKey(seed))
    qm = quantize_for_serving(cfg, params)
    quantize_s = time.perf_counter() - t0
    eng = qm.serve(max_batch=TOKEN_MAX_BATCH, max_len=TOKEN_MAX_LEN,
                   dispatch=dispatch)
    reqs = []
    t0 = time.perf_counter()
    with autotune.record_requests(reqs):
        handles = [eng.submit(p, max_new_tokens=MAX_NEW).handle
                   for p in _prompts(cfg, seed)]
        stats = eng.run()
    run_s = time.perf_counter() - t0
    tokens, errors = _served_rows(handles)
    res = {"dispatched": sorted({r.kernel for r in reqs}),
           "tokens": [list(map(int, t)) for t in tokens], "errors": errors,
           "decoded": stats.decoded_tokens, "run_s": run_s,
           "trips": _trips(eng)}
    log(f"[tokens] {cfg.name}: {len(qm.report)} quantized layers; kernels "
        f"dispatched {res['dispatched']}")
    log(f"[tokens] {len(tokens)}/{N_REQUESTS} requests done, "
        f"{res['decoded']} tokens decoded; host wall clock, not a device "
        f"metric: init + quantize {quantize_s:.3f} s, serve (compilation "
        f"included) {run_s:.3f} s")
    log(f"[tokens] trips: {res['trips']}")
    return res


def token_failures(res: dict) -> list:
    out = []
    if res["errors"] or len(res["tokens"]) != N_REQUESTS:
        out.append(f"tokens: {len(res['tokens'])}/{N_REQUESTS} requests "
                   f"done; errors {res['errors']}")
    if any(len(t) != MAX_NEW for t in res["tokens"]):
        out.append(f"tokens: expected {MAX_NEW} tokens per request, got "
                   f"{[len(t) for t in res['tokens']]}")
    if not res["dispatched"]:
        out.append("tokens: the served steps dispatched no kernel")
    out += _trip_failures("tokens", res["trips"])
    return out


def _trip_failures(phase: str, trips: dict) -> list:
    if trips["guard"] or any(trips["latch"].values()):
        return [f"{phase}: FallbackGuard tripped {trips}"]
    return []


def phase_mesh(vcfg, tcfg, seed: int = 0, dispatch=None, log=print) -> dict:
    """The sharded serving path: each engine on a four-chip mesh, compared
    with the same artifact served on one chip."""
    import jax
    import numpy as np

    from repro.launch.serve import parse_mesh, quantize_for_serving
    from repro.models import get_model
    from repro.recipe import quantize

    res = {}
    model = get_model(vcfg)
    qm = quantize(vcfg, model.init(vcfg, jax.random.PRNGKey(seed)),
                  "m2q-w8a8")
    images = _images(vcfg, seed)
    one = qm.serve(max_batch=MAX_BATCH, dispatch=dispatch)
    ref = one.classify(images)
    eng = qm.serve(max_batch=MAX_BATCH, dispatch=dispatch,
                   mesh=parse_mesh("4x1"))
    handles = [eng.submit(im) for im in images]
    eng.flush()
    rows, errors = _served_rows(handles)
    full = len(rows) == N_IMAGES
    # which one-chip row each sharded row is nearest to
    nearest = [int(np.argmin(np.linalg.norm(ref - r, axis=1)))
               for r in rows]
    res["vision"] = {
        "errors": errors, "delivered": len(rows),
        "rel": rel_l2(np.stack(rows), ref) if full else float("nan"),
        "own_rows": full and nearest == list(range(N_IMAGES)),
        "buckets": sorted(eng.stats.buckets_used),
        "trips": _trips(eng), "trips_one": _trips(one)}
    log(f"[mesh] vision 4x1 vs one chip: rel L2 {res['vision']['rel']!r} "
        f"(bound {MESH_BOUND}), every row nearest its own one-chip row: "
        f"{res['vision']['own_rows']}, buckets {res['vision']['buckets']}, "
        f"trips {res['vision']['trips']}")

    tmodel = get_model(tcfg)
    tqm = quantize_for_serving(
        tcfg, tmodel.init(tcfg, jax.random.PRNGKey(seed)))
    prompts = _prompts(tcfg, seed)
    outs = {}
    for name, mesh in (("one", None), ("1x4", parse_mesh("1x4"))):
        teng = tqm.serve(max_batch=TOKEN_MAX_BATCH, max_len=TOKEN_MAX_LEN,
                         dispatch=dispatch, mesh=mesh)
        hs = [teng.submit(p, max_new_tokens=MAX_NEW).handle for p in prompts]
        teng.run()
        toks, errs = _served_rows(hs)
        outs[name] = {"tokens": [list(map(int, t)) for t in toks],
                      "errors": errs, "trips": _trips(teng)}
    for o in outs.values():
        if not o["errors"] and len(o["tokens"]) == N_REQUESTS:
            o["ranks"] = _one_chip_ranks(tcfg, tmodel, tqm.params, prompts,
                                         o["tokens"], dispatch)
    res["tokens"] = outs
    same = outs["one"]["tokens"] == outs["1x4"]["tokens"]
    log(f"[mesh] tokens 1x4 vs one chip: identical greedy tokens {same}; "
        f"worst rank under the one-chip model: 1x4 "
        f"{max(outs['1x4'].get('ranks', [None]))}, one chip "
        f"{max(outs['one'].get('ranks', [None]))} (bound: under "
        f"{MESH_TOP_K}); trips {outs['1x4']['trips']}")
    return res


def _one_chip_ranks(cfg, model, params, prompts, tokens, dispatch) -> list:
    """Rank of every generated token among the one-chip model's logits at
    its position, teacher-forced on the request's own prompt + tokens (0:
    the greedy pick).  Right padding cannot reach earlier causal positions."""
    import jax
    import numpy as np

    from repro.kernels import ops
    seqs = [list(map(int, p)) + t for p, t in zip(prompts, tokens)]
    width = max(map(len, seqs))
    batch = np.array([s + [0] * (width - len(s)) for s in seqs], np.int32)
    with ops.dispatch(dispatch):
        logits = np.asarray(jax.jit(lambda p, t: model.forward(cfg, p, t))(
            params, batch), np.float32)[..., :cfg.vocab_size]
    ranks = []
    for i, (p, t) in enumerate(zip(prompts, tokens)):
        for j, tok in enumerate(t):
            row = logits[i, len(p) - 1 + j]
            ranks.append(int(np.sum(row > row[tok])))
    return ranks


def mesh_failures(res: dict) -> list:
    out = []
    v = res["vision"]
    if v["errors"] or v["delivered"] != N_IMAGES:
        out.append(f"mesh vision: {v['delivered']}/{N_IMAGES} rows; "
                   f"errors {v['errors']}")
    if not v["rel"] < MESH_BOUND:
        out.append(f"mesh vision: 4x1 vs one chip rel L2 {v['rel']!r} not "
                   f"under {MESH_BOUND}")
    if not v["own_rows"]:
        out.append("mesh vision: a 4x1 row is nearer another image's "
                   "one-chip row than its own")
    out += _trip_failures("mesh vision", v["trips"])
    out += _trip_failures("mesh vision (one chip)", v["trips_one"])
    for name, o in res["tokens"].items():
        if o["errors"] or len(o["tokens"]) != N_REQUESTS:
            out.append(f"mesh tokens {name}: errors {o['errors']}")
        elif any(len(t) != MAX_NEW for t in o["tokens"]):
            out.append(f"mesh tokens {name}: expected {MAX_NEW} tokens per "
                       f"request, got {[len(t) for t in o['tokens']]}")
        elif not max(o["ranks"]) < MESH_TOP_K:
            out.append(f"mesh tokens {name}: a token ranks "
                       f"{max(o['ranks'])} under the one-chip model, not "
                       f"under {MESH_TOP_K}")
        out += _trip_failures(f"mesh tokens {name}", o["trips"])
    return out


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def _run(phase, failures_of, failures: list, *args, **kw) -> None:
    try:
        failures += failures_of(phase(*args, **kw))
    except Exception:  # noqa: BLE001 — a phase that raised has failed
        traceback.print_exc()
        failures.append(f"{phase.__name__} raised")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh-sharded serving path")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for weights, images and prompts")
    args = ap.parse_args(argv)

    dev = device_info()
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        print("chip_smoke: no TPU found; this script runs only on the chip",
              file=sys.stderr)
        return 1
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {dev['count']}", file=sys.stderr)
        return 1

    from repro.checkout import enable_compile_cache
    from repro.configs.registry import ARCHS
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    failures: list = []
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always", RuntimeWarning)
        if args.chips == 1:
            _run(phase_vision, vision_failures, failures,
                 ARCHS[VISION_ARCH], args.seed)
            _run(phase_tokens, token_failures, failures,
                 ARCHS[TOKEN_ARCH], args.seed)
        else:
            _run(phase_mesh, mesh_failures, failures,
                 ARCHS[VISION_ARCH], ARCHS[TOKEN_ARCH], args.seed)
    for w in caught:
        print(f"warning: {w.message}", flush=True)
        if "FallbackGuard" in str(w.message):
            failures.append(f"warning: {w.message}")
    for f in failures:
        print(f"FAIL {f}", flush=True)
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
