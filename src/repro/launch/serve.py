"""Serving driver CLI: PTQ-quantize a model with M2Q and serve batched
requests through the continuous-batching engine (scheduler-core admission,
optional sharded execution).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --reduced \
      --requests 8 --max-new 16

Sharded serving (the device world must exist before jax initializes, e.g.
XLA_FLAGS=--xla_force_host_platform_device_count=16 for a virtual mesh):

  ... python -m repro.launch.serve --arch qwen1.5-0.5b --reduced --mesh 4x4
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from ..checkout import enable_compile_cache
from ..configs.registry import ARCHS, REDUCED
from ..models import get_model
from ..recipe import QuantizedModel, as_recipe, quantize
from ..serving.engine import Engine


def quantize_for_serving(cfg, params, batch: int = 2, calib_len: int = 32,
                         recipe="m2q-w8a8") -> QuantizedModel:
    """Offline PTQ via the recipe API: calibrate on random prompts, apply
    M2Q, return the persistable artifact (reduced demo configs get the
    taxonomy-pinning arch defaults from QuantRecipe.resolve).  Only the
    prompt shape is overridden; the recipe's other CalibSpec fields
    (batches, seed) are kept."""
    rec = as_recipe(recipe)
    rec = rec.replace(calib=dataclasses.replace(
        rec.calib, batch_size=batch, seq_len=calib_len))
    return quantize(cfg, params, rec)


def parse_mesh(spec: str):
    """'DATAxMODEL' (e.g. '4x4') -> jax Mesh over (data, model).  The
    process must already expose data*model devices."""
    try:
        n_data, n_model = (int(p) for p in spec.lower().split("x"))
    except ValueError:
        raise SystemExit(f"--mesh wants DATAxMODEL (e.g. 4x4), got {spec!r}")
    n_dev = len(jax.devices())
    if n_data * n_model > n_dev:
        raise SystemExit(
            f"--mesh {spec} needs {n_data * n_model} devices but only "
            f"{n_dev} exist (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_data * n_model} "
            "before launch for a virtual mesh)")
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-delay-ms", type=float, default=0.0,
                    help="admission deadline: >0 coalesces prefills until "
                         "the batch fills or the oldest request ages out")
    ap.add_argument("--mesh", default=None,
                    help="DATAxMODEL (e.g. 4x4): sharded execution via "
                         "repro.dist.sharding")
    ap.add_argument("--no-quant", action="store_true")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = (REDUCED if args.reduced else ARCHS)[args.arch]
    model = get_model(cfg)
    params = model.init(cfg, jax.random.PRNGKey(0))
    mesh = parse_mesh(args.mesh) if args.mesh else None
    engine_kw = dict(max_batch=args.max_batch, max_len=args.max_len,
                     max_delay_ms=args.max_delay_ms, mesh=mesh)
    if not args.no_quant:
        qm = quantize_for_serving(cfg, params)
        bits = {r.path: r.bits for r in qm.report}
        print(f"[serve] quantized {len(qm.report)} layers; "
              f"avg bits={np.mean(list(bits.values())):.2f}")
        eng = qm.serve(**engine_kw)
    else:
        eng = Engine(cfg, params, **engine_kw)
    rng = np.random.default_rng(1)
    for i in range(args.requests):
        plen = int(rng.integers(4, 17))
        eng.submit(rng.integers(0, cfg.vocab_size, plen, dtype=np.int32),
                   max_new_tokens=args.max_new)
    t0 = time.time()
    stats = eng.run()
    dt = time.time() - t0
    print(f"[serve] arch={cfg.name} requests={stats.finished} "
          f"decoded={stats.decoded_tokens} steps={stats.steps} "
          f"tok/s={stats.decoded_tokens / max(dt, 1e-9):.1f}"
          + (f" mesh={dict(mesh.shape)}" if mesh is not None else ""))
    print(f"[serve] queue p50={stats.p50_ms:.2f}ms p99={stats.p99_ms:.2f}ms "
          f"prefill-occupancy={stats.batch_occupancy:.2f} "
          f"padded-fraction={stats.padded_fraction:.2f} "
          f"flushes={stats.flush_reasons}")


if __name__ == "__main__":
    main()
