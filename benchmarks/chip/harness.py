"""One run of one cell: set-up, the measured window, the check, the result.

A cell of ``BENCHMARK.json`` names a configuration file (model, recipe,
the limits of its comparison), a traffic mix (``traffic/<mix>.json``) and
its metrics, each a reader ``metrics/<metric>.py`` found by name.  The
harness holds nothing particular to any of them:

1. set-up: weights from the seed on the device (``reference.make_params``),
   calibration images from the seed, ``recipe.quantize``, the engine at
   the mix's ``max_batch``, one forward at every bucket the mix uses, the
   image pool, and a started ``ServingDaemon``;
2. the window: the general generator (``traffic.run``) for ``seconds``,
   untraced; compilations inside it are counted.  A traced run then runs a
   second, traced window of at most ``TRACE_SECONDS`` at the same load:
   the device metrics read that one, the host-clock metrics the first;
3. the check: fallback trips, a ``tpu_custom_call`` for every
   kernel the configuration names, no compilation in either window, no
   failed request, and the served logits rows of a seeded sample of the
   first window's requests against the plain reference, after the program
   is freed;
4. the result: the cell's end-to-end metrics (untraced run) or per-layer
   metrics (traced run), the device, and the compared numbers with their
   limits, last.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import re
import shutil
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
TRACE_DIR = BENCH / "out" / "trace"
TRACE_SECONDS = 5.0      # a traced run's second window, at most
DRAIN_SECONDS = 60.0     # how long requests sent in the window may take
REF_BLOCK = 64           # reference rows per device call
WAVES = 4                # low-frequency waves per image

clock = time.perf_counter

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

_MODULES: dict = {}


def load_module(path: Path):
    """Import one file of this directory by its path (readers, work
    counts and the modules below, whose names may clash with others)."""
    path = Path(path)
    if path not in _MODULES:
        spec = importlib.util.spec_from_file_location(
            "bench_" + re.sub(r"\W", "_", str(path.relative_to(BENCH))),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


trace_mod = load_module(BENCH / "trace.py")
traffic = load_module(BENCH / "traffic.py")
ref = load_module(BENCH / "reference" / "efficientvit.py")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# the cell, from BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------


def cell_spec(bench: dict, name: str, root: Path = ROOT) -> dict:
    """The cell ``name``: its configuration, mix, chips and metrics."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; one of {sorted(cells)}")
    w = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return {"name": name, "chips": int(w["chips"]),
            "config": load_json(root / entry["file"]),
            "mix": load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
            "end_to_end": e2e, "per_layer": per_layer}


def program_config(config: dict):
    """The registry's ArchConfig for ``config["arch"]``, checked against
    the sizes the configuration file states."""
    from repro.configs.registry import ARCHS, REDUCED
    by_name = dict(ARCHS)
    by_name.update({c.name: c for c in REDUCED.values()})
    cfg = by_name[config["arch"]]
    stated = {"widths": tuple(config["widths"]),
              "depths": tuple(config["depths"]),
              "img_res": config["img_res"], "n_classes": config["n_classes"],
              "dim_per_head": config["head_dim"], "dtype": config["dtype"]}
    got = {k: getattr(cfg, k) for k in stated}
    if got != stated:
        raise ValueError(f"registry {cfg.name} is {got}, the configuration "
                         f"file states {stated}")
    return cfg


def program_recipe(config: dict):
    from repro.recipe import PRESETS
    spec = config["recipe"]
    base = PRESETS[spec["preset"]]
    policy = dataclasses.replace(base.policy, **spec.get("policy", {}))
    return base.replace(name=spec["name"], policy=policy)


def _rng(seed: int, stream: int):
    return np.random.default_rng([seed, stream])


def images(rng, n: int, res: int) -> np.ndarray:
    """``n`` float32 (res, res, 3) images drawn from ``rng``, each unlike
    the others: white noise at a contrast of its own, ``WAVES``
    low-frequency waves of colours of their own, and a colour offset.  The
    served rows of different images then lie apart, and a row returned to
    the wrong request shows."""
    out = rng.standard_normal((n, res, res, 3), np.float32)
    out *= rng.uniform(0.3, 1.0, (n, 1, 1, 1)).astype(np.float32)
    x = np.arange(res, dtype=np.float32) * np.float32(2 * np.pi / res)
    freq = rng.integers(-3, 4, (n, WAVES, 2)).astype(np.float32)
    phase = rng.uniform(0, 2 * np.pi, (n, WAVES)).astype(np.float32)
    colour = rng.normal(0, 0.7, (n, WAVES, 3)).astype(np.float32)
    for i in range(n):
        for k in range(WAVES):
            wave = np.cos(freq[i, k, 0] * x[:, None]
                          + freq[i, k, 1] * x[None, :] + phase[i, k])
            out[i] += wave[..., None] * colour[i, k]
    out += rng.normal(0, 0.5, (n, 1, 1, 3)).astype(np.float32)
    return out


def calibration_images(config: dict, seed: int):
    rng = _rng(seed, 1)
    c, r = config["calibration"], config["img_res"]
    return [images(rng, c["batch_size"], r) for _ in range(c["batches"])]


# ---------------------------------------------------------------------------
# counting compilations
# ---------------------------------------------------------------------------


class CompileCounter:
    """Counts JAX traces and backend compiles while ``active`` and, apart,
    the programs that the persistent compile cache was asked for and held
    (all of them, where a checkout's first run has filled it)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")
    ASKED = "/jax/compilation_cache/compile_requests_use_cache"
    HELD = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.active = False
        self.count = 0
        self.cache = {self.ASKED: 0, self.HELD: 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_cache)

    def _on(self, event, _secs, **_kw):
        if self.active and event in self.EVENTS:
            self.count += 1

    def _on_cache(self, event, **_kw):
        if event in self.cache:
            self.cache[event] += 1

    def close(self):
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self._on)
        monitoring.unregister_event_listener(self._on_cache)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


class Spans:
    """The benchmark's own host spans, ``with spans(name): ...``, kept as
    (name, start, end) on ``time.time_ns``, the clock of the profiler's
    trace (``trace.load`` places them by the trace's start).  They stand
    in for ``TraceAnnotation``: the profiler's host tracer, which records
    those, also records every chunk of every host-to-device copy and
    slows the host path several-fold."""

    def __init__(self):
        self.events = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t = time.time_ns()
        try:
            yield
        finally:
            self.events.append((trace_mod.SPAN_PREFIX + name, t,
                                time.time_ns()))


def set_up(config: dict, mix: dict, seed: int) -> dict:
    """Everything before the window: weights from the seed, quantize, the
    engine warmed at every bucket of the mix (recording each bucket's
    kernel launches), the image pool, and a started daemon."""
    import jax
    from repro.kernels import autotune
    from repro.recipe import quantize
    from repro.serving.daemon import ServingDaemon

    cfg = program_config(config)
    res = config["img_res"]
    params = ref.make_params(config, seed)
    jax.block_until_ready(params)
    calib = calibration_images(config, seed)
    t = clock()
    qm = quantize(cfg, params, program_recipe(config), calib_batches=calib)
    quantize_s = clock() - t
    eng = qm.serve(max_batch=int(mix["max_batch"]))
    t = clock()
    launches = {}
    for b in mix["buckets"]:
        reqs = []
        with autotune.record_requests(reqs):
            eng.classify(np.zeros((b, res, res, 3), np.float32))
        launches[b] = [{"kind": r.kernel, "M": r.M, "N": r.N, "K": r.K,
                        "meta": dict(r.meta)} for r in reqs]
    eng.stats.reset()
    warmup_s = clock() - t
    pool = images(_rng(seed, 2), int(mix["pool"]), res)
    daemon = ServingDaemon(eng, **_classes(mix)).start()
    return {"params": params, "calib": calib, "engine": eng,
            "daemon": daemon, "pool": pool, "launches": launches,
            "quantize_s": quantize_s, "warmup_s": warmup_s}


def wait_for(rec: dict) -> None:
    """Wait until every request of the window has ended, or until
    ``DRAIN_SECONDS`` after its close."""
    deadline = rec["t1"] + DRAIN_SECONDS
    for r in rec["requests"]:
        if r.handle is not None:
            with contextlib.suppress(Exception):
                r.handle.result(timeout=max(0.0, deadline - clock()))


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             t_start: float, peaks: dict, on_tpu: bool = True,
             control: bool = False) -> dict:
    """Run ``spec`` once and return the result line as a dict.

    ``t_start``: the process's start on ``clock``, from which ``setup_s``
    counts.  ``peaks``: the device's row of ``peaks.json``.  ``on_tpu``:
    False only for rehearsals on the CPU, where no ``tpu_custom_call``
    exists to count.  ``control``: also read the control (the reference in
    the nearest precision below the configuration's) in the program's
    place, under ``"control"`` in the result.
    """
    import jax
    from repro.kernels import ops

    config, mix = spec["config"], spec["mix"]
    res = config["img_res"]
    counter = CompileCounter()
    s = set_up(config, mix, seed)
    params, calib, eng, daemon, pool = (s["params"], s["calib"], s["engine"],
                                        s["daemon"], s["pool"])
    launches, quantize_s, warmup_s = (s["launches"], s["quantize_s"],
                                      s["warmup_s"])
    setup_s = clock() - t_start
    log(f"set-up {setup_s:.3f} s (quantize {quantize_s:.3f} s, warm-up "
        f"{warmup_s:.3f} s, buckets {mix['buckets']}; compile cache held "
        f"{counter.cache[counter.HELD]} of {counter.cache[counter.ASKED]} "
        f"programs)")

    # -- the window -----------------------------------------------------------
    def submit(im):
        return daemon.submit(im, slo=mix["slo"])

    counter.active = True
    rec = traffic.run(mix, submit, pool, _rng(seed, 3), seconds)
    counter.active = False
    wait_for(rec)
    stats = eng.stats
    t0, t1 = rec["t0"], rec["t1"]
    sent = [r for r in rec["requests"] if r.due < t1]
    ok = [r for r in sent if r.handle is not None and r.handle.state == "DONE"]
    run = {
        "setup_s": setup_s, "quantize_s": quantize_s, "warmup_s": warmup_s,
        "window_s": t1 - t0,
        "done_in_window": sum(1 for r in ok if r.done <= t1),
        "latency_ms": [(r.done - r.due) * 1e3 for r in ok],
        "submit_lag_ms": [(r.submitted - r.due) * 1e3 for r in sent],
        "queue_ms": list(stats.queue_ms),
        "images_per_batch": stats.items / max(stats.batches, 1),
        "launches": launches[max(launches)],
        "ops_per_image": None, "peaks": peaks,
        "dtype_bytes": np.dtype(config["dtype"]).itemsize, "trace": None,
        "lib": load_module(BENCH / "readers.py"),
        "workcount": lambda k: load_module(BENCH / "workcount" / f"{k}.py"),
    }
    log(f"window {run['window_s']:.3f} s: {len(sent)} sent, {len(ok)} done, "
        f"{run['done_in_window']} done inside it "
        f"({run['done_in_window'] / run['window_s']:.1f}/s); batches "
        f"{stats.batches} of {run['images_per_batch']:.2f} images, buckets "
        f"{sorted(stats.buckets_used)}; generator late p95 "
        f"{np.percentile(run['submit_lag_ms'], 95) if sent else 0:.3f} ms")
    device = device_info(jax)
    failed_window = failed = len(sent) - len(ok)
    if trace:
        traced = traced_window(jax, mix, submit, pool, _rng(seed, 5),
                               min(seconds, TRACE_SECONDS), counter, stats)
        failed += traced["failed"]
        run["trace"] = traced["trace"]
        # the device metrics read the traced window: a mean per executed
        # batch, as the traced forwards executed it
        run["images_per_batch"] = traced["images_per_batch"]
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
    counter.close()
    daemon.shutdown(drain=False)

    # -- the check ------------------------------------------------------------
    trips = (sum(ops.trip_counts().values())
             + eng.fallback_guard.stats()["trips"])
    kernels = kernel_calls(eng, launches, res, on_tpu)
    sample = _rng(seed, 4).permutation(len(ok))[: int(mix["sample"])]
    rows = np.stack([np.asarray(ok[i].handle.result()) for i in sample]) \
        if len(sample) else np.zeros((0, config["n_classes"]), np.float32)
    images = np.asarray([ok[i].image for i in sample], np.int64)
    del daemon, eng, rec, ok, s
    gc.collect()
    t = clock()
    readings = compare(config, params, calib, pool, images, rows, control)
    log(f"reference over {len(np.unique(images))} images "
        f"{clock() - t:.3f} s")
    missing = sorted(set(config["kernels"]) - set(kernels["dispatched"]))
    missing += sorted(k for k in kernels["dispatched"]
                      if on_tpu and not kernels["custom_calls"].get(k))
    checks = {
        "gap_max": [readings["gap_max"], config["limits"]["gap_max"]],
        "route_ratio_max": [readings["route_ratio_max"],
                            config["limits"]["route_ratio_max"]],
        "failed_requests": [failed, 0],
        "fallback_trips": [trips, 0],
        "window_compiles": [counter.count, 0],
        "kernels_missing": [len(missing), 0],
    }
    correct = all(v <= lim for v, lim in checks.values())
    log(f"{len(rows)} rows compared; kernels missing {missing}")
    log(f"kernels dispatched {kernels['dispatched']}, custom calls "
        f"{kernels['custom_calls']}")

    # -- the result -----------------------------------------------------------
    run["ops_per_image"] = _ops_per_image(config)
    metrics = read_metrics(spec["per_layer"] if trace else spec["end_to_end"],
                           run)
    device["memory_peak_bytes"] = kernels["memory_peak_bytes"]
    out = {"correct": bool(correct), "attempted": len(sent),
           "failed": failed_window,
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = trace_mod.breakdown(run["trace"])
    if control:
        out["control"] = {
            "gap_max": readings["control_gap_max"],
            "route_ratio_max": readings["control_route_ratio_max"]}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k} = {v!r} (limit {lim})")
    return out


def traced_window(jax, mix: dict, submit, pool, rng, seconds: float,
                  counter, stats) -> dict:
    """A second window at the same load under the profiler: its reduced
    trace, its failed requests and its images per executed batch
    (``stats``: the engine's ``ServeStats``)."""
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    # neither host tracer nor Python tracer: each slows the host path
    # several-fold (see ``Spans``); the device is traced in full
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    spans = Spans()
    items, batches = stats.items, stats.batches
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
    counter.active = True
    with spans("window"):
        rec = traffic.run(mix, submit, pool, rng, seconds, spans)
    counter.active = False
    wait_for(rec)
    jax.profiler.stop_trace()
    items, batches = stats.items - items, stats.batches - batches
    t1 = rec["t1"]
    sent = [r for r in rec["requests"] if r.due < t1]
    ok = [r for r in sent if r.handle is not None and r.handle.state == "DONE"]
    done = sum(1 for r in ok if r.done <= t1)
    reduced = trace_mod.reduce(trace_mod.load(_trace_file(), spans.events))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    log(f"traced window {t1 - rec['t0']:.3f} s: {len(sent)} sent, "
        f"{done} done inside it ({done / (t1 - rec['t0']):.1f}/s); batches "
        f"{batches} of {items / max(batches, 1):.2f} images")
    return {"trace": reduced, "failed": len(sent) - len(ok),
            "images_per_batch": items / max(batches, 1)}


def _classes(mix: dict) -> dict:
    """The daemon's SLO classes: the mix's own, where it states them."""
    if "classes" not in mix:
        return {}
    from repro.serving.slo import SLOClass
    return {"classes": tuple(SLOClass(**c) for c in mix["classes"])}


def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _memory_peak(jax) -> int:
    """Peak bytes in use on the fullest chip (0 where not reported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def _trace_file() -> str:
    files = sorted(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {TRACE_DIR}")
    return str(files[-1])


def _ops_per_image(config: dict) -> int:
    return load_module(BENCH / "modelops.py").ops_per_image(config)


_CUSTOM_CALL = re.compile(
    r"^\s*(?:ROOT\s+)?%([A-Za-z_]\w*?)(?:\.\d+)*\s*=.*"
    r'custom_call_target="tpu_custom_call"', re.M)


def kernel_calls(eng, launches: dict, res: int, on_tpu: bool) -> dict:
    """Kernels the warm-up traces dispatched, ``tpu_custom_call`` counts in
    the compiled forward at the largest bucket (TPU only), and the peak
    device memory, read before anything else runs on the device."""
    import jax
    out = {"dispatched": sorted({c["kind"] for b in launches.values()
                                 for c in b}),
           "custom_calls": {},
           "memory_peak_bytes": _memory_peak(jax)}
    if on_tpu:
        x = jax.ShapeDtypeStruct((max(launches), res, res, 3), np.float32)
        with eng._dispatch_scope():
            text = eng._fwd.lower(eng.params, x).compile().as_text()
        for m in _CUSTOM_CALL.finditer(text):
            out["custom_calls"][m.group(1)] = \
                out["custom_calls"].get(m.group(1), 0) + 1
    return out


def compare(config: dict, params, calib, pool, images, rows,
            control: bool) -> dict:
    """Served rows against the reference's rows for their own images.

    ``gap_max``: the widest relative L2 gap of a served row from its
    image's reference row.  ``route_ratio_max``: over the served rows, the
    largest ratio of a row's distance from its own image's reference row
    to its distance from the nearest reference row of another sampled
    image; over 1 where a row lies nearer another image than its own, as a
    row returned to the wrong request does.  With ``control``, the same
    two numbers for the control in the program's place.
    """
    import jax
    uniq, inv = np.unique(images, return_inverse=True)
    act_max = ref.calibrate(config, params, calib)
    compute = config["recipe"]["reference"]

    def rows_at(bits):
        qw = ref.quantize_weights(params, compute, bits)
        fwd = jax.jit(lambda w, a, x: ref.quantized_forward(
            config, w, a, x, bits))
        return np.concatenate([
            np.asarray(fwd(qw, act_max, pool[uniq[i:i + REF_BLOCK]]))
            for i in range(0, len(uniq), REF_BLOCK)]) if len(uniq) else \
            np.zeros((0, config["n_classes"]), np.float32)

    with jax.default_matmul_precision("highest"):
        want = rows_at(8)
        out = _gaps(rows, want, inv)
        if control:
            c = _gaps(rows_at(4)[inv], want, inv)
            out.update(control_gap_max=c["gap_max"],
                       control_route_ratio_max=c["route_ratio_max"])
    return out


def _gaps(rows, want, inv) -> dict:
    """``rows[i]`` served for image ``inv[i]``, whose reference row is
    ``want[inv[i]]``; ``want`` holds one row per distinct image."""
    if not len(rows):
        return {"gap_max": float("inf"), "route_ratio_max": float("inf")}
    rows = np.asarray(rows, np.float64)
    want = np.asarray(want, np.float64)
    own = np.linalg.norm(rows - want[inv], axis=1)
    # distances of every served row from every image's reference row
    sq = ((rows ** 2).sum(1)[:, None] + (want ** 2).sum(1)[None, :]
          - 2 * rows @ want.T)
    dist = np.sqrt(np.maximum(sq, 0.0))
    dist[np.arange(len(rows)), inv] = np.inf
    nearest = dist.min(1)
    return {"gap_max": float((own / np.linalg.norm(want[inv], axis=1)).max()),
            "route_ratio_max": float((own / nearest).max())}


def read_metrics(entries, run: dict) -> dict:
    """{name: {"value", "unit"}} from each metric's reader; a reader that
    finds nothing to read leaves its metric out."""
    out = {}
    for m in entries:
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(run)
        if value is None:
            log(f"metric {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
