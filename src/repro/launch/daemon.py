"""Serving-daemon driver CLI: wall-clock serving with SLO classes,
streaming, and (multi-)host mesh launch.

Single host — quantize (unless ``--no-quant``) and serve mixed
interactive + batch wall-clock traffic through the background
:class:`~repro.serving.daemon.ServingDaemon`, streaming the first
interactive request token by token:

  PYTHONPATH=src python -m repro.launch.daemon --arch qwen1.5-0.5b \
      --reduced --requests 8 --stream

``--smoke`` is the CI fast path (check.sh): tiny reduced config, one
streamed request with a tight timeout, clean drain, exact outcome
reconciliation — exits non-zero on any of those failing.

Multi-host — every process runs the same command with its own
``--process-id``; ``jax.distributed.initialize`` joins them into one
global device world, the ``--mesh`` spans it, and params/cache land via
``dist.sharding.put_global`` (cross-process placement, where
``jax.device_put`` cannot).  On backends without multiprocess execution
(the CPU backend) this is a DRY-RUN: distributed init, global mesh,
spec-conformant placement, and lowering of the prefill computation are
all verified, then the process reports and exits — the serve loop
itself runs only where the runtime can execute cross-process programs:

  python -m repro.launch.daemon --arch qwen1.5-0.5b --reduced \
      --mesh 2x4 --coordinator 127.0.0.1:9911 --num-processes 2 \
      --process-id 0   # and the same with --process-id 1

Supervision (docs/serving.md, "Supervision & recovery"):

* ``--health-file PATH`` runs the single-host serve path under a
  :class:`~repro.serving.supervisor.Supervisor` and writes its
  ``health()`` probe snapshot to PATH (atomic tmp + ``os.replace``)
  twice a second — poll it from outside the process.  On the multi-host
  path the same flag writes a per-process readiness marker
  ``PATH.p<process_id>`` once placement + lowering verify, and each
  process waits for ALL peers' markers before reporting
  ``peers-ready`` — a cross-host readiness barrier.
* ``--recovery-smoke`` is the crash-recovery CI stage: a journal-backed
  supervisor serving under an injected ``crash@decode`` fault — asserts
  the watchdog restarted the daemon, every request completed, the
  replayed results MATCH a fault-free reference, and the journal
  reconciles exactly.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

# NOTE: repro imports are deliberately LAZY (inside functions) in this
# module: multi-host launch must call jax.distributed.initialize()
# before ANY jax computation executes, and several repro modules run
# small computations at import time.  `import jax` alone is safe.
import jax
import numpy as np


def build_engine(args, mesh=None):
    from ..configs.registry import ARCHS, REDUCED
    from ..models import get_model
    from ..serving.engine import Engine
    from .serve import quantize_for_serving
    cfg = (REDUCED if args.reduced else ARCHS)[args.arch]
    model = get_model(cfg)
    params = model.init(cfg, jax.random.PRNGKey(0))
    engine_kw = dict(max_batch=args.max_batch, max_len=args.max_len,
                     mesh=mesh)
    if args.no_quant:
        return Engine(cfg, params, **engine_kw)
    qm = quantize_for_serving(cfg, params)
    print(f"[daemon] quantized {len(qm.report)} layers")
    return qm.serve(**engine_kw)


def _prompts(cfg, n, rng):
    return [rng.integers(0, cfg.vocab_size, int(rng.integers(4, 13)),
                         dtype=np.int32) for _ in range(n)]


def _write_json_atomic(path, obj) -> None:
    tmp = f"{path}.tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(obj, f, sort_keys=True)
    os.replace(tmp, path)


class _HealthWriter:
    """Background thread dumping ``snapshot()`` JSON to ``path`` (atomic
    replace, so readers never see a torn file)."""

    def __init__(self, path: str, snapshot, interval_s: float = 0.5):
        self.path = path
        self._snapshot = snapshot
        self._interval = interval_s
        self._stop = threading.Event()
        self._th = threading.Thread(target=self._run, daemon=True,
                                    name="repro-health-writer")

    def _run(self):
        while True:
            _write_json_atomic(self.path, self._snapshot())
            if self._stop.wait(self._interval):
                return

    def __enter__(self):
        self._th.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._th.join()
        _write_json_atomic(self.path, self._snapshot())  # final state


def serve_traffic(daemon, args) -> bool:
    """Submit mixed interactive/batch wall-clock traffic from a foreign
    thread, stream the first interactive request, report per-class
    latency.  Returns True when every outcome reconciled."""
    eng = daemon.engine
    cfg = eng.cfg
    rng = np.random.default_rng(0)
    n_inter = max(1, args.requests // 2)
    n_batch = args.requests - n_inter
    results = []

    def submitter():
        for p in _prompts(cfg, n_batch, rng):
            results.append(daemon.submit(p, slo="batch",
                                         max_new_tokens=args.max_new))
        for p in _prompts(cfg, n_inter - 1, rng):
            results.append(daemon.submit(p, slo="interactive",
                                         max_new_tokens=args.max_new))

    th = threading.Thread(target=submitter)
    th.start()
    streamed = []
    first = daemon.submit(_prompts(cfg, 1, rng)[0], slo="interactive",
                          max_new_tokens=args.max_new, stream=True)
    for tok in first.handle.tokens(timeout=args.timeout):
        streamed.append(tok)
        if args.stream:
            print(f"[daemon] stream tok={tok}", flush=True)
    th.join()
    results.append(first)
    for r in results:
        r.handle.result(timeout=args.timeout)
    daemon.shutdown(drain=True, timeout=args.timeout)
    if streamed != first.handle.result():
        print(f"[daemon] FAIL: streamed {streamed} != result "
              f"{first.handle.result()}")
        return False
    s = eng.stats
    if s.submitted != s.resolved:
        print(f"[daemon] FAIL: submitted={s.submitted} != "
              f"resolved={s.resolved}")
        return False
    cls = daemon.stats_summary()["classes"]
    for name, row in cls.items():
        print(f"[daemon] class={name} completed={row['completed']} "
              f"p50={row['p50_ms']:.1f}ms p99={row['p99_ms']:.1f}ms")
    print(f"[daemon] reconciled {s.submitted} requests; "
          f"streamed_tokens={s.streamed_tokens} "
          f"preemptions={s.preemptions}")
    return True


def serve_supervised(args, mesh=None) -> int:
    """Single-host serve path under a Supervisor, health snapshots on
    disk (``--health-file``): same mixed traffic as :func:`serve_traffic`
    but submitted through ``Supervisor.submit`` — restart-transparent —
    with supervisor-level outcome reconciliation."""
    from ..serving.supervisor import Supervisor
    sup = Supervisor(lambda: build_engine(args, mesh=mesh)).start()
    cfg = sup._daemon.engine.cfg
    rng = np.random.default_rng(0)
    n_inter = max(1, args.requests // 2)
    n_batch = args.requests - n_inter
    ok = True
    with _HealthWriter(args.health_file, sup.health):
        handles = [sup.submit(p, slo="batch", max_new_tokens=args.max_new)
                   for p in _prompts(cfg, n_batch, rng)]
        handles += [sup.submit(p, slo="interactive",
                               max_new_tokens=args.max_new)
                    for p in _prompts(cfg, n_inter - 1, rng)]
        streamed = []
        first = sup.submit(_prompts(cfg, 1, rng)[0], slo="interactive",
                           max_new_tokens=args.max_new, stream=True)
        for tok in first.tokens(timeout=args.timeout):
            streamed.append(tok)
            if args.stream:
                print(f"[daemon] stream tok={tok}", flush=True)
        handles.append(first)
        for h in handles:
            h.result(timeout=args.timeout)
        if streamed != first.result():
            print(f"[daemon] FAIL: streamed {streamed} != result "
                  f"{first.result()}")
            ok = False
        sup.shutdown(drain=True, timeout=args.timeout)
        s = sup.stats
        if s.submitted != s.resolved:
            print(f"[daemon] FAIL: submitted={s.submitted} != "
                  f"resolved={s.resolved}")
            ok = False
    health = sup.health()
    print(f"[daemon] supervised: {s.submitted} requests reconciled, "
          f"restarts={health['restarts']}, health -> {args.health_file}")
    return 0 if ok else 1


def recovery_smoke(args) -> int:
    """CI crash-recovery stage: journal-backed supervisor, first engine
    build armed with ``crash@decode`` AFTER a fault-free warmup (a cold
    first step would trip the hang watchdog) — assert restart happened,
    goodput is total, replayed results match a fault-free reference, and
    the journal reconciles exactly."""
    import tempfile
    from ..serving.engine import Engine
    from ..serving.faults import FaultInjector, FaultSpec
    from ..serving.journal import RequestJournal
    from ..serving.supervisor import RestartPolicy, Supervisor
    t0 = time.monotonic()
    eng0 = build_engine(args)
    cfg, params = eng0.cfg, eng0.params
    rng = np.random.default_rng(0)
    prompts = _prompts(cfg, max(2, args.requests), rng)

    refs = [eng0.submit(p, max_new_tokens=args.max_new) for p in prompts]
    eng0.run()
    expected = [r.handle.result() for r in refs]

    builds = []

    def factory():
        eng = Engine(cfg, params, max_batch=args.max_batch,
                     max_len=args.max_len)
        for p in prompts:  # warm every shape, fault-free, then arm
            eng.submit(p, max_new_tokens=args.max_new)
        eng.run()
        if not builds:
            eng.faults = FaultInjector(
                [FaultSpec.parse(f"crash@decode:{args.max_new}")])
        builds.append(1)
        return eng

    jpath = os.path.join(tempfile.mkdtemp(prefix="repro-recovery-"),
                         "journal.jsonl")
    sup = Supervisor(
        factory, journal=RequestJournal(jpath),
        policy=RestartPolicy(hang_threshold_s=max(10.0, args.timeout / 4),
                             backoff_base_s=0.02, poll_interval_s=0.05))
    sup.start()
    handles = [sup.submit(p, request_id=f"smoke-{i}",
                          max_new_tokens=args.max_new)
               for i, p in enumerate(prompts)]
    outs = [h.result(timeout=args.timeout) for h in handles]
    rec = sup.journal.reconcile()
    health = sup.health()
    sup.shutdown(drain=True, timeout=args.timeout)
    completed = sum(1 for o in outs if o is not None)
    goodput = completed / len(prompts)
    match = all(list(a) == list(b) for a, b in zip(outs, expected))
    ok = (sup.restarts >= 1 and goodput == 1.0 and match
          and rec["exact"] and rec["pending"] == 0
          and health["ready"]["ready"])
    if not ok:
        print(f"[daemon] RECOVERY SMOKE FAIL: restarts={sup.restarts} "
              f"goodput={goodput} match={match} reconcile={rec} "
              f"ready={health['ready']}")
        return 1
    print(f"[daemon] recovery smoke ok: crash@decode -> "
          f"{sup.restarts} restart(s), {sup.replayed} replayed, "
          f"goodput={goodput:.0%}, results match fault-free reference, "
          f"journal exact ({rec['submitted']} submits == "
          f"{rec['terminal']} terminals) in "
          f"{time.monotonic() - t0:.1f}s")
    return 0


def _peer_barrier(args, pid: int, info: dict) -> bool:
    """Multi-host readiness barrier over ``--health-file``: write this
    process's marker, wait for every peer's."""
    _write_json_atomic(f"{args.health_file}.p{pid}",
                       {"pid": pid, "ready": True, **info})
    want = [f"{args.health_file}.p{i}" for i in range(args.num_processes)]
    deadline = time.monotonic() + args.timeout
    while time.monotonic() < deadline:
        seen = sum(1 for p in want if os.path.exists(p))
        if seen == args.num_processes:
            print(f"[daemon:{pid}] peers-ready: {seen}/"
                  f"{args.num_processes} readiness markers", flush=True)
            return True
        time.sleep(0.1)
    print(f"[daemon:{pid}] FAIL: peer readiness barrier timed out "
          f"({seen}/{args.num_processes})")
    return False


def multihost_dryrun(args) -> int:
    """Distributed init + global mesh + cross-process placement +
    lowering; executes the serve loop only on backends that support
    multiprocess computations (not CPU)."""
    jax.distributed.initialize(coordinator_address=args.coordinator,
                               num_processes=args.num_processes,
                               process_id=args.process_id)
    pid = jax.process_index()
    n_global = len(jax.devices())
    n_local = len(jax.local_devices())
    print(f"[daemon:{pid}] distributed up: {args.num_processes} processes, "
          f"{n_global} global / {n_local} local devices", flush=True)
    from ..configs.registry import ARCHS, REDUCED
    from ..dist import sharding as shd
    from ..models import get_model
    from ..serving.daemon import ServingDaemon
    from .serve import parse_mesh
    cfg = (REDUCED if args.reduced else ARCHS)[args.arch]
    model = get_model(cfg)
    params = model.init(cfg, jax.random.PRNGKey(0))
    mesh = parse_mesh(args.mesh)
    pspecs = shd.param_specs(params, mesh)
    gparams = shd.put_global(params, pspecs, mesh)
    # placement check: every leaf's sharding is exactly its spec, and
    # this process holds only shards on its own devices
    n_leaves = n_sharded = 0
    from jax.sharding import NamedSharding
    for leaf, spec in zip(jax.tree.leaves(gparams), jax.tree.leaves(
            pspecs, is_leaf=lambda x: isinstance(
                x, jax.sharding.PartitionSpec))):
        n_leaves += 1
        want = NamedSharding(mesh, spec)
        if not leaf.sharding.is_equivalent_to(want, leaf.ndim):
            print(f"[daemon:{pid}] FAIL: leaf sharding {leaf.sharding} "
                  f"!= spec {want}")
            return 1
        if any(sh.data is None for sh in leaf.addressable_shards):
            print(f"[daemon:{pid}] FAIL: unmaterialized local shard")
            return 1
        if len(leaf.addressable_shards) < leaf.sharding.num_devices:
            n_sharded += 1
    print(f"[daemon:{pid}] placement-ok: {n_leaves} leaves on-spec, "
          f"{n_sharded} with non-addressable remote shards", flush=True)
    cache = model.init_cache(cfg, args.max_batch, args.max_len)
    gcache = shd.put_global(cache, shd.cache_specs(cache, mesh,
                                                   shard_model=True), mesh)
    toks = np.zeros((args.max_batch, 8), np.int32)
    gtoks = shd.put_global(toks, shd.batch_specs(toks, mesh), mesh)

    def prefill(p, c, t):
        return model.prefill(cfg, p, c, t)

    lowered = jax.jit(prefill).lower(gparams, gcache, gtoks)
    print(f"[daemon:{pid}] lowering-ok: prefill lowered over "
          f"mesh={dict(mesh.shape)}", flush=True)
    if args.health_file:
        # cross-host readiness barrier: all peers verified placement +
        # lowering before anyone proceeds (or reports dry-run success)
        if not _peer_barrier(args, pid, {
                "leaves": n_leaves, "sharded": n_sharded,
                "mesh": dict(mesh.shape), "unix_time": time.time()}):
            return 1
    if jax.default_backend() == "cpu" and args.num_processes > 1:
        # the CPU runtime raises "Multiprocess computations aren't
        # implemented on the CPU backend" at compile time — placement
        # and lowering above are the verifiable dry-run surface
        print(f"[daemon:{pid}] dry-run complete (CPU backend has no "
              "multiprocess execution; serve loop skipped)", flush=True)
        return 0
    lowered.compile()
    eng = build_engine(args, mesh=mesh)
    with ServingDaemon(eng) as daemon:
        ok = serve_traffic(daemon, args)
    return 0 if ok else 1


def smoke(args) -> int:
    """check.sh fast path: one streamed request end to end, wall-clock,
    with a tight timeout and a clean reconciled shutdown."""
    from ..serving.daemon import ServingDaemon
    t0 = time.monotonic()
    eng = build_engine(args)
    daemon = ServingDaemon(eng).start()
    streamed = []
    req = daemon.submit(np.arange(1, 9, dtype=np.int32),
                        slo="interactive", max_new_tokens=args.max_new,
                        stream=True)
    try:
        for tok in req.handle.tokens(timeout=args.timeout):
            streamed.append(tok)
    except TimeoutError as e:
        print(f"[daemon] SMOKE FAIL: {e}")
        return 1
    daemon.shutdown(drain=True, timeout=args.timeout)
    s = eng.stats
    ok = (streamed == req.handle.result()
          and len(streamed) == args.max_new
          and s.submitted == s.resolved == 1
          and not daemon.running)
    if not ok:
        print(f"[daemon] SMOKE FAIL: streamed={streamed} "
              f"result={req.handle.result()} submitted={s.submitted} "
              f"resolved={s.resolved} running={daemon.running}")
        return 1
    print(f"[daemon] smoke ok: {len(streamed)} tokens streamed "
          f"wall-clock in {time.monotonic() - t0:.1f}s, clean shutdown")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--timeout", type=float, default=120.0,
                    help="per-wait timeout (seconds) for streaming/"
                         "results/drain")
    ap.add_argument("--no-quant", action="store_true")
    ap.add_argument("--stream", action="store_true",
                    help="print each streamed token of the first "
                         "interactive request")
    ap.add_argument("--smoke", action="store_true",
                    help="CI fast path: one streamed request, tight "
                         "timeout, reconciled shutdown")
    ap.add_argument("--recovery-smoke", action="store_true",
                    help="CI crash-recovery stage: journal-backed "
                         "supervisor under an injected crash@decode "
                         "fault; asserts restart + replay + exact "
                         "journal reconciliation")
    ap.add_argument("--health-file", default=None,
                    help="write health()/readiness JSON here: periodic "
                         "supervisor snapshots (single host) or "
                         "per-process readiness markers + peer barrier "
                         "(multi-host)")
    ap.add_argument("--mesh", default=None,
                    help="DATAxMODEL over the GLOBAL device world")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of process 0 (multi-host launch)")
    ap.add_argument("--num-processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    args = ap.parse_args()

    from ..checkout import enable_compile_cache
    enable_compile_cache()
    if args.coordinator is not None:
        sys.exit(multihost_dryrun(args))
    if args.recovery_smoke:
        sys.exit(recovery_smoke(args))
    if args.smoke:
        sys.exit(smoke(args))
    from ..serving.daemon import ServingDaemon
    from .serve import parse_mesh
    mesh = parse_mesh(args.mesh) if args.mesh else None
    if args.health_file:
        sys.exit(serve_supervised(args, mesh=mesh))
    eng = build_engine(args, mesh=mesh)
    with ServingDaemon(eng) as daemon:
        ok = serve_traffic(daemon, args)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
