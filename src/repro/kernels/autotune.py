"""Shape-keyed block-size autotuner for the Pallas kernels.

Replaces the fixed power-of-two ``_block()`` heuristic in ops.py: each
(kernel, M, N, K) shape gets its block triple from a persistent JSON cache,
populated either lazily (timing candidate triples at first launch on a real
accelerator backend) or — the serving posture — OFFLINE by
``repro.launch.autotune_sweep``, which enumerates a deployment's shape set
and warms the cache before the first request ever traces (first-request
compile+tune latency is a real p99 tail at serving scale).

Cache keys are salted with the KERNEL VERSION and the BACKEND:

    <kernel>@v<version>:<M>x<N>x<K>:<backend>

so a committed cache from one backend can never serve block choices on
another, and a kernel rewrite (bump :data:`KERNEL_VERSIONS`) orphans every
stale entry instead of silently reusing blocks tuned for the old grid.  The
default cache file is per-backend too, inside the checkout
(``results/autotune/<backend>.json`` — the file the offline sweep writes and
the repository commits); ``REPRO_AUTOTUNE_CACHE`` overrides the path
wholesale.  Lookup is
CACHE-FIRST on every backend — a warmed cache serves its block choice even
where tuning itself is disabled — and every candidate actually timed bumps
:func:`tuning_probe_count`, so tests can assert a warmed trace performs
ZERO probes.

Interpret-safe fallback: on CPU / interpret mode (the container has no TPU)
timing the Python interpreter is meaningless, so on a cache miss the
fallback triple (:func:`fallback_blocks`) is returned immediately and
nothing is benchmarked or persisted.  Writes are atomic (tmp + rename) so
concurrent processes never observe a torn file.

A corrupt cache file NEVER takes the process down: truncated JSON, a
non-dict top level, entries that are not three ints, or keys that do not
parse as salted cache keys (foreign/legacy formats) are dropped with a
``RuntimeWarning`` and the cache rebuilds from scratch — a bad cache is a
performance bug, not a correctness one, so crashing over it is the wrong
trade.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import fcntl
import json
import os
import re
import threading
import time
import warnings
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax

from ..checkout import ROOT

Blocks = Tuple[int, int, int]

_LOCK = threading.Lock()
_CACHES: Dict[str, "AutotuneCache"] = {}

# bump a kernel's version when its grid/blocking semantics change: stale
# entries (tuned for the old grid) then miss instead of mis-steering the
# rewritten kernel.  dwconv_w4 is v2: the H-tiled (B, H-tiles, C-blocks)
# grid replaced the whole-map (B, C-blocks) grid in PR 9.
# int8_matmul is v2: row slabs of whole K and N (ops.int8_tile_plan)
# replaced 128-wide blocks, and the kernel reads and writes the
# activation dtype.
KERNEL_VERSIONS: Dict[str, int] = {
    "m2q_matmul": 1,
    "int8_matmul": 2,
    "int4_matmul": 1,
    "apot_matmul": 1,
    "dwconv_w4": 2,
    "relu_attn": 1,
    "decode_attn_int8": 1,
}

# <kernel>@v<version>:<M>x<N>x<K>:<backend>
_KEY_RE = re.compile(r"^[A-Za-z0-9_.-]+@v\d+:\d+x\d+x\d+:[A-Za-z0-9_]+$")


def cache_key(kernel: str, M: int, N: int, K: int,
              backend: Optional[str] = None) -> str:
    """The salted persistent-cache key for one kernel launch shape."""
    v = KERNEL_VERSIONS.get(kernel, 1)
    b = backend or jax.default_backend()
    return f"{kernel}@v{v}:{M}x{N}x{K}:{b}"


def committed_cache_path(backend: Optional[str] = None) -> str:
    """``<checkout>/results/autotune/<backend>.json``: the per-backend cache
    the offline sweep writes and the repository commits."""
    b = backend or jax.default_backend()
    return str(ROOT / "results" / "autotune" / f"{b}.json")


def default_cache_path(backend: Optional[str] = None) -> str:
    """``REPRO_AUTOTUNE_CACHE`` if set, else :func:`committed_cache_path`."""
    return os.environ.get("REPRO_AUTOTUNE_CACHE") or \
        committed_cache_path(backend)


def heuristic_block(m: int, cap: int = 128) -> int:
    """Largest power-of-two block <= cap that keeps tiny shapes legal."""
    b = 8
    while b * 2 <= min(m, cap):
        b *= 2
    return b


def heuristic_blocks(M: int, N: int, K: int, cap: int = 128) -> Blocks:
    return (heuristic_block(M, cap), heuristic_block(N, cap),
            heuristic_block(K, cap))


def fallback_blocks(kernel: str, M: int, N: int, K: int,
                    meta: Optional[dict] = None) -> Blocks:
    """The triple a launch runs where nothing is cached or timed.

    ``int8_matmul``: the VMEM row-slab plan (``ops.int8_tile_plan``) over
    the rows each device holds, in the activation dtype (``meta`` keys
    ``row_shards`` and ``x_bytes``, default one device and f32).  Every
    other kernel: :func:`heuristic_blocks`."""
    if kernel != "int8_matmul":
        return heuristic_blocks(M, N, K)
    from .ops import int8_tile_plan  # ops imports this module
    m = dict(meta or {})
    return int8_tile_plan(-(-M // m.get("row_shards", 1)), K, N,
                          m.get("x_bytes", 4))


def candidate_blocks(M: int, N: int, K: int) -> List[Blocks]:
    """Distinct legal triples around the heuristic: the heuristic itself,
    plus smaller-M (better pipelining at small batch) and 256-wide variants
    (fewer grid steps on large shapes)."""
    base = heuristic_blocks(M, N, K)
    cands = {base}
    for bm in {8, base[0] // 2 or 8, base[0], min(256, max(8, M))}:
        for bn in {base[1], min(256, base[1] * 2)}:
            for bk in {base[2], min(256, base[2] * 2)}:
                c = (heuristic_block(M, max(bm, 8)),
                     heuristic_block(N, max(bn, 8)),
                     heuristic_block(K, max(bk, 8)))
                cands.add(c)
    return sorted(cands)


# ---------------------------------------------------------------------------
# shape-request recording (the offline sweep's discovery hook) + probe count
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShapeRequest:
    """One block-choice request seen by :func:`blocks_for` (or noted by a
    kernel without block parameters, ``tunable=False``).  ``meta`` carries
    enough operand geometry for the offline sweep to reconstruct a real
    launch of the same shape (synthetic-operand tuning on an accelerator)."""

    kernel: str
    M: int
    N: int
    K: int
    tunable: bool = True
    meta: Tuple[Tuple[str, int], ...] = ()

    def key(self, backend: Optional[str] = None) -> str:
        return cache_key(self.kernel, self.M, self.N, self.K, backend)


_RECORDERS: contextvars.ContextVar = contextvars.ContextVar(
    "repro_autotune_recorders", default=())


@contextlib.contextmanager
def record_requests(dest: Optional[List[ShapeRequest]] = None):
    """Collect every ShapeRequest seen inside the scope (nestable; requests
    also reach enclosing recorders).  Works under jit tracing — lowering a
    model is exactly how the offline sweep discovers a deployment's shape
    set without running it."""
    sink: List[ShapeRequest] = [] if dest is None else dest
    token = _RECORDERS.set(_RECORDERS.get() + (sink,))
    try:
        yield sink
    finally:
        _RECORDERS.reset(token)


def _record(kernel: str, M: int, N: int, K: int, tunable: bool = True,
            meta: Optional[dict] = None) -> None:
    sinks = _RECORDERS.get()
    if not sinks:
        return
    req = ShapeRequest(kernel, int(M), int(N), int(K), tunable,
                       tuple(sorted((str(k), int(v))
                                    for k, v in (meta or {}).items())))
    for sink in sinks:
        sink.append(req)


def note_shape(kernel: str, M: int, N: int, K: int,
               meta: Optional[dict] = None) -> None:
    """Record a shape for a kernel WITHOUT block parameters (decode_attn):
    the sweep lists it for coverage/bench rows but never caches blocks."""
    _record(kernel, M, N, K, tunable=False, meta=meta)


_PROBES = 0


def tuning_probe_count() -> int:
    """How many candidate timings have run in this process — the sweep's
    zero-probes-at-serving-time assertion reads this."""
    return _PROBES


def reset_probe_count() -> None:
    global _PROBES
    _PROBES = 0


# ---------------------------------------------------------------------------
# persistent cache
# ---------------------------------------------------------------------------


def _valid_entry(v) -> bool:
    """A cache entry must be exactly three positive ints (a block triple);
    anything else — strings, floats, wrong arity — is corruption."""
    return (isinstance(v, (list, tuple)) and len(v) == 3
            and all(isinstance(x, int) and not isinstance(x, bool) and x > 0
                    for x in v))


def _read_cache_file(path: str) -> Dict[str, list]:
    """Read + sanitize one cache file.  NEVER raises on corruption:
    unreadable/truncated JSON, a non-dict top level, invalid entries, or
    keys that do not parse as ``kernel@vN:MxNxK:backend`` (legacy unsalted
    caches, foreign junk) produce a ``RuntimeWarning`` naming the file and
    the salvageable subset (usually empty -> the cache rebuilds)."""
    try:
        with open(path) as f:
            raw = json.load(f)
    except OSError:
        return {}  # no cache yet: the normal first-run case, no warning
    except ValueError as e:
        warnings.warn(
            f"autotune cache {path!r} is not valid JSON ({e}); ignoring it "
            "and rebuilding from scratch", RuntimeWarning, stacklevel=3)
        return {}
    if not isinstance(raw, dict):
        warnings.warn(
            f"autotune cache {path!r} top level is {type(raw).__name__}, "
            "expected a JSON object; ignoring it and rebuilding from "
            "scratch", RuntimeWarning, stacklevel=3)
        return {}
    data = {k: list(v) for k, v in raw.items()
            if isinstance(k, str) and _KEY_RE.match(k) and _valid_entry(v)}
    if len(data) != len(raw):
        warnings.warn(
            f"autotune cache {path!r}: dropped {len(raw) - len(data)} "
            "corrupt entries (each key must be kernel@vN:MxNxK:backend and "
            "each value three positive ints); keeping "
            f"the {len(data)} valid ones", RuntimeWarning, stacklevel=3)
    return data


class AutotuneCache:
    """JSON-backed {key: [bm, bn, bk]} map with atomic persistence.

    Corruption-tolerant: see :func:`_read_cache_file` — a damaged file
    warns and rebuilds instead of raising into kernel launches."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_cache_path()
        self._data: Dict[str, list] = {}
        self._loaded = False

    def load(self) -> "AutotuneCache":
        self._loaded = True
        self._data = _read_cache_file(self.path)
        return self

    def get(self, key: str) -> Optional[Blocks]:
        if not self._loaded:
            self.load()
        v = self._data.get(key)
        return tuple(int(x) for x in v) if v else None

    def put(self, key: str, blocks: Blocks, save: bool = True) -> None:
        if not self._loaded:
            self.load()
        self._data[key] = [int(b) for b in blocks]
        if save:
            self.save()

    def keys(self) -> List[str]:
        if not self._loaded:
            self.load()
        return sorted(self._data)

    def save(self) -> None:
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        # merge-on-write under an exclusive file lock: concurrent tuner
        # processes (and threads) each hold a partial in-memory view, and
        # the read-merge-replace must be atomic as a unit or a slower
        # writer drops the faster one's entries
        with _LOCK, open(f"{self.path}.lock", "w") as lf:
            try:
                fcntl.flock(lf, fcntl.LOCK_EX)
            except OSError:
                pass  # exotic filesystems: fall back to atomic replace only
            merged = _read_cache_file(self.path)
            merged.update(self._data)
            self._data = merged
            tmp = f"{self.path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(self._data, f, indent=0, sort_keys=True)
            os.replace(tmp, self.path)

    def __len__(self) -> int:
        if not self._loaded:
            self.load()
        return len(self._data)


def _shared_cache(path: Optional[str]) -> AutotuneCache:
    p = path or default_cache_path()
    with _LOCK:
        if p not in _CACHES:
            _CACHES[p] = AutotuneCache(p)
        return _CACHES[p]


def shared_cache(path: Optional[str] = None) -> AutotuneCache:
    """The process-wide cache object for ``path`` (the one kernel launches
    consult) — the offline sweep warms THIS instance so a sweep and a serve
    in the same process see one view."""
    return _shared_cache(path)


def measure(fn: Callable, *args, reps: int = 3) -> float:
    """Warmup + best-of-N wall-clock of ``fn(*args)``; the one timing
    harness shared by the tuner and benchmarks/kernel_bench."""
    fn(*args)  # warmup / compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _time_candidate(bench_fn: Callable[[Blocks], object], blocks: Blocks,
                    reps: int = 3) -> float:
    global _PROBES
    _PROBES += 1
    try:
        return measure(bench_fn, blocks, reps=reps)
    except Exception:
        return float("inf")


def blocks_for(kernel: str, M: int, N: int, K: int, *,
               interpret: bool = False,
               bench_fn: Optional[Callable[[Blocks], object]] = None,
               cache_path: Optional[str] = None,
               candidates: Optional[Sequence[Blocks]] = None,
               force_tune: bool = False,
               meta: Optional[dict] = None,
               operands: Sequence = ()) -> Blocks:
    """Resolve the block triple for one kernel launch.

    Lookup order: persistent cache (warmed offline by the sweep, or by a
    previous lazy tune on this backend) -> live tuning ->
    :func:`fallback_blocks`, which live tuning also times.  The
    cache is consulted FIRST on every backend — a committed cache serves
    its block choices even where tuning is disabled.  Tuning only happens
    on a real accelerator backend (or when ``force_tune`` is set, for
    tests) AND when a ``bench_fn`` is provided; every other case falls
    back to :func:`fallback_blocks` so the interpret path stays cheap and
    deterministic.  Every call is visible to :func:`record_requests` (the
    offline sweep's shape discovery), including calls made while tracing.
    ``operands``: the launch's array arguments; if any is a tracer the call
    is under a jit/vmap trace and nothing is timed or persisted.
    """
    _record(kernel, M, N, K, tunable=True, meta=meta)
    fallback = fallback_blocks(kernel, M, N, K, meta)
    key = cache_key(kernel, M, N, K)
    cache = _shared_cache(cache_path)
    if any(isinstance(a, jax.core.Tracer)
           for a in jax.tree_util.tree_leaves(operands)):
        # inside a jit/vmap trace the bench closure holds tracers:
        # "timing" it measures Python tracing, not the kernel.  Use the
        # cache if warm, else the fallback — and never persist from here.
        return cache.get(key) or fallback
    hit = cache.get(key)
    if hit is not None and not force_tune:
        return hit
    tunable = force_tune or (not interpret
                             and jax.default_backend() != "cpu")
    if not tunable or bench_fn is None:
        return fallback
    cands = list(candidates) if candidates else \
        sorted(set(candidate_blocks(M, N, K)) | {fallback})
    timed = [(_time_candidate(bench_fn, c), c) for c in cands]
    timed.sort(key=lambda t: (t[0], t[1]))
    if not timed or timed[0][0] == float("inf"):
        return fallback  # nothing ran: do not poison the persistent cache
    best = timed[0][1]
    cache.put(key, best)
    return best
