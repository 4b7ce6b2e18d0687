"""jit'd dispatch wrappers for the Pallas kernels.

Handles: padding to MXU-aligned block multiples, interpret-mode fallback on
CPU (the container has no TPU; interpret=True executes the kernel body in
Python — correctness validation per the task spec), leading-batch-dim
flattening, and QTensor-level entry points mirroring core.qtensor methods.

Block sizes come from the shape-keyed autotuner (kernels.autotune): the
persistent per-backend cache is consulted FIRST (warmed offline by
``launch/autotune_sweep.py`` so serving traces are pure cache hits); on a
cache miss a real accelerator times candidates once and persists the
winner, while CPU/interpret and traces fall back to
``autotune.fallback_blocks``: :func:`int8_tile_plan`'s row slabs for
``int8_matmul``, the power-of-two heuristic for the other kernels.

The M2Q path is permutation-free end to end: the merged byte payload is in
original filter order, the fused kernel emits ONE output array, and the old
concatenate + ``jnp.take`` inverse-permutation epilogue is gone.  Activation
quantization is fused into the m2q/int8 kernel prologues, so these entry
points take FLOAT activations plus a scalar scale.

Dispatch control is LAYERED (see :class:`DispatchConfig`):

1. a scoped :func:`dispatch` context (programmatic, nestable — what tests
   and the serving engines use),
2. the per-axis FAULT TRIP LATCH (:func:`trip_axis` /
   :func:`axis_tripped`): once a :class:`FallbackGuard` catches a kernel
   raise or non-finite kernel output on an axis, that axis resolves to
   the XLA path process-wide until :func:`reset_trip_latch` — graceful
   degradation that an explicit scope (a test forcing kernels on) still
   overrides,
3. the ``REPRO_PALLAS_DISPATCH`` / ``REPRO_PALLAS_CONV_DISPATCH`` /
   ``REPRO_PALLAS_ATTN_DISPATCH`` env vars (process-wide defaults; this
   module is the ONLY place they are read),
4. the backend default (kernels on a real TPU, pure-XLA QTensor paths
   elsewhere — the interpret path is a correctness harness, not a fast
   path).

The ``attn`` axis steers the ACTIVATION-side int8 attention kernels
(``relu_attn`` for EfficientViT's MSA token mixer, ``decode_attn_int8``
for the serving engine's int8-KV decode step).  Unlike the dense/conv
axes — where the kernel computes the identical function as the XLA
QTensor path — turning ``attn`` on for the MSA path CHANGES numerics to
int8-quantization tolerance (the f32 einsums it replaces never quantized
activations), which is why it has its own switch.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import math
import os
import warnings
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import tracing
from ..core.qtensor import QAPoT, QExpertM2Q, QM2Q, QUniform
from ..core.quant import act_scale_from_stats
from . import autotune
from .apot_matmul import apot_matmul
from .decode_attn_int8 import decode_attn_int8
from .dwconv_w4 import dwconv_w4, same_padding
from .int4_matmul import int4_matmul
from .int8_matmul import int8_matmul
from .m2q_matmul import m2q_matmul
from .relu_attn import relu_attn


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


@dataclasses.dataclass(frozen=True)
class DispatchConfig:
    """Scoped kernel-dispatch switches; ``None`` inherits the next layer.

    ``dense`` steers QTensor matmuls (nn.dense and quantized 1x1 PWConvs),
    ``conv`` steers the conv paths specifically, and ``attn`` the int8
    attention kernels (MSA ReLU linear attention + int8-KV decode); the
    conv/attn axes follow ``dense`` when unset — the same split the
    ``REPRO_PALLAS_DISPATCH`` / ``REPRO_PALLAS_CONV_DISPATCH`` /
    ``REPRO_PALLAS_ATTN_DISPATCH`` env vars expose.  The env vars are the
    process-wide defaults consulted only when NO scope field applies: any
    scoped field beats the env vars, so a scope with ``dense=True`` also
    re-enables conv/attn paths over a ``...=0`` env var (pass
    ``conv=False`` / ``attn=False`` explicitly to keep an axis pinned).
    Enter a scope with :func:`dispatch` (a nestable context manager), or
    hand the config to a serving engine (``Engine``/``VisionEngine`` take
    ``dispatch=``) to pin its traces regardless of ambient state.

    NOTE: dispatch is consulted at TRACE time; a jit cache keyed only on
    shapes will serve a stale trace if the config flips between calls of
    the same function object (use fresh closures per scope, as the HLO
    tests do).
    """

    dense: Optional[bool] = None
    conv: Optional[bool] = None
    attn: Optional[bool] = None

    def layered_over(self, base: "DispatchConfig") -> "DispatchConfig":
        return DispatchConfig(
            dense=self.dense if self.dense is not None else base.dense,
            conv=self.conv if self.conv is not None else base.conv,
            attn=self.attn if self.attn is not None else base.attn)


_DISPATCH_SCOPE: contextvars.ContextVar = contextvars.ContextVar(
    "repro_dispatch_scope", default=DispatchConfig())


def active_dispatch() -> DispatchConfig:
    """The currently scoped DispatchConfig (all-None outside any scope)."""
    return _DISPATCH_SCOPE.get()


@contextlib.contextmanager
def dispatch(config: Optional[DispatchConfig] = None, *,
             dense: Optional[bool] = None, conv: Optional[bool] = None,
             attn: Optional[bool] = None):
    """Scope kernel dispatch programmatically (nestable; None inherits).

        with ops.dispatch(dense=True):          # force kernels on
            ...
            with ops.dispatch(conv=False):      # ...but XLA conv paths here
                ...

    Takes an explicit :class:`DispatchConfig`, the ``dense=`` / ``conv=`` /
    ``attn=`` fields directly, or both — explicit fields layer over the
    config.  The scope overrides the env-var process defaults; unset fields
    fall through to the enclosing scope, then the env vars, then the
    backend default.
    """
    ov = DispatchConfig(dense, conv, attn)
    if config is not None:
        ov = ov.layered_over(config)
    token = _DISPATCH_SCOPE.set(ov.layered_over(_DISPATCH_SCOPE.get()))
    try:
        yield
    finally:
        _DISPATCH_SCOPE.reset(token)


_KERNEL_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "repro_kernel_mesh", default=None)


@contextlib.contextmanager
def kernel_mesh(mesh):
    """Scope the device mesh that kernel launches traced inside it run on.

    Mosaic kernels cannot be partitioned by XLA's SPMD partitioner, so under
    a mesh every launch is wrapped in a ``shard_map``: batch dims split over
    the ``data`` axis and attention heads over ``model`` (where they divide
    evenly); everything else — weights included — is replicated, so a
    model-sharded weight is gathered before the launch.  ``None`` (no mesh)
    launches the kernel directly.  The serving engines enter this scope
    around their traces when built with ``mesh=``."""
    token = _KERNEL_MESH.set(mesh)
    try:
        yield
    finally:
        _KERNEL_MESH.reset(token)


def _on_mesh(mesh, fn, args, dims, out_dims):
    """``fn(*args)``, per device of ``mesh`` (the :func:`kernel_mesh` scope,
    read by the op wrappers — outside the jitted cores, whose trace cache
    does not see context variables — and passed down as a static arg).

    ``dims`` names, per argument, the mesh axis of each array dim (None:
    replicated); an axis is used only if it has more than one device and
    divides every dim it names.  ``out_dims`` does the same for the one
    output."""
    if mesh is None:
        return fn(*args)
    sizes = dict(mesh.shape)
    use: dict = {}
    for a, d in zip(args, dims):
        for n, ax in zip(a.shape, d):
            if ax is not None:
                use[ax] = (use.get(ax, True) and sizes.get(ax, 1) > 1
                           and n % sizes[ax] == 0)

    def spec(d):
        return jax.sharding.PartitionSpec(
            *[ax if ax is not None and use.get(ax) else None for ax in d])

    return jax.shard_map(fn, mesh=mesh, in_specs=tuple(map(spec, dims)),
                         out_specs=spec(out_dims), check_vma=False)(*args)


_ROWS = ("data", None)  # (M, K) activations: rows follow the batch


def _row_shards(mesh) -> int:
    """Row pieces a matmul's activations split into under ``mesh``: pad M
    to ``bm`` times this, so every device's rows are whole blocks."""
    return 1 if mesh is None else int(dict(mesh.shape).get("data", 1))


def _env_flag(name: str) -> Optional[bool]:
    env = os.environ.get(name)
    if env is None:
        return None
    return env.strip().lower() not in ("", "0", "false")


# ---------------------------------------------------------------------------
# fault trip latch + FallbackGuard (graceful degradation to the XLA paths)
# ---------------------------------------------------------------------------


class NumericalError(RuntimeError):
    """A compute path produced non-finite (NaN/Inf) outputs — poisoned
    quantized forward, overflowing int accumulator, or a broken kernel.
    Raised by :class:`FallbackGuard`'s finite check and by the serving
    engines' decode-logits check (re-exported as
    ``repro.serving.errors.NumericalError``)."""


_TRIP_AXES = ("dense", "conv", "attn")
_TRIP_LATCH: dict = {ax: 0 for ax in _TRIP_AXES}


def trip_axis(axis: str) -> None:
    """Latch one dispatch axis onto the XLA fallback path (process-wide
    default; an explicit :func:`dispatch` scope still wins).  Raises
    ``ValueError`` for an unknown axis."""
    if axis not in _TRIP_LATCH:
        raise ValueError(f"unknown dispatch axis {axis!r}; one of "
                         f"{_TRIP_AXES}")
    _TRIP_LATCH[axis] += 1


def axis_tripped(axis: str) -> bool:
    return _TRIP_LATCH.get(axis, 0) > 0


def trip_counts() -> dict:
    """Per-axis trip counters (how often a FallbackGuard latched each)."""
    return dict(_TRIP_LATCH)


_INT8_PLANS: dict = {"slab": 0, "tiled": 0}


def int8_plan_counts() -> dict:
    """``int8_matmul`` launches traced (or run eagerly) in this process, by
    plan: ``slab`` takes whole K and N in each row-slab grid step,
    ``tiled`` blocks N or K (see :func:`int8_tile_plan`)."""
    return dict(_INT8_PLANS)


def reset_trip_latch() -> None:
    """Clear every axis latch (tests; or an operator re-arming kernels)."""
    for ax in _TRIP_LATCH:
        _TRIP_LATCH[ax] = 0


def _finite_flags(out) -> list:
    """One device scalar per inexact-dtype array leaf: all of it finite.
    Dispatched behind ``out``, not waited on; ``bool`` of each syncs."""
    return [jnp.all(jnp.isfinite(leaf))
            for leaf in jax.tree_util.tree_leaves(out)
            if isinstance(leaf, jax.Array)
            and jnp.issubdtype(leaf.dtype, jnp.inexact)]


def _poison_tree(out):
    """NaN-fill every inexact array leaf (the fault injector's kernel-site
    poisoning: simulates a silently-corrupting kernel)."""
    return jax.tree_util.tree_map(
        lambda x: (jnp.full_like(x, jnp.nan)
                   if isinstance(x, jax.Array)
                   and jnp.issubdtype(x.dtype, jnp.inexact) else x), out)


class FallbackGuard:
    """Retry-once-on-XLA wrapper around a kernel-dispatched step.

    ``run(fn, *args)`` calls ``fn(*args, fallback=False)``; if the call
    raises, or (with ``check_finite``) returns non-finite outputs, the
    guard records the trip, latches the configured dispatch axes onto the
    XLA path (:func:`trip_axis`), and re-runs ``fn(*args, fallback=True)``
    — the step's own XLA-path trace.  ``fn`` must take a STATIC
    ``fallback`` keyword that pins the XLA path for its trace (a scoped
    ``dispatch(dense=False, conv=False, attn=False)`` inside the traced
    body): dispatch is resolved at trace time, so retrying the *same*
    jitted trace under a different ambient scope would be a no-op.

    After the first trip the guard is latched: subsequent ``run`` calls go
    straight to the fallback path (no repeated failing-kernel attempts).
    Every trip emits a ``RuntimeWarning`` carrying the exception, so a run
    that silently fell back to XLA is visible in its output.
    ``run`` is :meth:`launch` then :meth:`finish`; a caller that waits on
    the device on another thread calls the two itself.
    ``faults``: optional ``serving.faults.FaultInjector`` consulted at
    ``site`` on every primary attempt — the harness provokes kernel
    raises/NaN-poisoning deterministically to prove this guard recovers.
    ``span_prefix``: the guard's ``repro.tracing`` spans are
    ``<span_prefix>.launch`` (each call of ``fn``) and
    ``<span_prefix>.sync`` (the finite check, which waits on the device).
    """

    def __init__(self, check_finite: bool = True, faults=None,
                 site: str = "kernel",
                 axes: Tuple[str, ...] = _TRIP_AXES,
                 span_prefix: str = "kernel"):
        self.check_finite = check_finite
        self.faults = faults
        self.site = site
        self.axes = axes
        self._launch = span_prefix + ".launch"
        self._sync = span_prefix + ".sync"
        self.tripped = False
        self.trips = 0
        self.retries = 0
        self.last_error: Optional[str] = None

    def run(self, fn, *args):
        return self.finish(*self.launch(fn, *args), fn, *args)

    def launch(self, fn, *args):
        """The first half of :meth:`run`: the fault, the call of ``fn``,
        the poison, and (with ``check_finite``) the finite check's device
        work, queued right behind ``fn``'s without waiting on it.  Returns
        ``(out, flags)``; hand both to :meth:`finish`, which waits on
        ``flags`` where there are any (None: nothing left to check)."""
        if self.tripped:
            self.retries += 1
            with tracing.span(self._launch):
                return fn(*args, fallback=True), None
        act = self.faults.on_call(self.site) if self.faults is not None \
            else None
        try:
            if act is not None:
                act.fire()
            with tracing.span(self._launch):
                out = fn(*args, fallback=False)
            if act is not None and act.poison:
                out = _poison_tree(out)
            return out, (_finite_flags(out) if self.check_finite else None)
        except Exception as e:  # noqa: BLE001 — any failure degrades
            return self._retry(e, fn, args), None

    def finish(self, out, flags, fn, *args):
        """The second half of :meth:`run`: wait on the finite ``flags``
        that :meth:`launch` queued, if any; a raise or a non-finite
        output trips the guard and re-runs ``fn`` on the XLA path."""
        if flags is None:
            return out
        try:
            with tracing.span(self._sync):
                bad = not all(bool(f) for f in flags)
            if bad:
                raise NumericalError(
                    f"non-finite output from kernel-dispatched step "
                    f"(site {self.site!r}); retrying on the XLA path")
            return out
        except Exception as e:  # noqa: BLE001 — any failure degrades
            return self._retry(e, fn, args)

    def _retry(self, e: Exception, fn, args):
        self.trips += 1
        self.tripped = True
        self.last_error = repr(e)
        for ax in self.axes:
            trip_axis(ax)
        warnings.warn(
            f"FallbackGuard at site {self.site!r} tripped: "
            f"{self.last_error}; dispatch axes {self.axes} latched to "
            "the XLA path", RuntimeWarning, stacklevel=3)
        self.retries += 1
        with tracing.span(self._launch):
            return fn(*args, fallback=True)

    def stats(self) -> dict:
        return {"tripped": self.tripped, "trips": self.trips,
                "retries": self.retries, "last_error": self.last_error}

    def reset(self) -> None:
        """Re-arm this guard (does NOT clear the process-wide axis latch;
        see :func:`reset_trip_latch`)."""
        self.tripped = False
        self.last_error = None


def dispatch_enabled() -> bool:
    """Should nn.dense route QTensor matmuls through the Pallas kernels?

    Resolution order: active :func:`dispatch` scope -> the fault trip
    latch (:func:`axis_tripped`: a tripped axis degrades to XLA
    process-wide) -> the ``REPRO_PALLAS_DISPATCH=1/0`` env var (process
    default; tests force it on to exercise the wiring) -> backend default
    (only on a real TPU: the interpret path is a Python correctness
    harness, ~1000x slower than XLA on CPU — wiring it into serving would
    tank the engine).
    """
    scoped = _DISPATCH_SCOPE.get().dense
    if scoped is not None:
        return scoped
    if axis_tripped("dense"):
        return False
    env = _env_flag("REPRO_PALLAS_DISPATCH")
    if env is not None:
        return env
    return jax.default_backend() == "tpu"


def conv_dispatch_enabled() -> bool:
    """Should nn.conv2d route QTensor convolutions through the Pallas
    kernels (PWConv -> m2q/int8/int4 matmul, depthwise -> dwconv_w4)?

    Resolution order: active scope ``conv`` -> active scope ``dense`` ->
    the ``conv`` fault trip latch -> the
    ``REPRO_PALLAS_CONV_DISPATCH=1/0`` env var (conv-only process
    default) -> :func:`dispatch_enabled`.  Note the quantized 1x1 PWConv
    never falls back to a dequantized-weight f32 convolution: with dispatch
    off it still runs the pure-XLA QTensor *matmul* path (see
    nn.layers.conv2d).
    """
    scope = _DISPATCH_SCOPE.get()
    if scope.conv is not None:
        return scope.conv
    if scope.dense is not None:
        return scope.dense
    if axis_tripped("conv"):
        return False
    env = _env_flag("REPRO_PALLAS_CONV_DISPATCH")
    if env is not None:
        return env
    return dispatch_enabled()


def attn_dispatch_enabled() -> bool:
    """Should nn.attention route through the fused int8 attention kernels
    (relu_linear_attention -> relu_attn, decode_attention_int8 ->
    decode_attn_int8)?

    Resolution order: active scope ``attn`` -> active scope ``dense`` ->
    the ``attn`` fault trip latch -> the
    ``REPRO_PALLAS_ATTN_DISPATCH=1/0`` env var (attention-only process
    default) -> :func:`dispatch_enabled` — layered exactly like the conv
    axis.  NOTE the MSA path quantizes activations the f32 einsums do not:
    flipping this axis moves numerics by int8-quantization error, so
    strict-parity tests pin ``attn`` explicitly.
    """
    scope = _DISPATCH_SCOPE.get()
    if scope.attn is not None:
        return scope.attn
    if scope.dense is not None:
        return scope.dense
    if axis_tripped("attn"):
        return False
    env = _env_flag("REPRO_PALLAS_ATTN_DISPATCH")
    if env is not None:
        return env
    return dispatch_enabled()


def kernel_supported(qt) -> bool:
    """True when the fused kernel computes the SAME function as the XLA
    QTensor path for this leaf (2-D weight, identical activation handling
    — calibrated int paths quantize activations, weights-only paths do
    not), so dispatch cannot change serving numerics."""
    if isinstance(qt, (QM2Q, QExpertM2Q)):
        return qt.payload.ndim == 2 and qt.act_scale is not None
    if isinstance(qt, QUniform):
        if qt.payload.ndim != 2 or qt.axis != 1:
            return False
        return qt.bits == 4 or (qt.bits == 8 and qt.act_scale is not None)
    if isinstance(qt, QAPoT):
        return qt.codes.ndim == 2 and qt.act_scale is None
    return False


def _pad2(x, m0, m1, value=0):
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)), constant_values=value)
    return x


def _pad1(x, m, value=0):
    p = (-x.shape[0]) % m
    if p:
        x = jnp.pad(x, ((0, p),), constant_values=value)
    return x


def _tile(b: int, n: int, align: int) -> int:
    """A block size the TPU can tile along a dim of size ``n``: a multiple
    of ``align``, or one block spanning the whole (padded) dim."""
    return b if b % align == 0 or b >= n else align


def _act_scale_or_default(x, act_scale):
    """Calibrated scalar scale, or a dynamic max-abs fallback.

    The fallback is a scalar reduce (fused by XLA into the surrounding
    graph) through the same act_scale_from_stats definition the calibrated
    path uses; the int8 payload itself never materializes in HBM — rounding
    happens inside the kernel prologue.
    """
    if act_scale is not None:
        return jnp.asarray(act_scale, jnp.float32).reshape(())
    return act_scale_from_stats(jnp.max(jnp.abs(x.astype(jnp.float32))))


# ---------------------------------------------------------------------------
# jitted cores (block sizes static) + autotuned public wrappers
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret", "mesh"))
def _int8_core(x, wq, act_scale, scale, zero_point, bm, bn, bk, interpret,
               mesh=None):
    M, K = x.shape
    N = wq.shape[1]
    bn, bk = _tile(bn, N, 128), _tile(bk, K, 128)
    xp = _pad2(x, bm * _row_shards(mesh), bk)
    wp = _pad2(wq, bk, bn)
    y = _on_mesh(mesh, partial(int8_matmul, bm=bm, bn=bn, bk=bk,
                               interpret=interpret),
                 (xp, wp, act_scale, _pad1(scale, bn), _pad1(zero_point, bn)),
                 (_ROWS, (None, None), (), (None,), (None,)), _ROWS)
    return y[:M, :N]


def int8_matmul_op(x, wq, act_scale, scale, zero_point,
                   interpret: Optional[bool] = None,
                   blocks: Optional[Tuple[int, int, int]] = None):
    """x (M,K) FLOAT activations, read in their own dtype; quantization is
    fused into the kernel; y (M,N) in x's dtype.  Blocks: the autotune
    cache, else :func:`int8_tile_plan` (``autotune.fallback_blocks``)."""
    interpret = _interpret_default() if interpret is None else interpret
    mesh = _KERNEL_MESH.get()
    M, K = x.shape
    N = wq.shape[1]
    if blocks is None:
        blocks = autotune.blocks_for(
            "int8_matmul", M, N, K, interpret=interpret, operands=(x,),
            meta={"x_bytes": x.dtype.itemsize,
                  "row_shards": _row_shards(mesh)},
            bench_fn=lambda b: _int8_core(x, wq, act_scale, scale, zero_point,
                                          *b, interpret, mesh))
    whole = _tile(blocks[1], N, 128) >= N and _tile(blocks[2], K, 128) >= K
    _INT8_PLANS["slab" if whole else "tiled"] += 1
    return _int8_core(x, wq, act_scale, scale, zero_point, *blocks, interpret,
                      mesh)


@partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret", "mesh"))
def _int4_core(x, packed, scale, zero_point, bm, bn, bk, interpret,
               mesh=None):
    M, K = x.shape
    N = packed.shape[1] * 2
    # the packed block holds bn/2 lanes: bn in steps of 256
    bn, bk = _tile(bn, N, 256), _tile(bk, K, 128)
    xp = _pad2(x, bm * _row_shards(mesh), bk)
    pp = _pad2(packed, bk, bn // 2)
    y = _on_mesh(mesh, partial(int4_matmul, bm=bm, bn=bn, bk=bk,
                               interpret=interpret),
                 (xp, pp, _pad1(scale, bn), _pad1(zero_point, bn)),
                 (_ROWS, (None, None), (None,), (None,)), _ROWS)
    return y[:M, :N]


def int4_matmul_op(x, packed, scale, zero_point,
                   interpret: Optional[bool] = None,
                   blocks: Optional[Tuple[int, int, int]] = None):
    interpret = _interpret_default() if interpret is None else interpret
    mesh = _KERNEL_MESH.get()
    M, K = x.shape
    N = packed.shape[1] * 2
    if blocks is None:
        blocks = autotune.blocks_for(
            "int4_matmul", M, N, K, interpret=interpret, operands=(x,),
            bench_fn=lambda b: _int4_core(x, packed, scale, zero_point, *b,
                                          interpret, mesh))
    return _int4_core(x, packed, scale, zero_point, *blocks, interpret, mesh)


@partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret", "mesh"))
def _apot_core(x, codes, scale, bm, bn, bk, interpret, mesh=None):
    M, K = x.shape
    N = codes.shape[1]
    xp = _pad2(x, bm * _row_shards(mesh), bk)
    # pad codes with the zero-flag byte so padded weights decode to 0
    cp = _pad2(codes, bk, bn, value=0x80)
    y = _on_mesh(mesh, partial(apot_matmul, bm=bm, bn=bn, bk=bk,
                               interpret=interpret),
                 (xp, cp, _pad1(scale, bn)),
                 (_ROWS, (None, None), (None,)), _ROWS)
    return y[:M, :N]


def apot_matmul_op(x, codes, scale, interpret: Optional[bool] = None,
                   blocks: Optional[Tuple[int, int, int]] = None):
    interpret = _interpret_default() if interpret is None else interpret
    mesh = _KERNEL_MESH.get()
    M, K = x.shape
    N = codes.shape[1]
    if blocks is None:
        blocks = autotune.blocks_for(
            "apot_matmul", M, N, K, interpret=interpret, operands=(x,),
            bench_fn=lambda b: _apot_core(x, codes, scale, *b, interpret,
                                          mesh))
    return _apot_core(x, codes, scale, *blocks, interpret, mesh)


@partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret", "mesh"))
def _m2q_core(x, act_scale, payload, u_scale, u_zp, a_scale,
              bm, bn, bk, interpret, mesh=None):
    M, K = x.shape
    N = payload.shape[1]
    bn, bk = _tile(bn, N, 128), _tile(bk, K, 128)
    xp = _pad2(x.astype(jnp.float32), bm * _row_shards(mesh), bk)
    # K-pad rows of the payload multiply quantized-zero activations; N-pad
    # columns carry zero scales — both vanish, any pad byte is safe.
    pp = _pad2(payload, bk, bn)
    y = _on_mesh(mesh, partial(m2q_matmul, bm=bm, bn=bn, bk=bk,
                               interpret=interpret),
                 (xp, act_scale, pp, _pad1(u_scale, bn), _pad1(u_zp, bn),
                  _pad1(a_scale, bn)),
                 (_ROWS, (), (None, None), (None,), (None,), (None,)), _ROWS)
    return y[:M, :N]


def m2q_matmul_op(x, act_scale, payload, u_scale, u_zp, a_scale,
                  interpret: Optional[bool] = None,
                  blocks: Optional[Tuple[int, int, int]] = None):
    """Fused permutation-free M2Q matmul.

    x (M,K) FLOAT; payload (K,N) merged int8 bytes in original filter
    order; u_scale/u_zp/a_scale (N,) zero-masked. Returns y (M,N) f32 —
    both engine halves summed in the kernel epilogue, no concat/gather.
    """
    interpret = _interpret_default() if interpret is None else interpret
    mesh = _KERNEL_MESH.get()
    M, K = x.shape
    N = payload.shape[1]
    if blocks is None:
        blocks = autotune.blocks_for(
            "m2q_matmul", M, N, K, interpret=interpret, operands=(x,),
            bench_fn=lambda b: _m2q_core(x, act_scale, payload, u_scale,
                                         u_zp, a_scale, *b, interpret, mesh))
    return _m2q_core(x, act_scale, payload, u_scale, u_zp, a_scale, *blocks,
                     interpret, mesh)


@partial(jax.jit, static_argnames=("kh", "kw", "stride", "bh", "bc",
                                   "interpret", "mesh"))
def _dwconv_core(x, packed, scale, zero_point, kh, kw, stride, bh, bc,
                 interpret, mesh=None):
    C = x.shape[-1]
    pc = (-C) % bc
    if pc:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, pc)))
        packed = jnp.pad(packed, ((0, 0), (0, pc // 2)))
        scale = jnp.pad(scale, (0, pc))
        zero_point = jnp.pad(zero_point, (0, pc))
    img = ("data", None, None, None)
    y = _on_mesh(mesh, partial(dwconv_w4, kh=kh, kw=kw, stride=stride,
                               bh=bh, bc=bc, interpret=interpret),
                 (x, packed, scale, zero_point),
                 (img, (None, None), (None,), (None,)), img)
    return y[..., :C]


def _dwconv_bc(bn: int, C: int) -> int:
    """Channel block: all C channels, or a multiple of 128 lanes (the TPU
    tiling rule for a block's last dimension)."""
    bc = min(bn, C)
    if bc < C and bc % 128:
        bc = min(128, C)
    return bc


# Per-grid-block VMEM budget for the H-tiled dwconv kernel.  With H-tiling
# the footprint is bounded by the TILE, not the feature map: one halo'd
# input slab (bh_in x WI x bc f32), one output slab (bh x WO x bc f32), and
# the decoded weight tile.  8 MiB leaves headroom in a 16 MiB-class VMEM for
# double-buffered pipelining of the next slab.  int8_tile_plan counts
# int8_matmul's row slabs against the same budget.
_DWCONV_VMEM_BYTES = 8 * 1024 * 1024


def _dwconv_tile_bytes(W: int, kh: int, kw: int, stride: int,
                       bh: int, bc: int) -> int:
    """f32 VMEM bytes one (bh, bc) grid block touches at map width W, with
    the (8, 128) tile padding VMEM applies to a block's last two dims."""
    pw = same_padding(W, kw, stride)
    wi = _round_up(W + pw[0] + pw[1], 8)
    wo = _round_up(-(-W // stride), 8)
    lanes = _round_up(bc, 128)
    bh_in = (bh - 1) * stride + kh
    # input slab + output slab + packed nibbles + decoded f32 weights
    return ((bh_in * wi + bh * wo) * lanes * 4
            + _round_up(kh * kw, 8) * (_round_up(bc // 2, 128) + lanes * 4))


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def dwconv_tile_plan(H: int, W: int, kh: int, kw: int, stride: int,
                     bh: Optional[int] = None, bc: int = 128,
                     budget: int = _DWCONV_VMEM_BYTES
                     ) -> Optional[Tuple[int, int]]:
    """Fit an H-tile plan (bh output rows, bc channels) under the VMEM
    budget, shrinking the requested blocks (rows first — channel tiles keep
    lane utilization; channels only down to one 128-lane tile) until one
    block fits.  Returns None only when even the minimal (1, 128) tile
    exceeds the budget — i.e. the tiler genuinely cannot block the map,
    not merely that the whole map is large."""
    ho = -(-H // stride)
    bh = ho if bh is None else max(1, min(int(bh), ho))
    while bh > 1 and _dwconv_tile_bytes(W, kh, kw, stride, bh, bc) > budget:
        bh = max(1, bh // 2)
    while bc > 128 and _dwconv_tile_bytes(W, kh, kw, stride, bh, bc) > budget:
        bc = max(128, bc // 256 * 128)
    if _dwconv_tile_bytes(W, kh, kw, stride, bh, bc) > budget:
        return None
    return bh, bc


def _int8_tile_bytes(bm: int, bn: int, bk: int, x_bytes: int) -> int:
    """VMEM bytes one int8_matmul grid step holds: the x, weight, scale,
    zero-point and output (x's dtype) blocks double-buffered, the int32
    accumulator and row sums, each padded to the (8, 128) tile of 32-bit
    data ((16, 128) at 16 bits, (32, 128) at 8)."""
    def tile(rows: int, cols: int, nbytes: int) -> int:
        return _round_up(rows, 32 // nbytes) * _round_up(cols, 128) * nbytes

    return (2 * (tile(bm, bk, x_bytes) + tile(bk, bn, 1) + tile(1, 1, 4)
                 + 2 * tile(1, bn, 4) + tile(bm, bn, x_bytes))
            + tile(bm, bn, 4) + tile(bm, 1, 4))


def _lane_blocks(n: int) -> list:
    """Blocks along a lane dim of size ``n``, largest first: all of it,
    then the multiples of 128 that tile ``n`` rounded up to 128."""
    t = -(-n // 128)
    return [n] + [128 * d for d in range(t - 1, 0, -1) if t % d == 0]


def int8_tile_plan(M: int, K: int, N: int, x_bytes: int,
                   budget: int = _DWCONV_VMEM_BYTES
                   ) -> Tuple[int, int, int]:
    """int8_matmul's blocks ``(bm, bn, bk)`` for an (M, K) x (K, N) launch
    whose x and y take ``x_bytes`` an element.

    Whole K and N (one weight block, fetched once) unless even the smallest
    row block beside them overflows ``budget``; then N, and after it K,
    take the largest multiple of 128 that fits and tiles them.  Rows: all
    of M if it fits, else the largest block in multiples of the x
    sublane tile that fits and divides M in at most four times the fewest
    steps; where none does, the fewest steps, with M padded up to them."""
    sub = 32 // x_bytes

    def nbytes(bm: int) -> int:
        return _int8_tile_bytes(bm, bn, bk, x_bytes)

    bk = K
    for bn in _lane_blocks(N):
        if nbytes(min(M, sub)) <= budget:
            break
    for bk in _lane_blocks(K):
        if nbytes(min(M, sub)) <= budget:
            break
    if nbytes(M) <= budget:
        return M, bn, bk
    # bytes grow by the same amount per ``sub`` rows
    per_sub = nbytes(2 * sub) - nbytes(sub)
    rows = sub * (1 + max(0, budget - nbytes(sub)) // per_sub)
    least = -(-M // rows)
    for steps in range(least, 4 * least + 1):
        if M % steps == 0 and (M // steps) % sub == 0:
            return M // steps, bn, bk
    return _round_up(-(-M // least), sub), bn, bk


def dwconv_w4_op(x, packed, scale, zero_point, kh: int = 3, kw: int = 3,
                 stride: int = 1, interpret: Optional[bool] = None,
                 blocks: Optional[Tuple[int, int, int]] = None):
    """x (B,H,W,C) float; packed (kh*kw, C/2) nibbles; SAME padding.

    The autotuner picks the (bh, bc) H-tile: candidate triples map bm -> bh
    (output rows per tile) and bn -> bc (channels per tile), each fitted
    under the VMEM budget by :func:`dwconv_tile_plan` before launch.
    """
    interpret = _interpret_default() if interpret is None else interpret
    mesh = _KERNEL_MESH.get()
    B, H, W, C = x.shape
    HO, WO = -(-H // stride), -(-W // stride)
    taps = kh * kw

    def _fit(b) -> Tuple[int, int]:
        plan = dwconv_tile_plan(H, W, kh, kw, stride,
                                bh=min(int(b[0]), HO),
                                bc=_dwconv_bc(int(b[1]), C))
        return plan or (1, _dwconv_bc(128, C))

    if blocks is None:
        # candidates are benched with the SAME fitted (bh, bc) that would
        # execute, so dedupe triples by their effective plan
        seen, cands = set(), []
        for c in autotune.candidate_blocks(HO, C, taps):
            p = _fit(c)
            if p not in seen:
                seen.add(p)
                cands.append(c)
        blocks = autotune.blocks_for(
            "dwconv_w4", B * HO * WO, C, taps,
            interpret=interpret, candidates=cands, operands=(x,),
            meta={"B": B, "H": H, "W": W, "C": C, "kh": kh, "kw": kw,
                  "stride": stride},
            bench_fn=lambda b: _dwconv_core(x, packed, scale, zero_point,
                                            kh, kw, stride, *_fit(b),
                                            interpret, mesh))
    bh, bc = _fit(blocks)
    return _dwconv_core(x, packed, scale, zero_point, kh, kw, stride, bh, bc,
                        interpret, mesh)


# ---------------------------------------------------------------------------
# fused int8 attention (MSA ReLU linear attention + int8-KV decode)
# ---------------------------------------------------------------------------


def _pad_axis(x, axis: int, mult: int):
    p = (-x.shape[axis]) % mult
    if p:
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, p)
        x = jnp.pad(x, pad)
    return x


@partial(jax.jit, static_argnames=("bn", "eps", "interpret", "mesh"))
def _relu_attn_core(q, k, v, bn, eps, interpret, mesh=None):
    B, N, H, D = q.shape
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # layer-wise max-abs act scales, computed on the post-ReLU range for
    # q/k (scalar reduces fused into the graph; the int8 payloads only
    # ever exist inside the kernel prologue — the PR 1 convention)
    sq = act_scale_from_stats(jnp.maximum(jnp.max(qf), 0.0))
    sk = act_scale_from_stats(jnp.maximum(jnp.max(kf), 0.0))
    sv = act_scale_from_stats(jnp.max(jnp.abs(vf)))
    heads = ("data", None, "model", None)
    y = _on_mesh(mesh, partial(relu_attn, bn=bn, eps=eps, interpret=interpret),
                 (_pad_axis(qf, 1, bn), _pad_axis(kf, 1, bn),
                  _pad_axis(vf, 1, bn), sq, sk, sv),
                 (heads, heads, heads, (), (), ()), heads)
    return y[:, :N]


def relu_attn_op(q, k, v, eps: float = 1e-6,
                 interpret: Optional[bool] = None,
                 blocks: Optional[Tuple[int, int, int]] = None):
    """Fused int8 ReLU linear attention; q/k/v (B,N,H,D) float.

    Padded k rows quantize to exact zeros (ReLU(0) -> 0) so padding never
    changes the unpadded outputs; padded q rows are sliced away.
    """
    interpret = _interpret_default() if interpret is None else interpret
    mesh = _KERNEL_MESH.get()
    B, N, H, D = q.shape
    if blocks is None:
        # only the q-row block matters (k/v/kv stay whole per (b, h));
        # dedupe candidate triples by it, mirroring dwconv_w4_op
        seen, cands = set(), []
        for c in autotune.candidate_blocks(N, D, B * H):
            if c[0] not in seen:
                seen.add(c[0])
                cands.append(c)
        blocks = autotune.blocks_for(
            "relu_attn", N, D, B * H, interpret=interpret, candidates=cands,
            operands=(q,),
            meta={"B": B, "N": N, "H": H, "D": D},
            bench_fn=lambda b: _relu_attn_core(q, k, v, b[0], eps, interpret,
                                               mesh))
    return _relu_attn_core(q, k, v, blocks[0], eps, interpret, mesh)


def decode_attn_int8_op(q, k_q, v_q, k_scale, v_scale, lengths,
                        window: Optional[int] = None,
                        scale: Optional[float] = None,
                        interpret: Optional[bool] = None):
    """Pallas twin of nn.attention.decode_attention_int8 (same shapes, same
    quantization definitions): q (B,1,Hq,D) float, int8 cache rows + per-row
    scales, lengths (B,).  Runs per (batch, kv-head) in one VMEM pass."""
    interpret = _interpret_default() if interpret is None else interpret
    mesh = _KERNEL_MESH.get()
    B, _, Hq, D = q.shape
    Hkv = k_q.shape[2]
    G = Hq // Hkv
    # no block parameters to tune, but the offline sweep still wants the
    # shape listed (coverage accounting + bench rows)
    autotune.note_shape("decode_attn_int8", B, Hq, D,
                        meta={"Hkv": Hkv, "T": k_q.shape[1],
                              "window": window or 0})
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    qh = q.reshape(B, Hkv, G, D).astype(jnp.float32)
    cache = ("data", None, "model", None)
    out = _on_mesh(
        mesh, partial(decode_attn_int8, scale=float(scale), window=window,
                      interpret=interpret),
        (qh, k_q, v_q, k_scale, v_scale,
         jnp.asarray(lengths, jnp.int32).reshape(B, 1)),
        (("data", "model", None, None), cache, cache,
         ("data", None, "model"), ("data", None, "model"), ("data", None)),
        ("data", "model", None, None))
    return out.reshape(B, 1, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# QTensor-level entry points (kernel-backed twins of core.qtensor methods)
# ---------------------------------------------------------------------------


def qtensor_matmul(x: jax.Array, qt, interpret: Optional[bool] = None):
    """Kernel-backed y = x @ W for 2-D QTensor leaves; x (..., K)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if not (isinstance(qt, QUniform) and qt.bits == 8):
        x2 = x2.astype(jnp.float32)  # int8_matmul reads x's own dtype
    if isinstance(qt, (QM2Q, QExpertM2Q)):
        sa = _act_scale_or_default(x2, qt.act_scale)
        y = m2q_matmul_op(x2, sa, qt.payload, qt.u_scale.reshape(-1),
                          qt.u_zp.reshape(-1), qt.a_scale.reshape(-1),
                          interpret=interpret)
    elif isinstance(qt, QUniform) and qt.bits == 8:
        sa = _act_scale_or_default(x2, qt.act_scale)
        y = int8_matmul_op(x2, qt.payload, sa, qt.scale.reshape(-1),
                           qt.zero_point.reshape(-1), interpret=interpret)
    elif isinstance(qt, QUniform) and qt.bits == 4:
        y = int4_matmul_op(x2, qt.payload,
                           qt.scale.reshape(-1), qt.zero_point.reshape(-1),
                           interpret=interpret)
    elif isinstance(qt, QAPoT):
        y = apot_matmul_op(x2, qt.codes, qt.scale.reshape(-1),
                           interpret=interpret)
    else:
        raise TypeError(type(qt))
    return y.reshape(*lead, y.shape[-1]).astype(x.dtype)


def dwconv_kernel_supported(qt, x, stride: int, groups: int,
                            padding: str) -> bool:
    """True when the packed-w4 depthwise kernel computes the same function
    as the dequantized-weight XLA conv for this leaf: a weights-only 4-bit
    QUniform whose HWIO shape is depthwise (cin-per-group == 1), flattened
    to a (kh*kw, C/2) payload by core.apply, under SAME padding — and
    :func:`dwconv_tile_plan` can fit an H-tile under the VMEM budget.  With
    the H-tiled grid the per-block footprint is bounded by the tile, not
    the feature map, so the plan only fails for maps so wide that even a
    single-row two-channel tile overflows VMEM — arbitrary-resolution maps
    (R256/R384/R512, detection sizes) all stay on the kernel."""
    if not isinstance(qt, QUniform) or qt.bits != 4 or qt.act_scale is not None:
        return False
    # axis must be the flattened payload's column (channel) axis, else the
    # (C,)-shaped scale/zp reshape feeds the kernel a per-row layout
    if qt.payload.ndim != 2 or qt.axis != 1:
        return False
    if len(qt.shape) != 4 or qt.shape[2] != 1:
        return False
    kh, kw, _, c = qt.shape
    if dwconv_tile_plan(x.shape[1], x.shape[2], kh, kw, max(stride, 1)) \
            is None:
        return False
    return (padding == "SAME" and stride >= 1 and groups == c
            and x.shape[-1] == c and qt.payload.shape[0] == kh * kw)


def qtensor_dwconv(x: jax.Array, qt, stride: int = 1,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Kernel-backed depthwise conv for a 4-bit QUniform conv leaf (payload
    (kh*kw, C/2) packed nibbles, shape aux = the original HWIO filter)."""
    kh, kw = int(qt.shape[0]), int(qt.shape[1])
    y = dwconv_w4_op(x.astype(jnp.float32), qt.payload,
                     qt.scale.reshape(-1), qt.zero_point.reshape(-1),
                     kh=kh, kw=kw, stride=stride, interpret=interpret)
    return y.astype(x.dtype)
