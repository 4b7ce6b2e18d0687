"""The vision engine under a serving daemon: a flush returns once its
batch is launched and the daemon's completion thread (``repro-complete``)
completes batches in launch order, with at most two in flight.  The rows
are those a direct ``classify`` gives, bit for bit; faults stay contained
to their batch or row; shutdown resolves every handle."""
import contextlib
import threading
import time

import jax
import numpy as np
import pytest

from repro import tracing
from repro.configs.registry import REDUCED
from repro.kernels import ops
from repro.models import get_model
from repro.serving.daemon import ServingDaemon
from repro.serving.errors import NumericalError
from repro.serving.faults import FaultInjector
from repro.serving.scheduler import CANCELLED, DONE, FAILED
from repro.serving.slo import SLOClass
from repro.serving.vision import Completions, VisionEngine

B = 4                                      # max_batch
BULK = SLOClass(name="bulk", priority=0, max_delay_ms=1000.0)
WAIT = 120                                 # seconds, a hang's bound


@pytest.fixture(scope="module")
def b1():
    cfg = REDUCED["efficientvit-b1-r224"]
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _images(cfg, n, seed=0):
    return np.random.default_rng(seed).normal(
        0, 1, (n, cfg.img_res, cfg.img_res, 3)).astype(np.float32)


def _engine(b1, **kw):
    cfg, params = b1
    return VisionEngine(cfg, params, max_batch=B, **kw)


def _hold():
    """An event, and a done-callback that keeps the completion thread in
    its delivery until the event is set: that batch stays in flight."""
    go = threading.Event()
    return go, lambda h: go.wait(WAIT)


def _settle(handles):
    """Wait until every handle has ended, however it ended."""
    for h in handles:
        with contextlib.suppress(Exception):
            h.result(timeout=WAIT)


def _closed_loop(daemon, images, outstanding):
    """Submit ``images`` in order, never more than ``outstanding``
    unanswered; the first delivery is held until two batches were
    submitted, so the second is launched behind the first."""
    cond = threading.Condition()
    done = [0]

    def mark(_h):
        with cond:
            done[0] += 1
            cond.notify_all()

    go, hold = _hold()
    handles = []
    for k, im in enumerate(images):
        with cond:
            assert cond.wait_for(
                lambda: len(handles) - done[0] < outstanding, WAIT)
        h = daemon.submit(im, slo="bulk")
        if k == 0:
            h.add_done_callback(hold)
        h.add_done_callback(mark)
        handles.append(h)
        if k == 2 * B - 1:
            go.set()
    for h in handles:
        h.result(timeout=WAIT)
    return handles


def test_a_closed_loop_overlaps_and_serves_the_rows_of_a_direct_classify(
        b1):
    cfg, _ = b1
    images = _images(cfg, 8 * B)
    eng = _engine(b1)
    want = eng.classify(images)
    eng.stats.reset()
    with ServingDaemon(eng, classes=(BULK,)) as daemon:
        handles = _closed_loop(daemon, images, 2 * B)
        comp = daemon._completions
    s = eng.stats
    assert s.batches == 8 and s.completed == 8 * B
    assert 0 < s.overlapped_batches < s.batches
    assert s.summary()["overlapped_batches"] == s.overlapped_batches
    assert comp.max_depth == Completions.DEPTH
    got = np.stack([h.result() for h in handles])
    assert np.array_equal(got, want)       # bit for bit, in request order


def test_a_third_batch_waits_until_the_oldest_is_completed(b1):
    cfg, _ = b1
    images = _images(cfg, 3 * B, seed=1)
    eng = _engine(b1)
    with ServingDaemon(eng, classes=(BULK,)) as daemon:
        comp = daemon._completions
        go, hold = _hold()
        handles = [daemon.submit(images[0], slo="bulk")]
        handles[0].add_done_callback(hold)
        handles += [daemon.submit(im, slo="bulk") for im in images[1:2 * B]]
        third = threading.Thread(target=lambda: handles.extend(
            daemon.submit(im, slo="bulk") for im in images[2 * B:]))
        third.start()
        until = time.monotonic() + WAIT
        while eng.scheduler.pending and time.monotonic() < until:
            time.sleep(0.01)
        time.sleep(0.2)   # room for a third batch to be (wrongly) handed
        # the first batch is held in its delivery, the second launched
        # behind it; the third was popped, and its flush waits for room
        assert eng.scheduler.pending == 0
        assert comp.max_depth == 2 and len(comp._queue) == 2
        assert eng.stats.batches == 1
        go.set()
        third.join(timeout=WAIT)
        assert not third.is_alive()
        for h in handles:
            h.result(timeout=WAIT)
    assert comp.max_depth == 2
    assert eng.stats.completed == 3 * B and eng.stats.overlapped_batches >= 1


def test_a_batch_is_launched_on_the_flushing_thread_and_completed_on_its_own(
        b1):
    cfg, _ = b1
    eng = _engine(b1)
    with tracing.recording() as rec:
        with ServingDaemon(eng, classes=(BULK,)) as daemon:
            _closed_loop(daemon, _images(cfg, 4 * B, seed=2), 2 * B)
    me = threading.current_thread().name

    def inside(outer):
        _, s, e, th = outer
        return [ev[0] for ev in sorted(rec.events, key=lambda ev: ev[1])
                if ev is not outer and ev[3] == th and ev[1] >= s
                and ev[2] <= e]

    batches = [ev for ev in rec.events if ev[0] == "vision.batch"]
    completes = [ev for ev in rec.events if ev[0] == "vision.complete"]
    assert len(batches) == len(completes) == 4
    # flushed by the submit that filled it, or by the serve loop where
    # that was awake first
    assert {ev[3] for ev in batches} <= {me, "repro-serve"}
    assert {ev[3] for ev in completes} == {"repro-complete"}
    launched_here = 0
    for ev in batches:
        phases = inside(ev)
        assert phases in (["vision.assemble", "vision.put"],
                          ["vision.assemble", "vision.put", "vision.launch"])
        launched_here += phases[-1] == "vision.launch"
    assert launched_here == eng.stats.overlapped_batches >= 1
    for ev in completes:
        phases = inside(ev)
        if phases[0] == "vision.launch":   # nothing was in flight before it
            phases = phases[1:]
        assert phases == ["vision.sync", "vision.fetch", "vision.deliver"]
    assert sum(inside(ev)[0] == "vision.launch" for ev in completes) \
        == 4 - launched_here


def test_shutdown_with_drain_delivers_everything_in_flight(b1):
    cfg, _ = b1
    images = _images(cfg, 3 * B + 2, seed=3)
    eng = _engine(b1)
    want = eng.classify(images)
    eng.stats.reset()
    daemon = ServingDaemon(eng, classes=(BULK,)).start()
    handles = [daemon.submit(im, slo="bulk") for im in images]
    daemon.shutdown(drain=True)
    assert all(h.state == DONE for h in handles)
    got = np.stack([h.result() for h in handles])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    s = eng.stats
    assert s.submitted == s.resolved == s.completed == len(images)
    assert daemon.outstanding == 0


def test_shutdown_without_drain_resolves_every_handle(b1):
    cfg, _ = b1
    images = _images(cfg, 3 * B + 2, seed=4)
    eng = _engine(b1)
    daemon = ServingDaemon(eng, classes=(BULK,)).start()
    handles = [daemon.submit(im, slo="bulk") for im in images]
    daemon.shutdown(drain=False)
    # the full batches were launched on submit: they complete; the two
    # images left queued are cancelled
    assert all(h.state == DONE for h in handles[:3 * B])
    assert all(h.state == CANCELLED for h in handles[3 * B:])
    s = eng.stats
    assert s.submitted == s.resolved == len(images)
    assert s.completed + s.cancelled == len(images)
    assert daemon.outstanding == 0
    assert not daemon._completions._thread.is_alive()


def test_a_poisoned_kernel_step_is_delivered_from_the_xla_retry(b1):
    cfg, params = b1
    ops.reset_trip_latch()
    try:
        images = _images(cfg, 2 * B, seed=5)
        eng = _engine(b1, faults=FaultInjector.parse("nan@vision.kernel:1"))
        with pytest.warns(RuntimeWarning, match="tripped"):
            with ServingDaemon(eng, classes=(BULK,)) as daemon:
                handles = [daemon.submit(im, slo="bulk") for im in images]
                for h in handles:
                    h.result(timeout=WAIT)
        assert eng.fallback_guard.tripped and eng.fallback_guard.trips >= 1
        ref = np.asarray(get_model(cfg).forward(cfg, params, images))
        np.testing.assert_allclose(np.stack([h.result() for h in handles]),
                                   ref, rtol=1e-4, atol=1e-4)
        assert eng.stats.completed == 2 * B and eng.stats.failed == 0
    finally:
        ops.reset_trip_latch()


def test_a_raise_in_the_completion_half_fails_its_batch_alone(b1):
    cfg, _ = b1
    images = _images(cfg, 3 * B, seed=6)
    eng = _engine(b1)
    want = eng.classify(images)
    complete, calls = eng._complete, []

    def fail_first(handles, step, act):
        calls.append(threading.current_thread().name)
        if len(calls) == 1:
            raise RuntimeError("completion failed")
        return complete(handles, step, act)

    eng._complete = fail_first
    with ServingDaemon(eng, classes=(BULK,)) as daemon:
        handles = [daemon.submit(im, slo="bulk") for im in images]
        _settle(handles)
    with pytest.raises(RuntimeError, match="completion failed"):
        handles[0].result()
    assert set(calls) == {"repro-complete"}
    assert all(h.state == FAILED for h in handles[:B])
    assert all(h.state == DONE for h in handles[B:])
    got = np.stack([h.result() for h in handles[B:]])
    assert np.array_equal(got, want[B:])
    assert eng.stats.failed == B and eng.stats.completed == 2 * B


def test_a_poisoned_row_fails_alone_under_the_daemon(b1):
    cfg, _ = b1
    images = _images(cfg, B, seed=7)
    eng = _engine(b1, faults=FaultInjector.parse("nan@vision:1"))
    want = eng.classify(images)
    with ServingDaemon(eng, classes=(BULK,)) as daemon:
        handles = [daemon.submit(im, slo="bulk") for im in images]
        with pytest.raises(NumericalError, match="non-finite"):
            handles[0].result(timeout=WAIT)
        got = np.stack([h.result(timeout=WAIT) for h in handles[1:]])
    assert np.array_equal(got, want[1:])
    assert eng.stats.failed == 1 and eng.stats.completed == B - 1


def test_a_done_callback_that_fills_a_batch_runs_it_on_the_completion_thread(
        b1):
    cfg, _ = b1
    images = _images(cfg, 2 * B, seed=8)
    eng = _engine(b1)
    more, threads = [], []

    def resubmit(_h):
        threads.append(threading.current_thread().name)
        more.extend(daemon.submit(im, slo="bulk") for im in images[B:])

    with ServingDaemon(eng, classes=(BULK,)) as daemon:
        first = [daemon.submit(im, slo="bulk") for im in images[:1]]
        first[0].add_done_callback(resubmit)
        first += [daemon.submit(im, slo="bulk") for im in images[1:B]]
        for h in first:
            h.result(timeout=WAIT)
        # the callback's batch completed before its submit returned
        assert threads == ["repro-complete"]
        assert all(h.state == DONE for h in more) and len(more) == B
    assert eng.stats.completed == 2 * B

