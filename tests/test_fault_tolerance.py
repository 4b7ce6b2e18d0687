"""Fault-tolerant serving: every behavior here is proven by PROVOKED
failures — the Handle terminal-state machine, per-batch containment in the
scheduler and both engines, admission control (reject/shed), per-request
deadlines over queued AND in-flight work, graceful degradation through the
FallbackGuard, numerics containment, clock misbehavior, and the
deterministic fault-injection harness itself."""
import json
import warnings

import jax
import numpy as np
import pytest

from repro.configs.registry import REDUCED
from repro.kernels import ops as _kops
from repro.models import get_model
from repro.serving.batching import ServeStats
from repro.serving.errors import (CancelledError, InjectedFault,
                                  NumericalError, QueueFullError,
                                  RequestTimedOut)
from repro.serving.faults import FaultInjector, FaultSpec
from repro.serving.scheduler import (CANCELLED, DONE, FAILED, PENDING,
                                     TIMED_OUT, FlushPolicy, OverloadPolicy,
                                     Scheduler)


class FakeClock:
    """Virtual seconds: tests drive deadlines without sleeping."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance_ms(self, ms):
        self.t += ms / 1000.0


def _ok_executor(handles, reason):
    for h in handles:
        h.set_result(h.payload)


# ---------------------------------------------------------------------------
# Handle terminal-state machine
# ---------------------------------------------------------------------------


def test_handle_state_machine_one_shot_transitions():
    stats = ServeStats()
    sched = Scheduler(stats=stats, clock=FakeClock())
    h = sched.submit("p")
    assert h.state == PENDING and not h.done() and h.exception() is None
    with pytest.raises(RuntimeError, match="no result yet"):
        h.result()
    assert h.set_result(42) and h.state == DONE and h.done()
    assert h.result() == 42
    # terminal states are sticky: late transitions are dropped, uncounted
    assert not h.set_exception(RuntimeError("late"))
    assert not h.cancel()
    assert h.result() == 42
    assert stats.completed == 1 and stats.failed == 0 and stats.cancelled == 0

    h2 = sched.submit("q")
    assert h2.set_exception(RuntimeError("boom"))
    assert h2.state == FAILED and h2.done() and not h2.cancelled()
    with pytest.raises(RuntimeError, match="boom"):
        h2.result()
    assert not h2.set_result(1)             # too late: stays FAILED
    with pytest.raises(RuntimeError, match="boom"):
        h2.result()

    h3 = sched.submit("r")
    assert h3.cancel() and h3.cancelled() and h3.state == CANCELLED
    with pytest.raises(CancelledError):
        h3.result()
    assert stats.completed == 1 and stats.failed == 1 and stats.cancelled == 1
    assert stats.resolved == 3 == stats.submitted


def test_handle_result_timeout_blocks_then_raises():
    sched = Scheduler(clock=FakeClock())
    h = sched.submit("p")
    with pytest.raises(TimeoutError, match="still PENDING"):
        h.result(timeout=0.01)              # nothing drives the scheduler
    h.set_result("done")
    assert h.result(timeout=0.01) == "done"


# ---------------------------------------------------------------------------
# scheduler: executor containment, overload, queued deadlines
# ---------------------------------------------------------------------------


def test_executor_exception_fails_only_its_batch_and_loop_survives():
    calls = {"n": 0}

    def flaky(handles, reason):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("batch 1 exploded")
        _ok_executor(handles, reason)

    clk = FakeClock()
    sched = Scheduler(policy=FlushPolicy(max_batch=2, max_delay_ms=None),
                      executor=flaky, clock=clk)
    bad = [sched.submit(v) for v in (1, 2)]      # full batch: runs inline
    ok = [sched.submit(v) for v in (3, 4)]
    assert all(h.state == FAILED for h in bad)
    for h in bad:
        with pytest.raises(RuntimeError, match="batch 1 exploded"):
            h.result()
    assert [h.result() for h in ok] == [3, 4]    # the loop kept serving
    s = sched.stats
    assert s.failed == 2 and s.completed == 2
    assert s.resolved == s.submitted == 4


def test_overload_policy_rejects_with_queue_full_error():
    sched = Scheduler(policy=FlushPolicy(max_batch=8, max_delay_ms=None),
                      clock=FakeClock(),
                      overload=OverloadPolicy(max_queue=2))
    h1, h2 = sched.submit(1), sched.submit(2)
    with pytest.raises(QueueFullError, match="max_queue=2"):
        sched.submit(3)
    # the refused submit made no handle: counted rejected, NOT submitted
    assert sched.stats.rejected == 1 and sched.stats.submitted == 2
    assert h1.state == PENDING and h2.state == PENDING


def test_overload_policy_sheds_oldest():
    sched = Scheduler(policy=FlushPolicy(max_batch=8, max_delay_ms=None),
                      clock=FakeClock(),
                      overload=OverloadPolicy(max_queue=2, shed_oldest=True))
    h1, h2 = sched.submit(1), sched.submit(2)
    h3 = sched.submit(3)                         # sheds h1, admits h3
    assert h1.state == FAILED
    with pytest.raises(QueueFullError, match="shed"):
        h1.result()
    assert h2.state == PENDING and h3.state == PENDING
    assert sched.stats.shed == 1 and sched.stats.submitted == 3
    assert sched.pending_payloads() == [2, 3]    # freshest traffic wins


def test_queued_request_times_out_and_never_executes():
    clk = FakeClock()
    ran = []

    def exec_(handles, reason):
        ran.extend(h.payload for h in handles)
        _ok_executor(handles, reason)

    sched = Scheduler(policy=FlushPolicy(max_batch=8, max_delay_ms=100.0),
                      executor=exec_, clock=clk)
    doomed = sched.submit("doomed", deadline_ms=20.0)
    safe = sched.submit("safe")
    clk.advance_ms(50)                           # past doomed's deadline,
    sched.poll()                                 # before the admission one
    assert doomed.state == TIMED_OUT
    with pytest.raises(RequestTimedOut):
        doomed.result()
    clk.advance_ms(60)                           # admission deadline fires
    sched.poll()
    assert safe.result() == "safe"
    assert "doomed" not in ran                   # expired work never ran
    assert sched.stats.timed_out == 1 and sched.stats.completed == 1
    with pytest.raises(ValueError, match="deadline_ms"):
        sched.submit("x", deadline_ms=0.0)


def test_cancelled_queued_request_is_dropped_not_executed():
    clk = FakeClock()
    ran = []

    def exec_(handles, reason):
        ran.extend(h.payload for h in handles)
        _ok_executor(handles, reason)

    sched = Scheduler(policy=FlushPolicy(max_batch=8, max_delay_ms=5.0),
                      executor=exec_, clock=clk)
    a, b = sched.submit("a"), sched.submit("b")
    assert a.cancel()
    clk.advance_ms(10)
    sched.poll()
    assert ran == ["b"] and b.result() == "b"
    assert a.state == CANCELLED
    assert sched.stats.resolved == sched.stats.submitted == 2


# ---------------------------------------------------------------------------
# clock misbehavior: the monotonic guard
# ---------------------------------------------------------------------------


def test_backwards_clock_never_unfires_deadline_or_negates_age():
    clk = FakeClock()
    sched = Scheduler(policy=FlushPolicy(max_batch=8, max_delay_ms=50.0),
                      clock=clk)
    clk.t = 10.0
    h = sched.submit("x", deadline_ms=60.0)
    clk.t = 10.040
    assert sched.oldest_age_ms() == pytest.approx(40.0)
    clk.t = 3.0                                  # clock steps BACKWARDS
    # ages never go negative, never even shrink: the guard holds the max
    assert sched.oldest_age_ms() == pytest.approx(40.0)
    assert sched.due() is None and h.state == PENDING
    clk.t = 10.035                               # still pre-deadline: fine
    assert sched.oldest_age_ms() == pytest.approx(40.0)
    clk.t = 10.070                               # past the request deadline
    sched.due()
    assert h.state == TIMED_OUT
    clk.t = 0.0                                  # backwards AGAIN
    assert h.state == TIMED_OUT                  # fired deadlines stay fired
    assert sched.now() >= 10.070


def test_stalled_clock_freezes_ages_without_firing_deadlines():
    clk = FakeClock()
    sched = Scheduler(policy=FlushPolicy(max_batch=8, max_delay_ms=50.0),
                      clock=clk)
    sched.submit("x", deadline_ms=1000.0)
    for _ in range(5):                           # clock never advances
        assert sched.due() is None
        assert sched.oldest_age_ms() == 0.0
    assert sched.pending == 1                    # nothing expired or flushed


# ---------------------------------------------------------------------------
# fault-injection harness
# ---------------------------------------------------------------------------


def test_fault_spec_parse_grammar():
    s = FaultSpec.parse("raise@decode:3")
    assert (s.kind, s.site, s.nth, s.every_k) == ("raise", "decode", 3, None)
    assert s.matches(3) and not s.matches(2) and not s.matches(6)
    r = FaultSpec.parse("nan@vision:*/5")
    assert r.every_k == 5 and r.matches(5) and r.matches(10)
    assert not r.matches(4)
    d = FaultSpec.parse("delay@prefill:1:75")
    assert d.kind == "delay" and d.delay_ms == 75.0
    inj = FaultInjector.parse("raise@decode:2, nan@vision:1")
    assert len(inj.specs) == 2
    for bad in ("oops", "explode@x:1", "raise@:1", "raise@a:zero",
                "raise@a:*/0"):
        with pytest.raises(ValueError, match="fault"):
            FaultInjector.parse(bad)


def test_fault_injector_fires_on_exact_call_and_from_env(monkeypatch):
    inj = FaultInjector.parse("raise@decode:2")
    assert inj.on_call("decode") is None         # call 1: clean
    act = inj.on_call("decode")                  # call 2: fires
    with pytest.raises(InjectedFault, match="call 2"):
        act.fire()
    assert inj.on_call("decode") is None         # call 3: clean again
    assert inj.on_call("vision") is None         # other sites untouched
    assert inj.fired == [("decode", 2, "raise")]
    assert inj.summary()["calls"] == {"decode": 3, "vision": 1}

    from repro.serving import faults
    monkeypatch.delenv(faults.ENV_VAR, raising=False)
    assert faults.from_env() is None
    monkeypatch.setenv(faults.ENV_VAR, "nan@vision:1")
    env_inj = faults.from_env()
    assert env_inj is not None and env_inj.specs[0].kind == "nan"
    monkeypatch.setenv(faults.ENV_VAR, "garbage")
    with pytest.raises(ValueError, match="malformed fault spec"):
        faults.from_env()


# ---------------------------------------------------------------------------
# FallbackGuard: graceful degradation to the XLA path
# ---------------------------------------------------------------------------


def test_fallback_guard_retries_on_xla_with_matching_outputs():
    _kops.reset_trip_latch()
    calls = []

    def step(x, fallback=False):
        calls.append(fallback)
        if not fallback:
            raise RuntimeError("kernel exploded")
        return x * 2.0

    g = _kops.FallbackGuard(check_finite=False, axes=("attn",))
    x = np.arange(4.0)
    np.testing.assert_array_equal(g.run(step, x), x * 2.0)
    assert calls == [False, True] and g.tripped and g.trips == 1
    assert _kops.axis_tripped("attn") and not _kops.axis_tripped("dense")
    # once tripped: straight to the fallback, no repeated kernel attempts
    np.testing.assert_array_equal(g.run(step, x), x * 2.0)
    assert calls == [False, True, True]
    assert g.stats()["retries"] == 2
    _kops.reset_trip_latch()
    assert not _kops.axis_tripped("attn")


def test_fallback_guard_trip_emits_runtime_warning():
    """A trip is never silent: the guard warns with the kernel's exception
    (a chip run that fell back to XLA shows it in its output)."""
    _kops.reset_trip_latch()
    try:
        def step(x, fallback=False):
            if not fallback:
                raise ValueError("Mosaic refused the block shape")
            return x

        g = _kops.FallbackGuard(check_finite=False, site="vision.kernel",
                                axes=("conv",))
        with pytest.warns(RuntimeWarning,
                          match=r"(?s)'vision.kernel' tripped.*Mosaic "
                                r"refused the block shape.*conv"):
            g.run(step, np.ones(2))
        # latched afterwards: the fallback runs without a new warning
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            g.run(step, np.ones(2))
    finally:
        _kops.reset_trip_latch()


def test_fallback_guard_nonfinite_output_trips_finite_check():
    _kops.reset_trip_latch()
    try:
        def step(x, fallback=False):
            return x + (np.nan if not fallback else 0.0)

        g = _kops.FallbackGuard(check_finite=True)
        out = g.run(step, jax.numpy.ones(3))
        assert np.all(np.isfinite(out)) and g.tripped
        assert "non-finite" in g.stats()["last_error"]
    finally:
        _kops.reset_trip_latch()


def test_trip_latch_layers_under_scope_and_over_env(monkeypatch):
    _kops.reset_trip_latch()
    try:
        monkeypatch.setenv("REPRO_PALLAS_DISPATCH", "1")
        assert _kops.dispatch_enabled()
        _kops.trip_axis("dense")
        assert not _kops.dispatch_enabled()      # latch beats the env var
        with _kops.dispatch(dense=True):
            assert _kops.dispatch_enabled()      # explicit scope beats latch
        assert _kops.trip_counts()["dense"] == 1
        with pytest.raises(ValueError, match="unknown dispatch axis"):
            _kops.trip_axis("bogus")
    finally:
        _kops.reset_trip_latch()


# ---------------------------------------------------------------------------
# token engine: containment, deadlines, cancellation, numerics
# ---------------------------------------------------------------------------


def _token_engine(max_batch=3, max_delay_ms=0.0, clock=None, **kw):
    cfg = REDUCED["qwen1.5-0.5b"]
    model = get_model(cfg)
    params = model.init(cfg, jax.random.PRNGKey(0))
    from repro.serving.engine import Engine
    if clock is not None:
        kw["clock"] = clock
    return cfg, Engine(cfg, params, max_batch=max_batch, max_len=64,
                       max_delay_ms=max_delay_ms, **kw)


def _prompts(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, int(rng.integers(4, 9)),
                         dtype=np.int32) for _ in range(n)]


def test_engine_prefill_fault_fails_only_its_group():
    cfg, eng = _token_engine(max_batch=2,
                             faults=FaultInjector.parse("raise@prefill:1"))
    cfg2, ref = _token_engine(max_batch=2)
    ps = _prompts(cfg, 4)
    reqs = [eng.submit(p, max_new_tokens=3) for p in ps]
    refs = [ref.submit(p, max_new_tokens=3) for p in ps]
    eng.run()
    ref.run()
    # group 1 (first two requests) died on the injected prefill fault...
    for r in reqs[:2]:
        assert r.handle.state == FAILED
        with pytest.raises(InjectedFault):
            r.handle.result()
    # ...group 2 completed with tokens identical to a fault-free engine
    for r, rr in zip(reqs[2:], refs[2:]):
        assert r.handle.state == DONE
        assert r.out_tokens == rr.out_tokens
    s = eng.stats
    assert s.failed == 2 and s.completed == 2
    assert s.resolved == s.submitted == 4


def test_engine_decode_fault_fails_live_slots_keeps_serving_queue():
    cfg, eng = _token_engine(max_batch=2,
                             faults=FaultInjector.parse("raise@decode:1"))
    ps = _prompts(cfg, 4, seed=1)
    reqs = [eng.submit(p, max_new_tokens=3) for p in ps]
    eng.run()
    # the first decode step failed both slots live in it; the two queued
    # requests were admitted afterwards and completed
    states = [r.handle.state for r in reqs]
    assert states[:2] == [FAILED, FAILED] and states[2:] == [DONE, DONE]
    for r in reqs[2:]:
        assert len(r.out_tokens) == 3
    assert eng.stats.resolved == eng.stats.submitted == 4


def test_engine_nan_decode_fails_one_slot_batchmates_unharmed():
    spec = "nan@decode:1"
    cfg, eng = _token_engine(max_batch=3,
                             faults=FaultInjector.parse(spec))
    cfg2, ref = _token_engine(max_batch=3)
    ps = _prompts(cfg, 3, seed=2)
    reqs = [eng.submit(p, max_new_tokens=4) for p in ps]
    refs = [ref.submit(p, max_new_tokens=4) for p in ps]
    eng.run()
    ref.run()
    # slot 0's cache was NaN-poisoned: that ONE request fails with
    # NumericalError instead of delivering garbage tokens
    assert reqs[0].handle.state == FAILED
    with pytest.raises(NumericalError, match="non-finite"):
        reqs[0].handle.result()
    # its batchmates decoded on, token-for-token identical to fault-free
    for r, rr in zip(reqs[1:], refs[1:]):
        assert r.handle.state == DONE
        assert r.out_tokens == rr.out_tokens
    assert eng.stats.failed == 1 and eng.stats.completed == 2


def test_engine_cancel_in_flight_frees_slot_for_queued_work():
    cfg, eng = _token_engine(max_batch=1)
    ps = _prompts(cfg, 2, seed=3)
    r1 = eng.submit(ps[0], max_new_tokens=30)
    r2 = eng.submit(ps[1], max_new_tokens=2)
    eng.step()                                   # r1 occupies the only slot
    assert eng.slots[0] is not None
    assert r1.handle.cancel()
    eng.run()
    with pytest.raises(CancelledError):
        r1.handle.result()
    # the cancelled request's slot was reclaimed and r2 completed
    assert r2.handle.state == DONE and len(r2.out_tokens) == 2
    assert eng.stats.cancelled == 1 and eng.stats.completed == 1


def test_engine_deadline_expires_in_flight_decode_and_frees_slot():
    clk = FakeClock()
    cfg, eng = _token_engine(max_batch=1, clock=clk)
    ps = _prompts(cfg, 2, seed=4)
    slow = eng.submit(ps[0], max_new_tokens=40, deadline_ms=25.0)
    fast = eng.submit(ps[1], max_new_tokens=2)
    eng.step()                                   # slow takes the only slot
    assert eng.slots[0] is not None and slow.handle.state == PENDING
    clk.advance_ms(30)                           # mid-decode deadline fires
    eng.run()
    assert slow.handle.state == TIMED_OUT
    with pytest.raises(RequestTimedOut, match="mid-decode"):
        slow.handle.result()
    assert fast.handle.state == DONE             # slot freed, queue served
    assert eng.stats.timed_out == 1 and eng.stats.completed == 1


def test_engine_queued_deadline_expires_while_engine_full():
    clk = FakeClock()
    cfg, eng = _token_engine(max_batch=1, clock=clk)
    ps = _prompts(cfg, 2, seed=5)
    eng.submit(ps[0], max_new_tokens=8)
    doomed = eng.submit(ps[1], max_new_tokens=2, deadline_ms=10.0)
    eng.step()                                   # slot busy, doomed queued
    clk.advance_ms(20)
    eng.step()                                   # sweep expires the queue
    assert doomed.handle.state == TIMED_OUT
    assert eng.stats.timed_out == 1


def test_engine_overload_bounds_admission_queue():
    cfg, eng = _token_engine(max_batch=1,
                             overload=OverloadPolicy(max_queue=1))
    ps = _prompts(cfg, 3, seed=6)
    eng.submit(ps[0], max_new_tokens=2)
    eng.step()                                   # slot taken
    eng.submit(ps[1], max_new_tokens=2)          # fills the queue
    with pytest.raises(QueueFullError):
        eng.submit(ps[2], max_new_tokens=2)
    assert eng.stats.rejected == 1
    eng.run()
    assert eng.stats.completed == 2


def test_engine_submit_validates_payload_up_front():
    cfg, eng = _token_engine(max_batch=1)
    with pytest.raises(ValueError, match="1-D"):
        eng.submit(np.zeros((2, 3), np.int32))
    with pytest.raises(ValueError, match="integer token ids"):
        eng.submit(np.array([0.5, 1.5], np.float32))
    with pytest.raises(ValueError, match="in \\[0,"):
        eng.submit(np.array([0, cfg.vocab_size + 7], np.int64))
    with pytest.raises(ValueError, match="in \\[0,"):
        eng.submit(np.array([-1, 3], np.int64))
    assert eng.scheduler.pending == 0            # nothing half-enqueued


# ---------------------------------------------------------------------------
# vision engine: containment, numerics, guard recovery
# ---------------------------------------------------------------------------


def _vision_engine(max_batch=4, max_delay_ms=None, **kw):
    cfg = REDUCED["efficientvit-b1-r224"]
    model = get_model(cfg)
    params = model.init(cfg, jax.random.PRNGKey(0))
    from repro.serving.vision import VisionEngine
    return cfg, model, params, VisionEngine(
        cfg, params, max_batch=max_batch, max_delay_ms=max_delay_ms, **kw)


def _imgs(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(0, 1, (n, cfg.img_res, cfg.img_res, 3)).astype(
        np.float32)


def test_vision_executor_fault_fails_batch_flush_continues():
    cfg, model, params, eng = _vision_engine(
        max_batch=8, faults=FaultInjector.parse("raise@vision:1"))
    imgs = _imgs(cfg, 4)
    handles = [eng.submit(im) for im in imgs]
    # the drained batch hit the injected fault: flush does NOT raise — it
    # fails the batch's handles and returns None (nothing delivered)
    assert eng.flush() is None
    for h in handles:
        assert h.state == FAILED
        with pytest.raises(InjectedFault):
            h.result()
    more = _imgs(cfg, 2, seed=9)
    h2 = [eng.submit(im) for im in more]
    out = eng.flush()                            # the engine kept serving
    ref = np.asarray(model.forward(cfg, params, more))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.stack([h.result() for h in h2]), ref,
                               rtol=1e-4, atol=1e-4)
    s = eng.stats
    assert s.failed == 4 and s.completed == 2
    assert s.resolved == s.submitted == 6


def test_vision_nan_poisoned_row_fails_alone():
    cfg, model, params, eng = _vision_engine(
        max_batch=4, faults=FaultInjector.parse("nan@vision:1"))
    imgs = _imgs(cfg, 4, seed=1)
    handles = [eng.submit(im) for im in imgs]
    eng.flush()
    assert handles[0].state == FAILED
    with pytest.raises(NumericalError, match="non-finite"):
        handles[0].result()
    ref = np.asarray(model.forward(cfg, params, imgs))
    for h, r in zip(handles[1:], ref[1:]):       # batchmates delivered
        np.testing.assert_allclose(h.result(), r, rtol=1e-4, atol=1e-4)
    assert eng.stats.failed == 1 and eng.stats.completed == 3


def test_vision_kernel_fault_recovers_through_fallback_guard():
    """The acceptance-criteria path: a NaN-poisoned kernel-dispatched
    forward is re-run on the XLA path with MATCHING outputs."""
    _kops.reset_trip_latch()
    try:
        cfg, model, params, eng = _vision_engine(
            max_batch=2, faults=FaultInjector.parse("nan@vision.kernel:1"))
        imgs = _imgs(cfg, 2, seed=2)
        handles = [eng.submit(im) for im in imgs]
        eng.flush()
        # the guard tripped on the poisoned primary attempt, retried on
        # XLA, and every request still completed with correct logits
        assert eng.fallback_guard.tripped
        assert _kops.axis_tripped("dense")
        ref = np.asarray(model.forward(cfg, params, imgs))
        np.testing.assert_allclose(
            np.stack([h.result() for h in handles]), ref,
            rtol=1e-4, atol=1e-4)
        assert eng.stats.completed == 2 and eng.stats.failed == 0
    finally:
        _kops.reset_trip_latch()


def test_vision_submit_validates_payload_up_front():
    cfg, model, params, eng = _vision_engine(max_batch=2)
    ok = _imgs(cfg, 1)[0]
    with pytest.raises(ValueError, match="expected"):
        eng.submit(ok[:-1])                      # wrong shape
    with pytest.raises(ValueError, match="dtype"):
        eng.submit(np.full(ok.shape, "x", dtype=object))
    bad = ok.copy()
    bad[0, 0, 0] = np.nan
    with pytest.raises(ValueError, match="NaN/Inf"):
        eng.submit(bad)
    assert eng.scheduler.pending == 0


def test_vision_queued_deadline_times_out():
    clk = FakeClock()
    cfg, model, params, eng = _vision_engine(max_batch=8, max_delay_ms=100.0,
                                             clock=clk)
    imgs = _imgs(cfg, 2, seed=3)
    doomed = eng.submit(imgs[0], deadline_ms=10.0)
    safe = eng.submit(imgs[1])
    clk.advance_ms(50)
    eng.poll()
    assert doomed.state == TIMED_OUT
    clk.advance_ms(60)
    eng.poll()
    assert safe.state == DONE
    assert eng.stats.timed_out == 1 and eng.stats.completed == 1


# ---------------------------------------------------------------------------
# satellites: autotune corruption, calibration numerics
# ---------------------------------------------------------------------------


def test_autotune_cache_tolerates_corruption(tmp_path):
    from repro.kernels.autotune import AutotuneCache, cache_key
    path = tmp_path / "autotune.json"
    key = cache_key("kern", 8, 8, 8, backend="cpu")
    good = cache_key("kern", 16, 16, 16, backend="cpu")
    cases = [
        "{truncated",                            # invalid JSON
        json.dumps([1, 2, 3]),                   # non-dict top level
        json.dumps({"k": "not-a-triple"}),       # corrupt entry
        json.dumps({"k": [8, "x", 8]}),          # non-int member
    ]
    for text in cases:
        path.write_text(text)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            cache = AutotuneCache(str(path)).load()
            assert len(cache) == 0               # rebuilt, not crashed
            assert any(issubclass(x.category, RuntimeWarning) for x in w)
        # save() merges through the same corrupt file without raising,
        # and the rewritten file is clean JSON
        cache.put(key, (8, 8, 8))
        reread = AutotuneCache(str(path)).load()
        assert reread.get(key) == (8, 8, 8)
    # valid entries survive alongside dropped corrupt ones
    path.write_text(json.dumps({good: [16, 16, 16], "bad": [1, 2]}))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        cache = AutotuneCache(str(path)).load()
    assert cache.get(good) == (16, 16, 16) and cache.get("bad") is None
    assert any("corrupt entries" in str(x.message) for x in w)


def test_calibration_rejects_nonfinite_activations():
    from repro.core.calibrate import CalibTensor
    store = {}
    t = CalibTensor(jax.numpy.ones((4, 4)), "blocks/0/qkv", store)
    t.record(np.ones((2, 4), np.float32))
    assert store["blocks/0/qkv"] == pytest.approx(1.0)
    poisoned = np.ones((2, 4), np.float32)
    poisoned[1, 2] = np.inf
    with pytest.raises(ValueError, match="blocks/0/qkv"):
        t.record(poisoned)
    assert store["blocks/0/qkv"] == pytest.approx(1.0)  # scale unpolluted


# ---------------------------------------------------------------------------
# stats reconciliation + docstring contract enforcement
# ---------------------------------------------------------------------------


def test_servestats_outcome_counters_and_reset():
    s = ServeStats()
    for kind in ("completed", "failed", "cancelled", "timed_out", "shed"):
        s.record_outcome(kind)
    s.record_outcome("rejected")
    assert s.resolved == 5                       # rejected is NOT resolved
    with pytest.raises(ValueError, match="unknown outcome"):
        s.record_outcome("vanished")
    summ = s.summary()
    assert summ["failed"] == 1 and summ["shed"] == 1 and summ["rejected"] == 1
    s.reset()
    assert s.resolved == 0 and s.rejected == 0


# every public serving entry point that can raise (or deliberately never
# raises) must SAY so in its docstring — suite-enforced so the contract
# cannot rot silently
_RAISE_DOCUMENTED = [
    ("repro.serving.scheduler", "Handle.result"),
    ("repro.serving.scheduler", "Scheduler.submit"),
    ("repro.serving.scheduler", "Scheduler.drain"),
    ("repro.serving.scheduler", "FlushPolicy"),
    ("repro.serving.scheduler", "OverloadPolicy"),
    ("repro.serving.engine", "Engine.submit"),
    ("repro.serving.vision", "VisionEngine.submit"),
    ("repro.serving.vision", "VisionEngine.poll"),
    ("repro.serving.vision", "VisionEngine.flush"),
    ("repro.serving.batching", "ServeStats.record_outcome"),
    ("repro.serving.batching", "pow2_bucket"),
    ("repro.serving.faults", "FaultSpec.parse"),
]


@pytest.mark.parametrize("mod_name,qualname", _RAISE_DOCUMENTED,
                         ids=[f"{m}:{q}" for m, q in _RAISE_DOCUMENTED])
def test_public_serving_entry_points_document_raise_behavior(mod_name,
                                                             qualname):
    import importlib
    obj = importlib.import_module(mod_name)
    for part in qualname.split("."):
        obj = getattr(obj, part)
    doc = obj.__doc__ or ""
    assert "aise" in doc, (                      # Raises/raises/re-raises
        f"{mod_name}.{qualname} is a public serving entry point but its "
        "docstring does not document raise behavior")


# ---------------------------------------------------------------------------
# debug numerics: pre-quantization NaN detection on a quantized engine
# ---------------------------------------------------------------------------


def _quantized_int8kv_model():
    """A calibrated (static act scales) int8-KV quantized artifact: the
    exact posture where activation quantization launders a cache NaN into
    finite logits (``NaN.astype(int8)`` is finite)."""
    from repro.recipe import quantize
    cfg = REDUCED["qwen1.5-0.5b"].replace(kv_cache_dtype="int8")
    model = get_model(cfg)
    params = model.init(cfg, jax.random.PRNGKey(0))
    return quantize(cfg, params, "m2q-w8a8")


def test_debug_numerics_catches_laundered_cache_nan():
    qm = _quantized_int8kv_model()
    kw = dict(max_batch=2, max_len=64)
    dbg = qm.serve(faults=FaultInjector.parse("nan@decode:1"),
                   debug_numerics=True, **kw)
    ref = qm.serve(faults=FaultInjector.parse("nan@decode:1"), **kw)
    ps = _prompts(qm.cfg, 2, seed=5)

    # default engine: the detection boundary — the logits-only check
    # misses the laundered NaN and delivers corrupt-but-finite tokens
    rref = [ref.submit(p, max_new_tokens=4) for p in ps]
    ref.run()
    assert rref[0].handle.state == DONE
    assert all(np.isfinite(rref[0].out_tokens))

    # debug engine: the per-step cache scan sees the NaN'd f32 scale rows
    # and fails ONLY the poisoned slot; its batchmate decodes on
    rdbg = [dbg.submit(p, max_new_tokens=4) for p in ps]
    dbg.run()
    assert rdbg[0].handle.state == FAILED
    with pytest.raises(NumericalError, match="non-finite"):
        rdbg[0].handle.result()
    assert rdbg[1].handle.state == DONE
    assert rdbg[1].out_tokens == rref[1].out_tokens


def test_debug_numerics_defaults_off_and_reads_env(monkeypatch):
    from repro.serving.engine import Engine
    cfg = REDUCED["qwen1.5-0.5b"]
    model = get_model(cfg)
    params = model.init(cfg, jax.random.PRNGKey(0))
    monkeypatch.delenv("REPRO_DEBUG_NUMERICS", raising=False)
    assert not Engine(cfg, params, max_batch=1, max_len=32).debug_numerics
    monkeypatch.setenv("REPRO_DEBUG_NUMERICS", "1")
    assert Engine(cfg, params, max_batch=1, max_len=32).debug_numerics
    # explicit constructor arg beats the env var
    assert not Engine(cfg, params, max_batch=1, max_len=32,
                      debug_numerics=False).debug_numerics
