"""The program's span recorder (repro.tracing) and the spans of the vision
serving path: off, nothing is recorded and one shared no-op is handed
out; on, each span carries its thread, and one executed batch records its
six phases in order inside ``vision.batch``."""
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
from repro.serving.slo import SLOClass
from repro.serving.vision import VisionEngine

BATCH_PHASES = ["vision.assemble", "vision.put", "vision.launch",
                "vision.sync", "vision.fetch", "vision.deliver"]


@pytest.fixture(scope="module")
def b1():
    cfg = REDUCED["efficientvit-b1-r224"]
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _images(cfg, n):
    return np.random.default_rng(0).normal(
        0, 1, (n, cfg.img_res, cfg.img_res, 3)).astype(np.float32)


def _inside(events, outer):
    """Events on ``outer``'s thread that lie inside it, in start order."""
    _, s, e, th = outer
    return sorted((ev for ev in events if ev is not outer and ev[3] == th
                   and ev[1] >= s and ev[2] <= e), key=lambda ev: ev[1])


def test_off_records_nothing_and_hands_out_one_no_op():
    a, b = tracing.span("x"), tracing.span("y")
    assert a is b is tracing.OFF
    with a:
        pass
    with tracing.recording() as rec:
        pass
    assert rec.events == [] and rec.dropped == 0
    assert tracing.span("z") is tracing.OFF


def test_nested_spans_carry_their_thread():
    def work():
        with tracing.span("outer"):
            with tracing.span("inner"):
                time.sleep(0.001)

    with tracing.recording() as rec:
        t = threading.Thread(target=work, name="client-7")
        t.start()
        t.join(timeout=10)
        with tracing.span("main"):
            pass
    assert not t.is_alive()
    by_name = {ev[0]: ev for ev in rec.events}
    assert set(by_name) == {"outer", "inner", "main"}
    assert by_name["outer"][3] == by_name["inner"][3] == "client-7"
    assert by_name["main"][3] == threading.current_thread().name
    outer, inner = by_name["outer"], by_name["inner"]
    assert outer[1] <= inner[1] < inner[2] <= outer[2]
    assert inner[2] - inner[1] >= 1_000_000       # time.time_ns


def test_recording_is_bounded_and_counts_what_it_drops(monkeypatch):
    monkeypatch.setattr(tracing, "LIMIT", 3)
    with tracing.recording() as rec:
        for k in range(5):
            with tracing.span(f"s{k}"):
                pass
    assert [ev[0] for ev in rec.events] == ["s0", "s1", "s2"]
    assert rec.dropped == 2


def test_one_recording_at_a_time_and_late_spans_are_not_kept():
    with tracing.recording() as rec:
        held = tracing.span("open-across-the-end")
        held.__enter__()
        with pytest.raises(RuntimeError, match="already open"):
            with tracing.recording():
                pass
    held.__exit__(None, None, None)
    assert rec.events == []


def test_one_batch_records_its_six_phases_in_order(b1):
    cfg, params = b1
    eng = VisionEngine(cfg, params, max_batch=2)
    with tracing.recording() as rec:
        handles = [eng.submit(im) for im in _images(cfg, 2)]
    assert all(h.state == "DONE" for h in handles)
    names = [ev[0] for ev in rec.events]
    assert names.count("vision.validate") == 2
    assert names.count("sched.enqueue") == 2
    batch = [ev for ev in rec.events if ev[0] == "vision.batch"]
    assert len(batch) == 1
    inside = _inside(rec.events, batch[0])
    assert [ev[0] for ev in inside] == BATCH_PHASES
    # the batch filled on the second submit: inline, on this thread
    assert batch[0][3] == threading.current_thread().name


def test_a_padded_batch_assembles_twice_inside_its_batch(b1):
    cfg, params = b1
    eng = VisionEngine(cfg, params, max_batch=4)
    with tracing.recording() as rec:
        handles = [eng.submit(im) for im in _images(cfg, 3)]
        eng.flush()
    assert all(h.state == "DONE" for h in handles)
    batch = next(ev for ev in rec.events if ev[0] == "vision.batch")
    assert [ev[0] for ev in _inside(rec.events, batch)] == \
        ["vision.assemble"] + BATCH_PHASES


def test_daemon_batches_carry_the_serve_thread_and_inline_ones_the_caller(
        b1):
    cfg, params = b1
    quick = SLOClass(name="quick", priority=0, max_delay_ms=1.0)
    eng = VisionEngine(cfg, params, max_batch=2)
    with tracing.recording() as rec:
        with ServingDaemon(eng, classes=(quick,)) as daemon:
            daemon.submit(_images(cfg, 1)[0], slo="quick").result(
                timeout=60)
        # the submit that fills a batch runs it on the submitting thread
        inline = VisionEngine(cfg, params, max_batch=2)
        client = threading.Thread(
            target=lambda: [inline.submit(im) for im in _images(cfg, 2)],
            name="client-3")
        client.start()
        client.join(timeout=60)
    assert not client.is_alive()
    threads = [ev[3] for ev in rec.events if ev[0] == "vision.batch"]
    assert threads == ["repro-serve", "client-3"]
    names = {ev[0] for ev in rec.events if ev[3] == "repro-serve"}
    assert {"daemon.tick", "daemon.sleep", "vision.batch"} <= names


def test_a_tripped_guard_launches_again_without_a_finite_check():
    guard = ops.FallbackGuard(check_finite=True, span_prefix="vision",
                              axes=())

    def step(x, fallback=False):
        if not fallback:
            raise RuntimeError("kernel refused")
        return x + 1

    with pytest.warns(RuntimeWarning, match="tripped"), \
            tracing.recording() as rec:
        out = guard.run(step, jax.numpy.ones(3))
    assert float(out[0]) == 2.0
    assert [ev[0] for ev in rec.events] == ["vision.launch"] * 2

