"""The benchmark's trace reduction (benchmarks/chip/trace.py).

Two inputs: hand-built events whose busy time, gaps, programs and kernel
times are worked out below, and ``data/chip_trace.xplane.pb``, a trace
recorded on a TPU v5e by ``data/record_trace.py`` (three executions of a
program holding a Pallas kernel ``tiny_kernel``, inside a window span).
"""
import importlib.util
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parents[1] / "benchmarks" / "chip"


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_{name}_under_test", BENCH / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


trace = _load("trace")

MS = 1_000_000  # ns


def _events():
    """A 100 ms window.  Device ops: [10, 30) a kernel launch and a fusion
    overlapping it, [50, 60) another launch, [97, 105) an op that runs past
    the window's end.  Programs: [10, 30) and [50, 60) whole, [97, 105)
    clipped.  Host spans: "submit" over [0, 12), "sleep" over [30, 50)."""
    return {
        "ops": {"/device:TPU:0": [
            ("int8_matmul.3", 10 * MS, 25 * MS),
            ("fusion.7", 20 * MS, 30 * MS),
            ("int8_matmul.12", 50 * MS, 60 * MS),
            ("fusion.7", 97 * MS, 105 * MS),
        ]},
        "modules": {"/device:TPU:0": [
            ("jit_step(1)", 10 * MS, 30 * MS),
            ("jit_step(1)", 50 * MS, 60 * MS),
            ("jit_step(1)", 97 * MS, 105 * MS),
        ]},
        "host": [
            ("bench:window", 0, 100 * MS),
            ("bench:submit", 0, 12 * MS),
            ("bench:sleep", 30 * MS, 50 * MS),
            ("bench:warmup", -50 * MS, -10 * MS),
        ],
    }


def test_busy_union_and_window():
    r = trace.reduce(_events())
    assert r["window_s"] == pytest.approx(0.100)
    # union: [10, 30) + [50, 60) + [97, 100) = 33 ms
    assert r["busy_s"] == pytest.approx(0.033)


def test_idle_gaps_are_labelled_by_the_host_span_over_them():
    r = trace.reduce(_events())
    # gaps: [0, 10) 10 ms, [30, 50) 20 ms, [60, 97) 37 ms; longest first
    assert [round(t * 1e3, 6) for t, _ in r["gaps"]] == [37.0, 20.0, 10.0]
    labels = [label for _, label in r["gaps"]]
    assert labels == ["(no span)", "sleep", "submit"]


def test_per_op_time_is_clipped_to_the_window():
    r = trace.reduce(_events())
    assert r["op_s"]["int8_matmul.3"] == pytest.approx(0.015)
    assert r["op_s"]["fusion.7"] == pytest.approx(0.013)   # 10 + 3 ms


def test_step_program_counts_only_whole_executions():
    r = trace.reduce(_events())
    assert r["step"] == "jit_step(1)"
    assert r["step_times"] == pytest.approx([0.020, 0.010])
    # kernel time inside those two executions, by base name
    assert r["kernel_s"]["int8_matmul"] == pytest.approx(0.025)
    assert r["kernel_s"]["fusion"] == pytest.approx(0.010)


def test_breakdown_lists_top_ops_and_gaps():
    b = trace.breakdown(trace.reduce(_events()), top=2)
    assert [n for n, _ in b["device_ops"]] == ["int8_matmul.3", "fusion.7"]
    assert b["idle_gaps"][0] == ["(no span)", pytest.approx(0.037)]
    assert len(b["idle_gaps"]) == 2


def test_base_name_strips_xla_numbering():
    assert trace.base_name("dwconv_w4.20") == "dwconv_w4"
    assert trace.base_name("fusion.1.2") == "fusion"
    assert trace.base_name("relu_attn") == "relu_attn"


def test_no_window_span_is_an_error():
    ev = _events()
    ev["host"] = [h for h in ev["host"] if h[0] != "bench:window"]
    with pytest.raises(ValueError, match="window"):
        trace.reduce(ev)


def test_window_without_device_ops_is_an_error():
    ev = _events()
    ev["ops"] = {"/device:TPU:0": []}
    with pytest.raises(ValueError, match="no device op"):
        trace.reduce(ev)


def test_recorded_chip_trace():
    """The recorded v5e trace: three executions of one program, each
    launching ``tiny_kernel``.  The device's clock runs about a millisecond
    ahead of the host's here, so the first execution falls before the
    window span opens and only whole executions inside it count."""
    path = HERE / "data" / "chip_trace.xplane.pb"
    events = trace.load(str(path))
    assert events["ops"], "no device plane with an XLA Ops line"
    r = trace.reduce(events)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["step"].startswith("jit_step")
    assert len(r["step_times"]) in (2, 3)
    assert r["kernel_s"].get("tiny_kernel", 0) > 0
    assert sum(r["kernel_s"].values()) <= sum(r["step_times"]) + 1e-9
    labels = {label for _, label in r["gaps"] if label}
    assert labels & {"sleep", "submit"}


def test_op_name_is_the_hlo_instruction_name():
    text = ('%int8_matmul.58 = f32[200704,128]{1,0} custom-call(f32[200704,'
            '128]{1,0} %pad_convert_fusion.4), custom_call_target="tpu_custom_'
            'call"')
    assert trace.op_name(text) == "int8_matmul.58"
    assert trace.op_name("jit_step(123)") == "jit_step(123)"


def test_own_spans_land_where_the_profiler_puts_the_same_span(tmp_path):
    """The harness's spans, on ``time.time_ns``, placed by the trace's
    start: within a millisecond of a ``TraceAnnotation`` around the same
    code (CPU trace, host tracer on)."""
    import sys
    import time

    import jax
    import jax.numpy as jnp
    sys.path.insert(0, str(BENCH))
    import harness

    spans = harness.Spans()
    jax.profiler.start_trace(str(tmp_path))
    x = jnp.ones((64, 64))
    with spans("work"), jax.profiler.TraceAnnotation("bench:work"):
        (x @ x).block_until_ready()
        time.sleep(0.02)
    jax.profiler.stop_trace()
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    profiler = [h for h in trace.load(str(path))["host"]
                if h[0] == "bench:work"]
    ours = trace.load(str(path), spans.events)["host"][0]
    assert len(profiler) == 1 and ours[0] == "bench:work"
    assert abs(ours[1] - profiler[0][1]) < MS
    assert abs(ours[2] - profiler[0][2]) < MS
    assert ours[2] - ours[1] >= 20 * MS
