"""The program's spans on a profiler trace (benchmarks/chip/phases.py).

Hand-built events whose gaps, brackets and batches are worked out below;
``data/chip_trace.xplane.pb`` (a trace recorded on a TPU v5e, see
test_bench_trace.py) with program spans laid over it; the compiled B1
forward's stage scopes; and, on the CPU, a traced window of the reduced
B1 served through the daemon with recording on.
"""
import importlib.util
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH = ROOT / "benchmarks" / "chip"


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_{name.replace('.', '_')}_phases_test", BENCH / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


phases = _load("phases")
trace = _load("trace")
readers = _load("readers")

MS = 1_000_000  # ns
MAIN, SERVE = "MainThread", "repro-serve"


def _batch(t, thread=MAIN, assemble=2, put=1, launch=1, sync=10, fetch=1,
           deliver=1):
    """One batch's spans from ``t`` ms: its phases back to back."""
    out, at = [], t
    for name, d in (("assemble", assemble), ("put", put), ("launch", launch),
                    ("sync", sync), ("fetch", fetch),
                    ("deliver", deliver)):
        out.append((f"vision.{name}", at * MS, (at + d) * MS, thread))
        at += d
    return [("vision.batch", t * MS, at * MS, thread)] + out


def _events():
    """A 60 ms window, one device.  Two batches on the main thread, at 0
    and 30 ms (16 ms each), each executing the step program from its
    launch's end + 0.5 ms for 9 ms; the daemon sleeps over [0, 60)."""
    ops, mods = [], []
    for t in (0, 30):
        s, e = (t + 3.5) * MS, (t + 12.5) * MS
        ops.append(("int8_matmul.4", s, e))
        mods.append(("jit__fwd_impl(1)", s, e))
    program = _batch(0) + _batch(30) + [("daemon.sleep", 0, 60 * MS, SERVE)]
    events = {"ops": {"/device:TPU:0": ops},
              "modules": {"/device:TPU:0": mods},
              "host": [("bench:window", 0, 60 * MS),
                       ("bench:submit", 0, 16 * MS),
                       ("bench:submit", 30 * MS, 46 * MS)]}
    return events, program


def test_a_gap_under_a_phase_is_labelled_by_it():
    events, program = _events()
    ph = phases.reduce(events, program, "jit__fwd_impl(1)")
    # idle: [0, 3.5), [12.5, 33.5), [42.5, 60); the first is assemble 2 ms
    # + put 1 ms + launch 0.5 ms
    assert ph["idle_s"] == pytest.approx((3.5 + 21 + 17.5) * 1e-3)
    assert ph["idle_by_phase"]["vision.put"] == pytest.approx(2e-3)
    assert ph["idle_by_phase"]["vision.assemble"] == pytest.approx(4e-3)
    # between batches only the daemon's sleep is over the device
    assert ph["idle_by_phase"]["daemon.sleep"] == pytest.approx(
        (14 + 14) * 1e-3)
    assert ph["idle_covered_s"] == pytest.approx(ph["idle_s"])
    labels = [label for _, label in ph["gap_labels"]]
    assert labels == ["daemon.sleep", "daemon.sleep", "vision.assemble"]


def test_a_gap_under_vision_put_is_labelled_vision_put():
    events = {"ops": {"/device:TPU:0": [("fusion.1", 0, 2 * MS),
                                        ("fusion.2", 7 * MS, 9 * MS)]},
              "modules": {"/device:TPU:0": []},
              "host": [("bench:window", 0, 10 * MS),
                       ("bench:submit", 0, 10 * MS)]}
    program = [("vision.batch", 1 * MS, 9 * MS, MAIN),
               ("vision.put", 2 * MS, 7 * MS, MAIN),
               ("daemon.sleep", 0, 10 * MS, SERVE)]
    ph = phases.reduce(events, program)
    assert ph["gap_labels"][0] == (pytest.approx(5e-3), "vision.put")
    assert ph["idle_by_phase"] == {"vision.put": pytest.approx(5e-3),
                                   "daemon.sleep": pytest.approx(1e-3)}


def test_gaps_under_no_program_span_keep_the_benchmark_label():
    events, _ = _events()
    ph = phases.reduce(events, [])
    assert [label for _, label in ph["gap_labels"]] == ["submit"] * 3
    assert ph["idle_covered_s"] == 0 and ph["phases"] == []


def test_a_deeper_span_wins_over_another_threads_shallower_one():
    spans = [("daemon.sleep", 0, 10, SERVE),
             ("vision.batch", 2, 8, MAIN), ("vision.put", 3, 5, MAIN),
             ("sched.enqueue", 6, 9, "client")]
    pieces = phases.innermost(spans)
    assert [(s, e, n) for s, e, n in pieces] == [
        (0, 2, "daemon.sleep"), (2, 3, "vision.batch"), (3, 5, "vision.put"),
        (5, 6, "vision.batch"), (6, 8, "sched.enqueue"),
        (8, 9, "sched.enqueue"), (9, 10, "daemon.sleep")]


def test_aligned_executions_lie_inside_their_brackets():
    events, program = _events()
    al = phases.reduce(events, program, "jit__fwd_impl(1)")["alignment"]
    assert al["executions"] == 2 and al["inside_share"] == 1.0
    assert al["max_violation_ms"] == 0 and al["shift_ms"] == 0


def test_a_device_clock_ahead_is_measured_and_shifted_away():
    """Device 1 ms ahead of the host: each execution starts 0.5 ms before
    its launch; the median offset centres them, and the new keys read the
    shifted spans."""
    events, program = _events()
    for key in ("ops", "modules"):
        events[key] = {k: [(n, s - MS, e - MS) for n, s, e in v]
                       for k, v in events[key].items()}
    ph = phases.reduce(events, program, "jit__fwd_impl(1)")
    al = ph["alignment"]
    assert al["inside_share"] == 0.0
    assert al["max_violation_ms"] == pytest.approx(0.5)
    # slack before the execution -0.5 ms, after it 2.5 ms: centre -1.5 ms
    assert al["offset_ms"] == pytest.approx(-1.5)
    assert al["shift_ms"] == pytest.approx(-1.5)
    assert al["shifted_inside_share"] == 1.0
    assert al["shifted_max_violation_ms"] == 0
    # the first batch now starts before the window, the second at 28.5 ms
    assert [sp[1] for sp in ph["phases"] if sp[0] == "vision.batch"] == \
        [pytest.approx(28.5 * MS)]


def test_readers_find_nothing_without_spans():
    assert all(v is None for v in phases.readings([]).values())
    only_other = [("daemon.sleep", 0, MS, SERVE)]
    assert all(v is None for v in phases.readings(only_other).values())


def test_readers_on_two_batches():
    program = (_batch(0, assemble=2, put=1, sync=10)
               + _batch(30, thread=SERVE, assemble=4, put=3, sync=20)
               + [("vision.assemble", 31 * MS, 32 * MS, MAIN)])
    r = phases.readings(program)
    # batch lengths 16 and 30 ms, less sync: 6 and 10
    assert r["batch_host_ms"] == pytest.approx(8.0)
    assert r["assemble_ms"] == pytest.approx(3.0)   # the stray on MAIN
    assert r["put_ms"] == pytest.approx(2.0)        # is in no batch
    assert r["batch_ms_p95"] == pytest.approx(np.percentile([16, 30], 95))
    assert r["inline_batch_share"] == pytest.approx(50.0)


def test_stage_map_follows_operands_where_metadata_is_missing():
    text = """HloModule jit_f, is_scheduled=true

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %n = f32[4]{0} negate(%p), metadata={op_name="jit(f)/stage9/neg"}
}

ENTRY %main.9 (Arg_0.1: f32[4]) -> f32[4] {
  %Arg_0.1 = f32[4]{0} parameter(0)
  %fusion.3 = f32[4]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/stem/mul"}
  %bitcast.2 = f32[4]{0} bitcast(%fusion.3)
  %int8_matmul.7 = f32[4]{0} custom-call(%bitcast.2), custom_call_target="tpu_custom_call", metadata={}
  %fusion.4 = f32[4]{0} fusion(%int8_matmul.7), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/stage1/add"}
  ROOT %copy.5 = f32[4]{0} copy(%fusion.4)
}
"""
    m = phases.stage_map(text)
    assert m == {"fusion.3": "stem", "bitcast.2": "stem",
                 "int8_matmul.7": "stem", "fusion.4": "stage1",
                 "copy.5": "stage1"}
    assert phases.stage_seconds({"fusion.3": 1.0, "int8_matmul.7": 2.0,
                                 "fusion.4": 0.5, "tuple.1": 0.25}, m) == \
        {"stem": 3.0, "stage1": 0.5, "(other)": 0.25}


def test_compiled_b1_forward_holds_every_stage_scope():
    import jax
    from repro.configs.registry import ARCHS
    from repro.models import get_model

    cfg = ARCHS["efficientvit-b1-r224"]
    model = get_model(cfg)
    params = jax.eval_shape(lambda: model.init(cfg, jax.random.PRNGKey(0)))
    x = jax.ShapeDtypeStruct((1, cfg.img_res, cfg.img_res, 3), np.float32)
    text = jax.jit(lambda p, im: model.forward(cfg, p, im)).lower(
        params, x).compile().as_text()
    stages = set(phases.stage_map(text).values())
    assert stages == {"stem", "head"} | {f"stage{k}" for k in range(5)}


def test_the_recorded_trace_reads_the_same_with_program_spans():
    """Program spans laid over the recorded v5e trace: the trace reduction
    and every per-layer reader of the device trace read what they read
    without them, and the gaps take the program's labels."""
    events = trace.load(str(HERE / "data" / "chip_trace.xplane.pb"))
    before = trace.reduce(events)
    lo, hi = phases._window(events)
    # a program span over each benchmark span, as the program would nest
    program = [("vision.sync" if n == "bench:submit" else "daemon.sleep",
                s, e, MAIN) for n, s, e in events["host"]
               if n != trace.WINDOW]
    ph = phases.reduce(events, program, before["step"])
    after = trace.reduce(events)
    for key in ("busy_s", "op_s", "step_times", "kernel_s", "gaps",
                "window_s", "modules"):
        assert after[key] == before[key], key
    run = {"trace": before, "launches": [], "peaks": {},
           "images_per_batch": 1, "ops_per_image": 1}
    run_after = dict(run, trace=after)
    for reader in (readers.step_ms, readers.idle_share):
        assert reader(run_after) == reader(run)
    labels = {label for _, label in ph["gap_labels"]}
    assert labels <= {"vision.sync", "daemon.sleep", "(no span)"}
    assert labels & {"vision.sync", "daemon.sleep"}
    assert 0 < ph["idle_covered_s"] <= ph["idle_s"] + 1e-12
    assert ph["idle_s"] == pytest.approx(
        before["window_s"] - before["busy_s"])
    assert all(lo <= s and e <= hi for _, s, e, _ in ph["phases"])


@pytest.fixture()
def interpret_kernels(monkeypatch):
    for axis in ("", "_CONV", "_ATTN"):
        monkeypatch.setenv(f"REPRO_PALLAS{axis}_DISPATCH", "1")


def test_a_traced_window_records_the_program_on_the_cpu(interpret_kernels,
                                                       monkeypatch,
                                                       tmp_path):
    """The reduced B1 behind the daemon, one traced window with recording
    on: the program's spans come back on the trace's clock, inside the
    benchmark's window span, and every reader finds its batches."""
    sys.path.insert(0, str(BENCH))
    import harness
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")

    config = json.loads((HERE / "data" / "evit-b1-reduced-int8.json")
                        .read_text())
    mix = {"kind": "closed", "slo": "bulk",
           "classes": [{"name": "bulk", "priority": 0,
                        "max_delay_ms": 1000.0}],
           "outstanding": 8, "max_batch": 4, "buckets": [4], "pool": 8,
           "sample": 8}
    s = harness.set_up(config, mix, 3_000_000_019)
    daemon = s["daemon"]
    try:
        w = phases.traced_window(
            harness, mix, lambda im: daemon.submit(im, slo="bulk"),
            s["pool"], harness._rng(1, 5), 1.0, s["engine"].stats,
            record=True)
    finally:
        daemon.shutdown(drain=False)
    lo, hi = phases._window(w["events"])
    inside = [sp for sp in w["program"] if sp[1] >= lo and sp[2] <= hi]
    assert w["failed"] == 0 and w["batches"] >= 2 and w["dropped"] == 0
    names = {sp[0] for sp in inside}
    assert {"vision.batch", "vision.put", "vision.sync", "vision.validate",
            "sched.enqueue"} <= names
    r = phases.readings(inside)
    assert all(v is not None for v in r.values()), r
    assert 0 < r["put_ms"] < r["batch_host_ms"] < r["batch_ms_p95"]
    assert phases.brackets(inside)
