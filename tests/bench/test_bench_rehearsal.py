"""Whole runs of the harness on the CPU at the reduced EfficientViT-B1,
with the Pallas kernels in interpret mode: everything a chip run does but
the look for the chip.

* a sound run comes out correct, with exactly the contract's keys;
* the control (the reference one precision step below the configuration,
  put in the program's place) fails the comparison;
* with the timed path broken underneath (``faults.py``): each request
  answered with its neighbour's row, two requests of a batch answered with
  each other's rows, a padding row handed to a request, or every answer
  altered where it is produced, ``correct`` comes out false.
"""
import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

import faults  # noqa: E402
import harness  # noqa: E402

CONFIG = json.loads((Path(__file__).parent / "data"
                     / "evit-b1-reduced-int8.json").read_text())
CLOSED = {"kind": "closed", "slo": "bulk",
          "classes": [{"name": "bulk", "priority": 0,
                       "max_delay_ms": 1000.0}],
          "outstanding": 16, "max_batch": 8, "buckets": [8], "pool": 12,
          "sample": 12}
# six requests outstanding against a bucket of 8: every batch is padded
PADDED = {"kind": "closed", "slo": "bulk",
          "classes": [{"name": "bulk", "priority": 0,
                       "max_delay_ms": 50.0}],
          "outstanding": 6, "max_batch": 8, "buckets": [8], "pool": 12,
          "sample": 12}
POISSON = {"kind": "poisson", "slo": "interactive", "rate_per_s": 40.0,
           "max_batch": 4, "buckets": [1, 2, 4], "pool": 12, "sample": 12}
PEAKS = {"bf16_flops_per_s": 1e12, "int8_ops_per_s": 2e12,
         "hbm_bytes_per_s": 1e11}
SEED = 3_000_000_007          # past 2**31, as the driver's seeds are


@pytest.fixture(scope="module", autouse=True)
def interpret_kernels():
    """Dispatch the Pallas kernels on the CPU, in interpret mode."""
    with pytest.MonkeyPatch.context() as mp:
        for axis in ("", "_CONV", "_ATTN"):
            mp.setenv(f"REPRO_PALLAS{axis}_DISPATCH", "1")
        yield


def _spec(cell, mix):
    spec = harness.cell_spec(
        json.loads((ROOT / "BENCHMARK.json").read_text()), cell)
    spec["config"], spec["mix"] = CONFIG, mix
    return spec


def _run(cell, mix, seed=SEED, control=False):
    return harness.run_cell(_spec(cell, mix), seed, 1.5, False,
                            time.perf_counter(), PEAKS, on_tpu=False,
                            control=control)


@pytest.fixture(scope="module")
def closed_run(interpret_kernels):
    return _run("b1-int8-offline", CLOSED, control=True)


def test_sound_run_is_correct(closed_run):
    checks = closed_run["checks"]
    assert closed_run["correct"], checks
    for name in ("gap_max", "route_ratio_max"):
        assert checks[name]["value"] < checks[name]["limit"]
    assert closed_run["attempted"] > 0 and closed_run["failed"] == 0


def test_last_line_has_the_contract_keys(closed_run):
    out = dict(closed_run)
    out.pop("control")                    # only asked for in readings
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert set(out["metrics"]) == {"images_per_s", "setup_s"}
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    json.dumps(out)                       # one JSON object


def test_control_fails_the_comparison(closed_run):
    ctrl = closed_run["control"]
    assert ctrl["gap_max"] > 3 * closed_run["checks"]["gap_max"]["value"]
    assert ctrl["gap_max"] > CONFIG["limits"]["gap_max"]


def test_online_run_reports_latency_over_every_request():
    out = _run("b1-int8-online", POISSON)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"latency_p50_ms", "setup_s"}
    assert out["attempted"] > 20


def _fails(fault, mix, seed, check):
    with faults.planted(fault):
        out = _run("b1-int8-offline", mix, seed=seed)
    assert not out["correct"]
    assert out["checks"][check]["value"] > out["checks"][check]["limit"]


def test_rows_returned_to_the_wrong_requests_fail():
    _fails("roll_batch", CLOSED, SEED + 1, "route_ratio_max")


@pytest.mark.parametrize("fault,mix,seed,check", [
    # two requests of a batch swap rows: each lies nearer the other image.
    # Every answered row is compared: with 12 rows drawn from a short
    # window on the CPU, the sample could miss every swapped pair
    ("swap_one_slot", dict(CLOSED, sample=10 ** 6), SEED + 3,
     "route_ratio_max"),
    # a padding image's row (zeros) handed to a request: a gap of 1
    ("leak_padding", PADDED, SEED + 4, "gap_max"),
])
def test_one_row_to_the_wrong_request_fails(fault, mix, seed, check):
    _fails(fault, mix, seed, check)


def test_answers_altered_where_produced_fail():
    _fails("alter_rows", CLOSED, SEED + 2, "gap_max")


def test_planted_faults_are_undone():
    from repro.serving.vision import VisionEngine
    run_batch = VisionEngine._run_batch
    for name in faults.FAULTS:
        with faults.planted(name):
            assert VisionEngine._run_batch is not run_batch
        assert VisionEngine._run_batch is run_batch
