"""The benchmark harness without a model: BENCHMARK.json and the files it
names, the traffic generator, the metric readers, and the CLI's refusal
to run without a TPU."""
import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_resolves_to_its_files():
    for w in BENCHMARK["workloads"]:
        spec = harness.cell_spec(BENCHMARK, w["name"])
        assert spec["config"]["name"] == w["config"]
        assert spec["mix"]["kind"] in ("closed", "poisson")
        assert spec["end_to_end"] and spec["per_layer"]
        names = {m["name"] for m in spec["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2


def test_every_metric_has_a_reader_file():
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        reader = harness.load_module(BENCH / "metrics" / f"{m['name']}.py")
        assert callable(reader.read), m["name"]


def test_config_files_match_the_registry():
    for c in BENCHMARK["configs"]:
        config = json.loads((ROOT / c["file"]).read_text())
        assert config["name"] == c["name"]
        assert c["file"].startswith("benchmarks/chip/configs/")
        harness.program_config(config)      # raises on any difference
        assert c["reduced"] == config["reduced"] == []


def test_benchmark_names_and_links_keep_to_the_contract():
    metrics = BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]
    cells = {w["name"]: w for w in BENCHMARK["workloads"]}
    for x in metrics + BENCHMARK["workloads"] + BENCHMARK["configs"]:
        assert NAME.match(x["name"]), x["name"]
    e2e = {m["name"]: m for m in BENCHMARK["end_to_end"]}
    for m in BENCHMARK["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells
            moved = e2e[m["moves"]]
            assert cell in moved.get("workloads", cells), (m["name"], cell)
    for m in BENCHMARK["end_to_end"]:
        assert 0 < m["bound"] <= 0.25
    assert {w["chips"] for w in cells.values()} == {1}


def test_arrivals_are_a_function_of_the_seed():
    a = harness.traffic.arrivals(800.0, 5.0, np.random.default_rng([7, 3]))
    b = harness.traffic.arrivals(800.0, 5.0, np.random.default_rng([7, 3]))
    c = harness.traffic.arrivals(800.0, 5.0, np.random.default_rng([8, 3]))
    assert np.array_equal(a, b)
    assert not np.array_equal(a[:50], c[:50])
    assert np.all(np.diff(a) > 0) and a[-1] < 5.0
    assert len(a) == len(c) == 4000
    # the same set of gaps, in another order
    gaps = np.sort(np.diff(np.concatenate([[0.0], a, [5.0]])))
    assert np.allclose(gaps, np.sort(np.diff(np.concatenate([[0.0], c,
                                                              [5.0]]))))
    # exponential: 4001 gaps fill the window, the median is ln 2 of the mean
    assert np.mean(gaps) == pytest.approx(5.0 / 4001, rel=1e-9)
    assert np.median(gaps) == pytest.approx(np.log(2) / 800, rel=0.1)


class _Handle:
    """Answers at once, like a request served inline."""

    state = "DONE"

    def add_done_callback(self, fn):
        fn(self)

    def result(self, timeout=None):
        return np.zeros(3)


def _submit(stall_at=None, stall_s=0.0):
    """A submit that takes no time, except that the ``stall_at``-th call
    blocks for ``stall_s`` seconds (a long forward run inline)."""
    n = {"calls": 0}

    def submit(_image):
        n["calls"] += 1
        if n["calls"] == stall_at:
            time.sleep(stall_s)
        return _Handle()
    return submit


def _run_dict(rec):
    t1 = rec["t1"]
    reqs = [r for r in rec["requests"] if r.due < t1]
    return {"latency_ms": [(r.done - r.due) * 1e3 for r in reqs],
            "done_in_window": sum(1 for r in reqs if r.done <= t1),
            "window_s": t1 - rec["t0"],
            "lib": harness.load_module(BENCH / "readers.py")}


def _read(metric, run):
    return harness.load_module(BENCH / "metrics" / f"{metric}.py").read(run)


def test_a_stall_moves_the_latency_tail_of_every_request_behind_it():
    mix = {"kind": "poisson", "rate_per_s": 400.0}
    pool = np.zeros((4, 2, 2, 3), np.float32)
    calm = _run_dict(harness.traffic.run(mix, _submit(), pool,
                                         np.random.default_rng(1), 1.0))
    stalled = _run_dict(harness.traffic.run(
        mix, _submit(stall_at=100, stall_s=0.3), pool,
        np.random.default_rng(1), 1.0))
    # the requests due during the 0.3 s stall (~120 at 400/s, over 10 %)
    # wait for it, though each is served in no time once submitted
    assert _read("latency_ms_p95.online", calm) < 20
    assert _read("latency_ms_p95.online", stalled) > 100
    assert len(stalled["latency_ms"]) == len(calm["latency_ms"])


def test_a_stall_lowers_the_rate_over_the_whole_window():
    mix = {"kind": "closed", "outstanding": 4, "max_batch": 4}
    pool = np.zeros((4, 2, 2, 3), np.float32)

    def slow(stall_at=None):
        inner = _submit(stall_at, 0.4)

        def submit(im):
            time.sleep(0.001)
            return inner(im)
        return submit
    calm = _run_dict(harness.traffic.run(mix, slow(), pool,
                                         np.random.default_rng(1), 1.0))
    stalled = _run_dict(harness.traffic.run(mix, slow(stall_at=50), pool,
                                            np.random.default_rng(1), 1.0))
    assert _read("images_per_s", stalled) < 0.8 * _read("images_per_s", calm)


def test_closed_loop_tops_the_last_batch_up_after_the_window():
    mix = {"kind": "closed", "outstanding": 3, "max_batch": 8}
    rec = harness.traffic.run(mix, _submit(), np.zeros((4, 2, 2, 3)),
                              np.random.default_rng(0), 0.05)
    assert len(rec["requests"]) % 8 == 0
    late = [r for r in rec["requests"] if r.due >= rec["t1"]]
    assert len(late) < 8
    assert late == rec["requests"][len(rec["requests"]) - len(late):]


def test_percentiles_are_taken_over_every_value():
    lib = harness.load_module(BENCH / "readers.py")
    values = list(range(1, 101))
    assert lib.percentile(values, 50) == pytest.approx(50.5)
    assert lib.percentile(values, 95) == pytest.approx(95.05)
    assert lib.percentile([], 95) is None


def test_readers_find_nothing_without_a_trace():
    run = {"trace": None, "launches": [], "lib":
           harness.load_module(BENCH / "readers.py")}
    for m in ("step_ms.offline", "mfu.offline", "int8_matmul_roofline",
              "device_idle_share.offline"):
        assert _read(m, run) is None


def test_images_are_a_function_of_the_seed_and_unlike_each_other():
    a = harness.images(np.random.default_rng([5, 2]), 6, 32)
    b = harness.images(np.random.default_rng([5, 2]), 6, 32)
    c = harness.images(np.random.default_rng([6, 2]), 6, 32)
    assert a.shape == (6, 32, 32, 3) and a.dtype == np.float32
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    # each image has a colour of its own: its channel means differ
    means = a.mean(axis=(1, 2))
    gaps = np.linalg.norm(means[:, None] - means[None], axis=-1)
    assert gaps[~np.eye(6, dtype=bool)].min() > 0.05


def _rows(n=8, k=50, noise=0.05, seed=0):
    rng = np.random.default_rng(seed)
    want = rng.standard_normal((n, k)) + 3.0      # a shared component
    rows = want + noise * rng.standard_normal((n, k))
    return rows, want


def test_route_ratio_sees_one_row_returned_to_the_wrong_request():
    rows, want = _rows()
    inv = np.arange(len(rows))
    sound = harness._gaps(rows, want, inv)
    assert sound["route_ratio_max"] < 0.5
    swapped = rows.copy()
    swapped[[0, 1]] = swapped[[1, 0]]
    assert harness._gaps(swapped, want, inv)["route_ratio_max"] > 1
    # a repeated image is the same image: its rows are not each other's
    # other image
    assert harness._gaps(rows[[0, 0, 1]], want[:2],
                         np.array([0, 0, 1]))["route_ratio_max"] < 0.5


def test_gap_max_is_relative_to_the_reference_row():
    rows, want = _rows(noise=0.0)
    rows[3] = want[3] * 1.2
    out = harness._gaps(rows, want, np.arange(len(rows)))
    assert out["gap_max"] == pytest.approx(0.2)


def test_reference_apot_takes_the_nearest_magnitude():
    """The reference's APoT weights (Eq. 5): each weight becomes the
    nearest sign x (2^-a + 2^-b) x (max - min) of its filter, a <= b <= 7,
    and the smallest code stands for zero."""
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    w = rng.standard_normal((64, 4)).astype(np.float32)
    got = np.asarray(harness.ref.apot_weights(jnp.asarray(w)))
    scale = w.max(0) - w.min(0)
    mags = sorted({2.0 ** -a + 2.0 ** -b for a in range(8)
                   for b in range(a, 8)})
    for i in range(w.shape[0]):
        for j in range(w.shape[1]):
            x = abs(w[i, j]) / scale[j]
            k = int(np.argmin([abs(x - m) for m in mags]))
            want = 0.0 if k == 0 else np.sign(w[i, j]) * mags[k] * scale[j]
            assert got[i, j] == pytest.approx(want, rel=1e-5, abs=1e-7)


def test_cli_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         BENCHMARK["workloads"][0]["name"], "--seed", "3000000019",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()

