"""The EfficientViT-B2 cell (``b2-int8-offline``): its entries in
BENCHMARK.json, the ``relu_attn`` work count and roofline reader, the
block scopes of the forward, and one whole closed-loop run of the harness
on the CPU at the registry's reduced B2, which keeps B2's 32-wide heads
(two in its last stage), with the Pallas kernels in interpret mode.

* the B2 cell resolves to the B2 file, the offline mix and its metrics,
  and the B1 cells keep the metrics they had;
* the sound run at the reduced B2 comes out correct against the plain
  reference, and the control (the reference at int4 in the program's
  place) fails ``gap_max``;
* the forward's ``mbconv`` / ``msa`` scopes reach the compiled program's
  metadata and change none of its instructions.
"""
import contextlib
import json
import re
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
CONFIG = json.loads((Path(__file__).parent / "data"
                     / "evit-b2-reduced-int8.json").read_text())
CLOSED = {"kind": "closed", "slo": "bulk",
          "classes": [{"name": "bulk", "priority": 0,
                       "max_delay_ms": 1000.0}],
          "outstanding": 16, "max_batch": 8, "buckets": [8], "pool": 12,
          "sample": 12}
PEAKS = {"bf16_flops_per_s": 1e12, "int8_ops_per_s": 2e12,
         "hbm_bytes_per_s": 1e11}
SEED = 3_000_000_016          # past 2**31, as the benchmark's seeds may be

OFFLINE = ["step_ms.offline", "mfu.offline", "int8_matmul_roofline",
           "dwconv_w4_roofline", "device_idle_share.offline"]


def _names(metrics):
    return [m["name"] for m in metrics]


def test_b2_cell_resolves_to_the_b2_file_and_the_offline_mix():
    spec = harness.cell_spec(BENCHMARK, "b2-int8-offline")
    assert spec["chips"] == 1
    assert spec["config"] == json.loads(
        (BENCH / "configs" / "evit-b2-r224-int8.json").read_text())
    assert spec["mix"] == json.loads(
        (BENCH / "traffic" / "offline-closed.json").read_text())
    assert _names(spec["end_to_end"]) == ["images_per_s", "setup_s"]
    assert _names(spec["per_layer"]) == (["quantize_s", "warmup_s"] + OFFLINE
                                         + ["relu_attn_roofline"])


@pytest.mark.parametrize("cell,per_layer", [
    ("b1-int8-offline", ["quantize_s", "warmup_s"] + OFFLINE),
    ("b1-int8-online", ["latency_ms_p95.online", "quantize_s", "warmup_s",
                        "submit_lag_ms_p95.online",
                        "queue_wait_ms_p95.online", "step_ms.online"]),
])
def test_b1_cells_keep_their_metrics(cell, per_layer):
    spec = harness.cell_spec(BENCHMARK, cell)
    assert _names(spec["per_layer"]) == per_layer


def test_relu_attn_work_at_a_b2_shape():
    """Stage 3 of B2 at bucket 16: 196 tokens, 6 heads of 32."""
    call = {"kind": "relu_attn", "M": 196, "N": 32, "K": 96,
            "meta": {"B": 16, "N": 196, "H": 6, "D": 32}}
    wc = harness.load_module(BENCH / "workcount" / "relu_attn.py")
    ops, nbytes = wc.work(call, 2)
    # k^T v and q (k^T v): 196 x 32 x 32 MACs each; k-sum and q . k-sum:
    # 196 x 32 each; per batch row and head, two operations per MAC
    assert ops == 2 * 16 * 6 * (2 * 196 * 32 * 32 + 2 * 196 * 32) \
        == 79_478_784
    # q, k, v and the output, 16 x 196 x 6 x 32 bf16 elements each
    assert nbytes == 4 * 602_112 * 2 == 4_816_896
    assert wc.PEAK == "int8_ops_per_s"


def test_relu_attn_roofline_reads_the_traced_kernel_time():
    call = {"kind": "relu_attn", "M": 49, "N": 32, "K": 192,
            "meta": {"B": 16, "N": 49, "H": 12, "D": 32}}
    reader = harness.load_module(BENCH / "metrics" / "relu_attn_roofline.py")
    lib = harness.load_module(BENCH / "readers.py")
    wc = harness.load_module(BENCH / "workcount" / "relu_attn.py")
    ops, nbytes = wc.work(call, 2)
    least = max(ops / PEAKS["int8_ops_per_s"],
                nbytes / PEAKS["hbm_bytes_per_s"])
    run = {"trace": {"kernel_s": {"relu_attn": 40 * least},
                     "step_times": [1.0, 1.0]},
           "launches": [call, dict(call, kind="int8_matmul")],
           "peaks": PEAKS, "dtype_bytes": 2, "lib": lib,
           "workcount": lambda k: harness.load_module(
               BENCH / "workcount" / f"{k}.py")}
    # two traced forwards of one launch each, in 40 least times
    assert reader.read(run) == pytest.approx(5.0)
    assert reader.read(dict(run, trace=None)) is None
    assert reader.read(dict(run, launches=run["launches"][1:])) is None


def _instructions(text):
    """(name, opcode and shape) of every instruction, metadata left out."""
    return re.findall(r"^\s*(?:ROOT\s+)?%(\S+)\s*=\s*(\S+ \S+?)\(", text,
                      re.M)


def test_block_scopes_reach_the_metadata_and_change_no_instruction(
        monkeypatch):
    import jax
    from repro.configs.registry import REDUCED
    from repro.models import get_model

    cfg = REDUCED["efficientvit-b2-r224"]
    model = get_model(cfg)
    params = jax.eval_shape(lambda: model.init(cfg, jax.random.PRNGKey(0)))
    x = jax.ShapeDtypeStruct((2, cfg.img_res, cfg.img_res, 3), np.float32)

    def compiled():
        return jax.jit(lambda p, im: model.forward(cfg, p, im)).lower(
            params, x).compile().as_text()

    scoped = compiled()
    last = len(cfg.widths) - 1
    for kind in ("mbconv", "msa"):
        assert f"stage{last}/{kind}/" in scoped
    assert "stage0/mbconv/" in scoped and "stage0/msa/" not in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = compiled()
    assert "stage0/" not in bare
    assert _instructions(scoped) == _instructions(bare)
    assert len(_instructions(scoped)) > 100


@pytest.fixture(scope="module")
def interpret_kernels():
    """Dispatch the Pallas kernels on the CPU, in interpret mode."""
    with pytest.MonkeyPatch.context() as mp:
        for axis in ("", "_CONV", "_ATTN"):
            mp.setenv(f"REPRO_PALLAS{axis}_DISPATCH", "1")
        yield


@pytest.fixture(scope="module")
def b2_run(interpret_kernels):
    spec = harness.cell_spec(BENCHMARK, "b2-int8-offline")
    spec["config"], spec["mix"] = CONFIG, CLOSED
    return harness.run_cell(spec, SEED, 1.5, False, time.perf_counter(),
                            PEAKS, on_tpu=False, control=True)


def test_reduced_b2_keeps_the_32_wide_heads():
    from repro.configs.registry import REDUCED
    cfg = REDUCED["efficientvit-b2-r224"]
    assert harness.program_config(CONFIG) is cfg
    assert cfg.dim_per_head == 32 == CONFIG["head_dim"]
    assert cfg.widths[-1] // cfg.dim_per_head >= 2
    assert "head_dim" not in CONFIG["reduced"]


def test_sound_b2_run_is_correct(b2_run):
    checks = b2_run["checks"]
    assert b2_run["correct"], checks
    for name in ("gap_max", "route_ratio_max"):
        assert checks[name]["value"] < checks[name]["limit"]
    assert b2_run["attempted"] > 0 and b2_run["failed"] == 0
    assert set(b2_run["metrics"]) == {"images_per_s", "setup_s"}


def test_b2_control_fails_the_comparison(b2_run):
    ctrl = b2_run["control"]
    assert ctrl["gap_max"] > 3 * b2_run["checks"]["gap_max"]["value"]
    assert ctrl["gap_max"] > CONFIG["limits"]["gap_max"]
