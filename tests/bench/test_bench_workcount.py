"""Work counts, model operations and the peaks table of the benchmark
(benchmarks/chip/workcount/, modelops.py, peaks.json, run.py)."""
import importlib.util
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"


def _load(rel):
    spec = importlib.util.spec_from_file_location(
        "bench_" + rel.replace("/", "_").replace(".", "_") + "_under_test",
        BENCH / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


# B1 R224 at bucket 16: the stage-3 1x1 conv 128 -> 512 over 14 x 14 maps
PW = {"kind": "int8_matmul", "M": 16 * 14 * 14, "N": 512, "K": 128,
      "meta": {}}


@pytest.mark.parametrize("kernel", ["int8_matmul"])
def test_matmul_work_at_a_b1_shape(kernel):
    ops, nbytes = _load(f"workcount/{kernel}.py").work(PW, 2)
    assert ops == 2 * 3136 * 512 * 128 == 411_041_792
    # int8 x (3136, 128) + int8 w (128, 512) + bf16 y (3136, 512)
    assert nbytes == 401_408 + 65_536 + 3_211_264


@pytest.mark.parametrize("stride,ops_want,bytes_want", [
    # stage 0: 3x3 over 16 x 112 x 112 x 64, stride 1
    (1, 231_211_008, (12_845_056 + 12_845_056) * 2 + 288),
    # stage-1 entry: the same map at stride 2 -> 56 x 56
    (2, 57_802_752, (12_845_056 + 3_211_264) * 2 + 288),
])
def test_dwconv_work_at_a_b1_shape(stride, ops_want, bytes_want):
    call = {"kind": "dwconv_w4", "M": 0, "N": 64, "K": 9,
            "meta": {"B": 16, "H": 112, "W": 112, "C": 64, "kh": 3,
                     "kw": 3, "stride": stride}}
    ops, nbytes = _load("workcount/dwconv_w4.py").work(call, 2)
    assert ops == ops_want
    assert nbytes == bytes_want


def test_every_work_count_names_a_peak_of_the_table():
    row = json.loads((BENCH / "peaks.json").read_text())["devices"][
        "TPU v5 lite"]
    for path in (BENCH / "workcount").glob("*.py"):
        assert _load(f"workcount/{path.name}").PEAK in row


def test_v5e_peaks_are_the_published_ones():
    row = json.loads((BENCH / "peaks.json").read_text())["devices"][
        "TPU v5 lite"]
    assert row["bf16_flops_per_s"] == 197e12
    assert row["int8_ops_per_s"] == 393e12
    assert row["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("config", ["evit-b1-r224-int8",
                                    "evit-b2-r224-int8"])
def test_model_ops_match_xla_cost_analysis(config):
    """Operations per image from shapes, against XLA's count for the float
    forward of the model as the program builds it (which also counts the
    norms and activations, a few percent)."""
    import jax
    import jax.numpy as jnp
    sys.path.insert(0, str(BENCH.parents[1] / "src"))
    from repro.configs.registry import ARCHS
    from repro.models import get_model

    c = _cfg(config)
    cfg = ARCHS[c["arch"]].replace(dtype="float32")
    model = get_model(cfg)
    params = jax.eval_shape(lambda: model.init(cfg, jax.random.PRNGKey(0)))
    x = jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.float32)
    cost = jax.jit(lambda p, im: model.forward(cfg, p, im)).lower(
        params, x).cost_analysis()
    xla = cost["flops"]
    ours = _load("modelops.py").ops_per_image(c)
    assert 0.95 * xla <= ours <= xla


def _fake_jax(platform, kind, n=1):
    dev = SimpleNamespace(platform=platform, device_kind=kind)
    return SimpleNamespace(devices=lambda: [dev] * n)


def test_unknown_device_kind_is_an_error():
    run = _load("run.py")
    with pytest.raises(run.NoChip, match="peaks.json"):
        run.device_peaks(_fake_jax("tpu", "TPU v99 imaginary"), 1)


def test_no_tpu_and_too_few_chips_are_errors():
    run = _load("run.py")
    with pytest.raises(run.NoChip, match="no TPU"):
        run.device_peaks(_fake_jax("cpu", "cpu"), 1)
    with pytest.raises(run.NoChip, match="needs 4 chips"):
        run.device_peaks(_fake_jax("tpu", "TPU v5 lite"), 4)
    assert run.device_peaks(_fake_jax("tpu", "TPU v5 lite"), 1)[
        "int8_ops_per_s"] == 393e12
