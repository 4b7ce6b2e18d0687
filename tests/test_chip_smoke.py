"""CPU rehearsal of ``chip_smoke.py``: its phase functions at the REDUCED
configs with the Pallas kernels in interpret mode, and its refusal to run
anywhere without a TPU."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

from repro.configs.registry import REDUCED
from repro.kernels import ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")
ALL_KERNELS = ops.DispatchConfig(dense=True, conv=True, attn=True)


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_vision_phase_rehearsal(chip_smoke):
    ops.reset_trip_latch()
    res = chip_smoke.phase_vision(REDUCED["efficientvit-b1-r224"], seed=0,
                                  dispatch=ALL_KERNELS, log=lambda s: None)
    assert res["delivered"] == chip_smoke.N_IMAGES and not res["errors"]
    assert res["finite"] and res["buckets"] == chip_smoke.BUCKETS
    assert res["parity_rel"] < chip_smoke.PARITY_BOUND
    assert res["dispatched"] == ["dwconv_w4", "m2q_matmul", "relu_attn"]
    # interpret mode lowers the kernels to plain HLO, so the kernel-count
    # check must flag every dispatched kind — and nothing else
    assert res["counts"] == {}
    assert chip_smoke.vision_failures(res) == [
        f"vision: no tpu_custom_call for dispatched kernel {k}"
        for k in res["dispatched"]]
    assert chip_smoke.vision_failures(
        dict(res, counts={k: 1 for k in res["dispatched"]})) == []
    # a tripped guard fails the phase
    tripped = dict(res, counts={k: 1 for k in res["dispatched"]},
                   trips={"latch": {"dense": 1}, "guard": 1})
    assert chip_smoke.vision_failures(tripped)[0].startswith(
        "vision: FallbackGuard tripped")


def test_token_phase_rehearsal(chip_smoke):
    ops.reset_trip_latch()
    res = chip_smoke.phase_tokens(REDUCED["qwen1.5-0.5b"], seed=0,
                                  dispatch=ALL_KERNELS, log=lambda s: None)
    assert len(res["tokens"]) == chip_smoke.N_REQUESTS
    assert all(len(t) == chip_smoke.MAX_NEW for t in res["tokens"])
    assert "m2q_matmul" in res["dispatched"]
    assert chip_smoke.token_failures(res) == []
    assert chip_smoke.token_failures(dict(res, tokens=res["tokens"][:2]))


def test_mesh_checks_catch_sharding_faults(chip_smoke):
    """The --chips 4 comparison passes rounding-level differences (a greedy
    flip that stays near the top of the one-chip logits) and fails a row
    landing on another image, a distant result, an off-logit token or a
    trip."""
    zero = {"latch": {"dense": 0, "conv": 0, "attn": 0}, "guard": 0}
    n, k = chip_smoke.N_IMAGES, chip_smoke.MESH_TOP_K
    toks = [[1] * chip_smoke.MAX_NEW] * chip_smoke.N_REQUESTS
    tok = {"tokens": toks, "errors": [], "trips": zero, "ranks": [0, 1]}
    vision = {"errors": [], "delivered": n, "rel": 0.041, "own_rows": True,
              "buckets": chip_smoke.BUCKETS, "trips": zero,
              "trips_one": zero}
    ok = {"vision": vision,
          "tokens": {"one": tok, "1x4": dict(tok, ranks=[0, k - 1])}}
    assert chip_smoke.mesh_failures(ok) == []
    bad_vision = [{"own_rows": False}, {"rel": chip_smoke.MESH_BOUND},
                  {"trips_one": dict(zero, guard=1)}]
    bad_tokens = [{"1x4": dict(tok, ranks=[k])},
                  {"one": dict(tok, tokens=toks[:1])}]
    for change in bad_vision:
        res = dict(ok, vision=dict(vision, **change))
        assert len(chip_smoke.mesh_failures(res)) == 1, change
    for change in bad_tokens:
        res = dict(ok, tokens=dict(ok["tokens"], **change))
        assert len(chip_smoke.mesh_failures(res)) == 1, change


@pytest.mark.parametrize("alone", [False, True],
                         ids=["in-checkout", "script-alone"])
def test_script_refuses_to_run_without_a_tpu(alone, tmp_path):
    """No TPU: the script prints the device line, exits non-zero and prints
    no result — in the checkout, and copied into an otherwise empty
    directory."""
    script = SCRIPT
    if alone:
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SCRIPT, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, script], capture_output=True,
                         text=True, timeout=120, cwd=os.path.dirname(script),
                         env=env)
    assert out.returncode != 0
    assert out.stdout.splitlines()[0].startswith("device: platform=cpu")
    for line in out.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
