"""Run one cell of BENCHMARK.json once, on the chip this process holds.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

Run from the root of a checkout.  The last line of standard output is the
result, one JSON object; the compared numbers and their limits are also
the last lines of standard error.  Exits non-zero, printing no result,
when JAX finds no TPU, fewer chips than the cell asks for, or a device
kind that ``peaks.json`` does not hold.

JAX's persistent compilation cache goes to ``$JAX_COMPILATION_CACHE_DIR``
where that is set, else to ``<checkout>/.jax_cache``; every compile is
kept, also the quick ones, so that only a checkout's first run compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json      # noqa: E402
import sys       # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent


class NoChip(RuntimeError):
    """The process holds no device this cell can run on."""


def device_peaks(jax, chips: int) -> dict:
    """The device's row of peaks.json, after checking that JAX holds at
    least ``chips`` TPUs of a known kind."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX holds {len(devs)}")
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    kind = devs[0].device_kind
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def compile_cache(jax) -> None:
    from repro.checkout import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    spec = harness.cell_spec(
        json.loads((harness.ROOT / "BENCHMARK.json").read_text()),
        args.workload)
    import jax
    try:
        peaks = device_peaks(jax, spec["chips"])
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    compile_cache(jax)
    out = harness.run_cell(spec, args.seed, args.seconds, bool(args.trace),
                           T_START, peaks)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
