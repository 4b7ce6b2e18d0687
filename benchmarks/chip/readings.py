"""Readings that set a cell's limits: the compared numbers of sound runs
over many seeds, of the control in the program's place, and of the
program with a fault planted under its timed path.

    python3 benchmarks/chip/readings.py --workload <cell> \\
        --seeds 101,102,... --control-seeds 3 --seconds 3 \\
        --faults swap_one_slot,leak_padding --fault-seeds 901,902,903

One process: each seed is a whole run of the cell (set-up, a short window
at the cell's own load, the check) with the control read beside the
program on the first ``--control-seeds`` seeds; then each fault of
``faults.py`` named in ``--faults`` on each of ``--fault-seeds``.  The
benchmark's own runs never read the control or plant a fault.  Prints
one JSON line per run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json      # noqa: E402
import sys       # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args(argv)

    import faults
    import harness
    import run as run_cli
    spec = harness.cell_spec(
        json.loads((harness.ROOT / "BENCHMARK.json").read_text()),
        args.workload)
    import jax
    try:
        peaks = run_cli.device_peaks(jax, spec["chips"])
    except run_cli.NoChip as e:
        print(f"readings.py: {e}", file=sys.stderr)
        return 3
    run_cli.compile_cache(jax)
    runs = [(int(x), k < args.control_seeds, None)
            for k, x in enumerate(args.seeds.split(","))]
    runs += [(int(x), False, name) for name in filter(None,
                                                      args.faults.split(","))
             for x in args.fault_seeds.split(",")]
    for seed, control, fault in runs:
        with (faults.planted(fault) if fault else contextlib.nullcontext()):
            out = harness.run_cell(spec, seed, args.seconds, False,
                                   time.perf_counter(), peaks,
                                   control=control)
        row = {"seed": seed, "fault": fault, "correct": out["correct"],
               "checks": {n: c["value"] for n, c in out["checks"].items()},
               "control": out.get("control"),
               "metrics": {n: m["value"] for n, m in out["metrics"].items()}}
        print(json.dumps(row), flush=True)
    print(json.dumps({"workload": args.workload,
                      "total_s": time.perf_counter() - T_START}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
