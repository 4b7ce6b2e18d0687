"""Find the knee of an open-loop cell: the highest offered rate at which
the backlog does not grow over the window.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \\
        --seconds <per rate> --rates 400,800,...

One process and one set-up; each rate then gets a window of its own.  A
rate holds when the requests sent in its window were answered inside it
(all but 2 %) and the second half of them waited no longer than the
first half (median within 1.5x + 2 ms): a growing queue fails both.
Prints one JSON line per rate and, last, the knee.  Its result goes into
the mix's file by hand, with the sweep recorded in PERF.md.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json      # noqa: E402
import sys       # noqa: E402

import numpy as np  # noqa: E402


def holds(row: dict) -> bool:
    return (row["answered_in_window"] >= 0.98 * row["sent"]
            and row["p50_second_half_ms"]
            <= 1.5 * row["p50_first_half_ms"] + 2.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    import harness
    import run as run_cli
    spec = harness.cell_spec(
        json.loads((harness.ROOT / "BENCHMARK.json").read_text()),
        args.workload)
    import jax
    try:
        run_cli.device_peaks(jax, spec["chips"])
    except run_cli.NoChip as e:
        print(f"sweep.py: {e}", file=sys.stderr)
        return 3
    run_cli.compile_cache(jax)
    mix = spec["mix"]
    s = harness.set_up(spec["config"], mix, args.seed)
    knee = None
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        rec = harness.traffic.run(
            dict(mix, rate_per_s=rate),
            lambda im: s["daemon"].submit(im, slo=mix["slo"]), s["pool"],
            np.random.default_rng([args.seed, 10 + k]), args.seconds)
        harness.wait_for(rec)
        sent = [r for r in rec["requests"] if r.handle is not None]
        lat = [(r.done - r.due) * 1e3 for r in sent]
        half = len(lat) // 2
        row = {"rate_per_s": rate, "sent": len(rec["requests"]),
               "answered_in_window": sum(1 for r in sent
                                         if r.done <= rec["t1"]),
               "p50_ms": float(np.median(lat)),
               "p95_ms": float(np.percentile(lat, 95)),
               "p50_first_half_ms": float(np.median(lat[:half])),
               "p50_second_half_ms": float(np.median(lat[half:]))}
        row["holds"] = holds(row)
        print(json.dumps(row), flush=True)
        if row["holds"]:
            knee = rate
    s["daemon"].shutdown(drain=False)
    print(json.dumps({"workload": args.workload, "knee_per_s": knee,
                      "setup_and_sweep_s": time.perf_counter() - T_START}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
