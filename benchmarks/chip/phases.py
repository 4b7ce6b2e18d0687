"""The program's own spans (``repro.tracing``) on a profiler trace: what
each idle gap of the device waited on, and the serving path's host phases.

A traced window records the program's spans (``tracing.recording()``)
beside the benchmark's (``harness.Spans``).  ``place`` puts them on the
trace's clock the way ``trace.load`` places the benchmark's, and
``reduce`` adds to ``trace.reduce``'s numbers, which it leaves as they
are:

* ``phases``: every program span that lies wholly inside the window,
  ``(name, start_ns, end_ns, thread)``;
* ``alignment``: the share of the step program's executions that lie
  inside their batch's ``vision.launch`` start -> ``vision.sync`` end, the
  largest distance by which one sticks out, and the median offset of the
  program spans that centres them, applied to the keys below where the
  raw share is under ``ALIGNED``;
* ``idle_by_phase``: device idle seconds under each innermost program span
  (deepest on its thread, then latest to start), ``idle_covered_s`` (idle
  seconds under any) and ``idle_s``;
* ``gap_labels``: for the longest idle gaps, longest first as in
  ``trace.reduce``'s ``gaps``, the program span that holds most of each,
  else the benchmark span over it.

``stage_map`` reads each instruction's stage (``stem``, ``stage0``...,
``head``: the model's ``jax.named_scope``) from the compiled step
program's ``op_name`` metadata, or from its operands' where it has none
(a Pallas kernel's custom call carries none).  The readers at the end
compute the batch host path's metrics from ``phases``.

Run one cell's traced windows on the chip, with recording on and off,
after one untraced window:

    python3 benchmarks/chip/phases.py --workload <cell> --seed <n> \\
        [--seconds 5] [--windows on,off]

One JSON line per traced window (images/s inside it, the readers, idle
by phase, alignment, breakdown with program labels and device ops by
stage), then ``{"device": ...}`` last.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import contextlib
import importlib.util
import json
import re
import shutil
import sys
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

BENCH = Path(__file__).resolve().parent
SERVE_THREAD = "repro-serve"     # ServingDaemon's serve loop
LAUNCH, SYNC, BATCH = "vision.launch", "vision.sync", "vision.batch"
ALIGNED = 0.99                   # share inside that needs no shift
_SCOPE = re.compile(r"^(stem|stage\d+|head)$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")

Span = Tuple[str, float, float, str]


def _load_trace():
    spec = importlib.util.spec_from_file_location(
        "bench_phases_trace", BENCH / "trace.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


trace = _load_trace()


# ---------------------------------------------------------------------------
# placing the program's spans
# ---------------------------------------------------------------------------


def profile_start(path: str) -> int:
    """The trace's start on ``time.time_ns`` (its ``profile_start_time``)."""
    from jax.profiler import ProfileData
    env = ProfileData.from_file(path).find_plane_with_name(trace.ENV_PLANE)
    start = dict(env.stats).get(trace.START_STAT) if env else None
    if start is None:
        raise ValueError(f"the trace has no {trace.START_STAT!r}")
    return start


def place(events, start: int) -> List[Span]:
    """``repro.tracing`` events, on the clock of a trace that began at
    ``start``."""
    return [(n, s - start, e - start, th) for n, s, e, th in events]


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------


def _window(events: dict) -> Tuple[float, float]:
    for n, s, e in events["host"]:
        if n == trace.WINDOW:
            return s, e
    raise ValueError(f"no {trace.WINDOW!r} span in the trace")


def _shift(spans: List[Span], by: float) -> List[Span]:
    return [(n, s + by, e + by, th) for n, s, e, th in spans]


def brackets(spans: List[Span]) -> List[Tuple[float, float]]:
    """(``vision.launch`` start, end of the ``vision.sync`` after it on the
    same thread) for every launch followed by a sync."""
    out = []
    by_thread = collections.defaultdict(list)
    for sp in spans:
        if sp[0] in (LAUNCH, SYNC):
            by_thread[sp[3]].append(sp)
    for evs in by_thread.values():
        evs.sort(key=lambda sp: sp[1])
        for a, b in zip(evs, evs[1:]):
            if a[0] == LAUNCH and b[0] == SYNC:
                out.append((a[1], b[2]))
    return sorted(out)


def alignment(runs: List[Tuple[float, float]], spans: List[Span]) -> dict:
    """How the step program's executions ``runs`` lie against their
    batches' launch -> sync brackets.

    Each execution is matched to the bracket it overlaps most, else the
    nearest.  ``inside_share``: the executions that lie wholly inside;
    ``max_violation_ms``: the farthest one sticks out.  ``offset_ms``: the
    median over executions of the shift of the program spans that would
    centre each in its bracket's slack.  ``shift_ms``: the shift applied,
    ``offset_ms`` where the raw share was under ``ALIGNED`` and the shift
    puts more executions inside, else 0; the ``shifted_*`` numbers are
    after it.  None where no execution or no bracket is there.
    """
    br = brackets(spans)
    if not runs or not br:
        return None

    def measure(by):
        inside, worst, centre = 0, 0.0, []
        starts = [b[0] + by for b in br]
        for s, e in runs:
            i = bisect.bisect_right(starts, s)
            near = [j for j in (i - 2, i - 1, i, i + 1) if 0 <= j < len(br)]
            b0, b1 = max(
                ((br[j][0] + by, br[j][1] + by) for j in near),
                key=lambda b: (trace._overlap(s, e, *b),
                               -min(abs(s - b[0]), abs(e - b[1]))))
            out = max(b0 - s, e - b1, 0.0)
            inside += out == 0
            worst = max(worst, out)
            centre.append(((s - b0) - (b1 - e)) / 2)
        return inside / len(runs), worst * 1e-6, float(np.median(centre))

    share, worst, centre = measure(0.0)
    shift = 0.0
    moved = (share, worst)
    if share < ALIGNED:
        after = measure(centre)
        if after[0] > share:
            shift, moved = centre, after[:2]
    return {"executions": len(runs), "inside_share": share,
            "max_violation_ms": worst, "offset_ms": centre * 1e-6,
            "shift_ms": shift * 1e-6, "shifted_inside_share": moved[0],
            "shifted_max_violation_ms": moved[1]}


def _depths(spans: List[Span]) -> List[int]:
    """Each span's nesting depth among the spans of its thread."""
    depth = [0] * len(spans)
    by_thread = collections.defaultdict(list)
    for i, sp in enumerate(spans):
        by_thread[sp[3]].append(i)
    for idx in by_thread.values():
        idx.sort(key=lambda i: (spans[i][1], -spans[i][2]))
        stack: List[int] = []
        for i in idx:
            while stack and spans[stack[-1]][2] <= spans[i][1]:
                stack.pop()
            depth[i] = len(stack)
            stack.append(i)
    return depth


def innermost(spans: List[Span]) -> List[Tuple[float, float, str]]:
    """The timeline cut where any span starts or ends, each piece under
    the innermost span over it: the deepest on its thread, then the latest
    to start.  Pieces under no span are left out."""
    spans = [sp for sp in spans if sp[2] > sp[1]]
    depth = _depths(spans)
    edges = sorted({t for _, s, e, _ in spans for t in (s, e)})
    opens = collections.defaultdict(list)
    closes = collections.defaultdict(list)
    for i, (_, s, e, _) in enumerate(spans):
        opens[s].append(i)
        closes[e].append(i)
    live: set = set()
    out = []
    for t0, t1 in zip(edges, edges[1:]):
        live.difference_update(closes[t0])
        live.update(opens[t0])
        if live:
            top = max(live, key=lambda i: (depth[i], spans[i][1]))
            out.append((t0, t1, spans[top][0]))
    return out


def idle_gaps(events: dict, lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """Idle intervals of the first device with ops in (lo, hi)."""
    for ops in events["ops"].values():
        ops = trace._clip(ops, lo, hi)
        if ops:
            busy = trace._union(ops)
            edges = [lo] + [t for b in busy for t in b] + [hi]
            return [(a, b) for a, b in zip(edges[::2], edges[1::2])
                    if b > a]
    return []


def _bench_label(host, g0: float, g1: float) -> str:
    """The benchmark span over the gap most, without its prefix."""
    best = max(host, key=lambda h: trace._overlap(g0, g1, h[1], h[2]),
               default=None)
    if best is None or trace._overlap(g0, g1, best[1], best[2]) <= 0:
        return "(no span)"
    name = best[0]
    return name[len(trace.SPAN_PREFIX):] \
        if name.startswith(trace.SPAN_PREFIX) else name


def reduce(events: dict, program: List[Span], step: Optional[str] = None,
           top_gaps: int = 10) -> dict:
    """The program's spans against the trace's device ops (see the module
    doc).  ``events``: ``trace.load``'s; ``program``: ``place``d program
    spans; ``step``: the step program's name (``trace.reduce``'s
    ``step``), whose executions are held against the brackets."""
    lo, hi = _window(events)
    runs = sorted((s, e) for mods in events["modules"].values()
                  for n, s, e in mods if n == step and s >= lo and e <= hi)
    align = alignment(runs, program)
    if align and align["shift_ms"]:
        program = _shift(program, align["shift_ms"] * 1e6)
    inside = [sp for sp in program if sp[1] >= lo and sp[2] <= hi]
    near = [(n, max(s, lo), min(e, hi), th) for n, s, e, th in program
            if e > lo and s < hi]
    pieces = innermost(near)
    gaps = idle_gaps(events, lo, hi)
    by_phase: Dict[str, float] = collections.defaultdict(float)
    per_gap = [collections.defaultdict(float) for _ in gaps]
    starts = [p[0] for p in pieces]
    for k, (g0, g1) in enumerate(gaps):
        i = max(0, bisect.bisect_right(starts, g0) - 1)
        while i < len(pieces) and pieces[i][0] < g1:
            t = trace._overlap(g0, g1, pieces[i][0], pieces[i][1])
            if t > 0:
                by_phase[pieces[i][2]] += t * 1e-9
                per_gap[k][pieces[i][2]] += t
            i += 1
    order = sorted(range(len(gaps)), key=lambda k: gaps[k][0] - gaps[k][1])
    host = [h for h in trace._clip(events["host"], lo, hi)
            if h[0] != trace.WINDOW]
    labels = []
    for k in order[:top_gaps]:
        g0, g1 = gaps[k]
        label = max(per_gap[k].items(), key=lambda kv: kv[1],
                    default=(None, 0))[0]
        labels.append(((g1 - g0) * 1e-9,
                       label or _bench_label(host, g0, g1)))
    return {"phases": inside, "alignment": align,
            "idle_by_phase": dict(by_phase),
            "idle_covered_s": sum(by_phase.values()),
            "idle_s": sum(b - a for a, b in gaps) * 1e-9,
            "gap_labels": labels}


def stage_map(hlo_text: str) -> Dict[str, str]:
    """{instruction name: stage} over the entry computation of a compiled
    program: the first ``stem``/``stage<k>``/``head`` part of the
    instruction's ``op_name``, else (a kernel's custom call has no
    metadata) its first operand's stage, followed back."""
    entry = hlo_text[hlo_text.index("\nENTRY"):]
    end = entry.find("\n}")
    own: Dict[str, Optional[str]] = {}
    operands: Dict[str, List[str]] = {}
    for line in entry[:end if end > 0 else None].splitlines()[1:]:
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        md = _OP_NAME.search(rest)
        own[name] = next((p for p in md.group(1).split("/")
                          if _SCOPE.match(p)), None) if md else None
        operands[name] = _OPERAND.findall(
            rest.split(", metadata=")[0].split(", calls=")[0])
    memo: Dict[str, Optional[str]] = {}

    def stage(name: str) -> Optional[str]:
        if name not in memo:
            memo[name] = None       # a cycle ends here
            memo[name] = own[name] or next(
                filter(None, (stage(o) for o in operands[name]
                              if o in own)), None)
        return memo[name]

    return {name: s for name in own if (s := stage(name))}


def stage_seconds(op_s: Dict[str, float], stages: Dict[str, str]
                  ) -> Dict[str, float]:
    """Device seconds by stage (``(other)``: ops of no stage)."""
    out: Dict[str, float] = collections.defaultdict(float)
    for name, t in op_s.items():
        out[stages.get(name, "(other)")] += t
    return dict(out)


def breakdown(reduced: dict, ph: dict, stages: Dict[str, str],
              top: int = 10) -> dict:
    """``trace.breakdown`` with each gap labelled by ``ph``'s program span
    and each device op named ``<stage>/<op>`` where its stage is known."""
    ops = sorted(reduced["op_s"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[f"{stages[n]}/{n}" if n in stages else n, t]
                           for n, t in ops],
            "idle_gaps": [[label, t] for t, label in ph["gap_labels"][:top]]}


# ---------------------------------------------------------------------------
# readers: the batch host path, from ``phases``
# ---------------------------------------------------------------------------


def batches(phases: List[Span]) -> List[Tuple[Span, Dict[str, float]]]:
    """Each ``vision.batch`` span with the seconds of each phase inside it
    on its thread (summed where a phase recurs, as padding does)."""
    by_thread = collections.defaultdict(list)
    for sp in sorted(phases, key=lambda sp: sp[1]):
        by_thread[sp[3]].append(sp)
    starts = {th: [sp[1] for sp in sps] for th, sps in by_thread.items()}
    out = []
    for b in phases:
        if b[0] != BATCH:
            continue
        parts: Dict[str, float] = collections.defaultdict(float)
        sps = by_thread[b[3]]
        for n, s, e, _ in sps[bisect.bisect_left(starts[b[3]], b[1]):
                              bisect.bisect_right(starts[b[3]], b[2])]:
            if n != BATCH and e <= b[2]:
                parts[n] += (e - s) * 1e-9
        out.append((b, dict(parts)))
    return out


def _mean_ms(values) -> Optional[float]:
    return float(np.mean(values)) * 1e3 if len(values) else None


def batch_host_ms(phases: List[Span]) -> Optional[float]:
    """Mean over executed batches of ``vision.batch`` less its
    ``vision.sync``, ms."""
    return _mean_ms([(b[2] - b[1]) * 1e-9 - p.get(SYNC, 0.0)
                     for b, p in batches(phases)])


def phase_ms(phases: List[Span], phase: str) -> Optional[float]:
    """Mean over executed batches of ``phase``'s time in each, ms."""
    return _mean_ms([p.get(phase, 0.0) for _, p in batches(phases)])


def batch_ms_p95(phases: List[Span]) -> Optional[float]:
    """95th percentile of ``vision.batch``, ms."""
    t = [(e - s) * 1e-6 for n, s, e, _ in phases if n == BATCH]
    return float(np.percentile(t, 95)) if t else None


def inline_batch_share(phases: List[Span]) -> Optional[float]:
    """% of ``vision.batch`` spans on a thread other than the daemon's."""
    th = [t for n, _, _, t in phases if n == BATCH]
    return sum(t != SERVE_THREAD for t in th) / len(th) * 100 if th \
        else None


def readings(phases: List[Span]) -> Dict[str, Optional[float]]:
    """The batch host path's metrics, by the names a benchmark entry would
    give them (the cell's suffix left off)."""
    return {"batch_host_ms": batch_host_ms(phases),
            "assemble_ms": phase_ms(phases, "vision.assemble"),
            "put_ms": phase_ms(phases, "vision.put"),
            "batch_ms_p95": batch_ms_p95(phases),
            "inline_batch_share": inline_batch_share(phases)}


# ---------------------------------------------------------------------------
# traced windows on the chip
# ---------------------------------------------------------------------------


def traced_window(harness, mix: dict, submit, pool, rng, seconds: float,
                  stats, record: bool) -> dict:
    """``harness.traced_window``'s window, with the program's spans
    recorded over it when ``record``: the trace's events, the placed
    program spans, images/s inside the window and batches."""
    import jax
    from repro import tracing

    shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    spans = harness.Spans()
    items, batches_ = stats.items, stats.batches
    with (tracing.recording() if record
          else contextlib.nullcontext()) as rec:
        jax.profiler.start_trace(str(harness.TRACE_DIR),
                                 profiler_options=options)
        with spans("window"):
            run = harness.traffic.run(mix, submit, pool, rng, seconds,
                                      spans)
        harness.wait_for(run)
        jax.profiler.stop_trace()
    path = harness._trace_file()
    events = harness.trace_mod.load(path, spans.events)
    program = place(rec.events, profile_start(path)) if record else []
    shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    t0, t1 = run["t0"], run["t1"]
    sent = [r for r in run["requests"] if r.due < t1]
    done = sum(1 for r in sent if r.handle is not None
               and r.handle.state == "DONE" and r.done <= t1)
    return {"events": events, "program": program,
            "images_per_s": done / (t1 - t0),
            "failed": sum(1 for r in sent if r.handle is None
                          or r.handle.state != "DONE"),
            "batches": stats.batches - batches_,
            "images_per_batch": (stats.items - items)
            / max(stats.batches - batches_, 1),
            "dropped": rec.dropped if record else 0}


def window_line(w: dict, stages: Dict[str, str], record: bool) -> dict:
    """One traced window reduced to its result line."""
    reduced = trace.reduce(w["events"])
    line = {"recording": record, "images_per_s": w["images_per_s"],
            "failed": w["failed"], "batches": w["batches"],
            "images_per_batch": w["images_per_batch"],
            "idle_share": 100 * (1 - reduced["busy_s"] / reduced["window_s"]),
            "step_ms": float(np.mean(reduced["step_times"])) * 1e3
            if reduced["step_times"] else None,
            "device_s_by_stage": stage_seconds(reduced["op_s"], stages),
            "breakdown": trace.breakdown(reduced)}
    if record:
        ph = reduce(w["events"], w["program"], reduced["step"])
        line.update(readings(ph["phases"]))
        line.update(
            alignment=ph["alignment"], idle_s=ph["idle_s"],
            idle_covered_s=ph["idle_covered_s"],
            idle_covered_share=100 * ph["idle_covered_s"] / ph["idle_s"]
            if ph["idle_s"] else None,
            idle_by_phase=dict(sorted(ph["idle_by_phase"].items(),
                                      key=lambda kv: -kv[1])),
            breakdown=breakdown(reduced, ph, stages),
            spans=len(w["program"]), dropped=w["dropped"])
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0,
                    help="each window's, the untraced first one's too")
    ap.add_argument("--windows", default="on,off",
                    help="recording on or off in each traced window")
    args = ap.parse_args(argv)
    windows = [w == "on" for w in args.windows.split(",")]

    sys.path.insert(0, str(BENCH))
    import harness
    import run as run_py
    spec = harness.cell_spec(
        json.loads((harness.ROOT / "BENCHMARK.json").read_text()),
        args.workload)
    import jax
    try:
        run_py.device_peaks(jax, spec["chips"])
    except run_py.NoChip as e:
        print(f"phases.py: {e}", file=sys.stderr)
        return 3
    run_py.compile_cache(jax)
    config, mix = spec["config"], spec["mix"]
    s = harness.set_up(config, mix, args.seed)
    eng, daemon = s["engine"], s["daemon"]
    x = jax.ShapeDtypeStruct((max(mix["buckets"]), config["img_res"],
                              config["img_res"], 3), np.float32)
    with eng._dispatch_scope():
        stages = stage_map(eng._fwd.lower(eng.params, x).compile().as_text())
    harness.log(f"stage map: {len(stages)} instructions, "
                f"{dict(collections.Counter(stages.values()))}")

    def submit(im):
        return daemon.submit(im, slo=mix["slo"])

    warm = harness.traffic.run(mix, submit, s["pool"],
                               harness._rng(args.seed, 3), args.seconds)
    harness.wait_for(warm)
    for k, record in enumerate(windows):
        w = traced_window(harness, mix, submit, s["pool"],
                          harness._rng(args.seed, 5 + k), args.seconds,
                          eng.stats, record)
        line = window_line(w, stages, record)
        harness.log(f"window {k} recording={'on' if record else 'off'}: "
                    f"{line['images_per_s']:.1f} images/s, idle "
                    f"{line['idle_share']:.2f} %, covered "
                    f"{line.get('idle_covered_share')}, device s by stage "
                    f"{line['device_s_by_stage']}")
        print(json.dumps({"window": k, **line}), flush=True)
    daemon.shutdown(drain=False)
    print(json.dumps({"device": harness.device_info(jax)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
