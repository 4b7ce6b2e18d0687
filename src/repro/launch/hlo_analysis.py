"""Loop-aware static analysis of optimized (post-SPMD) HLO text.

``compiled.cost_analysis()`` visits each while-loop body ONCE, which
undercounts scanned-layer models by ~L and chunked attention by the chunk
count.  This module re-derives roofline inputs from the HLO text itself:

* computations are segmented; every ``while`` op's body/condition are
  resolved; trip counts are recovered from the loop-bound constant in the
  condition computation; nested loops multiply.
* FLOPs: dot ops contribute 2 * prod(result_dims) * prod(contracting_dims)
  (x trip multiplier), split by operand dtype (int8 dots run at 2x bf16 peak
  on the MXU — the M2Q uniform-half advantage); convolutions are estimated
  from kernel size.
* Traffic: per top-level op (post-fusion), result + operand bytes
  (x multiplier), excluding pure control ops — an HBM-traffic proxy at the
  same altitude XLA's own cost model uses, but loop-aware.
* Collectives: result bytes per opcode (x multiplier).

All numbers are PER PARTITION (the SPMD module is per-device).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

_CONTROL_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "while",
    "conditional", "call", "bitcast", "after-all", "partition-id",
    "replica-id", "get-dimension-size", "copy-done", "all-gather-done",
    "all-reduce-done", "collective-permute-done", "opt-barrier",
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_TYPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s+(ROOT\s+)?%?([\w.-]+)\s*=\s*"
    r"(\(.*?\)|[a-z][a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"([a-z][a-z0-9-]*)\((.*)$")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.-]+)\s*(?:\(.*\))?\s*->.*{")
_ENTRY_RE = re.compile(r"^ENTRY\s+%?([\w.-]+)", re.M)
_NAME_REF_RE = re.compile(r"%([\w.-]+)")
_CALLEE_ATTR_RE = re.compile(r"(?:calls|to_apply)=%?([\w.-]+)")
_WHILE_COMP_RE = re.compile(r"(?:body|condition)=%?([\w.-]+)")


def _shape_elems(dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n


def _tok_bytes(tok: str) -> int:
    total = 0
    for dt, dims in _TYPE_RE.findall(tok):
        total += _shape_elems(dims) * _DTYPE_BYTES.get(dt, 4)
    return total


def _tok_first_shape(tok: str) -> Tuple[str, List[int]]:
    m = _TYPE_RE.search(tok)
    if not m:
        return "f32", []
    dims = [int(d) for d in m.group(2).split(",")] if m.group(2) else []
    return m.group(1), dims


@dataclasses.dataclass
class Instr:
    name: str
    opcode: str
    result_tok: str
    args: str  # everything after the opening paren (operands + attrs)
    is_root: bool = False

    def split_args(self) -> Tuple[str, str]:
        depth = 1
        for i, ch in enumerate(self.args):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    return self.args[:i], self.args[i + 1:]
        return self.args, ""

    def operand_names(self) -> List[str]:
        ops, _ = self.split_args()
        return _NAME_REF_RE.findall(ops)

    def attrs(self) -> str:
        return self.split_args()[1]


def parse_computations(text: str) -> Dict[str, List[Instr]]:
    comps: Dict[str, List[Instr]] = {}
    cur: Optional[str] = None
    for line in text.splitlines():
        if cur is None:
            m = _COMP_RE.match(line)
            if m:
                cur = m.group(1)
                comps[cur] = []
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTR_RE.match(line)
        if m:
            comps[cur].append(Instr(name=m.group(2), result_tok=m.group(3),
                                    opcode=m.group(4), args=m.group(5),
                                    is_root=bool(m.group(1))))
    return comps


def _trip_count(comps, cond_name: str) -> int:
    """Loop bound = the largest small-int constant compared in the cond."""
    best = 1
    for ins in comps.get(cond_name, []):
        if ins.opcode == "constant":
            m = re.match(r"\s*(-?\d+)\s*\)?", ins.args)
            if m:
                v = int(m.group(1))
                if 1 <= v <= 10_000_000:
                    best = max(best, v)
    return best


def computation_multipliers(comps) -> Dict[str, int]:
    """Execution-count multiplier per computation (nested loops compose)."""
    mult = {name: 0 for name in comps}
    referenced = set()
    per_comp_callees: Dict[str, List[Tuple[str, int]]] = {n: [] for n in comps}
    for cname, instrs in comps.items():
        for ins in instrs:
            if ins.opcode == "while":
                m_b = re.search(r"body=%?([\w.-]+)", ins.args)
                m_c = re.search(r"condition=%?([\w.-]+)", ins.args)
                if m_b and m_c:
                    trip = _trip_count(comps, m_c.group(1))
                    per_comp_callees[cname].append((m_b.group(1), trip))
                    per_comp_callees[cname].append((m_c.group(1), trip))
                    referenced.update((m_b.group(1), m_c.group(1)))
            else:
                for m in re.finditer(r"(?:to_apply|calls)=%?([\w.-]+)",
                                     ins.args):
                    per_comp_callees[cname].append((m.group(1), 1))
                    referenced.add(m.group(1))
                m = re.search(r"branch_computations=\{([^}]*)\}", ins.args)
                if m:
                    for b in m.group(1).split(","):
                        b = b.strip().lstrip("%")
                        per_comp_callees[cname].append((b, 1))
                        referenced.add(b)
    roots = [n for n in comps if n not in referenced]
    for r in roots:
        mult[r] = 1
    changed = True
    iters = 0
    while changed and iters < 100:
        changed = False
        iters += 1
        for cname, callees in per_comp_callees.items():
            if mult.get(cname, 0) <= 0:
                continue
            for callee, k in callees:
                want = mult[cname] * k
                if callee in mult and mult[callee] < want:
                    mult[callee] = want
                    changed = True
    return mult


def _dot_flops(ins: Instr, shapes: Dict[str, str]) -> Tuple[float, str]:
    _, res = _tok_first_shape(ins.result_tok)
    names = ins.operand_names()
    if not names:
        return 0.0, "f32"
    lhs_tok = shapes.get(names[0], "")
    lhs_dt, lhs_dims = _tok_first_shape(lhs_tok)
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", ins.attrs())
    if not m:
        return 0.0, lhs_dt
    k = 1
    for ci in m.group(1).split(","):
        if ci != "" and int(ci) < len(lhs_dims):
            k *= lhs_dims[int(ci)]
    n = 1
    for d in res:
        n *= d
    # dtype classification: prefer int when either side is s8/u8
    rhs_dt = "f32"
    if len(names) > 1:
        rhs_dt, _ = _tok_first_shape(shapes.get(names[1], ""))
    dt = "s8" if ("8" in lhs_dt or "8" in rhs_dt) and (
        lhs_dt.startswith(("s", "u")) or rhs_dt.startswith(("s", "u"))) else lhs_dt
    return 2.0 * n * k, dt


def _conv_flops(ins: Instr, shapes: Dict[str, str]) -> float:
    _, res = _tok_first_shape(ins.result_tok)
    names = ins.operand_names()
    if len(names) < 2 or not res:
        return 0.0
    _, kdims = _tok_first_shape(shapes.get(names[1], ""))
    if not kdims:
        return 0.0
    n = 1
    for d in res:
        n *= d
    out_feat = res[-1]
    k = 1
    for d in kdims:
        k *= d
    if out_feat in kdims:
        k //= out_feat
    else:
        k //= kdims[-1]
    g = 1
    m = re.search(r"feature_group_count=(\d+)", ins.attrs())
    if m:
        g = int(m.group(1))
    return 2.0 * n * max(k, 1) / max(g, 1)


def _fusion_read_write(ins: Instr, comps, shapes) -> Tuple[float, float]:
    """HBM traffic of a fusion op: per-operand reads shrink to the
    dynamic-slice window when the fused computation only slices that
    parameter; dynamic-update-slice roots write only the update."""
    mcall = re.search(r"calls=%?([\w.-]+)", ins.args)
    callee = comps.get(mcall.group(1)) if mcall else None
    operands = ins.operand_names()
    full = [_tok_bytes(shapes.get(nm, "")) for nm in operands]
    write = _tok_bytes(ins.result_tok)
    if callee is None:
        return float(sum(full)), float(write)
    # map parameter index -> local name; find slice/update usage
    param_idx: Dict[str, int] = {}
    sliced: Dict[int, int] = {}
    update_write = None
    local_shapes = {i.name: i.result_tok for i in callee}
    unary_src = {}  # name -> single-operand source (convert/bitcast/copy/...)
    for i in callee:
        if i.opcode == "parameter":
            m = re.match(r"\s*(\d+)", i.args)
            if m:
                param_idx[i.name] = int(m.group(1))
        elif i.opcode in ("convert", "bitcast", "copy", "transpose",
                          "reshape", "broadcast"):
            names = i.operand_names()
            if names:
                unary_src[i.name] = names[0]

    def to_param(name, depth=8):
        while depth and name not in param_idx and name in unary_src:
            name = unary_src[name]
            depth -= 1
        return param_idx.get(name)

    for i in callee:
        if i.opcode == "dynamic-slice":
            names = i.operand_names()
            j = to_param(names[0]) if names else None
            if j is not None:
                sliced[j] = min(sliced.get(j, 1 << 62),
                                _tok_bytes(i.result_tok))
        elif i.opcode in ("dynamic-update-slice", "scatter"):
            names = i.operand_names()
            upd_name = names[1] if i.opcode == "dynamic-update-slice" else (
                names[2] if len(names) > 2 else None)
            if upd_name:
                upd = _tok_bytes(local_shapes.get(upd_name, "")) or \
                    _tok_bytes(shapes.get(upd_name, ""))
                if upd:
                    update_write = (update_write or 0) + upd
            j = to_param(names[0]) if names else None
            if j is not None:
                sliced.setdefault(j, 0)  # aliased buffer: not fully re-read
    reads = 0.0
    for j, fb in enumerate(full):
        reads += min(fb, sliced[j]) if j in sliced else fb
    if update_write is not None:
        write = update_write
    return reads, float(write)


def op_histogram(text: str, weighted: bool = True,
                 include_fused: bool = False) -> Dict[str, int]:
    """Loop-aware opcode histogram.

    Default counts STANDALONE (top-level, post-fusion) ops — fusion-interior
    instructions are registers, not HBM-visible ops, so callee computations
    of fusions/custom-calls are excluded.  ``include_fused=True`` counts the
    interiors too (strictest check: "no gather exists ANYWHERE in this
    module", fused or not).  ``weighted`` multiplies by while-loop trip
    counts (a gather inside an L-layer scan counts L times).  Used by the
    kernel benchmarks to prove the fused M2Q path emits zero
    gather/concatenate per quantized layer."""
    comps = parse_computations(text)
    mult = computation_multipliers(comps)
    # exclude fusion/custom-call interiors AND applied computations (reduce/
    # sort/scatter bodies) — none are HBM-visible ops; while bodies stay in
    fused_callees = set()
    for instrs in comps.values():
        for ins in instrs:
            if ins.opcode in ("fusion", "custom-call"):
                mcall = re.search(r"calls=%?([\w.-]+)", ins.args)
                if mcall:
                    fused_callees.add(mcall.group(1))
            for m in re.finditer(r"to_apply=%?([\w.-]+)", ins.args):
                fused_callees.add(m.group(1))
    hist: Dict[str, int] = {}
    for cname, instrs in comps.items():
        m = mult.get(cname, 0)
        if cname in fused_callees:
            if not include_fused:
                continue
            m = max(m, 1)  # callees carry no trip multiplier of their own
        if m <= 0:
            continue
        for ins in instrs:
            hist[ins.opcode] = hist.get(ins.opcode, 0) + (m if weighted else 1)
    return hist


_KERNEL_CALL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([A-Za-z_]\w*?)(?:\.\d+)*\s*=.*"
    r'custom_call_target="tpu_custom_call"', re.M)


def kernel_counts(text: str) -> Dict[str, int]:
    """{kernel name: number of ``tpu_custom_call`` instructions} in compiled
    TPU HLO text.  Each Pallas kernel's ``pallas_call(name=...)`` becomes
    the instruction name (``%m2q_matmul.3 = ... custom-call(...)``), so the
    counts say which kernels a compiled program really launches."""
    counts: Dict[str, int] = {}
    for m in _KERNEL_CALL_RE.finditer(text):
        counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    return counts


def analyze(text: str) -> dict:
    comps = parse_computations(text)
    mult = computation_multipliers(comps)
    # name -> result type token (instruction names are unique module-wide in
    # optimized HLO; last-write-wins is fine for our purposes)
    shapes: Dict[str, str] = {}
    producers: Dict[str, Instr] = {}
    for instrs in comps.values():
        for ins in instrs:
            shapes[ins.name] = ins.result_tok
            producers[ins.name] = ins

    def bf16_promoted(name: str, depth: int = 4) -> bool:
        """True if an f32 value is the CPU backend's promotion of a bf16
        tensor (XLA CPU has no native bf16 GEMM/reduce, so it wraps them in
        convert fusions / '_promoted' reducers; a TPU build keeps bf16).
        Detected by a convert-ish producer whose operands — or, for fusions,
        whose callee parameters / interior converts — are bf16."""
        while depth > 0:
            ins = producers.get(name)
            if ins is None:
                return False
            if ins.opcode == "fusion" and "convert" in ins.name:
                m = re.search(r"calls=%?([\w.-]+)", ins.args)
                for ci in comps.get(m.group(1), []) if m else []:
                    dt, _ = _tok_first_shape(ci.result_tok)
                    if ci.opcode == "parameter" and dt == "bf16":
                        return True
                    if ci.opcode == "convert":
                        src = ci.operand_names()
                        sdt, _ = _tok_first_shape(
                            shapes.get(src[0], "") if src else "")
                        # local names resolve within the callee
                        for cj in comps.get(m.group(1), []):
                            if src and cj.name == src[0]:
                                sdt, _ = _tok_first_shape(cj.result_tok)
                        if sdt == "bf16":
                            return True
            if ins.opcode in ("convert", "bitcast", "copy") or (
                    ins.opcode == "fusion" and "convert" in ins.name):
                for nm in ins.operand_names():
                    dt, _ = _tok_first_shape(shapes.get(nm, ""))
                    if dt == "bf16":
                        return True
                names = ins.operand_names()
                if not names:
                    return False
                name = names[0]
                depth -= 1
                continue
            return False
        return False
    flops = 0.0
    flops_by_dtype: Dict[str, float] = {}
    traffic = 0.0
    coll_bytes = {c: 0.0 for c in _COLLECTIVES}
    coll_counts = {c: 0 for c in _COLLECTIVES}
    fused_callees = set()
    for instrs in comps.values():
        for ins in instrs:
            if ins.opcode in ("fusion", "custom-call"):
                mcall = re.search(r"calls=%?([\w.-]+)", ins.args)
                if mcall:
                    fused_callees.add(mcall.group(1))
    for cname, instrs in comps.items():
        m = mult.get(cname, 0)
        if m <= 0:
            continue
        in_fused = cname in fused_callees
        for ins in instrs:
            op = ins.opcode
            if op == "dot":
                f, dt = _dot_flops(ins, shapes)
                if dt in ("f32", "f64"):
                    names = ins.operand_names()
                    if any(bf16_promoted(nm) for nm in names[:2]):
                        dt = "bf16"  # CPU-promoted; TPU runs this dot in bf16
                flops += m * f
                flops_by_dtype[dt] = flops_by_dtype.get(dt, 0.0) + m * f
            elif op == "convolution":
                f = _conv_flops(ins, shapes)
                flops += m * f
                flops_by_dtype["conv"] = flops_by_dtype.get("conv", 0.0) + m * f
            if op in _CONTROL_OPS or in_fused:
                continue  # fused interiors are registers, not HBM traffic
            for c in _COLLECTIVES:
                if op == c or op == c + "-start":
                    b = _tok_bytes(ins.result_tok)
                    # promoted-from-bf16 collectives move bf16 on TPU
                    dt, _ = _tok_first_shape(ins.result_tok)
                    if dt in ("f32", "f64") and (
                            "promoted" in ins.args
                            or any(bf16_promoted(nm)
                                   for nm in ins.operand_names()[:2])):
                        b //= 2
                    coll_bytes[c] += m * b
                    coll_counts[c] += m
                    break
            rb = _tok_bytes(ins.result_tok)
            obs = [_tok_bytes(shapes.get(nm, "")) for nm in ins.operand_names()]
            if op == "fusion":
                r, w = _fusion_read_write(ins, comps, shapes)
                traffic += m * (r + w)
            elif op in ("dynamic-update-slice", "scatter"):
                # in-place: write = update ~ operands minus the aliased buffer
                traffic += m * (sum(obs) - (max(obs) if obs else 0))
            elif op in ("dynamic-slice", "gather"):
                traffic += m * rb  # only the window moves
            else:
                traffic += m * (rb + sum(obs))
    return {
        "dot_flops": flops,
        "dot_flops_by_dtype": flops_by_dtype,
        "traffic_bytes": traffic,
        "collective_bytes": coll_bytes,
        "collective_counts": coll_counts,
        "collective_total_bytes": float(sum(coll_bytes.values())),
        "n_computations": len(comps),
    }


# ---------------------------------------------------------------------------
# def-use graph (fusion-boundary-crossing) for the qlint rule engine
# ---------------------------------------------------------------------------

def is_float_dtype(dt: str) -> bool:
    return dt.startswith(("f", "bf")) and dt != "false"


def is_int_dtype(dt: str) -> bool:
    return dt.startswith(("s", "u")) and dt != "u"  # s4/s8/.../u4/u8/...


class Graph:
    """Module-wide def-use graph over optimized HLO text.

    ``op_histogram``/``analyze`` treat fusion interiors as opaque; the
    qlint dtype-flow rules (no-dequant-matmul, no-gather-concat,
    unguarded-act-quant) need to ATTRIBUTE interior instructions back to
    the values that feed them, so this graph stitches call boundaries:

    * caller operand i  ->  callee ``parameter(i)``  (fusions, calls,
      applied computations, while init);
    * callee ROOT       ->  the call instruction's result (so users of a
      fusion see through to the producing interior instruction);
    * while body ROOT   ->  body/condition parameters (loop carry).

    Instruction names are unique module-wide in optimized HLO, so edges
    are keyed by bare names.  ``edges`` maps a value name to the
    instructions consuming it (crossing boundaries); ``redges`` is the
    inverse.  The binding is positional and conservative: an over-
    approximate reachability, which is the right polarity for "no X is
    reachable from a quantized parameter" rules.
    """

    def __init__(self, text: str):
        self.comps = parse_computations(text)
        m = _ENTRY_RE.search(text)
        self.entry: Optional[str] = m.group(1) if m else (
            next(iter(self.comps)) if self.comps else None)
        self.shapes: Dict[str, str] = {}
        self.producers: Dict[str, Instr] = {}
        self.comp_of: Dict[str, str] = {}
        self.params: Dict[str, List[Optional[str]]] = {}
        self.roots: Dict[str, Optional[str]] = {}
        for cname, instrs in self.comps.items():
            plist: List[Optional[str]] = []
            root = None
            for ins in instrs:
                self.shapes[ins.name] = ins.result_tok
                self.producers[ins.name] = ins
                self.comp_of[ins.name] = cname
                if ins.is_root:
                    root = ins.name
                if ins.opcode == "parameter":
                    mp = re.match(r"\s*(\d+)", ins.args)
                    idx = int(mp.group(1)) if mp else len(plist)
                    while len(plist) <= idx:
                        plist.append(None)
                    plist[idx] = ins.name
            if root is None and instrs:
                root = instrs[-1].name  # ROOT is conventionally last
            self.params[cname] = plist
            self.roots[cname] = root
        # callsites first: tuple_element() resolves parameters through them
        self.callsites: Dict[str, List[str]] = {}  # comp -> caller instrs
        for cname, instrs in self.comps.items():
            for ins in instrs:
                for k in self._callees(ins):
                    if k in self.comps:
                        self.callsites.setdefault(k, []).append(ins.name)
        self.edges: Dict[str, List[str]] = {}
        self.redges: Dict[str, List[str]] = {}
        for cname, instrs in self.comps.items():
            for ins in instrs:
                operands = ins.operand_names()
                if ins.opcode == "get-tuple-element":
                    # element-precise edge: a gte consumes ONE tuple slot,
                    # not the whole loop-carried state — without this every
                    # value in a while body is "reachable" from every other
                    mi = re.search(r"index=(\d+)", ins.args)
                    srcs = (self.tuple_element(operands[0], int(mi.group(1)))
                            if mi and operands else [])
                    for s in srcs or operands:
                        self._edge(s, ins.name)
                    continue
                for o in operands:
                    self._edge(o, ins.name)
                for k in self._callees(ins):
                    if k not in self.comps:
                        continue
                    for i, p in enumerate(self.params.get(k, [])):
                        if p is not None and i < len(operands):
                            self._edge(operands[i], p)
                    root = self.roots.get(k)
                    if root:
                        self._edge(root, ins.name)

    @staticmethod
    def _callees(ins: Instr) -> List[str]:
        if ins.opcode == "while":
            return _WHILE_COMP_RE.findall(ins.args)
        out = _CALLEE_ATTR_RE.findall(ins.args)
        mb = re.search(r"branch_computations=\{([^}]*)\}", ins.args)
        if mb:
            out += [b.strip().lstrip("%")
                    for b in mb.group(1).split(",") if b.strip()]
        return out

    def tuple_element(self, name: str, k: int, _depth: int = 0,
                      _seen=None) -> List[str]:
        """Producing value name(s) of element ``k`` of tuple value
        ``name``, looking through tuple/gte/while/fusion plumbing.  A
        loop-carried tuple resolves to BOTH the init element and the
        body-root element (the value of any iteration).  Empty when
        unresolvable."""
        if _depth > 24:
            return []
        if _seen is None:
            _seen = set()
        if (name, k) in _seen:
            return []
        _seen.add((name, k))
        ins = self.producers.get(name)
        if ins is None:
            return []
        operands = ins.operand_names()
        if ins.opcode == "tuple":
            return [operands[k]] if k < len(operands) else []
        if ins.opcode == "while":
            out = []
            if operands:
                out += self.tuple_element(operands[0], k, _depth + 1, _seen)
            mb = re.search(r"body=%?([\w.-]+)", ins.args)
            root = self.roots.get(mb.group(1)) if mb else None
            if root:
                out += self.tuple_element(root, k, _depth + 1, _seen)
            return out
        if ins.opcode == "parameter":
            comp = self.comp_of.get(name, "")
            try:
                idx = self.params.get(comp, []).index(name)
            except ValueError:
                return []
            out = []
            for cs in self.callsites.get(comp, []):
                ci = self.producers[cs]
                cops = ci.operand_names()
                if ci.opcode == "while":
                    if cops:
                        out += self.tuple_element(cops[0], k, _depth + 1,
                                                  _seen)
                    mb = re.search(r"body=%?([\w.-]+)", ci.args)
                    root = self.roots.get(mb.group(1)) if mb else None
                    if root:
                        out += self.tuple_element(root, k, _depth + 1, _seen)
                elif idx < len(cops):
                    out += self.tuple_element(cops[idx], k, _depth + 1, _seen)
            return out
        if ins.opcode in ("fusion", "call", "conditional", "custom-call"):
            out = []
            for kk in self._callees(ins):
                root = self.roots.get(kk)
                if root:
                    out += self.tuple_element(root, k, _depth + 1, _seen)
            return out or [name]
        if ins.opcode == "get-tuple-element":
            mi = re.search(r"index=(\d+)", ins.args)
            if operands and mi:
                out = []
                for nm in self.tuple_element(operands[0], int(mi.group(1)),
                                             _depth + 1, _seen):
                    out += self.tuple_element(nm, k, _depth + 1, _seen)
                return out
            return [name]
        if ins.opcode in ("copy", "bitcast", "optimization-barrier",
                          "opt-barrier", "copy-start", "copy-done"):
            if operands:
                return self.tuple_element(operands[0], k, _depth + 1, _seen)
        return [name]  # opaque producer: the whole value stands in

    def _edge(self, src: str, dst: str) -> None:
        if src == dst:
            return
        lst = self.edges.setdefault(src, [])
        if not lst or lst[-1] != dst:
            lst.append(dst)
        self.redges.setdefault(dst, []).append(src)

    def dtype_of(self, name: str) -> str:
        return _tok_first_shape(self.shapes.get(name, ""))[0]

    def entry_params(self) -> List[Optional[str]]:
        """Entry-computation parameter names ordered by parameter index
        (index i lines up with the i-th flattened jit argument leaf)."""
        return self.params.get(self.entry or "", [])

    def loop_comps(self) -> set:
        """Computations executing inside any ``while`` (bodies, conds, and
        everything they transitively call — fusion interiors included)."""
        stack: List[str] = []
        for instrs in self.comps.values():
            for ins in instrs:
                if ins.opcode == "while":
                    stack.extend(_WHILE_COMP_RE.findall(ins.args))
        out: set = set()
        while stack:
            c = stack.pop()
            if c in out or c not in self.comps:
                continue
            out.add(c)
            for ins in self.comps[c]:
                stack.extend(_CALLEE_ATTR_RE.findall(ins.args))
                stack.extend(_WHILE_COMP_RE.findall(ins.args))
                mb = re.search(r"branch_computations=\{([^}]*)\}", ins.args)
                if mb:
                    stack.extend(b.strip().lstrip("%")
                                 for b in mb.group(1).split(",") if b.strip())
        return out
