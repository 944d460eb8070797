"""Custom HLO cost analyzer with while-loop trip-count attribution.

``compiled.cost_analysis()`` visits each instruction ONCE — the body of a
``lax.scan`` (lowered to ``while``) is counted a single time regardless of
trip count, which undercounts flops/bytes/collectives of scan-over-layers
models by the period count.  This module parses the optimized HLO text
and rebuilds the cost model with correct loop multipliers:

  * computations are parsed into blocks; ``while`` instructions link
    body/condition computations; trip counts come from the loop-condition
    ``constant(N)`` + LT compare pattern (JAX scans always lower this way);
  * only *executable* computations are walked (entry, while bodies,
    conditional branches).  Fusion internals / reduce ``to_apply`` regions
    are skipped — their cost is the call-site I/O, matching fused traffic;
  * per instruction: bytes = operand bytes + result bytes;
    flops for dot (2 * result_elems * contracted_elems) and convolution;
  * collective bytes = operand bytes of all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute, x loop multiplier.

Validated against ``cost_analysis()`` on unrolled small models in
tests/test_hlo_analysis.py.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "token": 0, "opaque": 0,
}

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter",
                  "all-to-all", "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_COMP_HDR = re.compile(
    r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$")
_INSTR_RE = re.compile(
    r"^(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(\([^=]*?\)|[a-z0-9]+\[[\d,]*\]"
    r"(?:\{[^}]*\})?)\s*([a-z0-9\-]+)\((.*)$")


def _shape_list(text: str) -> List[Tuple[str, List[int]]]:
    out = []
    for dt, dims in _SHAPE_RE.findall(text):
        if dt in _DTYPE_BYTES:
            out.append((dt, [int(d) for d in dims.split(",") if d]))
    return out


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _shape_list(text):
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class Instr:
    name: str
    result_text: str
    opcode: str
    rest: str          # everything after the opening paren

    @property
    def result_bytes(self) -> int:
        return _shape_bytes(self.result_text)

    @property
    def result_elems(self) -> int:
        shapes = _shape_list(self.result_text)
        if not shapes:
            return 0
        n = 1
        for d in shapes[0][1]:
            n *= d
        return n


@dataclass
class Computation:
    name: str
    instrs: List[Instr] = field(default_factory=list)


def parse_computations(hlo: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for raw in hlo.splitlines():
        line = re.sub(r"/\*.*?\*/", "", raw).strip()
        if not line or line.startswith("//"):
            continue
        if " = " not in line:
            m = _COMP_HDR.match(line)
            if m:
                cur = Computation(m.group(1))
                comps[cur.name] = cur
                continue
        if line.startswith("}"):
            continue
        m = _INSTR_RE.match(line)
        if m and cur is not None:
            name, result_text, opcode, rest = m.groups()
            cur.instrs.append(Instr(name, result_text, opcode, rest))
    return comps


def _operand_names(rest: str) -> List[str]:
    # operands are inside the first balanced paren group
    depth, end = 1, len(rest)
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    args = rest[:end]
    return re.findall(r"%([\w\.\-]+)", args)


def _attr(rest: str, key: str) -> Optional[str]:
    m = re.search(key + r"=%?([\w\.\-]+)", rest)
    return m.group(1) if m else None


def _branch_comps(rest: str) -> List[str]:
    m = re.search(r"branch_computations=\{([^}]*)\}", rest)
    if m:
        return re.findall(r"%?([\w\.\-]+)", m.group(1))
    out = []
    for key in ("true_computation", "false_computation"):
        v = _attr(rest, key)
        if v:
            out.append(v)
    return out


@dataclass
class HloCost:
    flops: float = 0.0
    bytes: float = 0.0
    coll_bytes: float = 0.0
    coll_by_op: Dict[str, float] = field(default_factory=dict)
    coll_count: Dict[str, int] = field(default_factory=dict)
    trip_counts: Dict[str, int] = field(default_factory=dict)
    transcendentals: float = 0.0
    # bytes/flops attributed to instructions whose op_name metadata
    # matches a requested tag (e.g. "flash_attention") — used to credit
    # Pallas-kernel deployments in the roofline (DESIGN.md §6)
    tag_bytes: Dict[str, float] = field(default_factory=dict)
    tag_flops: Dict[str, float] = field(default_factory=dict)
    tag_coll_bytes: Dict[str, float] = field(default_factory=dict)

    def add_coll(self, op: str, nbytes: float, mult: float):
        self.coll_bytes += nbytes * mult
        self.coll_by_op[op] = self.coll_by_op.get(op, 0.0) + nbytes * mult
        self.coll_count[op] = self.coll_count.get(op, 0) + int(mult)


def _dot_flops(instr: Instr, shapes: Dict[str, str]) -> float:
    ops = _operand_names(instr.rest)
    if not ops:
        return 0.0
    lhs_text = shapes.get(ops[0], "")
    lhs_shapes = _shape_list(lhs_text)
    if not lhs_shapes:
        return 0.0
    lhs_dims = lhs_shapes[0][1]
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", instr.rest)
    contract = 1
    if m and m.group(1):
        for idx in m.group(1).split(","):
            i = int(idx)
            if i < len(lhs_dims):
                contract *= lhs_dims[i]
    return 2.0 * instr.result_elems * contract


def _conv_flops(instr: Instr, shapes: Dict[str, str]) -> float:
    ops = _operand_names(instr.rest)
    if len(ops) < 2:
        return 0.0
    k_shapes = _shape_list(shapes.get(ops[1], ""))
    if not k_shapes:
        return 0.0
    k_elems = 1
    for d in k_shapes[0][1]:
        k_elems *= d
    m = re.search(r"feature_group_count=(\d+)", instr.rest)
    groups = int(m.group(1)) if m else 1
    # per output element: 2 * kernel_elems / (out_features * groups) ... use
    # the standard approximation 2 * out_elems * kernel_elems / out_features
    out_feats = k_shapes[0][1][-1] if k_shapes[0][1] else 1
    return 2.0 * instr.result_elems * max(1, k_elems // max(1, out_feats))


def _trip_count(cond: Computation) -> int:
    """JAX scan condition: compare(%iv, %constant(N)), direction=LT."""
    consts = []
    for ins in cond.instrs:
        if ins.opcode == "constant":
            m = re.match(r"(\-?\d+)", ins.rest)
            if m:
                consts.append(int(m.group(1)))
        m2 = re.search(r"constant\((\-?\d+)\)", ins.rest)
        if m2:
            consts.append(int(m2.group(1)))
    pos = [c for c in consts if c > 0]
    return max(pos) if pos else 1


_METADATA_RE = re.compile(r'op_name="([^"]*)"')


DEFAULT_TAGS = ("flash_attention", "slstm_cell", "mlstm_chunkwise",
                "mamba_scan")


def analyze_hlo(hlo: str, tags: Tuple[str, ...] = DEFAULT_TAGS
                ) -> HloCost:
    comps = parse_computations(hlo)
    # map instruction name -> result text (for operand shape lookups)
    shapes: Dict[str, str] = {}
    for comp in comps.values():
        for ins in comp.instrs:
            shapes[ins.name] = ins.result_text
        # computation parameters also define shapes via header — skip; JAX
        # HLO references params as instructions ("%param = f32[..] parameter")

    cost = HloCost()
    referenced = set()
    for comp in comps.values():
        for ins in comp.instrs:
            for key in ("condition", "body", "calls", "to_apply"):
                v = _attr(ins.rest, key)
                if v:
                    referenced.add(v)
            referenced.update(_branch_comps(ins.rest))
    entries = [n for n in comps if n not in referenced]

    def _tags_of(ins: Instr):
        m = _METADATA_RE.search(ins.rest)
        name = m.group(1) if m else ""
        return [t for t in tags if t in name]

    def _tag(ins: Instr, nbytes: float, nflops: float):
        for t in _tags_of(ins):
            cost.tag_bytes[t] = cost.tag_bytes.get(t, 0.0) + nbytes
            cost.tag_flops[t] = cost.tag_flops.get(t, 0.0) + nflops

    def walk(comp_name: str, mult: float, visiting=()):
        comp = comps.get(comp_name)
        if comp is None or comp_name in visiting:
            return
        for ins in comp.instrs:
            op = ins.opcode
            if op == "while":
                cond_name = _attr(ins.rest, "condition")
                body_name = _attr(ins.rest, "body")
                trips = _trip_count(comps[cond_name]) if cond_name in comps \
                    else 1
                cost.trip_counts[body_name or "?"] = trips
                if body_name:
                    walk(body_name, mult * trips,
                         visiting + (comp_name,))
                continue
            if op == "conditional":
                branches = _branch_comps(ins.rest)
                # exactly one branch executes per call: average the cost
                # over branches (lax.switch branches here are isomorphic)
                for b in branches:
                    walk(b, mult / max(1, len(branches)),
                         visiting + (comp_name,))
                continue
            # bytes: operands + result (fusion internals are skipped, so
            # this measures fused traffic)
            op_names = _operand_names(ins.rest)
            obytes = sum(_shape_bytes(shapes.get(n, "")) for n in op_names)
            ins_bytes = 0.0
            ins_flops = 0.0
            is_dus_fusion = (op == "fusion"
                             and "dynamic-update-slice" in ins.name)
            is_ds_fusion = (op == "fusion" and not is_dus_fusion
                            and "dynamic-slice" in ins.name)
            if op == "dynamic-update-slice" or is_dus_fusion:
                # in-place update (donated/aliased buffers): traffic is
                # read+write of the UPDATE slice, not the full buffer(s).
                if op == "dynamic-update-slice":
                    upd = _shape_bytes(shapes.get(op_names[1], "")) \
                        if len(op_names) > 1 else 0
                else:
                    # exclude every big loop-carried buffer operand
                    # (>= half the result size), count the rest
                    sizes = [_shape_bytes(shapes.get(n, ""))
                             for n in op_names]
                    thresh = ins.result_bytes / 2
                    upd = sum(s for s in sizes if s < thresh)
                ins_bytes = 2.0 * upd * mult
                cost.bytes += ins_bytes
            elif op == "dynamic-slice" or is_ds_fusion:
                # sliced read of a big buffer (scan xs / KV lookup):
                # traffic = slice read + result write
                ins_bytes = 2.0 * ins.result_bytes * mult
                cost.bytes += ins_bytes
            elif op not in ("parameter", "constant", "get-tuple-element",
                            "tuple", "bitcast"):
                ins_bytes = (obytes + ins.result_bytes) * mult
                cost.bytes += ins_bytes
            if op == "dot":
                ins_flops = _dot_flops(ins, shapes) * mult
            elif op == "convolution":
                ins_flops = _conv_flops(ins, shapes) * mult
            elif op == "fusion":
                # count dot/conv inside the fusion computation (bytes are
                # already the fusion I/O)
                sub = comps.get(_attr(ins.rest, "calls") or "")
                if sub:
                    for s in sub.instrs:
                        if s.opcode == "dot":
                            ins_flops += _dot_flops(s, shapes) * mult
                        elif s.opcode == "convolution":
                            ins_flops += _conv_flops(s, shapes) * mult
                        elif s.opcode in ("exponential", "tanh", "log",
                                          "power", "rsqrt", "sqrt"):
                            cost.transcendentals += s.result_elems * mult
            cost.flops += ins_flops
            _tag(ins, ins_bytes, ins_flops)
            base = None
            for c in COLLECTIVE_OPS:
                if op == c or op.startswith(c + "-"):
                    base = c
                    break
            if base:
                cost.add_coll(base, float(obytes), mult)
                for t in _tags_of(ins):
                    cost.tag_coll_bytes[t] = \
                        cost.tag_coll_bytes.get(t, 0.0) + obytes * mult

    for e in entries:
        walk(e, 1.0)
    return cost
