"""Static roofline analysis of compiled (SPMD, per-device) HLO text.

XLA's ``compiled.cost_analysis()`` counts every instruction ONCE — a
``jax.lax.scan`` over 36 layers reports 1/36 of the real FLOPs (verified
empirically; see tests/test_hlo_analysis.py).  Since this framework scans
every depth dimension (layers, microbatches, attention chunks), module-
level cost_analysis is useless for a roofline.  This module re-derives
the three roofline inputs from the optimized HLO text with **while-loop
trip counts** (XLA's ``known_trip_count`` backend annotation) multiplied
through the call graph:

* **FLOPs** — ``dot`` instructions: 2·|result|·|contracted dims| from the
  operand shapes (MXU work; elementwise VPU flops are excluded — they are
  never the v5e bottleneck at these shapes);
* **HBM bytes** — Σ (result + operand bytes) over materialized
  instructions (fusion bodies excluded: a fusion reads its operands and
  writes its result once; tuples/bitcasts/parameters excluded like XLA's
  own bytes-accessed);
* **collective bytes** — every ``all-reduce / all-gather / reduce-scatter
  / all-to-all / collective-permute`` (sync or ``-start`` async), with
  operand bytes derived from result shape + group size, and modeled ring
  **wire bytes** (all-reduce 2(g−1)/g·S etc.) — the number a link-level
  roofline actually wants.

Everything here is text parsing — no jax device state — so it runs
identically on the dry-run's 512 fake devices and in unit tests.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1,
    "f8e5m2fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%[\w.\-]+\s*=\s*(?P<ret>.*?)\s+"
    r"(?P<kind>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<async>-start)?\(")
_WHILE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%[\w.\-]+\s*=\s*.*?\bwhile\(.*?"
    r"condition=%(?P<cond>[\w.\-]+),\s*body=%(?P<body>[\w.\-]+)")
_CALL_RE = re.compile(r"\b(?:call|async-start)\(.*?to_apply=%(?P<callee>[\w.\-]+)")
_COND_RE = re.compile(r"branch_computations=\{(?P<branches>[^}]*)\}")
_TRIP_RE = re.compile(r'known_trip_count[^0-9]*([0-9]+)')
_GROUPS_EXPL_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def shape_bytes(type_str: str) -> int:
    """Total bytes of all array shapes appearing in an HLO type string
    (handles tuples: sums every dtype[dims] group)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_EXPL_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    m = re.search(r"source_target_pairs=\{", line)
    if m:
        return 2  # permute: pairwise
    return 1


@dataclass
class Collective:
    kind: str
    result_bytes: int
    operand_bytes: int
    wire_bytes: int
    group_size: int
    trips: int = 1

    @property
    def total_operand_bytes(self) -> int:
        return self.operand_bytes * self.trips

    @property
    def total_wire_bytes(self) -> int:
        return self.wire_bytes * self.trips


def _derive_bytes(kind: str, result_bytes: int, g: int) -> Tuple[int, int]:
    """(operand_bytes, modeled ring wire bytes per device)."""
    g = max(g, 1)
    if kind == "all-gather":
        op = result_bytes // g
        wire = result_bytes - op            # receive everyone else's shard
    elif kind == "reduce-scatter":
        op = result_bytes * g
        wire = result_bytes * (g - 1)       # send g-1 shards of result size
    elif kind == "all-reduce":
        op = result_bytes
        wire = int(2 * result_bytes * (g - 1) / g)
    elif kind == "all-to-all":
        op = result_bytes
        wire = int(result_bytes * (g - 1) / g)
    else:  # collective-permute: one send + one recv of the buffer
        op = result_bytes
        wire = result_bytes
    return op, wire


@dataclass
class _Computation:
    name: str
    collectives: List[Collective] = field(default_factory=list)
    # (callee, multiplier) edges: while bodies get trip_count, others 1
    calls: List[Tuple[str, int]] = field(default_factory=list)
    flops: float = 0.0          # dot/conv flops of this body (once)
    hbm_bytes: float = 0.0      # materialized result+operand bytes (once)
    is_fusion_body: bool = False


def _split_computations(hlo: str) -> Dict[str, List[str]]:
    """computation name -> its instruction lines."""
    comps: Dict[str, List[str]] = {}
    cur: Optional[str] = None
    header = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\([^)]*\))?.*\{\s*$")
    for line in hlo.splitlines():
        if not line.startswith(" ") and line.rstrip().endswith("{"):
            m = header.match(line.strip())
            cur = m.group(1) if m else None
            if cur is not None:
                comps[cur] = []
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is not None:
            comps[cur].append(line)
    return comps


def _entry_name(hlo: str) -> Optional[str]:
    m = re.search(r"^ENTRY\s+%?([\w.\-]+)", hlo, re.M)
    return m.group(1) if m else None


_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<ret>\([^)]*\)|\S+)\s+(?P<op>[\w\-]+)"
    r"\((?P<args>[^)]*)\)")
_DIMS_RE = re.compile(r"\[([0-9,]*)\]")
_LHS_C_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_CALLS_RE = re.compile(r"calls=%([\w.\-]+)")
_TOAPPLY_RE = re.compile(r"to_apply=%([\w.\-]+)")
_TF_COND_RE = re.compile(
    r"true_computation=%([\w.\-]+),\s*false_computation=%([\w.\-]+)")

# instructions that are free / metadata-only for HBM-byte accounting
_FREE_OPS = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "add-dependency", "while", "call", "conditional",
    "partition-id", "replica-id", "opt-barrier", "domain",
})
_ASYNC_DONE = frozenset({
    "all-reduce-done", "all-gather-done", "collective-permute-done",
    "all-to-all-done", "copy-done", "async-done", "async-update",
    "send-done", "recv-done",
})


def _type_dims(type_str: str) -> List[int]:
    m = _DIMS_RE.search(type_str)
    if not m or not m.group(1):
        return []
    return [int(d) for d in m.group(1).split(",")]


def _fusion_io_costs(lines: List[str]) -> Tuple[Dict[int, Optional[int]],
                                                Optional[int]]:
    """Effective I/O bytes of a fused computation.

    A fusion reads its operands and writes its result ONCE — except when a
    parameter is only ever dynamic-sliced (scan reading one layer of a
    stacked buffer: the fusion reads just the slice) or the root is a
    dynamic-update-slice / scatter (scan carry or cache update: writes
    just the slice).  Counting full buffers here overcounts stacked-
    parameter reads by L×.

    Dtype-normalization converts are treated as TRANSPARENT when tracking
    a buffer from parameter to slice op: XLA *CPU* promotes bf16
    scatter/DUS through full-buffer f32 converts (float normalization),
    which a TPU build would not emit — following the buffer through
    convert/copy/bitcast keeps the analysis TPU-faithful.

    Returns ({param_index: bytes or None=full}, result_bytes or None=full).
    """
    _TRANSPARENT = ("convert", "copy", "bitcast", "reshape")
    types: Dict[str, str] = {}
    param_of: Dict[str, int] = {}
    uses: Dict[str, List[Tuple[str, List[str]]]] = defaultdict(list)
    instr_op: Dict[str, str] = {}
    instr_args: Dict[str, List[str]] = {}
    root: Optional[str] = None
    for line in lines:
        mi = _INSTR_RE.match(line)
        if not mi:
            continue
        name, ret, op, args = (mi.group("name"), mi.group("ret"),
                               mi.group("op"), mi.group("args"))
        types[name] = ret
        instr_op[name] = op
        operands = re.findall(r"%([\w.\-]+)", args)
        instr_args[name] = operands
        for o in operands:
            uses[o].append((op, operands))
        if op == "parameter":
            m = re.match(r"\s*(\d+)", args)
            if m:
                param_of[name] = int(m.group(1))
        if line.lstrip().startswith("ROOT"):
            root = name
    if root is None and lines:
        for line in reversed(lines):
            mi = _INSTR_RE.match(line)
            if mi:
                root = mi.group("name")
                break

    def alias_set(pname: str) -> set:
        """pname plus every transparent-unary instruction fed (only) by it."""
        al = {pname}
        changed = True
        while changed:
            changed = False
            for iname, op in instr_op.items():
                if (iname not in al and op in _TRANSPARENT and
                        instr_args.get(iname) and
                        instr_args[iname][0] in al):
                    al.add(iname)
                    changed = True
        return al

    param_costs: Dict[int, Optional[int]] = {}
    for pname, idx in param_of.items():
        al = alias_set(pname)
        ext_uses = []   # uses of any alias member outside the alias chain
        for member in al:
            for iname, op in instr_op.items():
                if iname in al:
                    continue
                ops = instr_args.get(iname, [])
                for pos, o in enumerate(ops):
                    if o == member:
                        ext_uses.append((op, pos, iname))
        if ext_uses and all(op == "dynamic-slice" and pos == 0
                            for op, pos, _ in ext_uses):
            param_costs[idx] = sum(shape_bytes(types.get(iname, ""))
                                   for op, pos, iname in ext_uses)
        elif ext_uses and all(op in ("dynamic-update-slice", "scatter")
                              and pos == 0 for op, pos, _ in ext_uses):
            param_costs[idx] = 0    # passed-through carry buffer
        elif not ext_uses and root in al:
            param_costs[idx] = 0    # pure pass-through to the root
        else:
            param_costs[idx] = None  # full read

    def elem_cost(name: str) -> Optional[int]:
        # walk back through transparent unaries to the slice-updating op
        seen = 0
        while (instr_op.get(name) in _TRANSPARENT and
               instr_args.get(name) and seen < 8):
            name = instr_args[name][0]
            seen += 1
        op = instr_op.get(name)
        ops = instr_args.get(name, [])
        if op == "dynamic-update-slice":
            if len(ops) > 1 and ops[1] in types:
                return shape_bytes(types[ops[1]])   # writes the slice
        if op == "scatter":
            if len(ops) > 2 and ops[2] in types:
                return 2 * shape_bytes(types[ops[2]])
        return None

    result_cost: Optional[int] = None
    if root is not None:
        if instr_op.get(root) == "tuple":
            total, any_special = 0, False
            for o in instr_args.get(root, []):
                c = elem_cost(o)
                if c is None:
                    total += shape_bytes(types.get(o, ""))
                else:
                    any_special = True
                    total += c
            result_cost = total if any_special else None
        else:
            result_cost = elem_cost(root)
    return param_costs, result_cost


VMEM_RESIDENT_LIMIT = 64 * 1024 * 1024   # invariant operands ≤ this stay
                                         # in VMEM across loop iterations


def _loop_invariant_names(lines: List[str]) -> set:
    """Names (incl. transparent-unary aliases) that a while BODY carries
    through unchanged: tuple elements whose ROOT position is the
    pass-through of the same GTE index.  A TPU build keeps such operands
    (weights of a sequential scan) resident in VMEM — charging their full
    size per iteration overstates HBM traffic by the trip count."""
    gte_idx: Dict[str, int] = {}
    alias_src: Dict[str, str] = {}
    root_ops: List[str] = []
    for line in lines:
        mi = _INSTR_RE.match(line)
        if not mi:
            continue
        name, op, args = mi.group("name"), mi.group("op"), mi.group("args")
        operands = re.findall(r"%([\w.\-]+)", args)
        if op == "get-tuple-element":
            mo = re.search(r"index=(\d+)", line)
            if mo and operands:
                gte_idx[name] = int(mo.group(1))
        if op in ("convert", "copy", "bitcast", "reshape") and operands:
            alias_src[name] = operands[0]
        if line.lstrip().startswith("ROOT") and op == "tuple":
            root_ops = operands

    def resolve(n: str) -> str:
        seen = 0
        while n in alias_src and seen < 8:
            n = alias_src[n]
            seen += 1
        return n

    invariant_idx = {i for i, o in enumerate(root_ops)
                     if gte_idx.get(resolve(o)) == i}
    inv = {n for n, i in gte_idx.items() if i in invariant_idx}
    # transparent closure
    changed = True
    while changed:
        changed = False
        for n, src in alias_src.items():
            if src in inv and n not in inv:
                inv.add(n)
                changed = True
    return inv


def parse_module(hlo: str) -> Dict[str, _Computation]:
    """Full per-computation analysis: collectives, dot FLOPs, HBM bytes,
    call edges.  Fusion bodies contribute FLOPs but not bytes (their I/O
    is charged at the fusion boundary, slice-aware)."""
    split = _split_computations(hlo)
    fusion_bodies = set(_CALLS_RE.findall(hlo))
    fusion_costs = {name: _fusion_io_costs(lines)
                    for name, lines in split.items()
                    if name in fusion_bodies}
    comps: Dict[str, _Computation] = {}

    for name, lines in split.items():
        c = _Computation(name, is_fusion_body=(name in fusion_bodies))
        invariant = _loop_invariant_names(lines)
        types: Dict[str, str] = {}

        def op_bytes(o: str) -> int:
            """Operand read cost: loop-invariant VMEM-resident = free."""
            if o not in types:
                return 0
            b = shape_bytes(types[o])
            if o in invariant and b <= VMEM_RESIDENT_LIMIT:
                return 0
            return b

        for line in lines:
            mi = _INSTR_RE.match(line)
            if not mi:
                continue
            iname, ret, op, args = (mi.group("name"), mi.group("ret"),
                                    mi.group("op"), mi.group("args"))
            types[iname] = ret
            operands = re.findall(r"%([\w.\-]+)", args)

            # ---- FLOPs: dot_general ------------------------------------
            if op == "dot" and operands:
                lhs_t = types.get(operands[0])
                if lhs_t is not None:
                    lhs_dims = _type_dims(lhs_t)
                    mc = _LHS_C_RE.search(line)
                    contracted = 1
                    if mc and mc.group(1):
                        for d in mc.group(1).split(","):
                            di = int(d)
                            if di < len(lhs_dims):
                                contracted *= lhs_dims[di]
                    out_elems = 1
                    for d in _type_dims(ret):
                        out_elems *= d
                    c.flops += 2.0 * out_elems * contracted

            # ---- collectives -------------------------------------------
            mcoll = _COLL_RE.match(line)
            if mcoll:
                rb = shape_bytes(mcoll.group("ret"))
                g = _group_size(line)
                opb, wire = _derive_bytes(mcoll.group("kind"), rb, g)
                c.collectives.append(Collective(
                    mcoll.group("kind"), rb, opb, wire, g))

            # ---- HBM bytes ---------------------------------------------
            if op not in _FREE_OPS and op not in _ASYNC_DONE:
                if op == "dynamic-update-slice":
                    # in-place: read+write the updated slice only (operand 1)
                    upd = (shape_bytes(types[operands[1]])
                           if len(operands) > 1 and operands[1] in types
                           else 0)
                    b = 2 * upd
                elif op in ("dynamic-slice", "gather"):
                    # reads only the sliced/gathered elements
                    b = 2 * shape_bytes(ret)
                elif op == "scatter":
                    upd = (shape_bytes(types[operands[2]])
                           if len(operands) > 2 and operands[2] in types
                           else shape_bytes(ret))
                    b = 2 * upd
                elif op == "fusion":
                    callee = _CALLS_RE.search(line)
                    pcosts, rcost = fusion_costs.get(
                        callee.group(1) if callee else "", ({}, None))
                    b = shape_bytes(ret) if rcost is None else rcost
                    for i, o in enumerate(operands):
                        if o not in types:
                            continue
                        pc = pcosts.get(i, None)
                        b += op_bytes(o) if pc is None else pc
                else:
                    b = shape_bytes(ret)
                    for o in operands:
                        b += op_bytes(o)
                c.hbm_bytes += b

            # ---- call edges --------------------------------------------
            mw = _WHILE_RE.match(line)
            if mw:
                mt = _TRIP_RE.search(line)
                trips = int(mt.group(1)) if mt else 1
                c.calls.append((mw.group("body"), trips))
                c.calls.append((mw.group("cond"), trips + 1))
                continue
            if op in ("call", "fusion", "reduce", "map", "sort", "scatter",
                      "reduce-window", "select-and-scatter", "async-start",
                      "all-reduce", "all-reduce-start", "reduce-scatter"):
                ma = _TOAPPLY_RE.search(line) or _CALLS_RE.search(line)
                if ma:
                    c.calls.append((ma.group(1), 1))
            if op == "conditional":
                mc2 = _COND_RE.search(line)
                if mc2:
                    for b in mc2.group("branches").split(","):
                        b = b.strip().lstrip("%")
                        if b:
                            c.calls.append((b, 1))
                mtf = _TF_COND_RE.search(line)
                if mtf:
                    c.calls.append((mtf.group(1), 1))
                    c.calls.append((mtf.group(2), 1))
        comps[name] = c
    return comps


def module_analysis(hlo: str) -> Dict:
    """Trip-count-aware per-device totals for the compiled module:
    {flops, hbm_bytes, collectives:{...}}."""
    comps = parse_module(hlo)
    entry = _entry_name(hlo)
    per_kind: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {"count": 0, "operand_bytes": 0, "wire_bytes": 0})
    tot = {"flops": 0.0, "hbm_bytes": 0.0}

    def visit(name: str, mult: float, depth: int = 0):
        if name not in comps or depth > 64 or mult <= 0:
            return
        c = comps[name]
        tot["flops"] += c.flops * mult
        if not c.is_fusion_body:
            tot["hbm_bytes"] += c.hbm_bytes * mult
        for col in c.collectives:
            k = per_kind[col.kind]
            k["count"] += mult
            k["operand_bytes"] += col.operand_bytes * mult
            k["wire_bytes"] += col.wire_bytes * mult
        for callee, trips in c.calls:
            visit(callee, mult * trips, depth + 1)

    if entry:
        visit(entry, 1)
    return {
        "flops": tot["flops"],
        "hbm_bytes": tot["hbm_bytes"],
        "collectives": {
            "per_kind": {k: dict(v) for k, v in sorted(per_kind.items())},
            "operand_bytes": int(sum(k["operand_bytes"]
                                     for k in per_kind.values())),
            "wire_bytes": int(sum(k["wire_bytes"]
                                  for k in per_kind.values())),
            "n_collectives": int(sum(k["count"]
                                     for k in per_kind.values())),
        },
    }


_TPU_KERNEL_RE = re.compile(
    r'^\s*(?:ROOT\s+)?%(?P<name>[\w\-]+?)(?:\.\d+)?\s*=.*'
    r'custom_call_target="tpu_custom_call"', re.M)


def tpu_kernels(hlo: str) -> Dict[str, int]:
    """Pallas kernels in compiled TPU HLO: ``{name: call sites}``, where
    ``name`` is the instruction's base name — a ``pallas_call``'s
    ``name=`` (``mixed_matmul``, ``paged_attention``, …).  A kernel whose
    caller fell back to its XLA path is simply absent."""
    out: Dict[str, int] = defaultdict(int)
    for m in _TPU_KERNEL_RE.finditer(hlo):
        out[m.group("name")] += 1
    return dict(out)


def collective_summary(hlo: str) -> Dict:
    """Back-compat wrapper: just the collective block of module_analysis."""
    return module_analysis(hlo)["collectives"]


def _multipliers(hlo: str) -> Tuple[Dict[str, _Computation], Dict[str, float]]:
    comps = parse_module(hlo)
    entry = _entry_name(hlo)
    mults: Dict[str, float] = defaultdict(float)

    def visit(name, mult, depth=0):
        if name not in comps or depth > 64:
            return
        mults[name] += mult
        for callee, trips in comps[name].calls:
            visit(callee, mult * trips, depth + 1)

    if entry:
        visit(entry, 1)
    return comps, mults


def top_contributors(hlo: str, k: int = 12) -> Dict[str, List]:
    """The §Perf drill-down: which computations dominate each roofline
    term (flops / HBM bytes / collective wire bytes), trip-weighted."""
    comps, mults = _multipliers(hlo)
    rows = []
    for name, c in comps.items():
        m = mults.get(name, 0)
        if m == 0:
            continue
        coll = sum(x.wire_bytes for x in c.collectives)
        rows.append({
            "name": name, "mult": m,
            "flops": c.flops * m,
            "bytes": (0 if c.is_fusion_body else c.hbm_bytes) * m,
            "coll_wire": coll * m,
            "coll_ops": [(x.kind, x.operand_bytes, x.group_size)
                         for x in c.collectives[:8]],
        })
    return {
        "by_flops": sorted(rows, key=lambda r: -r["flops"])[:k],
        "by_bytes": sorted(rows, key=lambda r: -r["bytes"])[:k],
        "by_coll": sorted(rows, key=lambda r: -r["coll_wire"])[:k],
    }


def instruction_bytes(hlo: str, comp_name: str, k: int = 15) -> List[Tuple]:
    """Top byte-weighted instructions inside one computation (drill-down
    one level deeper than top_contributors)."""
    split = _split_computations(hlo)
    lines = split.get(comp_name, [])
    fusion_bodies = set(_CALLS_RE.findall(hlo))
    fusion_costs = {n: _fusion_io_costs(ls) for n, ls in split.items()
                    if n in fusion_bodies}
    invariant = _loop_invariant_names(lines)
    types: Dict[str, str] = {}

    def op_bytes(o: str) -> int:
        if o not in types:
            return 0
        b = shape_bytes(types[o])
        if o in invariant and b <= VMEM_RESIDENT_LIMIT:
            return 0
        return b

    out = []
    for line in lines:
        mi = _INSTR_RE.match(line)
        if not mi:
            continue
        name, ret, op, args = (mi.group("name"), mi.group("ret"),
                               mi.group("op"), mi.group("args"))
        types[name] = ret
        operands = re.findall(r"%([\w.\-]+)", args)
        if op in _FREE_OPS or op in _ASYNC_DONE:
            continue
        if op == "dynamic-update-slice":
            b = 2 * (shape_bytes(types[operands[1]])
                     if len(operands) > 1 and operands[1] in types else 0)
        elif op in ("dynamic-slice", "gather"):
            b = 2 * shape_bytes(ret)
        elif op == "fusion":
            callee = _CALLS_RE.search(line)
            pcosts, rcost = fusion_costs.get(
                callee.group(1) if callee else "", ({}, None))
            b = shape_bytes(ret) if rcost is None else rcost
            for i, o in enumerate(operands):
                if o in types:
                    pc = pcosts.get(i, None)
                    b += op_bytes(o) if pc is None else pc
        else:
            b = shape_bytes(ret) + sum(op_bytes(o) for o in operands)
        mo = re.search(r'op_name="([^"]*)"', line)
        out.append((b, op, ret[:48], (mo.group(1)[-80:] if mo else "")))
    out.sort(reverse=True)
    return out[:k]


# ---------------------------------------------------------------------------
# Roofline terms (TPU v5e constants — DESIGN.md §6)
# ---------------------------------------------------------------------------
PEAK_FLOPS = 197e12        # bf16 FLOP/s per chip
HBM_BW = 819e9             # bytes/s per chip
ICI_BW = 50e9              # bytes/s per link (per chip, one direction)


def roofline_terms(flops: float, hbm_bytes: float, coll_bytes: float,
                   *, peak=PEAK_FLOPS, hbm=HBM_BW, ici=ICI_BW) -> Dict:
    """Three per-device roofline times (seconds) + the dominant term.

    Inputs are PER-DEVICE quantities (cost_analysis of the SPMD module and
    the per-device collective summary), so no further chip division.
    """
    t_compute = flops / peak
    t_memory = hbm_bytes / hbm
    t_collective = coll_bytes / ici
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_collective}
    dominant = max(terms, key=terms.get)
    bound = max(t_compute, t_memory, t_collective)
    return {
        **terms,
        "dominant": dominant.replace("_s", ""),
        "step_time_lower_bound_s": bound,
        # fraction of the bound spent doing useful math — the roofline score
        "compute_fraction": t_compute / bound if bound > 0 else 0.0,
    }
