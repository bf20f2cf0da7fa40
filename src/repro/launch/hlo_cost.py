"""Trip-count-aware cost model over compiled HLO text.

XLA's ``compiled.cost_analysis()`` counts a ``while`` body ONCE, so any
program built from ``lax.scan`` (layers, microbatches, CE chunks, blockwise
attention) under-reports FLOPs/bytes by the trip count.  This module parses
the compiled HLO, builds the computation call graph with execution
multipliers (``known_trip_count`` from backend_config), and accumulates:

  * flops       — 2·prod(result_dims)·prod(contracting_dims) per dot op,
  * bytes       — Σ (result + operand buffer bytes) per op (post-fusion HLO,
                  so fusion internals are already collapsed),
  * collectives — payload bytes per collective kind,

each multiplied by the execution count of its enclosing computation.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?[^=]*?\)?)\s*([\w\-]+)\(")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$")
_CALLED_RE = re.compile(r"(?:body|condition|to_apply|called_computations=\{|branch_computations=\{|calls)=?%?([\w.\-]+)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_OPERAND_RE = re.compile(r"%([\w.\-]+)")

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_dims(dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def _type_bytes(type_str: str) -> int:
    return sum(_shape_dims(s) * _DTYPE_BYTES.get(dt, 4)
               for dt, s in _SHAPE_RE.findall(type_str))


@dataclasses.dataclass
class Op:
    name: str
    kind: str
    result_type: str
    line: str


@dataclasses.dataclass
class Computation:
    name: str
    ops: list
    symbols: dict          # op name -> result type string


_COMMENT_RE = re.compile(r"/\*.*?\*/")
# TPU layouts carry tiling and memory-space parentheses after the shape
# (`bf16[8,128]{1,0:T(8,128)(2,1)S(1)}`), which would read as an opcode
_LAYOUT_RE = re.compile(r"(\])\{[^{}]*\}")


def parse_computations(hlo: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    hlo = _LAYOUT_RE.sub(r"\1", _COMMENT_RE.sub("", hlo))
    for line in hlo.splitlines():
        mc = _COMP_RE.match(line)
        if mc and ("->" in line):
            cur = Computation(mc.group(1), [], {})
            comps[cur.name] = cur
            continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        md = _DEF_RE.match(line)
        if md:
            name, rtype, kind = md.group(1), md.group(2).strip(), md.group(3)
            cur.ops.append(Op(name, kind, rtype, line))
            cur.symbols[name] = rtype
    return comps


def execution_counts(comps: dict[str, Computation], entry: str) -> dict[str, float]:
    """Propagate execution multipliers from the entry computation."""
    counts: dict[str, float] = defaultdict(float)
    counts[entry] = 1.0
    # iterate to fixpoint over the (acyclic) call graph
    order = list(comps)
    for _ in range(len(comps) + 2):
        changed = False
        new = defaultdict(float)
        new[entry] = 1.0
        for cname in order:
            mult = counts.get(cname, 0.0)
            if mult <= 0:
                continue
            for op in comps[cname].ops:
                callees = _CALLED_RE.findall(op.line)
                if not callees:
                    continue
                trip = 1.0
                if op.kind == "while":
                    mt = _TRIP_RE.search(op.line)
                    trip = float(mt.group(1)) if mt else 1.0
                for callee in callees:
                    if callee in comps:
                        new[callee] += mult * trip
            pass
        # recompute from scratch each round (handles nesting depth ≤ rounds)
        for k, v in new.items():
            if abs(counts.get(k, 0.0) - v) > 1e-9:
                changed = True
        counts = new
        if not changed:
            break
    return counts


def _find_entry(hlo: str, comps: dict[str, Computation]) -> str:
    m = re.search(r"ENTRY\s+%?([\w.\-]+)", hlo)
    if m and m.group(1) in comps:
        return m.group(1)
    # fallback: computation that nobody calls
    called = set()
    for c in comps.values():
        for op in c.ops:
            called.update(x for x in _CALLED_RE.findall(op.line) if x in comps)
    for name in comps:
        if name not in called:
            return name
    return next(iter(comps))


_DOT_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")


def _dot_flops(op: Op, symbols: dict) -> float:
    """2 · prod(result dims) · prod(contracting dims of lhs)."""
    result_elems = sum(_shape_dims(s) for _, s in _SHAPE_RE.findall(op.result_type))
    mc = _DOT_CONTRACT_RE.search(op.line)
    # first operand name after the opcode
    after = op.line.split(op.kind + "(", 1)[1]
    operands = _OPERAND_RE.findall(after)
    contract = 1
    if mc and operands:
        lhs_t = symbols.get(operands[0])
        if lhs_t:
            m = _SHAPE_RE.search(lhs_t)
            if m:
                dims = [int(d) for d in m.group(2).split(",") if d]
                for idx in mc.group(1).split(","):
                    if idx:
                        i = int(idx)
                        if i < len(dims):
                            contract *= dims[i]
    return 2.0 * result_elems * contract


@dataclasses.dataclass
class HloCost:
    flops: float
    bytes: float
    coll_bytes: dict            # per kind + "total"
    coll_counts: dict
    dot_flops_detail: int = 0   # number of dot ops seen


def analyze_hlo(hlo: str) -> HloCost:
    comps = parse_computations(hlo)
    entry = _find_entry(hlo, comps)
    counts = execution_counts(comps, entry)

    flops = 0.0
    nbytes = 0.0
    coll = {c: 0.0 for c in _COLLECTIVES}
    coll["total"] = 0.0
    coll_n = {c: 0 for c in _COLLECTIVES}
    n_dots = 0

    _SKIP = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast",
             "while", "conditional", "call", "after-all", "iota")

    def _operands(op: Op) -> list[str]:
        after = op.line.split(op.kind + "(", 1)
        if len(after) != 2:
            return []
        return _OPERAND_RE.findall(after[1].split(")", 1)[0])

    def _fusion_operand_bytes(comp: Computation, op: Op) -> float:
        """Slice-aware operand traffic for a fusion: if an operand is only
        consumed by dynamic-slice ops inside the fused computation (the scan
        per-step weight read), count the slice size, not the full buffer."""
        callees = [c for c in _CALLED_RE.findall(op.line) if c in comps]
        fused = comps.get(callees[0]) if callees else None
        operands = _operands(op)
        total = 0.0
        param_of = {}
        if fused is not None:
            idx_re = re.compile(r"parameter\((\d+)\)")
            for fop in fused.ops:
                if fop.kind == "parameter":
                    m = idx_re.search(fop.line)
                    if m:
                        param_of[int(m.group(1))] = fop.name
        for i, oname in enumerate(operands):
            full = _type_bytes(comp.symbols.get(oname, ""))
            if fused is not None and i in param_of:
                pname = param_of[i]
                consumers = [fop for fop in fused.ops
                             if pname in _operands(fop)]
                if consumers and all(c.kind == "dynamic-slice" for c in consumers):
                    total += sum(_type_bytes(c.result_type) for c in consumers)
                    continue
            total += full
        return total

    for cname, comp in comps.items():
        mult = counts.get(cname, 0.0)
        if mult <= 0:
            continue
        # fused computations' internals: HBM traffic is accounted at the
        # enclosing fusion op; dots inside are still counted (with mult)
        is_fused_body = "fused_computation" in cname or cname.endswith(".clone")
        for op in comp.ops:
            if op.kind in _SKIP:
                continue
            rbytes = _type_bytes(op.result_type)
            if op.kind in ("dot", "convolution"):
                flops += _dot_flops(op, comp.symbols) * mult
                n_dots += 1
            kind = next((c for c in _COLLECTIVES
                         if op.kind == c or op.kind == c + "-start"), None)
            if kind:
                coll[kind] += rbytes * mult
                coll["total"] += rbytes * mult
                coll_n[kind] += 1
            if is_fused_body:
                continue  # HBM traffic counted at the enclosing fusion op
            if op.kind == "dynamic-slice":
                nbytes += 2.0 * rbytes * mult
            elif op.kind == "dynamic-update-slice":
                ops_ = _operands(op)
                upd = _type_bytes(comp.symbols.get(ops_[1], "")) if len(ops_) > 1 else rbytes
                nbytes += 2.0 * upd * mult
            elif op.kind in ("broadcast", "reshape", "gather"):
                nbytes += 2.0 * rbytes * mult if op.kind == "gather" else rbytes * mult
            elif op.kind == "fusion":
                nbytes += (rbytes + _fusion_operand_bytes(comp, op)) * mult
            else:
                obytes = sum(_type_bytes(comp.symbols.get(o, ""))
                             for o in _operands(op))
                nbytes += (rbytes + obytes) * mult
    return HloCost(flops=flops, bytes=nbytes, coll_bytes=coll,
                   coll_counts=coll_n, dot_flops_detail=n_dots)


# ---------------------------------------------------------------------------
# Collective-permute link classification (ICI vs DCI)
# ---------------------------------------------------------------------------

_PAIRS_RE = re.compile(r"collective-permute[\w-]*\([^)]*\).*?"
                       r"source_target_pairs=\{((?:\{\d+,\d+\},?)+)\}")
_PAIR_RE = re.compile(r"\{(\d+),(\d+)\}")


def permute_link_classes(hlo: str, mesh, pod_axis: str = "pod") -> dict:
    """Classify every collective-permute in compiled HLO as ICI or DCI.

    ``source_target_pairs`` carry partition ids, which index the executable's
    device assignment — ``mesh.devices.flatten()`` order for a jit over the
    mesh — so ``np.unravel_index(pid, mesh.devices.shape)`` recovers each
    endpoint's mesh coordinates. An op is:

      * ``ici``   — every non-self pair stays within one pod;
      * ``dci``   — every non-self pair crosses pods AND preserves all
                    non-pod coordinates (the permutation rides ONLY the pod
                    axis — pure DCI, no incidental intra-pod hops);
      * ``mixed`` — anything else (e.g. a flat ring whose edges wrap across
                    a pod boundary while also shifting the data coord).

    The hierarchical-gossip CI gate asserts ici > 0, dci > 0, mixed == 0.
    """
    import numpy as np

    mesh = getattr(mesh, "mesh", mesh)            # WorkerMesh → Mesh
    axis_names = tuple(mesh.axis_names)
    if pod_axis not in axis_names:
        raise ValueError(f"mesh has no {pod_axis!r} axis: {axis_names}")
    pod_i = axis_names.index(pod_axis)
    shape = mesh.devices.shape
    out = {"ici": 0, "dci": 0, "mixed": 0, "ops": []}
    for m in _PAIRS_RE.finditer(hlo):
        pairs = [(int(a), int(b)) for a, b in _PAIR_RE.findall(m.group(1))]
        classes = set()
        for s, t in pairs:
            if s == t:
                continue
            sc = np.unravel_index(s, shape)
            tc = np.unravel_index(t, shape)
            same_pod = sc[pod_i] == tc[pod_i]
            others_fixed = all(a == b for i, (a, b) in enumerate(zip(sc, tc))
                               if i != pod_i)
            if same_pod:
                classes.add("ici")
            elif others_fixed:
                classes.add("dci")
            else:
                classes.add("mixed")
        if not classes:
            continue                               # all-self-pairs no-op
        cls = classes.pop() if len(classes) == 1 else "mixed"
        out[cls] += 1
        out["ops"].append({"class": cls, "n_pairs": len(pairs)})
    return out
