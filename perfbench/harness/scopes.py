"""Device time of a traced window by program layer, from the compiled
step's op metadata.

The train step names its layers with ``jax.named_scope``: ``model``,
``optimizer``, ``stats`` and ``gossip``, and below them ``embed``,
``attention``, ``ssd``, ``mlp``, ``lm_head`` (the model) and ``pack``,
``mix``, ``unpack`` (the bus). The profiler trace names each device op by
its HLO instruction (``%fusion.731 = ...``) and holds no metadata; the
compiled step's ``as_text()`` gives every instruction its ``op_name``,
``jit(step)/model/vmap(transpose(jvp(lm_head)))/...``. Both come from the
one executable the window runs, so the instruction names match.

An op's layer is the first step scope on its ``op_name`` path once the
transform wrappers (``vmap(``, ``jvp(``, ``transpose(``) are peeled off;
``model`` splits into ``model_fwd`` and ``model_bwd`` (a ``transpose(`` on
the path). An op that carries only a model scope (a constant XLA hoisted
out of the step's scopes) counts as the model's. Instructions XLA made
without metadata are resolved through their fused body or their
neighbours (:func:`op_names`). Only ops inside the step
module's own intervals (the trace's "XLA Modules" line) are attributed:
other programs in the window, such as transfers, reuse instruction names.
Containers (``while``, ``call``, ``conditional``) are left out, as in
:func:`harness.trace.breakdown`, since their bodies' ops are counted.

A program without the scopes (an older build) reads as all ``unscoped``.
"""
from __future__ import annotations

import dataclasses
import re

from harness import trace as T

STEP_SCOPES = ("model", "optimizer", "stats", "gossip")
SUB_SCOPES = {"model": ("embed", "attention", "ssd", "mlp", "lm_head"),
              "gossip": ("pack", "mix", "unpack")}
LAYERS = ("model_fwd", "model_bwd", "optimizer", "stats", "gossip")
UNSCOPED = "unscoped"

_WRAP = re.compile(r"^([A-Za-z_]\w*)\((.*)\)$")
_INST = re.compile(r"^\s*(?:ROOT )?%?(?P<inst>[^ ]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_META = re.compile(r',? metadata=\{(?:[^}"]|"(?:[^"\\]|\\.)*")*\}')
_MODULE = re.compile(r"^HloModule (\S+?),?\s")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([^\s(]+) \(")
_CALLS = re.compile(r"calls=%([^\s,]+)")
_CALLED = re.compile(r"(?:calls|body|condition|to_apply)=%([^\s,}]+)"
                     r"|branch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([^\s,()]+)")
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


@dataclasses.dataclass
class ScopedOp(T.Op):
    inst: str = ""          # the HLO instruction's name, e.g. fusion.731
    scope: str = ""         # a layer of LAYERS, UNSCOPED, or "" (not step)
    sub: str = ""           # the first two scopes, e.g. gossip/pack


@dataclasses.dataclass
class ScopedTrace(T.Trace):
    modules: list = dataclasses.field(default_factory=list)
    # (device, module name, start_ns, end_ns) of each program run


def reduce(raw: list) -> ScopedTrace:
    """:func:`harness.trace.reduce`, keeping each op's instruction name and
    the "XLA Modules" intervals."""
    base = T.reduce(raw)
    ops, modules = [], []
    for plane in raw:
        m = re.match(r"^/device:[A-Z]+:(\d+)", plane["plane"])
        if not m:
            continue
        dev = int(m.group(1))
        for ln in plane["lines"]:
            if ln["name"] == "XLA Ops":
                for e in ln["events"]:
                    name, cat = T.stable_name(e["name"])
                    ops.append(ScopedOp(dev, e["start_ns"], e["duration_ns"],
                                        name, cat, inst=inst_of(e["name"])))
            elif ln["name"] == "XLA Modules":
                modules += [(dev, e["name"], e["start_ns"],
                             e["start_ns"] + e["duration_ns"])
                            for e in ln["events"]]
    return ScopedTrace(ops, base.host, base.window, base.devices, modules)


def inst_of(hlo: str) -> str:
    """The instruction name an HLO line (or a trace event) starts with."""
    m = _INST.match(hlo)
    return m.group("inst") if m else ""


def module_name(hlo_text: str) -> str:
    m = _MODULE.match(hlo_text)
    return m.group(1) if m else ""


def op_names(hlo_text: str) -> dict:
    """Instruction name -> ``op_name``, for every instruction of the text.

    XLA leaves some instructions without metadata. A fusion whose root is
    a bitcast or a layout change of its own takes the last ``op_name`` of
    the computation it calls (callees are printed before callers): the op
    nearest its root. A copy, an async slice or a memory-space move XLA
    put in (no ``op_name``, or only an argument's path) takes the
    ``op_name`` of the nearest instruction that consumes its result, else
    of the nearest that produced its input: the layer that needed it. A
    fusion with no ``op_name`` inside either (XLA's write of one leaf into
    the bus, say) looks the other way first: it goes with what it reads,
    since its consumer may be a fusion XLA named after another layer's op.
    Failing both (a loop XLA made to split a large copy), it takes the
    ``op_name`` of the instruction that calls its computation.
    Instructions with none of these stay out.
    """
    named, last, comp = {}, {}, None
    args: dict = {}           # instruction -> operands in its computation
    comp_of: dict = {}        # instruction -> its computation
    callers: dict = {}        # computation -> the instruction calling it
    fusions = set()           # fusions with no op_name inside either
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        inst = inst_of(line)
        if not inst:
            continue
        om = _OP_NAME.search(line)
        # a name outside jit(...) is an argument's path (a copy of a
        # parameter), not where in the program the op comes from
        name = om.group(1) if om and om.group(1).startswith("jit(") else None
        if name is None:
            called = _CALLS.search(line)
            name = last.get(called.group(1)) if called else None
            if called and name is None:
                fusions.add(inst)
        if name:
            named[inst] = name
            last[comp] = name
        for one, many in _CALLED.findall(line):
            for callee in [one] if one else re.findall(r"%([^\s,]+)", many):
                callers.setdefault(callee, inst)
        args[inst] = [a for a in _OPERAND.findall(line.split(" = ", 1)[1])
                      if comp_of.get(a) == comp]
        comp_of[inst] = comp
    users: dict = {}
    for inst, operands in args.items():
        for a in operands:
            users.setdefault(a, []).append(inst)
    out = dict(named)
    for inst in args:
        if inst not in named:
            first, then = (args, users) if inst in fusions else (users, args)
            found = _nearest(inst, first, named) or _nearest(inst, then, named)
            if found:
                out[inst] = found
    for inst in args:
        caller = inst
        while caller not in out and comp_of.get(caller) in callers:
            caller = callers[comp_of[caller]]
        if caller in out:
            out[inst] = out[caller]
    return out


def _nearest(inst: str, edges: dict, named: dict) -> str | None:
    """The ``op_name`` of the nearest named instruction along ``edges``."""
    seen, frontier = {inst}, [inst]
    while frontier:
        nxt = []
        for i in frontier:
            for j in edges.get(i, ()):
                if j in named:
                    return named[j]
                if j not in seen:
                    seen.add(j)
                    nxt.append(j)
        frontier = nxt
    return None


def strip_metadata(hlo_text: str) -> str:
    """The instructions alone: op metadata and the stack-frame tables
    dropped (what two builds that differ only in names must share)."""
    out, skip = [], False
    for line in hlo_text.splitlines():
        if line.strip() in _TABLES:
            skip = True
        elif skip and not line.strip():
            skip = False
            continue
        if not skip:
            out.append(_META.sub("", line))
    return "\n".join(out)


def _tokens(op_name: str) -> tuple[list, bool]:
    """The path's names with wrappers peeled, and whether a ``transpose(``
    wrapper is on it. A name inside ``jit(...)`` is a function's, never a
    scope."""
    names, bwd = [], False
    for part in op_name.split("/"):
        is_fun = False
        while True:
            m = _WRAP.match(part)
            if not m:
                break
            bwd |= m.group(1) == "transpose"
            is_fun |= m.group(1) in ("jit", "pjit")
            part = m.group(2)
        names.append("" if is_fun else part)
    return names, bwd


def layer_of(op_name: str) -> tuple[str, str]:
    """(layer, first two scopes) of an ``op_name``; (UNSCOPED, "") where
    no step scope is on the path."""
    names, bwd = _tokens(op_name)
    top = next((i for i, n in enumerate(names) if n in STEP_SCOPES), None)
    if top is None:
        top = next((i for i, n in enumerate(names)
                    if n in SUB_SCOPES["model"]), None)
        if top is None:
            return UNSCOPED, ""
        step, rest = "model", names[top:]
    else:
        step, rest = names[top], names[top + 1:]
    sub = next((n for n in rest if n in SUB_SCOPES.get(step, ())), None)
    layer = ("model_bwd" if bwd else "model_fwd") if step == "model" else step
    return layer, step + ("/" + sub if sub else "")


def attribute(trace: ScopedTrace, hlo_text: str,
              module: str | None = None) -> ScopedTrace:
    """Give each op of the step module (``module``, else the one
    ``hlo_text`` names) its layer; ops of other programs and containers
    keep ``scope`` empty."""
    module = module or module_name(hlo_text)
    names = op_names(hlo_text)
    runs: dict = {}
    for dev, name, s, e in trace.modules:
        if name == module or name.startswith(module + "("):
            runs.setdefault(dev, []).append((s, e))
    for op in trace.ops:
        inside = any(s <= op.start_ns and op.end_ns <= e
                     for s, e in runs.get(op.device, ()))
        if not inside or op.category in T.CONTAINERS:
            op.scope = op.sub = ""
            continue
        op.scope, op.sub = layer_of(names.get(op.inst, ""))
    return trace


def _in_window(trace, op) -> float:
    lo, hi = trace.window
    return max(0.0, min(op.end_ns, hi) - max(op.start_ns, lo))


def per_step(trace: ScopedTrace, steps: int, chips: int) -> dict:
    """ms per step and chip of each layer (None where no op ran), of
    ``unscoped``, and of device busy; with the depth-2 scopes' ms."""
    per = 1e-6 / (steps * chips)
    tot: dict = {}
    subs: dict = {}
    for op in trace.ops:
        if not op.scope:
            continue
        d = _in_window(trace, op)
        if d <= 0:
            continue
        tot[op.scope] = tot.get(op.scope, 0.0) + d
        if op.sub:
            subs[op.sub] = subs.get(op.sub, 0.0) + d
    busy = sum(T.length(T.busy_intervals(trace, dev)) for dev in trace.devices)
    out = {k: (tot[k] * per if k in tot else None)
           for k in LAYERS + (UNSCOPED,)}
    out["busy"] = busy * per
    out["subs"] = {k: v * per for k, v in
                   sorted(subs.items(), key=lambda kv: -kv[1])}
    return out


def top_ops(trace: ScopedTrace, steps: int, chips: int, scope: str,
            min_ms: float = 0.0) -> list:
    """The stable names of the ops of layer ``scope`` (or UNSCOPED) with
    their ms per step and chip, largest first, those above ``min_ms``."""
    tot: dict = {}
    for op in trace.ops:
        if op.scope == scope:
            tot[op.name] = tot.get(op.name, 0.0) + _in_window(trace, op)
    per = 1e-6 / (steps * chips)
    return [(k, v * per) for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            if v * per > min_ms]


def line(found: dict, n_subs: int = 10) -> str:
    """The ``scopes:`` diagnostic line."""
    parts = [f"{k} {'-' if found[k] is None else format(found[k], '.3f')}"
             for k in LAYERS + (UNSCOPED,)]
    parts.append(f"busy {found['busy']:.3f}")
    subs = list(found["subs"].items())[:n_subs]
    return ("scopes: ms/step/chip " + " ".join(parts) + " | "
            + " ".join(f"{k} {v:.3f}" for k, v in subs))
