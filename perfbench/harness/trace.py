"""Profiler trace of a short window, reduced to device operations under
stable names.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. Device planes are ``/device:<KIND>:<n>``; their "XLA Ops" line
holds one event per HLO operation run, with its start and duration in
nanoseconds on the same clock as the host's ``TraceAnnotation`` spans.
HLO numbering (``fusion.731``) changes with every recompile, so every
operation is grouped under a stable name instead: its opcode and result
shape, and for a custom call its target (``custom-call:tpu_custom_call``
is a Pallas kernel). The trace holds no op metadata, so named scopes and
kernel names are not visible here.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re

# "%fusion.741 = bf16[4,2048,49155]{1,2,0:T(8,128)(2,1)} fusion(...), ..."
_HLO = re.compile(r"^%(?P<inst>[^ ]+) = (?P<shape>\(.*?\)|[a-z0-9]+\[[0-9,]*\])"
                  r"\S* (?P<op>[a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
# ops whose interval holds other ops' (a loop's body runs inside it)
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Op:
    device: int
    start_ns: float
    dur_ns: float
    name: str              # stable name
    category: str

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    ops: list               # Op, every device
    host: list              # (name, start_ns, dur_ns) of host annotations
    window: tuple           # (start_ns, end_ns) of the "window" annotation
    devices: list           # device ids that ran something

    def device_ops(self, device: int) -> list:
        return [o for o in self.ops if o.device == device]


def _stats(ev) -> dict:
    out = {}
    for k, v in getattr(ev, "stats", ()) or ():
        out[k] = v
    return out


def stable_name(text: str) -> tuple[str, str]:
    """(stable name, opcode) of one device op, from the
    HLO instruction the trace names it by: the opcode and the result's
    shape, with the call target for a custom call (``tpu_custom_call`` is
    a Pallas kernel). The instruction's number is dropped."""
    m = _HLO.match(text)
    if not m:
        return text.split(" ")[0].lstrip("%"), ""
    op, shape = m.group("op"), m.group("shape")
    if shape.startswith("("):             # a tuple: name it by its first
        first = re.match(r"\(([a-z0-9]+\[[0-9,]*\])", shape)
        shape = f"({first.group(1)},...)" if first else "tuple"
    if op == "custom-call":
        t = _TARGET.search(text)
        op = f"custom-call:{t.group(1) if t else '?'}"
    return f"{op} {shape}", op


def compact(text: str) -> str:
    """What :func:`stable_name` reads of an HLO instruction, and no more
    (for keeping recorded traces small)."""
    m = _HLO.match(text)
    if not m:
        return text[:200]
    t = _TARGET.search(text)
    return text[:m.end()] + (f' custom_call_target="{t.group(1)}"' if t
                             else "")


def _profile_file(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def raw_planes(log_dir: str) -> list:
    """The newest trace under ``log_dir`` as plain data: planes, their
    lines, and the events of each with start, duration and stats."""
    import jax

    pd = jax.profiler.ProfileData.from_file(_profile_file(log_dir))
    return [{"plane": plane.name, "lines": [
        {"name": ln.name, "events": [
            {"name": e.name, "start_ns": e.start_ns,
             "duration_ns": e.duration_ns,
             "stats": {k: str(v)[:200] for k, v in _stats(e).items()}}
            for e in ln.events]} for ln in plane.lines]}
        for plane in pd.planes]


def reduce(raw: list) -> Trace:
    """Device ops under stable names, and the host annotations."""
    ops, host, window = [], [], None
    for plane in raw:
        m = re.match(r"^/device:[A-Z]+:(\d+)", plane["plane"])
        for ln in plane["lines"]:
            if m and ln["name"] == "XLA Ops":
                for e in ln["events"]:
                    name, cat = stable_name(e["name"])
                    ops.append(Op(int(m.group(1)), e["start_ns"],
                                  e["duration_ns"], name, cat))
            elif plane["plane"].startswith("/host"):
                for e in ln["events"]:
                    if e["name"] in ("window", "dispatch", "wait"):
                        host.append((e["name"], e["start_ns"],
                                     e["duration_ns"]))
                        if e["name"] == "window":
                            window = (e["start_ns"],
                                      e["start_ns"] + e["duration_ns"])
    if window is None:
        raise ValueError("the trace holds no 'window' annotation")
    return Trace(ops, host, window, sorted({o.device for o in ops}))


def collect(log_dir: str, dump: str | None = None) -> Trace:
    """Read and reduce the newest trace under ``log_dir``; ``dump`` also
    writes its plain data as gzipped JSON."""
    raw = raw_planes(log_dir)
    if dump:
        with gzip.open(dump, "wt") as f:
            json.dump(raw, f)
    return reduce(raw)


def union(intervals) -> list:
    """Merge (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Parts of merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def busy_intervals(trace: Trace, device: int) -> list:
    lo, hi = trace.window
    return clip(union((o.start_ns, o.end_ns) for o in trace.device_ops(device)),
                lo, hi)


def breakdown(trace: Trace, n: int = 10) -> dict:
    """The device operations that took most time (summed over devices, per
    stable name) and the longest idle gaps of device 0, each named by the
    host annotation under its midpoint."""
    lo, hi = trace.window
    tot: dict[str, float] = {}
    for o in trace.ops:
        if o.category in CONTAINERS:
            continue
        d = min(o.end_ns, hi) - max(o.start_ns, lo)
        if d > 0:
            tot[o.name] = tot.get(o.name, 0.0) + d * 1e-9
    ops = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    dev0 = trace.devices[0] if trace.devices else 0
    gaps = subtract([(lo, hi)], union(busy_intervals(trace, dev0)))
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = 0.5 * (s + e)
        what = "other"
        for name, hs, hd in trace.host:
            if name != "window" and hs <= mid <= hs + hd:
                what = name
                break
        named.append([what, (e - s) * 1e-9])
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": named}
