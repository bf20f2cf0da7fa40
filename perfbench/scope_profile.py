"""Device time of a cell's train step by program layer.

    python3 perfbench/scope_profile.py --workload <cell> --seed <n> \
        [--steps 16] [--out DIR]

Runs from the root of a checkout, on the chip the cell asks for. Builds the
cell's step as ``perfbench/run.py`` does, warms it up, traces ``--steps``
steps (dispatched as the benchmark's window dispatches them), and puts
every device op of the window down to a layer of the program through the
compiled step's op metadata (``harness.scopes``). Prints to standard
error the ``scopes:`` line (ms per step and chip of each layer,
``unscoped`` and busy, then the largest depth-2 scopes), each layer's
largest ops, the unscoped ops above 1 ms per step, and the seconds the
attribution took. The last line of standard output is the reading as
JSON, with the sha256 of the compiled step's instructions once the op
metadata is stripped: two builds whose scopes differ and whose code does
not give the same digest.

``--out`` also writes there, per cell, the reading
(``<cell>.scopes.json``), the compiled step's text (``<cell>.hlo.txt.gz``)
and the window's trace, compacted (``<cell>.trace.json.gz``), so the
attribution can be made again without the chip; and, on one chip, the
window's first two steps with the instruction -> ``op_name`` map of their
ops (``<cell>.scopes-fixture.json.gz``, the form of the recorded pair
under ``perfbench/tests/data``).

A program without the scopes reads as all ``unscoped``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".perfbench_cache", "jax")
WORK_DIR = os.path.join(ROOT, ".perfbench_cache", "scopes")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def compact_raw(raw: list) -> list:
    """What :func:`harness.scopes.reduce` reads of a trace, names compacted
    (``harness.trace.compact``) and stats dropped."""
    from harness import trace as T

    keep_host = ("window", "dispatch", "wait")
    out = []
    for plane in raw:
        lines = []
        for ln in plane["lines"]:
            if plane["plane"].startswith("/device"):
                if ln["name"] not in ("XLA Ops", "XLA Modules"):
                    continue
                evs = [{"name": T.compact(e["name"])
                        if ln["name"] == "XLA Ops" else e["name"],
                        "start_ns": e["start_ns"],
                        "duration_ns": e["duration_ns"], "stats": {}}
                       for e in ln["events"]]
            elif plane["plane"].startswith("/host"):
                evs = [{"name": e["name"], "start_ns": e["start_ns"],
                        "duration_ns": e["duration_ns"], "stats": {}}
                       for e in ln["events"] if e["name"] in keep_host]
            else:
                continue
            if evs:
                lines.append({"name": ln["name"], "events": evs})
        if lines:
            out.append({"plane": plane["plane"], "lines": lines})
    return out


def first_steps(small: list, module: str, n: int) -> list:
    """The device ops of the first ``n`` runs of ``module`` in a compacted
    one-device trace, under a ``window`` annotation spanning them."""
    dev = next(p for p in small if p["plane"].startswith("/device"))
    lines = {ln["name"]: ln["events"] for ln in dev["lines"]}
    runs = [e for e in lines["XLA Modules"]
            if e["name"].startswith(module + "(")][:n]
    lo = runs[0]["start_ns"]
    hi = runs[-1]["start_ns"] + runs[-1]["duration_ns"]
    ops = [e for e in lines["XLA Ops"]
           if lo <= e["start_ns"] and e["start_ns"] + e["duration_ns"] <= hi]
    window = {"name": "window", "start_ns": lo, "duration_ns": hi - lo,
              "stats": {}}
    return [{"plane": dev["plane"],
             "lines": [{"name": "XLA Modules", "events": runs},
                       {"name": "XLA Ops", "events": ops}]},
            {"plane": "/host:CPU",
             "lines": [{"name": "python3", "events": [window]}]}]


def traced(step, state, batches, tr, steps: int, first: int):
    """Trace ``steps`` steps dispatched as the benchmark's window does;
    returns (plain trace, steps run)."""
    import jax

    from harness import trace as T
    from harness.window import CompileCounter, run_window

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # host annotations only
    shutil.rmtree(WORK_DIR, ignore_errors=True)
    os.makedirs(WORK_DIR)
    counter = CompileCounter()
    jax.profiler.start_trace(WORK_DIR, profiler_options=opts)
    try:
        win = run_window(step, state, batches, seconds=math.inf,
                         log_every=tr["log_every"], counter=counter,
                         max_steps=steps, first=first, annotate=True)
    finally:
        jax.profiler.stop_trace()
        counter.close()
    raw = T.raw_planes(WORK_DIR)
    shutil.rmtree(WORK_DIR, ignore_errors=True)
    log(f"traced window: steps {win.steps} seconds {win.seconds:.6f} "
        f"compiles {win.compiles}")
    return raw, win.steps


def write_out(out: str, cell: str, reading: dict, text: str, raw: list,
              chips: int) -> None:
    from harness import scopes

    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"{cell}.scopes.json"), "w") as f:
        json.dump(reading, f, indent=1)
    with gzip.open(os.path.join(out, f"{cell}.hlo.txt.gz"), "wt") as f:
        f.write(text)
    small = compact_raw(raw)
    with gzip.open(os.path.join(out, f"{cell}.trace.json.gz"), "wt") as f:
        json.dump({"trace": small, "steps": reading["steps"],
                   "chips": chips}, f)
    if chips == 1 and any(p["plane"].startswith("/device") for p in small):
        two = first_steps(small, reading["module"], 2)
        insts = {scopes.inst_of(e["name"])
                 for e in two[0]["lines"][1]["events"]}
        names = {k: v for k, v in scopes.op_names(text).items()
                 if k in insts}
        with gzip.open(os.path.join(out, f"{cell}.scopes-fixture.json.gz"),
                       "wt") as f:
            json.dump({"trace": two, "op_names": names,
                       "module": reading["module"], "steps": 2,
                       "chips": 1}, f, separators=(",", ":"))


def profile_cell(cell, devices, *, seed: int, steps: int,
                 out: str | None = None) -> dict:
    import jax

    from harness import scopes, tokens
    from harness.program import build, seed_key

    tr = cell.traffic
    chips = len(devices)
    prog = build(cell, devices)
    pool = tokens.batch_pool(seed, tr, cell.model["vocab_size"])
    with prog.mesh_ctx():
        state = prog.init_state(prog.replicate(
            prog.init_replica(seed_key(seed))))
        batches = [prog.batch(b) for b in pool]
        step = prog.step.lower(state, batches[0]).compile()
        warm = tr["checked_steps"] + tr["warm_steps"]
        for k in range(warm):
            state, met = step(state, batches[k])
        jax.block_until_ready((state, met))
        log(f"setup: seconds {time.perf_counter() - T_START:.3f}")
        raw, n = traced(step, state, batches, tr, steps, warm)
        t = time.perf_counter()
        text = step.as_text()
        found_trace = scopes.attribute(scopes.reduce(raw), text)
        found = scopes.per_step(found_trace, n, chips)
        attribute_s = time.perf_counter() - t
        del state, step, batches, met
    log(scopes.line(found))
    for layer in scopes.LAYERS:
        top = scopes.top_ops(found_trace, n, chips, layer)[:5]
        log(f"top {layer}: " + " | ".join(f"{k} {v:.3f}" for k, v in top))
    for name, ms in scopes.top_ops(found_trace, n, chips, scopes.UNSCOPED,
                                   min_ms=1.0):
        log(f"unscoped: {name} {ms:.3f} ms/step/chip")
    layers = sum(found[k] or 0.0 for k in scopes.LAYERS + (scopes.UNSCOPED,))
    log(f"attribute: seconds {attribute_s:.3f} text_bytes {len(text)} "
        f"layers_sum {layers:.3f} busy {found['busy']:.3f}")
    stripped = scopes.strip_metadata(text)
    reading = {"cell": cell.name, "steps": n, "chips": chips,
               "module": scopes.module_name(text), "ms_per_step": found,
               "attribute_s": attribute_s,
               "stripped_sha256": hashlib.sha256(
                   stripped.encode()).hexdigest()}
    if out:
        write_out(out, cell.name, reading, text, raw, chips)
    return reading


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from harness import spec

    cell = spec.load(args.workload, ROOT)
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < cell.chips:
        log(f"scope_profile.py: {cell.name} needs {cell.chips} accelerator "
            f"chip(s); JAX found {len(devices)} {devices[0].platform} "
            "device(s)")
        return 3
    reading = profile_cell(cell, devices[:cell.chips], seed=args.seed,
                           steps=args.steps, out=args.out)
    print(json.dumps(reading), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
