"""One run of one cell: set-up, the checked steps, the window, the traced
window, the reference and the result line."""
from __future__ import annotations

import math
import os
import shutil
import sys
import time
import types

import jax
import numpy as np

from harness import check, tokens, trace as T, work
from harness.program import build, seed_key
from harness.spec import BENCH_DIR, metric_reader
from harness.window import CompileCounter, run_window
from references.train_step import reference_readings


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cell, devices, *, seed: int, seconds: float, trace: bool,
             t_start: float, setup: dict | None = None,
             fault: str | None = None, limits: dict | None = None,
             dump: str | None = None) -> dict:
    """Run ``cell`` on ``devices``; returns the result object.

    ``t_start`` is the process's start on ``time.perf_counter``;
    ``setup`` holds what was timed before this call (backend init).
    ``fault`` breaks the timed path (see ``harness.program.build``);
    ``limits`` replaces the cell's own (for runs at another size).
    ``dump`` receives a plain copy of the profiler trace.
    """
    setup = dict(setup or {})
    tr, m = cell.traffic, cell.model
    counter = CompileCounter()
    lap = time.perf_counter()

    def timed(name):
        nonlocal lap
        now = time.perf_counter()
        setup[name] = now - lap
        lap = now

    prog = build(cell, devices, fault=fault)
    key = seed_key(seed)
    pool = tokens.batch_pool(seed, tr, m["vocab_size"])
    timed("build_s")
    readings = check.program_readings(prog.init_replica)
    checked, warm = tr["checked_steps"], tr["warm_steps"]
    with prog.mesh_ctx():
        state = jax.block_until_ready(
            prog.init_state(prog.replicate(prog.init_replica(key))))
        timed("weights_s")
        batches = jax.block_until_ready([prog.batch(b) for b in pool])
        timed("inputs_s")
        step = prog.step.lower(state, batches[0]).compile()
        timed("step_compile_s")
        state, found = readings(step, state, batches[:checked], key)
        timed("checked_steps_s")
        for k in range(warm):
            state, met = step(state, batches[checked + k])
        jax.block_until_ready((state, met))
        timed("warm_steps_s")
        setup_s = time.perf_counter() - t_start

        win = run_window(step, state, batches, seconds=seconds,
                         log_every=tr["log_every"], counter=counter,
                         first=checked + warm)
        state = win.state
        log(f"window: steps {win.steps} seconds {win.seconds:.6f} "
            f"compiles {win.compiles} longest_sync_s {win.longest_sync_s:.6f} "
            f"gc_collections {win.gc_collections} "
            f"longest_gc_s {win.longest_gc_s:.6f}")
        traced = None
        if trace:
            traced = _traced_window(step, state, batches, tr, counter, dump)
            state = traced.pop("state")
        peak = _peak_bytes(devices)
        win.state = None
        del state, step, batches, met
    # a loaded executable keeps its temp memory: unload them all
    jax.clear_caches()
    counter.close()
    log("setup: " + " ".join(f"{k} {v:.3f}" for k, v in setup.items())
        + f" setup_s {setup_s:.3f}")
    for fun, secs, hit in counter.log:
        log(f"setup: program {fun} {'cache_load' if hit else 'compile'}_s "
            f"{secs:.3f}")

    tokens_per_s = win.steps * tokens.tokens_per_step(tr) / win.seconds
    failed = int(np.sum(~np.isfinite(win.losses)))

    # the reference, once the program's state is freed, on device 0
    t_ref = time.perf_counter()
    arch = cell.reference()
    with jax.default_device(devices[0]):
        replica = jax.device_put(prog.init_replica(key), devices[0])
        ref = reference_readings(arch, m, tr, replica, pool[:checked])
        del replica
    found_gaps = check.gaps(found, ref)
    log(f"reference: seconds {time.perf_counter() - t_ref:.3f} losses "
        f"{np.round(ref['losses'], 6).tolist()} program "
        f"{np.round(found['losses'], 6).tolist()}")
    limits = limits or check.load_limits(cell.name, BENCH_DIR)
    correct, checks = check.judge(found_gaps, limits)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": int(win.steps),
              "failed": failed}
    if not trace:
        values = {"tokens_per_s": (tokens_per_s, "tokens/s"),
                  "setup_s": (setup_s, "s")}
        result["metrics"] = {mt["name"]: {"value": values[mt["name"]][0],
                                          "unit": mt["unit"]}
                             for mt in cell.end_to_end}
    else:
        ctx = _context(cell, prog, traced["trace"], traced["steps"],
                       tokens_per_s, dev.device_kind, len(devices))
        result["metrics"] = {}
        for mt in cell.per_layer:
            v = metric_reader(mt["name"])(ctx)
            if v is not None:
                result["metrics"][mt["name"]] = {"value": float(v),
                                                 "unit": mt["unit"]}
        device["busy_s"] = ctx.busy_s
        device["window_s"] = ctx.window_s
    result["device"] = device
    if trace:
        result["breakdown"] = T.breakdown(traced["trace"])
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k} {v!r} limit {lim!r}")
    return result


_PROFILE = jax.profiler.ProfileOptions()
_PROFILE.python_tracer_level = 0        # host annotations only


def _traced_window(step, state, batches, tr, counter, dump):
    work_dir = os.path.join(os.path.dirname(BENCH_DIR), ".perfbench_cache",
                            "trace")
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    jax.profiler.start_trace(work_dir, profiler_options=_PROFILE)
    try:
        win = run_window(step, state, batches, seconds=math.inf,
                         log_every=tr["log_every"], counter=counter,
                         max_steps=tr["trace_steps"], annotate=True)
    finally:
        jax.profiler.stop_trace()
    t = T.collect(work_dir, dump)
    shutil.rmtree(work_dir, ignore_errors=True)
    log(f"traced window: steps {win.steps} seconds {win.seconds:.6f} "
        f"compiles {win.compiles} device_ops {len(t.ops)} "
        f"devices {t.devices}")
    return {"trace": t, "steps": win.steps, "state": win.state}


def _context(cell, prog, trace, steps, tokens_per_s, device_kind, chips):
    """What the per-layer readers read."""
    lo, hi = trace.window
    window_s = (hi - lo) * 1e-9
    busy = [T.length(T.busy_intervals(trace, d)) for d in trace.devices]
    shapes = jax.eval_shape(prog.init_replica, seed_key(0))
    tr = cell.traffic
    leaves = jax.tree.leaves(shapes)
    per_replica = sum(int(np.prod(x.shape)) for x in leaves)
    mix_work = work.gossip_mix_work(tr, per_replica,
                                    leaves[0].dtype.itemsize, chips)
    return types.SimpleNamespace(
        cell=cell, trace=trace, steps=steps, chips=chips,
        tokens_per_s=tokens_per_s, window_s=window_s,
        busy_s=float(np.mean(busy)) * 1e-9 if busy else 0.0,
        flops_per_token=cell.reference().train_flops_per_token(
            cell.model, tr["seq_len"]),
        peaks=work.peaks(device_kind, BENCH_DIR), mix_work=mix_work,
        ops_in_window=lambda: [o for o in trace.ops
                               if lo <= o.start_ns and o.end_ns <= hi])
