"""Benchmark of the gossip train step on the chip; one run of one cell.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs from the root of a checkout. The cell (configuration, traffic,
metrics) is read from BENCHMARK.json and the files it names. The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device`` and, traced,
``breakdown``; then ``checks``, each number compared with its limit. The
diagnostics go to standard error, the compared numbers last.

Exits non-zero, printing no result, where JAX finds no accelerator, fewer
chips than the cell asks for, or no program to run.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".perfbench_cache", "jax")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default=None,
                    help="also write the raw trace here (gzipped JSON)")
    args = ap.parse_args(argv)
    if args.seed < 0:
        print("run.py: --seed must be a whole number >= 0", file=sys.stderr)
        return 2
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from harness import spec

    cell = spec.load(args.workload, ROOT)
    import jax

    t = time.perf_counter()
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        import repro  # noqa: F401  the system under test
    except ImportError as e:
        print(f"run.py: the program to benchmark is missing ({e})",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    setup = {"backend_init_s": time.perf_counter() - t}
    if devices[0].platform == "cpu" or len(devices) < cell.chips:
        print(f"run.py: {cell.name} needs {cell.chips} accelerator chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    from harness.cell import run_cell

    result = run_cell(cell, devices[:cell.chips], seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      t_start=T_START, setup=setup, dump=args.dump_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
