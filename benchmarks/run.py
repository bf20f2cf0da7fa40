"""Benchmark driver: one bench per paper table/figure + the roofline table.

Prints ``bench,name,us_per_call,derived`` CSV rows, writes JSON artifacts to
results/bench/ (provenance-stamped via ``benchmarks.common.save_json``), and
ends with a summary table — one row per lane: key metric + artifact path —
so a ``--quick`` CI run is readable without trawling results/bench/.

Usage: python benchmarks/run.py [--quick] [only_name]
``--quick`` runs reduced problem sizes where a bench supports it (CI smoke).
"""
from __future__ import annotations

import inspect
import sys
import time


BENCHES = [
    ("table1", "benchmarks.bench_table1"),
    ("fig1", "benchmarks.bench_fig1"),
    ("fig2_fig4", "benchmarks.bench_fig2"),
    ("fig5", "benchmarks.bench_fig5"),
    ("toy_fig7", "benchmarks.bench_toy"),
    ("appC", "benchmarks.bench_appc"),
    ("kernels", "benchmarks.bench_kernels"),
    ("bus", "benchmarks.bench_bus"),
    ("groups", "benchmarks.bench_groups"),
    ("sim", "benchmarks.bench_sim"),
    ("dci_compress", "benchmarks.bench_dci_compress"),
    ("sim_scale", "benchmarks.bench_sim_scale"),
    ("faults", "benchmarks.bench_faults"),
    ("roofline", "benchmarks.bench_roofline"),
    ("serving", "benchmarks.bench_serving"),
]


def _key_metric(rows: list[dict]) -> str:
    """First numeric field of the first row — the lane's headline number."""
    for r in rows:
        for k, v in r.items():
            if isinstance(v, bool) or k in ("bench",):
                continue
            if isinstance(v, (int, float)):
                return f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
    return "-"


def main() -> None:
    import importlib

    from benchmarks import common
    from repro.launch.cache import use_compile_cache

    use_compile_cache()

    argv = [a for a in sys.argv[1:]]
    quick = "--quick" in argv
    if quick:
        argv.remove("--quick")
    only = argv[0] if argv else None
    if only and only not in {n for n, _ in BENCHES}:
        raise SystemExit(f"unknown bench {only!r}; choose from "
                         f"{[n for n, _ in BENCHES]}")
    print("bench,name,us_per_call,derived")
    failures = []
    summary: list[tuple[str, str, str, float]] = []
    for name, modname in BENCHES:
        if only and only != name:
            continue
        t0 = time.perf_counter()
        art0 = len(common.ARTIFACTS)
        try:
            mod = importlib.import_module(modname)
            kwargs = {}
            if quick and "quick" in inspect.signature(mod.run).parameters:
                kwargs["quick"] = True
            rows = mod.run(**kwargs)
        except Exception as e:  # pragma: no cover
            failures.append((name, repr(e)))
            print(f"{name},ERROR,0,{e!r}")
            continue
        dt = (time.perf_counter() - t0) * 1e6
        for r in rows:
            tag = r.get("problem") or r.get("arch") or r.get("dist") or \
                r.get("heterogeneity") or r.get("combo") or r.get("topology") or ""
            extra = {k: v for k, v in r.items()
                     if k not in ("bench", "problem", "arch", "dist", "topology")}
            derived = ";".join(f"{k}={v}" for k, v in list(extra.items())[:6])
            print(f"{name},{tag},{dt / max(len(rows), 1):.0f},{derived}")
        arts = [p for _, p in common.ARTIFACTS[art0:]]
        summary.append((name, _key_metric(rows),
                        arts[-1] if arts else "-",
                        (time.perf_counter() - t0)))

    if summary:
        print()
        print(f"{'lane':<10} {'key metric':<28} {'wall':>7}  artifact")
        print(f"{'-' * 10} {'-' * 28} {'-' * 7}  {'-' * 8}")
        for name, metric, art, secs in summary:
            print(f"{name:<10} {metric:<28} {secs:6.1f}s  {art}")
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
