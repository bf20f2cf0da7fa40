"""Does topology matter? The paper's Fig. 2 vs Fig. 4 in one script.

    PYTHONPATH=src python examples/topology_matters.py

Trains the same softmax classifier with DSM on a ring and on a clique, first
with a random data split (per-iteration curves coincide — Fig. 2), then with
a pathological split-by-label (topology suddenly matters — Fig. 4).
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

from benchmarks import common
from repro.core import topology as T

M_WORKERS = 16


def sparkline(vals, width=48):
    lo, hi = min(vals), max(vals)
    chars = "▁▂▃▄▅▆▇█"
    idx = np.linspace(0, len(vals) - 1, width).astype(int)
    return "".join(chars[int((vals[i] - lo) / max(hi - lo, 1e-9) * 7)] for i in idx)


def main():
    problem = common.problem_classifier(S=1024, n_classes=16)
    ring = T.undirected_ring(M_WORKERS)
    clique = T.clique(M_WORKERS)
    print(f"ring spectral gap: {ring.spectral_gap:.4f}   "
          f"clique spectral gap: {clique.spectral_gap:.4f}\n")

    for split in ("random", "by_label"):
        l_ring, _, _ = common.run_dsm(problem, ring, steps=200, lr=0.5, split=split)
        l_clique, _, _ = common.run_dsm(problem, clique, steps=200, lr=0.5, split=split)
        gap = float(np.mean(l_ring[-30:]) - np.mean(l_clique[-30:]))
        drop = float(l_clique[0] - np.mean(l_clique[-30:]))
        print(f"=== split = {split}")
        print(f"  ring   {sparkline(l_ring)}  final {np.mean(l_ring[-30:]):.4f}")
        print(f"  clique {sparkline(l_clique)}  final {np.mean(l_clique[-30:]):.4f}")
        print(f"  tail gap = {gap:+.4f} ({gap / drop:+.1%} of total loss drop)")
        verdict = ("indistinguishable — topology does NOT matter (paper Fig. 2)"
                   if abs(gap) < 0.05 * drop else
                   "clique clearly ahead — topology DOES matter (paper Fig. 4)")
        print(f"  -> {verdict}\n")


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    main()
