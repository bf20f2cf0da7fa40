"""Paper Fig. 5 end-to-end with REAL losses: ring vs clique crossing in
virtual wall-clock under a heavy-tail straggler distribution.

The original figure glues a loss-vs-iteration curve onto a separate timing
recursion. Here both axes come from ONE event-driven simulation
(`repro.sim`): every worker runs actual JAX train steps under its own
virtual clock, so we can show the two claims on the same run:

  (a) loss vs ITERATION: the clique (better mixing, λ2 = 0) wins or ties;
  (b) loss vs VIRTUAL TIME: the ring wins — a straggler only stalls its two
      neighbors, while the clique's global barrier collapses throughput to
      the slowest worker each round.

Writes `results/fig5_realloss.json` with both curve pairs.

    PYTHONPATH=src python examples/fig5_realloss.py [--quick]
"""
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

from benchmarks import common
from repro import telemetry
from repro.core import topology as T
from repro.sim import scenarios, time_to_target

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results")


def simulate(problem, topo, *, steps, lr=0.5, scen_seed=7):
    # heavier tail than the default Spark shape: rare 8x slowdowns
    scen = scenarios.heavy_tail("spark", seed=scen_seed,
                                p_slow=0.1, slow_factor=8.0)
    return common.run_sim(problem, topo, rounds=steps, lr=lr,
                          protocol="sync", scenario=scen)


def run(quick: bool = False) -> dict:
    M = 8 if quick else 16
    steps = 60 if quick else 200
    problem = common.problem_classifier()
    out = {}
    for name, topo in (("ring", T.undirected_ring(M)), ("clique", T.clique(M))):
        r = simulate(problem, topo, steps=steps)
        t, f = r.eval_curve()
        out[name] = {"vtime": t.tolist(), "loss": f.tolist(),
                     "iterations": list(range(1, len(f) + 1))}
    target = max(min(out[n]["loss"]) for n in out) + 0.05
    summary = {"M": M, "steps": steps, "target": target}
    for name in out:
        t = np.asarray(out[name]["vtime"]); f = np.asarray(out[name]["loss"])
        summary[f"{name}_final_loss"] = float(f[-1])
        summary[f"{name}_final_vtime"] = float(t[-1])
        summary[f"{name}_time_to_target"] = time_to_target(t, f, target)
    out["summary"] = summary
    telemetry.stamp(out, config=summary, writer="fig5_realloss")
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "fig5_realloss.json"), "w") as fp:
        json.dump(out, fp, indent=1)
    return out


def main(quick: bool = False):
    out = run(quick)
    s = out["summary"]
    print(f"M={s['M']} workers, {s['steps']} rounds, heavy-tail stragglers\n")
    print(f"{'':>8} {'final loss':>11} {'total vtime':>12} "
          f"{'t(loss<%.2f)':>14}" % s["target"])
    for name in ("ring", "clique"):
        print(f"{name:>8} {s[f'{name}_final_loss']:11.4f} "
              f"{s[f'{name}_final_vtime']:12.1f} "
              f"{s[f'{name}_time_to_target']:14.1f}")
    print("\nloss-vs-iteration: clique wins or ties (faster consensus);")
    print("loss-vs-virtual-time: ring wins (no global barrier) — the curves")
    print("cross, which is the paper's Fig. 5 with real training dynamics.")


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    main(quick="--quick" in sys.argv[1:])
