"""Two link classes, three topologies: hier vs ring vs clique wall-clock
crossing under DCI ≫ ICI (the mesh-aware companion of fig5_realloss.py).

The paper's Fig. 5 world charges every link equally. On a real multi-pod
machine the gossip edges split into two classes — cheap intra-pod ICI hops
and expensive cross-pod DCI hops — and the mesh-aware simulator charges each
class its own latency/bandwidth against the exact per-device payload the
gossip bus ships (`BusLayout.padded_bytes`). Three runs on one scenario:

  * ``clique`` (sync): best mixing, but the global barrier now waits on DCI
    *every* round — throughput collapses to the cross-pod latency.
  * ``ring`` (sync): the paper's wall-clock winner loses its edge here. Its
    pod-boundary edges are DCI, and the synchronous lag wraps around the
    ring within ~M/pods rounds, so steady-state rounds are DCI-bound too.
    Only the first few rounds (interior workers, lag still propagating) are
    cheap — the ring leads *early*.
  * ``hier`` (kronecker ring-over-pods ⊗ clique-in-pod, `hier` protocol):
    barrier on intra-pod neighbors only; cross-pod snapshots ride DCI
    messages that stay in flight while the pod keeps mixing (SGP-style
    overlap). Rounds stay ICI-bound at near-clique mixing quality.

The loss-vs-virtual-time curves of hier and the flat ring CROSS: the ring is
below while its DCI lag is still propagating, then the hier run blows past
and stays below for the rest of the horizon — topology *and* link classes
matter. Writes `results/hier_crossing.json` (curves + crossing point +
per-class byte/time accounting).

    PYTHONPATH=src python examples/hier_wallclock.py [--quick]
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
from repro.sim import MeshSpec, scenarios, time_to_target

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results")

ICI_LATENCY = 0.02


def crossing_time(t_a, f_a, t_b, f_b, n_grid: int = 400):
    """First common-grid time where curve a dips below curve b for good.

    Returns (t_cross, b_led_before): the virtual time after which a stays
    below b, and whether b was strictly below a anywhere before it (a true
    crossing rather than dominance from the start)."""
    lo = max(t_a[0], t_b[0])
    hi = min(t_a[-1], t_b[-1])
    grid = np.linspace(lo, hi, n_grid)
    a = np.interp(grid, t_a, f_a)
    b = np.interp(grid, t_b, f_b)
    below = a < b
    # last index where a is NOT below b; everything after is a's regime
    not_below = np.nonzero(~below)[0]
    if len(not_below) == len(grid):
        return float("inf"), bool(np.any(b < a))
    start = 0 if not len(not_below) else int(not_below[-1]) + 1
    t_cross = float(grid[start])
    return t_cross, bool(np.any(b[:start] < a[:start]))


def run(quick: bool = False) -> dict:
    # 2 pods with a LONG interior stretch: the flat ring's lag needs ~M/2
    # rounds to wrap, so the ring genuinely leads early before hier crosses
    pods, pod_size = (2, 8) if quick else (2, 16)
    M = pods * pod_size
    dci = 12.0 if quick else 25.0
    lr = 0.8
    sync_rounds = 30 if quick else 60
    hier_rounds = 200 if quick else 650
    problem = common.problem_classifier()
    mesh = MeshSpec.pods(M, pods)
    scen = scenarios.datacenter("spark", dci_latency=dci,
                                ici_latency=ICI_LATENCY, seed=7)

    jobs = (
        ("ring", T.undirected_ring(M), "sync", sync_rounds, 1),
        ("clique", T.clique(M), "sync", sync_rounds, 1),
        ("hier", T.hier(pods, pod_size), "hier", hier_rounds, 4),
    )
    out = {}
    for name, topo, proto, rounds, eval_every in jobs:
        r = common.run_sim(problem, topo, rounds=rounds, lr=lr,
                           protocol=proto, scenario=scen, mesh=mesh,
                           eval_every=eval_every)
        t, f = r.eval_curve()
        out[name] = {
            "protocol": proto, "rounds": rounds,
            "vtime": t.tolist(), "loss": f.tolist(),
            "final_vtime": float(r.virtual_time),
            "link_accounting": r.trace.link_accounting(),
            "payload_bytes": r.trace.meta.get("mesh", {}).get("payload_bytes"),
        }

    t_r = np.asarray(out["ring"]["vtime"]); f_r = np.asarray(out["ring"]["loss"])
    t_h = np.asarray(out["hier"]["vtime"]); f_h = np.asarray(out["hier"]["loss"])
    t_cross, ring_led = crossing_time(t_h, f_h, t_r, f_r)
    horizon = min(t_r[-1], t_h[-1])
    target = max(np.interp(horizon, t_r, f_r), np.interp(horizon, t_h, f_h))
    summary = {
        "M": M, "pods": pods, "dci_latency": dci, "ici_latency": ICI_LATENCY,
        "lr": lr, "hier_crosses_ring_at_vtime": t_cross,
        "ring_leads_before_crossing": ring_led,
        "loss_target": float(target),
    }
    for name in ("ring", "clique", "hier"):
        t = np.asarray(out[name]["vtime"]); f = np.asarray(out[name]["loss"])
        summary[f"{name}_final_loss"] = float(f[-1])
        summary[f"{name}_time_to_target"] = time_to_target(t, f, target)
    out["summary"] = summary
    telemetry.stamp(out, config=summary, writer="hier_wallclock")
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "hier_crossing.json"), "w") as fp:
        json.dump(out, fp, indent=1)
    return out


def main(quick: bool = False):
    out = run(quick)
    s = out["summary"]
    print(f"M={s['M']} workers in {s['pods']} pods, "
          f"DCI latency {s['dci_latency']} vs ICI {s['ici_latency']} "
          f"(DCI >> ICI)\n")
    print(f"{'':>8} {'final loss':>11} {'t(loss<%.3f)':>15}" % s["loss_target"])
    for name in ("ring", "clique", "hier"):
        print(f"{name:>8} {s[f'{name}_final_loss']:11.4f} "
              f"{s[f'{name}_time_to_target']:15.1f}")
    print(f"\nhier crosses below the flat ring at virtual time "
          f"{s['hier_crosses_ring_at_vtime']:.1f}"
          + (" (ring led before that — a true crossing)"
             if s["ring_leads_before_crossing"] else ""))
    print("ring loses its Fig.-5 edge once its pod-boundary edges cost DCI;")
    print("hier keeps DCI out of the barrier (in-flight cross-pod rounds)")
    print("and wins wall-clock at near-clique mixing quality.")


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    main(quick="--quick" in sys.argv[1:])
