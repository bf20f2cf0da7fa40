"""Riding a regional outage: fault-tolerant hier vs a fault-free flat ring.

The robustness headline for the fleet-scale story. Three runs on M=32
workers in 2 pods under the two-link-class datacenter world (DCI >> ICI):

  * ``ring-nofault`` (sync): the paper's wall-clock winner on a *healthy*
    fleet — the bar to beat.
  * ``ring-outage`` (sync + barrier_timeout): the same flat ring when pod
    1's DCI links go dark mid-run. Its pod-boundary edges are dead, every
    barrier that needs a cross-pod snapshot stalls to the timeout, and the
    run limps through on survivor-renormalized degraded commits.
  * ``hier-outage`` (hier + barrier_timeout): hierarchical gossip under the
    SAME outage. Barriers are intra-pod only, cross-pod snapshots ride
    stale buffers, so the outage costs staleness — not stalls.

The crossing claim: hier under a regional outage still reaches the common
loss target in less virtual time than the flat ring needs on a fleet with
NO fault at all — topology choice buys robustness for free. Writes
``results/outage_crossing.json`` (curves, vtime-to-target, per-class
downtime + retried-byte accounting from ``Trace.link_accounting``).

``--trace`` additionally exports a full telemetry bundle per job under
``results/runs/outage/<job>/`` — ``trace.json``, a Perfetto-loadable
``perfetto.json`` timeline (worker lanes, link-fault windows, health-gauge
counters), and ``telemetry.json`` — with gossip-health gauges (spectral
gap / effective neighbors of the active mixing matrix) sampled across the
outage. Summarize with ``python -m repro.telemetry.report
results/runs/outage/<job>``.

    PYTHONPATH=src python examples/outage_wallclock.py [--quick] [--trace]
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


def run(quick: bool = False, trace: bool = False) -> dict:
    pods, pod_size = (2, 8) if quick else (2, 16)
    M = pods * pod_size
    dci = 12.0 if quick else 25.0
    lr = 0.8
    sync_rounds = 30 if quick else 60
    hier_rounds = 200 if quick else 650
    # the outage opens after the early transient and stays down for a
    # stretch worth several DCI round-trips
    outage_start = 8.0 * dci
    outage_duration = (10.0 if quick else 16.0) * dci
    timeout = 3.0 * dci

    problem = common.problem_classifier()
    mesh = MeshSpec.pods(M, pods)
    healthy = scenarios.datacenter("spark", dci_latency=dci,
                                   ici_latency=ICI_LATENCY, seed=7)
    outage = scenarios.regional_outage(pod=1, start=outage_start,
                                       duration=outage_duration,
                                       dist="spark", dci_latency=dci,
                                       ici_latency=ICI_LATENCY, seed=7)

    jobs = (
        ("ring-nofault", T.undirected_ring(M), "sync", sync_rounds, 1,
         healthy, {}),
        ("ring-outage", T.undirected_ring(M), "sync", sync_rounds, 1,
         outage, {"barrier_timeout": timeout}),
        ("hier-outage", T.hier(pods, pod_size), "hier", hier_rounds, 4,
         outage, {"barrier_timeout": timeout}),
    )
    out = {}
    for name, topo, proto, rounds, eval_every, scen, kw in jobs:
        if trace:
            kw = dict(kw, health=True,
                      run_dir=os.path.join(RESULTS, "runs", "outage", name))
        r = common.run_sim(problem, topo, rounds=rounds, lr=lr,
                           protocol=proto, scenario=scen, mesh=mesh,
                           eval_every=eval_every, **kw)
        t, f = r.eval_curve()
        acct = r.trace.link_accounting()
        out[name] = {
            "protocol": proto, "rounds": rounds, "scenario": scen.name,
            "vtime": t.tolist(), "loss": f.tolist(),
            "final_vtime": float(r.virtual_time),
            "link_accounting": acct,
        }

    # common target: the worst final loss among the three runs, so every
    # curve reaches it inside its own horizon
    target = max(float(np.asarray(out[n]["loss"])[-1]) for n in out)
    summary = {
        "M": M, "pods": pods, "dci_latency": dci, "ici_latency": ICI_LATENCY,
        "outage": {"pod": 1, "start": outage_start,
                   "duration": outage_duration},
        "barrier_timeout": timeout, "lr": lr, "loss_target": target,
    }
    for name in out:
        t = np.asarray(out[name]["vtime"]); f = np.asarray(out[name]["loss"])
        summary[f"{name}_final_loss"] = float(f[-1])
        summary[f"{name}_time_to_target"] = time_to_target(t, f, target)
    summary["hier_outage_beats_healthy_ring"] = bool(
        summary["hier-outage_time_to_target"]
        < summary["ring-nofault_time_to_target"])
    dci_acct = out["hier-outage"]["link_accounting"]["dci"]
    summary["hier_dci_downtime"] = dci_acct["downtime"]
    summary["hier_dci_retried_bytes"] = dci_acct["retried_bytes"]
    out["summary"] = summary
    telemetry.stamp(out, config=summary, writer="outage_wallclock")
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "outage_crossing.json"), "w") as fp:
        json.dump(out, fp, indent=1)
    return out


def main(quick: bool = False, trace: bool = False):
    out = run(quick, trace=trace)
    s = out["summary"]
    o = s["outage"]
    print(f"M={s['M']} workers in {s['pods']} pods; pod {o['pod']}'s DCI "
          f"links dark over t=[{o['start']:.0f}, "
          f"{o['start'] + o['duration']:.0f}] "
          f"(DCI latency {s['dci_latency']}, ICI {s['ici_latency']})\n")
    print(f"{'':>14} {'final loss':>11} {'t(loss<%.3f)':>15}" % s["loss_target"])
    for name in ("ring-nofault", "ring-outage", "hier-outage"):
        print(f"{name:>14} {s[f'{name}_final_loss']:11.4f} "
              f"{s[f'{name}_time_to_target']:15.1f}")
    print(f"\nDCI downtime charged to the hier run: "
          f"{s['hier_dci_downtime']:.0f} vtime, "
          f"{s['hier_dci_retried_bytes']} bytes held + retried")
    verdict = ("BEATS" if s["hier_outage_beats_healthy_ring"] else
               "does NOT beat")
    print(f"hier THROUGH the outage {verdict} the flat ring on a fleet "
          f"with no fault at all:")
    print("barriers stay intra-pod, the outage costs staleness — not "
          "stalls — while the flat")
    print("ring pays the timeout on every barrier its dead pod-boundary "
          "edges starve.")
    if trace:
        print("\ntelemetry bundles (perfetto.json loads at ui.perfetto.dev):")
        for name in ("ring-nofault", "ring-outage", "hier-outage"):
            print(f"  results/runs/outage/{name}/")
    if not s["hier_outage_beats_healthy_ring"]:
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    main(quick="--quick" in sys.argv[1:], trace="--trace" in sys.argv[1:])
