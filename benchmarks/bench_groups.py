"""Worker-group mesh sweep: gossip degree × model-shard factor k.

The worker-group composition (launch/mesh.WorkerMesh + the per-model-shard
bus path in core/bus.py) claims two HLO-level invariants:

* **collective count** per gossip step stays `degree` — one bulk
  collective-permute per non-identity Birkhoff permutation — at EVERY shard
  factor k (sharding the replica must not fragment the exchange);
* **per-device collective bytes** drop ~1/k: each device packs only its
  1/k of the replica by flat-buffer rows (bus layout v2 — tensor-sharded
  leaves as local shards, indivisible leaves row-split), so the paper's
  O(degree) per-worker exchange is also O(1/k) per device — the property
  that lets the technique run where a replica no longer fits one device
  (nemotron-4-340b).

This bench compiles the fused bus mix on forced host-device meshes
(M workers × k model shards), measures both quantities from the partitioned
HLO via launch/hlo_cost, and asserts them — including the layout-v2 **byte
efficiency gate**: per-device cp bytes must stay within 0.95× of the ideal
``degree × bytes(params)/k`` (the pre-v2 layout sat at 0.89× at k=4 from
32-row tile padding + replicated indivisible leaves). Results land in
results/bench/groups.json plus the padding sweep in
results/bench/groups_padding.json (CI uploads both artifacts).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks import common

_CHILD = r"""
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import topology as T, bus
from repro.core.gossip import GossipSpec, mix_pytree_reference
from repro.launch.hlo_cost import analyze_hlo

M, KS, DEGREES = %(M)d, %(ks)s, %(degrees)s

def topo_of(d):
    if d == 1:
        return T.directed_ring_lattice(M, 1)
    if d == 2:
        return T.undirected_ring(M)
    if d == M - 1:
        return T.clique(M)
    return T.ring_lattice(M, d)

key = jax.random.PRNGKey(0)
params = {"w": jax.random.normal(key, (M, 256, 8, 128)),   # shards /k on dim2
          "emb": jax.random.normal(key, (M, 1024, 256)),
          "v": jax.random.normal(key, (M, 33, 5))}         # indivisible: row-split
payload_bytes = sum(int(x.nbytes) // M for x in params.values())
rows = []
for d in DEGREES:
    topo = topo_of(d)
    ref = mix_pytree_reference(params, topo.A)
    for k in KS:
        mesh = jax.make_mesh((M, k), ("data", "model"),
                                axis_types=(jax.sharding.AxisType.Auto,) * 2,
                                devices=jax.devices()[: M * k])
        spec = GossipSpec(topology=topo, backend="fused",
                          worker_axes=("data",),
                          model_axis="model" if k > 1 else None)
        m_ax = "model" if k > 1 else None
        pspecs = {"w": P("data", None, m_ax, None),
                  "emb": P("data", None, m_ax),
                  "v": P("data", None, None)}
        with jax.set_mesh(mesh):
            p = jax.tree.map(lambda x, s: jax.device_put(
                x, jax.NamedSharding(mesh, s)), params, pspecs)
            f = jax.jit(lambda q: bus.mix_bus(q, spec, mesh,
                                              param_specs=pspecs))
            out = f(p)
            hlo = f.lower(p).compile().as_text()
        for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
            assert np.allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6), ("numerics", d, k)
        hc = analyze_hlo(hlo)
        rows.append({
            "degree": d, "shard_factor_k": k, "workers": M,
            "payload_bytes_per_worker": payload_bytes,
            "cp_count": hc.coll_counts["collective-permute"],
            "cp_bytes_per_device": hc.coll_bytes["collective-permute"],
        })
print("JSON:" + json.dumps(rows))
"""


def run(quick: bool = False) -> list[dict]:
    M = 4
    ks = [1, 2] if quick else [1, 2, 4]
    degrees = [1, 2] if quick else [1, 2, 3]
    env = dict(os.environ)
    # the sweep counts HLO on forced host devices by design; pinned to the
    # CPU, the child never asks for a chip this process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={M * max(ks)}")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    code = _CHILD % {"M": M, "ks": ks, "degrees": degrees}
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=1800)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    line = next(l for l in res.stdout.splitlines() if l.startswith("JSON:"))
    raw = json.loads(line[len("JSON:"):])

    rows, padding = [], []
    base = {r["degree"]: r["cp_bytes_per_device"]
            for r in raw if r["shard_factor_k"] == 1}
    for r in raw:
        d, k = r["degree"], r["shard_factor_k"]
        ratio = base[d] / r["cp_bytes_per_device"]
        # layout-v2 byte contract: per-device cp bytes vs the ideal
        # degree × bytes(params)/k — anything below 0.95 means tile padding
        # or replicated leaves crept back into the bulk collectives.
        ideal = d * r["payload_bytes_per_worker"] / k
        eff = ideal / r["cp_bytes_per_device"]
        row = dict(r, bench="groups",
                   combo=f"deg{d}_k{k}",
                   bytes_ratio_vs_k1=ratio,
                   ideal_cp_bytes_per_device=ideal,
                   byte_efficiency=eff)
        rows.append(row)
        padding.append({
            "bench": "groups_padding", "combo": row["combo"],
            "cp_bytes_per_device": r["cp_bytes_per_device"],
            "ideal_cp_bytes_per_device": ideal,
            "byte_efficiency": eff,
            "padding_overhead_pct": 100.0 * (1.0 / eff - 1.0),
        })
    # Artifacts are written BEFORE the gate so a failing lane still uploads
    # the sweep that shows the regression (CI uploads with `if: always()`).
    common.save_json("groups", rows)
    common.save_json("groups_padding", padding)
    for row in rows:
        d, k = row["degree"], row["shard_factor_k"]
        # HLO-level contracts of the worker-group composition:
        assert row["cp_count"] == d, row        # one bulk collective per perm
        assert row["bytes_ratio_vs_k1"] > 0.75 * k, row  # bytes ~ 1/k
        assert row["byte_efficiency"] >= 0.95, row  # gate: ≤5% pad overhead
    return rows
