"""Multi-device integration tests (subprocesses with forced host devices)."""
import pytest

from conftest import run_in_subprocess


@pytest.mark.slow
def test_ppermute_gossip_matches_dense_oracle():
    out = run_in_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import topology as T, gossip as G
mesh = jax.make_mesh((4,2), ("data","model"), axis_types=(jax.sharding.AxisType.Auto,)*2)
for topo in [T.undirected_ring(4), T.clique(4), T.directed_ring_lattice(4,2), T.hypercube(2)]:
    spec = G.GossipSpec(topology=topo, backend="ppermute", worker_axes=("data",))
    params = {"w": jnp.arange(4*6, dtype=jnp.float32).reshape(4,6), "b": jnp.ones((4,3))}
    ref = G.mix_pytree_reference(params, topo.A)
    with jax.set_mesh(mesh):
        sh = jax.NamedSharding(mesh, P("data"))
        p = jax.tree.map(lambda x: jax.device_put(x, sh), params)
        out = jax.jit(lambda q: G.mix_pytree(q, spec, mesh))(p)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-6), topo.name
print("gossip-ok")
""")
    assert "gossip-ok" in out


@pytest.mark.slow
def test_multipod_gossip_over_two_axes():
    out = run_in_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import topology as T, gossip as G
mesh = jax.make_mesh((2,2,2), ("pod","data","model"), axis_types=(jax.sharding.AxisType.Auto,)*3)
topo = T.undirected_ring(4)
spec = G.GossipSpec(topology=topo, backend="ppermute", worker_axes=("pod","data"))
x = {"w": jnp.arange(4*4, dtype=jnp.float32).reshape(4,4)}
ref = G.mix_pytree_reference(x, topo.A)
with jax.set_mesh(mesh):
    sh = jax.NamedSharding(mesh, P(("pod","data")))
    p = jax.tree.map(lambda v: jax.device_put(v, sh), x)
    out = jax.jit(lambda q: G.mix_pytree(q, spec, mesh))(p)
assert np.allclose(np.asarray(out["w"]), np.asarray(ref["w"]), atol=1e-6)
print("multipod-ok")
""")
    assert "multipod-ok" in out


@pytest.mark.slow
def test_gossip_vs_allreduce_training_equivalence_distributed():
    """Clique+ppermute ≡ pmean baseline on the same data, end to end."""
    out = run_in_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import topology as T
from repro.core.gossip import GossipSpec
from repro.core.decentralized import make_train_step, init_state, replicate_for_workers
from repro.optim import momentum_sgd
mesh = jax.make_mesh((4,2), ("data","model"), axis_types=(jax.sharding.AxisType.Auto,)*2)
def loss(p, b): return jnp.mean((p["x"] - b)**2)
targets = jnp.tile(jnp.asarray([[1.,2.]]), (4,1))
opt = momentum_sgd(0.1, 0.9)
with jax.set_mesh(mesh):
    sA = init_state(replicate_for_workers({"x": jnp.zeros(2)}, 4), opt)
    stepA = jax.jit(make_train_step(loss, opt,
        gossip=GossipSpec(topology=T.clique(4), backend="ppermute", worker_axes=("data",)),
        mode="gossip", mesh=mesh))
    sB = init_state({"x": jnp.zeros(2)}, opt)
    stepB = jax.jit(make_train_step(loss, opt, mode="allreduce"))
    for _ in range(20):
        sA, _ = stepA(sA, targets)
        sB, _ = stepB(sB, targets[0])
assert np.allclose(np.asarray(sA.params["x"][0]), np.asarray(sB.params["x"]), atol=1e-5)
print("equiv-ok")
""")
    assert "equiv-ok" in out


@pytest.mark.slow
def test_nemotron_gossip_dryrun_technique_on():
    """nemotron-4-340b (reduced shapes, full distribution config) lowers in
    GOSSIP mode on the multi-pod worker mesh — the technique-on flip that
    worker-group meshes buy; gossip must show up as bulk collective-permutes."""
    out = run_in_subprocess("""
import repro.launch.mesh as mesh_lib
mesh_lib.MULTI_POD = (2, 2, 2)
import repro.launch.dryrun as dr
dr.INPUT_SHAPES.update({"train_4k": dict(seq_len=64, global_batch=8, kind="train")})
res = dr.run_one("nemotron-4-340b", "train_4k", multi_pod=True,
                 gossip_backend="fused", reduced=True)
assert res.ok, res.error
assert res.mode == "gossip", res.mode
assert res.coll_counts["collective-permute"] > 0, res.coll_counts
print("nemotron-gossip-ok", res.coll_counts)
""")
    assert "nemotron-gossip-ok" in out


@pytest.mark.slow
def test_dryrun_small_mesh_end_to_end():
    """The dry-run machinery itself on a 4x2 host-device mesh with reduced
    configs — one arch per family, all three shape kinds."""
    out = run_in_subprocess("""
import repro.launch.mesh as mesh_lib
mesh_lib.SINGLE_POD = (4, 2); mesh_lib.MULTI_POD = (2, 2, 2)
import repro.launch.dryrun as dr
from repro.configs import get_config
dr.INPUT_SHAPES.update({
    "train_4k": dict(seq_len=128, global_batch=8, kind="train"),
    "prefill_32k": dict(seq_len=256, global_batch=4, kind="prefill"),
    "decode_32k": dict(seq_len=256, global_batch=8, kind="decode"),
})
dr.get_config = lambda name: get_config(name, reduced=True)
for arch in ["granite-3-2b", "mamba2-2.7b", "mixtral-8x7b"]:
    for shape in ["train_4k", "prefill_32k", "decode_32k"]:
        for mp in (False, True):
            res = dr.run_one(arch, shape, multi_pod=mp)
            assert res.ok, (arch, shape, mp, res.error)
            assert res.roofline["bottleneck"] in ("compute", "memory", "collective")
print("dryrun-ok")
""", timeout=900)
    assert "dryrun-ok" in out
