"""The four-worker mesh train step with the dropless expert layer compiles
for a TPU v5e 2x2 (described, nothing runs): one worker per chip, the
grouped matmuls run per chip inside a shard_map, and the compiler puts in
no all-gather, of the expert weights, the pair buffer or anything else."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.core import topology as T
from repro.core.decentralized import init_state, make_train_step
from repro.core.gossip import GossipSpec
from repro.launch.hlo_cost import analyze_hlo
from repro.launch.mesh import WorkerMesh
from repro.models import model as M
from repro.optim import momentum_sgd

W = 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_mesh_step_with_expert_share_compiles_without_all_gather(topo):
    cfg = get_config("deepseek-v2-lite-16b", n_layers=3, d_model=256,
                     n_heads=2, d_ff=512, vocab_size=1024, n_experts=8,
                     router_experts=64, d_ff_expert=256, remat=True,
                     scan_layers=True)
    mesh = jax.make_mesh((W,), ("data",), devices=topo.devices[:W],
                         axis_types=(jax.sharding.AxisType.Auto,))
    wm = WorkerMesh.from_mesh(mesh)
    opt = momentum_sgd(1e-2, 0.9)
    spec = GossipSpec.for_mesh(T.make("ring", W), wm, backend="fused")
    step = make_train_step(lambda p, b: M.loss_fn(p, cfg, b), opt,
                           gossip=spec, mesh=wm, compute_stats=False)
    sharded = NamedSharding(mesh, P("data"))
    with jax.set_mesh(mesh):
        params = jax.eval_shape(lambda: jax.tree.map(
            lambda x: jnp.broadcast_to(x, (W,) + x.shape),
            M.init(jax.random.PRNGKey(0), cfg)))
        state = jax.eval_shape(lambda p: init_state(p, opt), params)
        state = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharded if s.ndim else
            NamedSharding(mesh, P())), state)
        batch = {"tokens": jax.ShapeDtypeStruct((W, 2, 257), jnp.int32,
                                                sharding=sharded)}
        text = jax.jit(step, donate_argnums=(0,)).lower(
            state, batch).compile().as_text()
    counts = analyze_hlo(text).coll_counts
    assert counts["all-gather"] == 0, counts
    assert counts["all-to-all"] == 0, counts
    assert "ragged-dot" in text and "tpu_custom_call" in text
