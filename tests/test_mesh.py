"""WorkerMesh factorization: the single source of truth for worker axes ×
model subgroup, consumed by shardings / gossip / bus / dryrun / train."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import topology as T
from repro.core.decentralized import make_train_step
from repro.core.gossip import GossipSpec
from repro.launch.mesh import WorkerMesh, n_workers, worker_axes
from repro.optim import sgd


def _mesh11():
    return jax.make_mesh((1, 1), ("data", "model"),
                            axis_types=(jax.sharding.AxisType.Auto,) * 2)


def _mesh111():
    return jax.make_mesh((1, 1, 1), ("pod", "data", "model"),
                            axis_types=(jax.sharding.AxisType.Auto,) * 3)


def test_from_mesh_factors_out_model_axis():
    wm = WorkerMesh.from_mesh(_mesh11())
    assert wm.worker_axes == ("data",)
    assert wm.model_axis == "model"
    assert wm.n_workers == 1 and wm.model_factor == 1
    assert wm.wa == "data"
    assert wm.worker_spec(None, "model") == P("data", None, "model")


def test_from_mesh_multipod_worker_axes():
    wm = WorkerMesh.from_mesh(_mesh111())
    assert wm.worker_axes == ("pod", "data")
    assert wm.wa == ("pod", "data")
    assert wm.worker_spec() == P(("pod", "data"))
    assert "workers[" in wm.describe()


def test_from_mesh_without_model_axis():
    mesh = jax.make_mesh((1,), ("data",),
                            axis_types=(jax.sharding.AxisType.Auto,))
    wm = WorkerMesh.from_mesh(mesh)
    assert wm.model_axis is None and wm.model_factor == 1
    assert wm.worker_axes == ("data",)


def test_ensure_is_idempotent_and_wraps_meshes():
    mesh = _mesh11()
    wm = WorkerMesh.ensure(mesh)
    assert isinstance(wm, WorkerMesh)
    assert WorkerMesh.ensure(wm) is wm
    assert WorkerMesh.ensure(None) is None
    assert WorkerMesh.raw(wm) is mesh
    assert WorkerMesh.raw(mesh) is mesh
    assert WorkerMesh.raw(None) is None
    # legacy helpers delegate to the same factorization
    assert worker_axes(mesh) == wm.worker_axes
    assert n_workers(mesh) == wm.n_workers
    assert worker_axes(wm) == wm.worker_axes


def test_gossip_spec_for_mesh_binds_axes():
    wm = WorkerMesh.from_mesh(_mesh111())
    spec = GossipSpec.for_mesh(T.undirected_ring(4), wm, backend="fused")
    assert spec.worker_axes == ("pod", "data")
    assert spec.model_axis is None          # k == 1 ⇒ no model sharding
    assert spec.backend == "fused"


def test_fsdp_train_mode_is_retired():
    with pytest.raises(ValueError, match="WorkerMesh"):
        make_train_step(lambda p, b: jnp.sum(p["x"]), sgd(0.1), mode="fsdp")


def test_shardings_accept_worker_mesh_and_raw_mesh():
    from repro.configs import get_config
    from repro.launch import shardings as shard_lib

    cfg = get_config("granite-3-2b", reduced=True)
    mesh = _mesh11()
    wm = WorkerMesh.ensure(mesh)
    a = shard_lib.param_pspecs(cfg, mesh, "gossip")
    b = shard_lib.param_pspecs(cfg, wm, "gossip")
    assert jax.tree.all(jax.tree.map(lambda x, y: x == y, a, b,
                                     is_leaf=lambda x: isinstance(x, P)))
    # every gossip spec leads with the worker axes entry
    for p in jax.tree.leaves(a, is_leaf=lambda x: isinstance(x, P)):
        assert p[0] == "data", p


def test_nemotron_config_is_technique_on():
    from repro.configs import get_config

    cfg = get_config("nemotron-4-340b")
    assert cfg.dp_mode == "gossip"          # the point of worker-group meshes
    assert cfg.serve_sharding == "fsdp"     # serving still spreads one replica
