"""Flat-buffer gossip bus: layout round-trips, fused-backend numerics vs the
dense oracle + unfused update, and the bulk-collective count guarantee."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_in_subprocess
from repro.core import bus
from repro.core import topology as T
from repro.core.decentralized import (
    init_state,
    make_train_step,
    replicate_for_workers,
)
from repro.core.gossip import GossipSpec, mix_pytree, mix_pytree_reference
from repro.optim import momentum_sgd, sgd

KEY = jax.random.PRNGKey(0)

# Kernel tiles kept small so interpret-mode tests stay fast on CPU.
BLK = dict(block_r=32, block_c=128)   # mix_bus: kernel tile caps
PLAN = dict(block_r=32)               # plan_layout: layout fixes cols to LANE


def _tree(M, seed=0, dtypes=(jnp.float32,)):
    """Pytree with awkward leaf shapes straddling padding boundaries."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt2 = dtypes[-1]
    return {
        "scalar": jax.random.normal(ks[0], (M, 1)),
        "vec": jax.random.normal(ks[1], (M, 127)),       # just under a lane row
        "mat": jax.random.normal(ks[2], (M, 33, 5)),
        "deep": {"a": jax.random.normal(ks[3], (M, 128)),  # exactly one row
                 "b": jax.random.normal(ks[4], (M, 129)).astype(dt2)},
        "big": jax.random.normal(ks[5], (M, 70, 41)),
    }


# ---------------------------------------------------------------------------
# Layout round-trip
# ---------------------------------------------------------------------------


def _chip_tree(M, seed=0, dtypes=(jnp.float32,)):
    """The chip cells' leaf shapes in small: a stacked lane-ragged minor dim
    after whole lanes (mamba2 in_proj, 2560 × 10576, 10576 = 80 + 128·82), a
    row count that is not whole sublane tiles (the 49155-row vocab table),
    heads under one lane (granite's 32 × 64), per-head scalars and a 1-D
    scale."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    dt = dtypes[-1]
    return {
        "in_proj": jax.random.normal(ks[0], (M, 2, 128, 80 + 128)).astype(dt),
        "embed": jax.random.normal(ks[1], (M, 35, 256)).astype(dt),
        "wq": jax.random.normal(ks[2], (M, 2, 24, 4, 64)).astype(dt),
        "A_log": jax.random.normal(ks[3], (M, 5, 80)).astype(dt),
        "scale": jax.random.normal(ks[4], (M, 80)),
    }


@pytest.mark.parametrize("make", [_tree, _chip_tree])
@pytest.mark.parametrize("lead_ndim", [0, 1])
@pytest.mark.parametrize("dtypes", [(jnp.float32,), (jnp.float32, jnp.bfloat16)])
def test_pack_unpack_roundtrip(lead_ndim, dtypes, make):
    tree = make(4, dtypes=dtypes)
    if lead_ndim == 0:  # strip the worker dim: per-worker view
        tree = jax.tree.map(lambda x: x[0], tree)
    layout = bus.plan_layout(tree, lead_ndim=lead_ndim, **PLAN)
    bufs = bus.pack(tree, layout, lead_ndim=lead_ndim)
    assert len(bufs) == len(set(jnp.dtype(d) for d in dtypes))
    # every slot starts on a whole sublane tile and holds its leaf
    # row-major, a lane-ragged minor dim after whole lanes swapped with it
    leaves = jax.tree.leaves(tree)
    for g, buf in zip(layout.groups, bufs):
        sub = bus.sublane_rows(g.dtype)
        for slot in g.slots:
            assert slot.offset % sub == 0 and slot.rows % sub == 0
            leaf = np.asarray(leaves[slot.leaf_id], np.float32)
            if leaf.ndim - lead_ndim >= 2 and leaf.shape[-1] % bus.LANE and \
                    not leaf.shape[-2] % bus.LANE:
                leaf = np.swapaxes(leaf, -1, -2)
            flat = leaf.reshape(leaf.shape[:lead_ndim] + (-1,))
            rows = np.asarray(buf[..., slot.offset:slot.offset + slot.rows, :],
                              np.float32).reshape(flat.shape[:-1] + (-1,))
            np.testing.assert_array_equal(rows[..., :slot.size], flat)
            assert not rows[..., slot.size:].any()
    back = bus.unpack(bufs, layout, lead_ndim=lead_ndim)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(tree)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_layout_is_cached_and_padded_to_tiles():
    tree = _tree(4)
    l1 = bus.plan_layout(tree, **PLAN)
    l2 = bus.plan_layout(jax.tree.map(lambda x: x * 2, tree), **PLAN)
    assert l1 is l2  # same structure/shapes/dtypes → cache hit
    M = 4  # lead_ndim=1 layout counts per-worker (trailing) elements
    assert l1.payload_elements() == sum(x.size // M for x in jax.tree.leaves(tree))
    for g in l1.groups:
        # layout v2: whole dtype-native sublane tiles (8 rows for fp32) per
        # slot, one-lane-tile-wide rows — padding under one sublane tile per
        # slot, not a full 32-row block
        sub = bus.sublane_rows(g.dtype)
        assert g.rows % sub == 0 and g.cols == bus.LANE
        assert g.rows * g.cols >= g.n
        assert g.rows * g.cols - g.n < sub * bus.LANE * len(g.slots)
        for slot in g.slots:
            assert slot.rows * g.cols - slot.size < sub * bus.LANE


def test_pack_padding_is_zero():
    tree = {"x": jnp.ones((2, 5))}
    layout = bus.plan_layout(tree, **PLAN)
    (buf,) = bus.pack(tree, layout)
    flat = np.asarray(buf).reshape(2, -1)
    assert np.all(flat[:, :5] == 1.0) and np.all(flat[:, 5:] == 0.0)


# ---------------------------------------------------------------------------
# Fused backend vs dense oracle + unfused update
# ---------------------------------------------------------------------------

TOPOLOGIES = [
    lambda M: T.directed_ring_lattice(M, 1),   # degree 1
    lambda M: T.undirected_ring(M),            # degree 2 ring
    lambda M: T.ring_lattice(M, 4),            # degree-4 circulant (2-nbr/side)
    lambda M: T.clique(M),                     # degree M-1
]


@pytest.mark.parametrize("M", [4, 8])
@pytest.mark.parametrize("topo_i", range(len(TOPOLOGIES)))
def test_fused_mix_matches_oracle(M, topo_i):
    if topo_i == 2 and M == 4:
        pytest.skip("ring_lattice(4, 4) needs d < M")
    topo = TOPOLOGIES[topo_i](M)
    params = _tree(M, seed=topo_i)
    spec = GossipSpec(topology=topo, backend="fused")
    out = bus.mix_bus(params, spec, None, **BLK)
    ref = mix_pytree_reference(params, topo.A)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-6)


def test_fused_mix_and_update_matches_unfused_chain():
    """Fused mix−η·u matches the same-order unfused chain to fp32 round-off
    (XLA may contract mul+add to FMA inside the fused pass, so the last ulp
    can differ from the eager chain — anything beyond that is a real bug)."""
    M = 4
    topo = T.undirected_ring(M)
    params = _tree(M, dtypes=(jnp.float32,))
    updates = jax.tree.map(
        lambda x: jax.random.normal(KEY, x.shape, x.dtype), params)
    spec = GossipSpec(topology=topo, backend="fused")
    eta = 0.37
    out = bus.mix_bus(params, spec, None, updates=updates, eta=eta, **BLK)

    # identical summation order in plain fp32 jnp: a0·w + Σ w_p·perm − η·u
    a0, others = bus._split_perms(spec)
    def chain(x, u):
        acc = x * np.float32(a0)
        for w, perm in others:
            acc = acc + x[np.asarray(perm)] * np.float32(w)
        return acc - np.float32(eta) * u
    ref = jax.tree.map(chain, params, updates)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_fused_train_step_matches_einsum_step():
    """End-to-end: fused mix+update ≡ einsum mix then unfused update."""
    M = 4
    topo = T.undirected_ring(M)

    def quad_loss(p, b):
        return jnp.sum((p["x"] - b) ** 2)

    targets = jnp.arange(M * 2, dtype=jnp.float32).reshape(M, 2)
    opt = momentum_sgd(0.05, 0.9)
    states, specs = [], [GossipSpec(topology=topo, backend=be)
                         for be in ("fused", "einsum")]
    for spec in specs:
        step = jax.jit(make_train_step(quad_loss, opt, gossip=spec,
                                       mode="gossip"))
        s = init_state(replicate_for_workers({"x": jnp.zeros(2)}, M), opt)
        for _ in range(20):
            s, m = step(s, targets)
        states.append(s)
    np.testing.assert_allclose(np.asarray(states[0].params["x"]),
                               np.asarray(states[1].params["x"]),
                               rtol=1e-5, atol=1e-6)
    assert np.isfinite(float(m.loss))


@pytest.mark.parametrize("period", [2, 3])
def test_fused_period_matches_einsum(period):
    M = 4
    topo = T.undirected_ring(M)

    def quad_loss(p, b):
        return jnp.sum((p["x"] - b) ** 2)

    targets = jnp.arange(M * 2, dtype=jnp.float32).reshape(M, 2)
    opt = sgd(0.05)
    outs = []
    for be in ("fused", "einsum"):
        spec = GossipSpec(topology=topo, backend=be, period=period)
        step = jax.jit(make_train_step(quad_loss, opt, gossip=spec,
                                       mode="gossip"))
        s = init_state(replicate_for_workers({"x": jnp.zeros(2)}, M), opt)
        for _ in range(7):
            s, _ = step(s, targets)
        outs.append(np.asarray(s.params["x"]))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-6)


def test_fused_time_varying_one_peer():
    M = 8

    def quad_loss(p, b):
        return jnp.sum((p["x"] - b) ** 2)

    targets = jnp.arange(M * 2, dtype=jnp.float32).reshape(M, 2)
    opt = sgd(0.05)
    outs = []
    for be in ("fused", "einsum"):
        spec = GossipSpec(topology=T.undirected_ring(M), backend=be,
                          time_varying="one_peer_exp")
        step = jax.jit(make_train_step(quad_loss, opt, gossip=spec,
                                       mode="gossip"))
        s = init_state(replicate_for_workers({"x": jnp.zeros(2)}, M), opt)
        for _ in range(9):
            s, _ = step(s, targets)
        outs.append(np.asarray(s.params["x"]))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-6)


def test_fused_chunked_matches_unchunked():
    M = 4
    topo = T.undirected_ring(M)
    params = _tree(M)
    spec = GossipSpec(topology=topo, backend="fused")
    one = bus.mix_bus(params, spec, None, nchunks=1, **BLK)
    many = bus.mix_bus(params, spec, None, nchunks=4, **BLK)
    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(many)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


# ---------------------------------------------------------------------------
# Double-buffered chunked path vs the dense oracle (dtypes × uneven rows)
# ---------------------------------------------------------------------------

# Tolerances per dtype: the oracle mixes in the leaf dtype; the bus kernel
# accumulates in fp32 and casts once — bf16 agreement is one rounding step.
_TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-6),
        jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _uneven_tree(M, dtype, seed=3):
    """Row counts that do NOT split evenly into chunks: 5 blocks of 32 rows
    at BLK (640 payload rows / chunk sizes 2-2-1 for nchunks=3) plus a tail
    leaf straddling the last tile."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    t = {
        "a": jax.random.normal(ks[0], (M, 155, 128)),   # 19840 elems
        "b": jax.random.normal(ks[1], (M, 37)),         # ragged tail
        "c": jax.random.normal(ks[2], (M, 3, 129)),     # crosses a lane row
    }
    return jax.tree.map(lambda x: x.astype(dtype), t)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("nchunks", [2, 3, 5])
def test_chunked_mix_matches_dense_oracle(dtype, nchunks):
    """nchunks > 1 pipelined slicing vs the dense W·A oracle — the chunk
    boundaries (uneven whole-block splits) must not perturb any element."""
    M = 4
    topo = T.undirected_ring(M)
    params = _uneven_tree(M, dtype)
    spec = GossipSpec(topology=topo, backend="fused")
    out = bus.mix_bus(params, spec, None, nchunks=nchunks, **BLK)
    ref = mix_pytree_reference(
        jax.tree.map(lambda x: x.astype(jnp.float32), params), topo.A)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        assert a.dtype == dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **_TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_chunked_mix_and_update_matches_oracle(dtype):
    """Chunked fused mix−η·u vs oracle chain, both dtypes, mixed-dtype tree
    (two dtype groups chunk independently)."""
    M = 4
    topo = T.ring_lattice(M, 2)
    params = _uneven_tree(M, dtype)
    params["extra32"] = jax.random.normal(jax.random.PRNGKey(9), (M, 41, 7))
    updates = jax.tree.map(
        lambda x: jax.random.normal(KEY, x.shape).astype(x.dtype), params)
    spec = GossipSpec(topology=topo, backend="fused")
    eta = 0.25
    out = bus.mix_bus(params, spec, None, updates=updates, eta=eta,
                      nchunks=3, **BLK)
    pf = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    uf = jax.tree.map(lambda x: x.astype(jnp.float32), updates)
    ref = jax.tree.map(lambda m, u: m - np.float32(eta) * u,
                       mix_pytree_reference(pf, topo.A), uf)
    for a, b, p in zip(jax.tree.leaves(out), jax.tree.leaves(ref),
                       jax.tree.leaves(params)):
        assert a.dtype == p.dtype
        tol = _TOL[jnp.bfloat16] if p.dtype == jnp.bfloat16 else _TOL[jnp.float32]
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol)


def test_mix_pytree_dispatches_fused():
    M = 4
    topo = T.undirected_ring(M)
    params = _tree(M)
    spec = GossipSpec(topology=topo, backend="fused")
    out = mix_pytree(params, spec, None)
    ref = mix_pytree_reference(params, topo.A)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Collective count: exactly one bulk ppermute per non-identity permutation
# ---------------------------------------------------------------------------


def test_bulk_collectives_per_step_model():
    for topo, expect in [(T.undirected_ring(8), 2),
                         (T.ring_lattice(8, 4), 4),
                         (T.clique(4), 3),
                         (T.directed_ring_lattice(8, 1), 1)]:
        spec = GossipSpec(topology=topo, backend="fused")
        assert bus.bulk_collectives_per_step(spec) == expect, topo.name
        assert bus.bulk_collectives_per_step(spec, nchunks=2) == 2 * expect


@pytest.mark.slow
def test_sharded_fused_collective_count_and_numerics():
    """On a real 8-device mesh: HLO has exactly len(non-identity perms)
    collective-permutes for the WHOLE pytree (vs leaves × perms before),
    and the result matches the dense oracle."""
    out = run_in_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import topology as T, bus
from repro.core.gossip import GossipSpec, mix_pytree, mix_pytree_reference
mesh = jax.make_mesh((4,2), ("data","model"))
key = jax.random.PRNGKey(0)
params = {"w": jax.random.normal(key, (4, 37, 5)),
          "b": jnp.ones((4, 3)), "c": jax.random.normal(key, (4, 257))}
n_leaves = len(jax.tree.leaves(params))
for topo in [T.undirected_ring(4), T.clique(4), T.directed_ring_lattice(4, 2)]:
    spec = GossipSpec(topology=topo, backend="fused", worker_axes=("data",))
    expect = bus.bulk_collectives_per_step(spec)
    ref = mix_pytree_reference(params, topo.A)
    with jax.set_mesh(mesh):
        sh = jax.NamedSharding(mesh, P("data"))
        p = jax.tree.map(lambda x: jax.device_put(x, sh), params)
        f = jax.jit(lambda q: mix_pytree(q, spec, mesh))
        out = f(p)
        hlo = f.lower(p).compile().as_text()
    n_cp = hlo.count("collective-permute-start(") or hlo.count("collective-permute(")
    assert n_cp == expect, (topo.name, n_cp, expect)
    assert n_cp < n_leaves * len(spec.permutations)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        assert np.allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6), topo.name
print("bus-sharded-ok")
""")
    assert "bus-sharded-ok" in out


@pytest.mark.slow
def test_model_sharded_bus_bytes_drop_by_k():
    """Worker-group composition (WorkerMesh): with each replica tensor-sharded
    k ways over 'model', the bus packs per-model-shard buffers and its bulk
    ppermutes move ~1/k the per-device bytes of the unsharded path — at the
    SAME collective count — and the mixed result still matches the dense
    oracle. This is the HLO-level contract that lets the paper's technique
    run where a replica no longer fits one device."""
    out = run_in_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import topology as T, bus
from repro.core.gossip import GossipSpec, mix_pytree_reference
from repro.launch.hlo_cost import analyze_hlo

M = 4
key = jax.random.PRNGKey(0)
params = {"w": jax.random.normal(key, (M, 256, 8, 128)),   # dim2 shards /k
          "emb": jax.random.normal(key, (M, 1024, 256)),   # dim2 shards /k
          "v": jax.random.normal(key, (M, 33, 5))}         # indivisible: repl
topo = T.undirected_ring(M)
ref = mix_pytree_reference(params, topo.A)
stats = {}
for k in (1, 2):
    mesh = jax.make_mesh((M, k), ("data", "model"),
                            axis_types=(jax.sharding.AxisType.Auto,) * 2,
                            devices=jax.devices()[: M * k])
    spec = GossipSpec(topology=topo, backend="fused", worker_axes=("data",),
                      model_axis="model" if k > 1 else None)
    m_ax = "model" if k > 1 else None
    pspecs = {"w": P("data", None, m_ax, None),
              "emb": P("data", None, m_ax),
              "v": P("data", None, None)}
    with jax.set_mesh(mesh):
        p = jax.tree.map(lambda x, s: jax.device_put(
            x, jax.NamedSharding(mesh, s)), params, pspecs)
        f = jax.jit(lambda q: bus.mix_bus(q, spec, mesh, param_specs=pspecs))
        out = f(p)
        hlo = f.lower(p).compile().as_text()
    hc = analyze_hlo(hlo)
    stats[k] = (hc.coll_counts["collective-permute"],
                hc.coll_bytes["collective-permute"])
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        assert np.allclose(np.asarray(a), np.asarray(b),
                           rtol=1e-5, atol=1e-6), ("numerics", k)
# degree-2 ring: exactly 2 bulk collectives at EVERY shard factor
assert stats[1][0] == 2 and stats[2][0] == 2, stats
ratio = stats[1][1] / stats[2][1]
assert 1.8 < ratio < 2.2, ("per-device cp bytes must drop ~1/k", stats, ratio)
print(f"sharded-bytes-ok ratio={ratio:.3f}")
""")
    assert "sharded-bytes-ok" in out


@pytest.mark.slow
def test_model_sharded_fused_train_step_matches_meshless():
    """End-to-end make_train_step with param_specs on a (workers × model)
    WorkerMesh ≡ the meshless fused step (same topology, same data)."""
    out = run_in_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import topology as T
from repro.core.gossip import GossipSpec
from repro.core.decentralized import make_train_step, init_state, replicate_for_workers
from repro.launch.mesh import WorkerMesh, make_host_mesh
from repro.optim import momentum_sgd

M = 4
topo = T.undirected_ring(M)
def loss(p, b): return jnp.sum((p["x"] - b) ** 2)
targets = jnp.arange(M * 8, dtype=jnp.float32).reshape(M, 8)
opt = momentum_sgd(0.05, 0.9)

# meshless reference (single-process bus emulation)
spec0 = GossipSpec(topology=topo, backend="fused")
s0 = init_state(replicate_for_workers({"x": jnp.zeros(8)}, M), opt)
step0 = jax.jit(make_train_step(loss, opt, gossip=spec0, mode="gossip"))
for _ in range(10):
    s0, _ = step0(s0, targets)

# WorkerMesh: 4 workers x 2-way model sharding of the replica
wm = WorkerMesh.from_mesh(make_host_mesh(data=4, model=2))
spec = GossipSpec.for_mesh(topo, wm, backend="fused")
pspecs = {"x": P("data", "model")}
with jax.set_mesh(wm.mesh):
    s1 = init_state(replicate_for_workers({"x": jnp.zeros(8)}, M), opt)
    step1 = jax.jit(make_train_step(loss, opt, gossip=spec, mode="gossip",
                                    mesh=wm, param_specs=pspecs))
    for _ in range(10):
        s1, _ = step1(s1, targets)
np.testing.assert_allclose(np.asarray(s0.params["x"]), np.asarray(s1.params["x"]),
                           rtol=1e-5, atol=1e-6)
print("mesh-train-ok")
""")
    assert "mesh-train-ok" in out


def test_degenerate_single_worker():
    topo = T.clique(1)
    params = {"x": jnp.arange(6, dtype=jnp.float32).reshape(1, 6)}
    upd = {"x": jnp.ones((1, 6))}
    spec = GossipSpec(topology=topo, backend="fused")
    out = bus.mix_bus(params, spec, None, updates=upd, eta=-1.0, **BLK)
    np.testing.assert_allclose(np.asarray(out["x"]),
                               np.asarray(params["x"] + 1.0), atol=1e-6)
