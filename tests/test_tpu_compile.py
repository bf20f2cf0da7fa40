"""Compile the main path's kernels, the sharded gossip bus and the train
steps with the flash-attention kernel for a TPU v5e.

Nothing runs: the TPU compiler installed with JAX compiles for a described
``v5e:2x2`` topology, which refuses what the chip would refuse (unaligned
tiles, VMEM overruns, kernels that cannot be partitioned) and shows the
collectives the compiler put in. The topology is described inside a fixture
so that importing this file never loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import get_config
from repro.core import bus
from repro.core import topology as T
from repro.core.decentralized import init_state, make_train_step
from repro.core.gossip import GossipSpec
from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.gossip_mix.kernel import gossip_mix_2d
from repro.kernels.quant_pack.kernel import quantize_pack_2d
from repro.launch.hlo_cost import analyze_hlo
from repro.launch.mesh import WorkerMesh
from repro.models import model as M
from repro.models.params import abstract_tree
from repro.optim import momentum_sgd

WORKERS = 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _granite_layer(dtype, lead=()):
    """One granite-3-2b layer plus the vocab table, at published widths."""
    cfg = get_config("granite-3-2b", n_layers=1)
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(lead + s.shape, dtype),
                        abstract_tree(M.model_defs(cfg), dtype))


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("in_place", [False, True])
def test_gossip_mix_compiles_at_granite_bus_size(one_chip, dtype, in_place):
    """The fused mix + update over one granite layer's bus rows: with
    separate neighbor buffers and donation (the sharded bus), or reading
    the neighbors in place from M stacked workers (the one-chip bus)."""
    layout = bus.plan_layout(_granite_layer(dtype), lead_ndim=0)
    (g,) = layout.groups
    rows = g.rows * (WORKERS if in_place else 1)
    buf = _spec((rows, g.cols), dtype, one_chip)
    scalars = (_spec((3,), jnp.float32, one_chip),
               _spec((1,), jnp.float32, one_chip))
    ring = [np.roll(np.arange(WORKERS), s) for s in (1, -1)]

    def step(w, n0, n1, wts, u, eta):
        nbrs, kw = ([w, w], dict(sources=ring)) if in_place else \
            ([n0, n1], dict(donate=True))
        return gossip_mix_2d(w, nbrs, wts, u, eta, block_r=g.block_r,
                             block_c=g.cols, **kw)

    hlo = jax.jit(step).lower(buf, buf, buf, scalars[0], buf,
                              scalars[1]).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("rows", [8, 64, 4096])
def test_quantize_pack_compiles(one_chip, rows):
    x = _spec((rows, bus.LANE), jnp.float32, one_chip)
    hlo = jax.jit(lambda v: quantize_pack_2d(v)).lower(x).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_one_chip_bus_compiles_at_granite_width(one_chip):
    """The single-process bus over 4 workers' granite layer and vocab
    table: the compiled kernel, and no indexed gather (the TPU compiler
    splits one over a multi-GB bus into thousands)."""
    p = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip),
                     _granite_layer(jnp.bfloat16, (WORKERS,)))
    spec = GossipSpec(topology=T.undirected_ring(WORKERS), backend="fused")
    f = jax.jit(lambda q, u: bus.mix_bus(q, spec, None, updates=u, eta=-1.0,
                                         interpret=False))
    hlo = f.lower(p, p).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert " gather(" not in hlo


def test_one_chip_bus_packs_leaves_straight_into_rows(one_chip):
    """4 workers' mamba2 in_proj (minor dim 10576, not whole lanes) and
    vocab table go into the bus leaf by leaf: no loop splitting a copy, no
    flat [workers, elements] row of the whole bus, one gossip_mix call."""
    cfg = get_config("mamba2-2.7b", n_layers=1)
    tree = abstract_tree(M.model_defs(cfg), jnp.bfloat16)
    leaves = {"in_proj": tree["segments"][0][0]["mix"]["in_proj"],
              "embed": tree["embed"]}
    assert leaves["in_proj"].shape[-1] % bus.LANE
    p = {k: _spec((WORKERS,) + s.shape, s.dtype, one_chip)
         for k, s in leaves.items()}
    spec = GossipSpec(topology=T.undirected_ring(WORKERS), backend="fused")
    f = jax.jit(lambda q, u: bus.mix_bus(q, spec, None, updates=u, eta=-1.0,
                                         interpret=False))
    hlo = f.lower(p, p).compile().as_text()
    assert " while(" not in hlo
    flat_rows = [int(n) for n in re.findall(r"bf16\[4,(\d+)\]", hlo)]
    assert not [n for n in flat_rows if n >= 2**20], flat_rows
    calls = [ln for ln in hlo.splitlines()
             if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(calls) == 1 and "%gossip_mix" in calls[0], calls


@pytest.mark.parametrize("with_specs", [False, True])
def test_sharded_ring_bus_compiles_on_2x2(topo, with_specs):
    """One worker per chip: the ring runs one bulk collective-permute per
    non-identity permutation, around the compiled kernel."""
    mesh = Mesh(np.array(topo.devices), ("data",))
    sh = NamedSharding(mesh, P("data"))
    params = {"w": _spec((WORKERS, 2048, 8192), jnp.bfloat16, sh),
              "n": _spec((WORKERS, 2048), jnp.bfloat16, sh)}
    specs = {"w": P("data"), "n": P("data")} if with_specs else None
    spec = GossipSpec(topology=T.undirected_ring(WORKERS), backend="fused")
    f = jax.jit(lambda q: bus.mix_bus(q, spec, mesh, interpret=False,
                                      param_specs=specs))
    hlo = f.lower(params).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert analyze_hlo(hlo).coll_counts["collective-permute"] == \
        bus.bulk_collectives_per_step(spec)


# ---------------------------------------------------------------------------
# The train steps with the Pallas flash-attention kernel
#
# Off the chip ``jax.default_backend()`` is ``cpu``, so each test makes the
# kernel's backend probe answer TPU: ``attention_any`` then takes the kernel
# and the kernel is compiled rather than interpreted. On the 2x2 mesh, one
# worker per chip, the kernel runs per chip inside a ``shard_map`` and the
# compiler puts in no all-gather. On one chip the workers fold into the
# kernel's batch grid, and no attention kernel's result looks like another
# kernel's to the benchmark's readers: no 2-D ``[rows,128]`` (the gossip mix)
# and none of the grouped matmuls' shapes.
# ---------------------------------------------------------------------------

W = WORKERS
_RESULT = re.compile(r"= \(?([a-z0-9]+)\[([\d,]*)\]")


@pytest.fixture
def tpu_probe(monkeypatch):
    monkeypatch.setattr(flash_ops, "on_tpu", lambda: True)


def _flash_calls(text):
    """The flash kernels' custom-call lines of a compiled module."""
    return [ln.strip() for ln in text.splitlines()
            if "custom-call(" in ln and "tpu_custom_call" in ln
            and "%flash_attention" in ln]


def _compile_step(cfg, seq, sharding, mesh=None):
    opt = momentum_sgd(1e-2, 0.9)
    if mesh is None:
        spec, wm = GossipSpec(topology=T.make("ring", W), backend="fused"), None
    else:
        wm = WorkerMesh.from_mesh(mesh)
        spec = GossipSpec.for_mesh(T.make("ring", W), wm, backend="fused")
    step = make_train_step(lambda p, b: M.loss_fn(p, cfg, b), opt,
                           gossip=spec, mesh=wm, compute_stats=mesh is None)
    params = jax.eval_shape(lambda: jax.tree.map(
        lambda x: jnp.broadcast_to(x, (W,) + x.shape),
        M.init(jax.random.PRNGKey(0), cfg)))
    state = jax.eval_shape(lambda p: init_state(p, opt), params)
    scalar = sharding if mesh is None else NamedSharding(mesh, P())
    state = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sharding if s.ndim else scalar), state)
    batch = {"tokens": jax.ShapeDtypeStruct((W, 1, seq + 1), jnp.int32,
                                            sharding=sharding)}
    return jax.jit(step, donate_argnums=(0,)).lower(
        state, batch).compile().as_text()


def _flash_mesh(topo):
    return jax.make_mesh((W,), ("data",), devices=topo.devices[:W],
                         axis_types=(jax.sharding.AxisType.Auto,))


@pytest.mark.parametrize("arch", ["granite", "dsv2lite"])
def test_mesh_step_runs_the_kernel_per_chip_without_all_gather(
        topo, tpu_probe, arch):
    """granite-3-2b's layer at published widths (GQA 32/8 heads of 64),
    and DeepSeek-V2-Lite's latent attention (qk 192, v 128) on a narrow
    residual: forward, dQ and dK/dV kernels, each per chip."""
    if arch == "granite":
        cfg = get_config("granite-3-2b", n_layers=1)
        seq, heads, kv_heads, d_qk, d_v = 1024, 32, 8, 64, 64
    else:
        cfg = get_config("deepseek-v2-lite-16b", n_layers=2, d_model=256,
                         d_ff=512, vocab_size=1024, n_experts=8,
                         router_experts=64, d_ff_expert=256, remat=True,
                         scan_layers=True)
        seq, heads, kv_heads, d_qk, d_v = 512, 16, 16, 192, 128
    mesh = _flash_mesh(topo)
    with jax.set_mesh(mesh):
        text = _compile_step(cfg, seq, NamedSharding(mesh, P("data")), mesh)
    assert analyze_hlo(text).coll_counts["all-gather"] == 0
    results = {_RESULT.search(ln).group(2) for ln in _flash_calls(text)}
    # one row per chip: forward o, dQ, and dK
    assert {f"1,{heads},{seq},{d_v}", f"1,{heads},{seq},{d_qk}",
            f"1,{kv_heads},{seq},{d_qk}"} <= results, results


def test_one_chip_step_folds_workers_into_the_kernel_grid(topo, tpu_probe):
    """The vmapped one-chip step: 4 workers' rows in each kernel call, and
    no attention result a benchmark reader would take for another kernel."""
    cfg = get_config("granite-3-2b", n_layers=1)
    text = _compile_step(cfg, 2048, SingleDeviceSharding(topo.devices[0]))
    calls = _flash_calls(text)
    shapes = [tuple(int(d) for d in _RESULT.search(ln).group(2).split(","))
              for ln in calls]
    # the forward once (remat keeps its output), dQ and dK/dV
    assert sorted(ln.split(" = ")[0].split(".")[0] for ln in calls) == [
        "%flash_attention_dkv", "%flash_attention_dq",
        "%flash_attention_fwd"], calls
    assert all(len(s) == 4 and s[0] == W for s in shapes), shapes
    assert not [s for s in shapes if len(s) == 2 and s[1] == 128]
    # dsv2lite-ring4-4chip's grouped matmuls: 4 x 4096 tokens x top-6
    # pairs through d_ff_expert 1408 and d_model 2048, 8 experts held
    gmm = {(98304, 1408), (98304, 2048), (8, 2048, 1408), (8, 1408, 2048)}
    assert not gmm & set(shapes)
    # every custom call's tuple elements too: no [rows,128] anywhere
    for ln in calls:
        head = ln.split("custom-call(")[0]
        assert not re.search(r"\[\d+,128\]", head), head


def test_allreduce_step_runs_the_kernel_per_chip_without_all_gather(
        topo, tpu_probe):
    """The all-reduce baseline on the 2x2 mesh: no vmap, one replicated
    copy of granite-3-2b's layer, the batch's rows over the chips. Each
    chip's forward, dQ and dK/dV kernels take its own row, and the compiler
    gathers nothing to them."""
    cfg = get_config("granite-3-2b", n_layers=1)
    seq = 1024
    mesh = _flash_mesh(topo)
    opt = momentum_sgd(1e-2, 0.9)
    step = make_train_step(lambda p, b: M.loss_fn(p, cfg, b), opt,
                           mode="allreduce", mesh=WorkerMesh.from_mesh(mesh))
    params = jax.eval_shape(lambda: M.init(jax.random.PRNGKey(0), cfg))
    state = jax.eval_shape(lambda p: init_state(p, opt), params)
    replicated = NamedSharding(mesh, P())
    state = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=replicated), state)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (W, seq + 1), jnp.int32, sharding=NamedSharding(mesh, P("data")))}
    with jax.set_mesh(mesh):
        text = jax.jit(step, donate_argnums=(0,)).lower(
            state, batch).compile().as_text()
    assert analyze_hlo(text).coll_counts["all-gather"] == 0
    results = {_RESULT.search(ln).group(2) for ln in _flash_calls(text)}
    assert {f"1,32,{seq},64", f"1,8,{seq},64"} <= results, results
