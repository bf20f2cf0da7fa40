"""Compile the main path's kernels and the sharded gossip bus for a TPU v5e.

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
from repro.core.gossip import GossipSpec
from repro.kernels.gossip_mix.kernel import gossip_mix_2d
from repro.kernels.quant_pack.kernel import quantize_pack_2d
from repro.launch.hlo_cost import analyze_hlo
from repro.models import model as M
from repro.models.params import abstract_tree

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
