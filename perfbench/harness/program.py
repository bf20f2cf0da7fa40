"""The system under test, driven as its users drive it.

The timed entry is the jitted train step that ``repro.train.train()``
builds: ``make_train_step(loss, momentum_sgd(lr, mu), gossip=...)`` under
``jax.jit(..., donate_argnums=(0,))``. With ``placement: mesh`` it is the
step ``chip_smoke.py`` runs on four chips: one worker per chip on a
``("data",)`` mesh, ``GossipSpec.for_mesh``, no gradient statistics.

The weights are the benchmark's own (``references.<arch>.init_leaf``), made
on the device in one jitted call from the seed, in the layout and dtype the
program stores them, and every worker starts from the same replica, as
``train()``'s users start (``replicate_for_workers``). Only the layout
(``jax.eval_shape`` of the program's ``init``) comes from the program.

The one replica is kept as a materialized array wherever it is compared
against: XLA may skip a float32 -> bfloat16 -> float32 round trip inside
one program (excess precision), so a replica made again and widened in the
same program as a comparison can be the unrounded float32 values.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


def seed_key(seed: int) -> np.ndarray:
    """A threefry key as a uint32[2] host array, for any seed below 2**64:
    the compiled init takes it as an argument, so one program serves all
    seeds."""
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)


def _names(path) -> tuple[str, ...]:
    return tuple(str(k.key) for k in path if hasattr(k, "key"))


@dataclasses.dataclass
class Program:
    cfg: Any                 # the program's ModelConfig
    step: Any                # jax.jit(step, donate_argnums=(0,))
    init_replica: Any        # jitted: key -> one replica (layout, dtype)
    replicate: Any           # jitted: replica -> worker-stacked params
    init_state: Any          # jitted: params -> TrainState
    batch_sharding: Any      # for device_put of a step batch (or None)
    mesh_ctx: Any            # () -> context manager the program runs under
    gossip: Any              # GossipSpec or None

    def batch(self, host_tokens: np.ndarray) -> dict:
        if self.batch_sharding is None:
            return {"tokens": jax.device_put(host_tokens)}
        return {"tokens": jax.device_put(host_tokens, self.batch_sharding)}


def build(cell, devices, *, fault: str | None = None) -> Program:
    """Build the cell's step, weights and state makers on ``devices``.

    ``fault`` breaks the timed path underneath, for the test that sees
    ``correct`` come out false: ``frozen`` returns the state unchanged,
    ``drop_half`` leaves half of every row out of the loss, ``no_mix``
    leaves the exchange out (the ring becomes the identity),
    ``no_momentum`` drops the momentum from the update (plain SGD).
    """
    from repro.configs.base import ModelConfig
    from repro.core import topology as T
    from repro.core.decentralized import (TrainState, init_state,
                                          make_train_step)
    from repro.core.gossip import GossipSpec
    from repro.launch.mesh import WorkerMesh
    from repro.models import model as M
    from repro.optim import momentum_sgd

    tr, m = cell.traffic, cell.model
    cfg = ModelConfig(name=cell.config["name"], **m)
    arch = cell.reference()
    opt_cfg = tr["optimizer"]
    if opt_cfg["name"] != "momentum_sgd":
        raise ValueError(f"unsupported optimizer {opt_cfg['name']!r}")
    opt = momentum_sgd(opt_cfg["lr"],
                       0.0 if fault == "no_momentum" else opt_cfg["mu"])

    def loss(p, b):
        if fault == "drop_half":
            t = b["tokens"]
            half = (t.shape[-1] - 1) // 2
            b = {"tokens": t[..., :half + 1]}
        return M.loss_fn(p, cfg, b)

    if tr["mode"] != "gossip":
        raise ValueError(f"unsupported mode {tr['mode']!r}")
    W = tr["workers"]
    mesh_ctx, wm = contextlib.nullcontext, None
    param_sharding = batch_sharding = replicated = None
    topo = T.make(tr["topology"], W)
    if fault == "no_mix":
        topo = T.Topology(name="identity", A=np.eye(W), directed=False,
                          circulant_offsets=(0,))
    if tr["placement"] == "mesh":
        mesh = jax.make_mesh((W,), ("data",), devices=devices[:W],
                             axis_types=(jax.sharding.AxisType.Auto,))
        wm = WorkerMesh.from_mesh(mesh)
        spec = GossipSpec.for_mesh(topo, wm, backend=tr["backend"])
        mesh_ctx = functools.partial(jax.set_mesh, mesh)
        param_sharding = batch_sharding = NamedSharding(mesh, P("data"))
        replicated = NamedSharding(mesh, P())
    else:
        spec = GossipSpec(topology=topo, backend=tr["backend"])
    step = make_train_step(loss, opt, gossip=spec, mesh=wm,
                           compute_stats=tr["compute_stats"])
    if fault == "frozen":
        inner = step
        step = lambda s, b: (s, inner(s, b)[1])

    dtype = jnp.dtype(m["param_dtype"])
    shapes = jax.eval_shape(functools.partial(M.init, cfg=cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def one_replica(key_data):
        key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
        leaves = [arch.init_leaf(_names(path), s.shape,
                                 jax.random.fold_in(key, i), m).astype(dtype)
                  for i, (path, s) in enumerate(flat)]
        return treedef.unflatten(leaves)

    def replicate(replica):
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (W,) + x.shape),
                            replica)

    if param_sharding is not None:
        init_replica = jax.jit(one_replica, out_shardings=replicated)
        stack = jax.jit(replicate, out_shardings=param_sharding)
        state_shardings = TrainState(replicated, param_sharding,
                                     param_sharding)
        make_state = jax.jit(lambda p: init_state(p, opt),
                             out_shardings=state_shardings)
    else:
        init_replica = jax.jit(one_replica)
        stack = jax.jit(replicate)
        make_state = jax.jit(lambda p: init_state(p, opt))
    return Program(cfg, jax.jit(step, donate_argnums=(0,)), init_replica,
                   stack, make_state, batch_sharding, mesh_ctx, spec)
