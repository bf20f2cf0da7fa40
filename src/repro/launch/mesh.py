"""Worker-group meshes: how the device mesh factors into gossip workers.

The paper's decentralized graph lives *between* replicas; at scale a replica
no longer fits one device and must itself be sharded. :class:`WorkerMesh` is
the single source of truth for that factorization: the device mesh splits
into **worker axes** (hosting the M decentralized workers — the nodes of the
gossip topology) × an intra-replica **model axis** (tensor/FSDP sharding of
each worker's replica, shard factor k). Every layer — shardings, the gossip
backends, the flat-buffer bus, the dry-run, the train loop — consumes a
WorkerMesh instead of re-deriving axis splits ad hoc.

Mesh construction is a function (not a module-level constant) so importing
this module never touches jax device state; the dry-run forces 512 host
devices *before* any jax import (see dryrun.py).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import numpy as np


SINGLE_POD = (16, 16)                  # 256 chips
MULTI_POD = (2, 16, 16)                # 2 pods × 256 chips = 512

MODEL_AXIS = "model"


@dataclasses.dataclass(frozen=True)
class WorkerMesh:
    """A device mesh factored into worker axes × an intra-replica model axis.

    Attributes:
      mesh: the underlying ``jax.sharding.Mesh`` (or abstract mesh).
      worker_axes: mesh axis name(s) hosting the decentralized workers, e.g.
        ``('data',)`` or ``('pod', 'data')`` for multi-pod.
      model_axis: axis sharding each worker's replica (``None`` ⇒ replicas
        are unsharded; shard factor k = 1).
    """

    mesh: Any
    worker_axes: tuple[str, ...]
    model_axis: str | None = MODEL_AXIS

    @classmethod
    def from_mesh(cls, mesh, model_axis: str | None = MODEL_AXIS) -> "WorkerMesh":
        """Factor ``mesh``: every axis except ``model_axis`` hosts workers."""
        names = tuple(mesh.axis_names)
        ma = model_axis if model_axis in names else None
        return cls(mesh=mesh,
                   worker_axes=tuple(a for a in names if a != ma),
                   model_axis=ma)

    @classmethod
    def ensure(cls, mesh_or_wm) -> "WorkerMesh | None":
        """Normalize: accept a WorkerMesh, a raw mesh, or None."""
        if mesh_or_wm is None or isinstance(mesh_or_wm, cls):
            return mesh_or_wm
        return cls.from_mesh(mesh_or_wm)

    @staticmethod
    def raw(mesh_or_wm):
        """The underlying jax mesh from either form (None passes through)."""
        if isinstance(mesh_or_wm, WorkerMesh):
            return mesh_or_wm.mesh
        return mesh_or_wm

    # -- factor sizes -------------------------------------------------------
    @property
    def n_workers(self) -> int:
        out = 1
        for a in self.worker_axes:
            out *= self.mesh.shape[a]
        return out

    @property
    def model_factor(self) -> int:
        """k — how many ways each worker's replica is sharded."""
        if self.model_axis is None or self.model_axis not in self.mesh.axis_names:
            return 1
        return self.mesh.shape[self.model_axis]

    # -- PartitionSpec helpers ---------------------------------------------
    @property
    def wa(self):
        """The worker axes as a PartitionSpec entry (name or tuple)."""
        return self.worker_axes[0] if len(self.worker_axes) == 1 \
            else self.worker_axes

    def worker_spec(self, *trailing):
        """P(worker_axes, *trailing) — leading worker dim + given entries."""
        from jax.sharding import PartitionSpec as P
        return P(self.wa, *trailing)

    def bus_row_tile(self, dtype="float32") -> int:
        """Row-count quantum of the gossip bus (layout v2) on this mesh.

        The bus plans every dtype group's flat-buffer rows as a multiple of
        ``sublane(dtype) × model_factor``, so each model shard owns whole
        sublane tiles and the buffer splits over the model axis by rows with
        no re-tiling (`repro.core.bus.plan_layout` pass 1).
        """
        from repro.core.bus import sublane_rows
        return sublane_rows(dtype) * self.model_factor

    # -- simulator mirror ---------------------------------------------------
    def sim_payload_bytes(self, params_template, param_specs=None, *,
                          lead_ndim: int = 0, wire_dtype=None) -> int:
        """Per-device bytes of ONE bulk gossip collective on this mesh.

        Exactly ``BusLayout.padded_bytes`` of the layout-v2 plan for the
        local shard view: tensor-sharded leaves contribute their 1/k shard,
        every other leaf its ``⌈n/k⌉`` row-split chunk, rows padded to whole
        sublane tiles per shard. This is the payload the mesh-aware
        simulator charges per message, so virtual time reflects the real
        wire bytes layout v2 ships. ``params_template`` is a per-worker
        pytree (abstract ``ShapeDtypeStruct`` leaves work); ``lead_ndim``
        leading dims (a stacked worker dim) are ignored.

        ``wire_dtype`` ('bfloat16'|'int8') prices the compressed DCI lane
        instead: the same plan's ``padded_bytes(wire_dtype)`` — quantized
        group bytes plus the int8 per-row fp32 scales.
        """
        from repro.core.bus import plan_layout, sharded_leaf_flags

        k = self.model_factor
        leaves, treedef = jax.tree_util.tree_flatten(params_template)
        sizes = [int(np.prod(x.shape[lead_ndim:], dtype=np.int64))
                 for x in leaves]
        if k <= 1:
            flags = (True,) * len(leaves)
        elif param_specs is None:
            flags = (False,) * len(leaves)   # row-split everything
        else:
            flags = sharded_leaf_flags(param_specs, self.model_axis,
                                       treedef=treedef)
        local = []
        for x, n, f in zip(leaves, sizes, flags):
            if f and n % k:
                raise ValueError(
                    f"leaf of {n} elements marked tensor-sharded but does "
                    f"not divide the model factor {k}")
            local.append(jax.ShapeDtypeStruct((n // k if f else n,), x.dtype))
        layout = plan_layout(treedef.unflatten(local), lead_ndim=0, shards=k,
                             leaf_sharded=flags)
        return layout.padded_bytes(wire_dtype)

    def sim_spec(self, *, params_template=None, param_specs=None,
                 dci_dtype=None):
        """Mirror into a :class:`repro.sim.scenarios.MeshSpec`: worker group
        = coordinate along the leading worker axis (the 'pod' axis on
        multi-pod meshes — single-axis meshes are one group), payload bytes
        from :meth:`sim_payload_bytes` when a template is given.
        ``dci_dtype`` additionally prices cross-pod messages at the
        compressed wire bytes (``dci_payload_bytes``)."""
        from repro.sim.scenarios import MeshSpec

        sizes = [int(self.mesh.shape[a]) for a in self.worker_axes]
        n = int(np.prod(sizes))
        # one pod when there is no pod axis; else group by the leading axis
        inner = n if len(sizes) == 1 else n // sizes[0]
        payload = dci_payload = 0
        if params_template is not None:
            payload = self.sim_payload_bytes(params_template, param_specs)
            if dci_dtype is not None:
                dci_payload = self.sim_payload_bytes(
                    params_template, param_specs, wire_dtype=dci_dtype)
        return MeshSpec(group_of=tuple(i // inner for i in range(n)),
                        payload_bytes=payload,
                        dci_payload_bytes=dci_payload, name=self.describe())

    # -- mesh passthrough ---------------------------------------------------
    @property
    def axis_names(self):
        return self.mesh.axis_names

    @property
    def shape(self):
        return self.mesh.shape

    def describe(self) -> str:
        w = "×".join(f"{a}={self.mesh.shape[a]}" for a in self.worker_axes)
        k = self.model_factor
        return f"workers[{w}]={self.n_workers} × {self.model_axis or '-'}={k}"


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_worker_mesh(*, multi_pod: bool = False) -> WorkerMesh:
    """Production WorkerMesh: (pod×)data workers × 16-way model groups."""
    return WorkerMesh.from_mesh(make_production_mesh(multi_pod=multi_pod))


def make_host_mesh(data: int = 2, model: int = 2, pod: int | None = None):
    """Small mesh over whatever local devices exist (tests/examples)."""
    n = len(jax.devices())
    if pod:
        assert pod * data * model <= n
        return jax.make_mesh((pod, data, model), ("pod", "data", "model"),
                                axis_types=(jax.sharding.AxisType.Auto,) * 3)
    assert data * model <= n, (data, model, n)
    return jax.make_mesh((data, model), ("data", "model"),
                            axis_types=(jax.sharding.AxisType.Auto,) * 2)


def worker_axes(mesh) -> tuple[str, ...]:
    """Mesh axes hosting the decentralized workers (all but 'model').

    Thin wrapper over :class:`WorkerMesh` kept for call-site brevity."""
    return WorkerMesh.ensure(mesh).worker_axes


def n_workers(mesh) -> int:
    return WorkerMesh.ensure(mesh).n_workers


# ---------------------------------------------------------------------------
# Ops the compiler does not partition, run per worker or per device
# ---------------------------------------------------------------------------
#
# Some ops the compiler does not split over a mesh or a vmap: the TPU
# compiler refuses a Pallas call whose operands are sharded, and
# ``lax.ragged_dot``'s TPU form takes no batch dim. These helpers put such
# an op inside a ``shard_map`` over the mesh in context, so that each
# device runs its own share. Axes a ``shard_map`` around the call has
# already taken (manual axes) are left alone.


def _free_axes(mesh) -> list[str]:
    return [] if mesh.empty else [a for a in mesh.axis_names
                                  if a not in mesh.manual_axes]


def _entry(axes):
    return axes[0] if len(axes) == 1 else tuple(axes)


def batch_all(n: int, batched, args):
    """``args`` with a leading dim of ``n`` each: the unbatched ones (a
    ``custom_vmap`` rule's ``in_batched`` False) broadcast to it."""
    import jax.numpy as jnp

    return tuple(a if b else jnp.broadcast_to(a, (n,) + a.shape)
                 for a, b in zip(args, batched))


def per_worker(f, args, fold: bool = False):
    """``f`` over the leading (worker) dim of ``args``: inside a shard_map
    over the mesh's worker axes when their size is that dim (one worker per
    device group), else in a loop, or, with ``fold``, in one call of ``f``
    with the workers folded into the next dim (for an ``f`` that treats
    that dim row by row, and splits it over the devices itself, as
    :func:`per_device` does)."""
    from jax.sharding import PartitionSpec as P

    n = args[0].shape[0]
    mesh = jax.sharding.get_abstract_mesh()
    axes = [a for a in _free_axes(mesh) if a != MODEL_AXIS]
    if axes and math.prod(mesh.shape[a] for a in axes) == n:
        spec = P(_entry(axes))
        local = lambda *a: jax.tree.map(lambda y: y[None],
                                        f(*(t[0] for t in a)))
        out_spec = jax.tree.map(lambda _: spec,
                                jax.eval_shape(f, *(t[0] for t in args)))
        return jax.shard_map(local, mesh=mesh, in_specs=(spec,) * len(args),
                             out_specs=out_spec, axis_names=set(axes))(*args)
    if fold:
        out = f(*(t.reshape((n * t.shape[1],) + t.shape[2:]) for t in args))
        return jax.tree.map(lambda y: y.reshape((n, -1) + y.shape[1:]), out)
    return jax.lax.map(lambda a: f(*a), args)


def per_device(f, args, model_dim: int | None = None):
    """``f`` on ``args`` inside a shard_map over the free axes of the mesh
    in context: the leading (row) dim split over the worker axes, and dim
    ``model_dim`` over the model axis, each where every arg's size there
    divides; over an axis that splits neither, each device runs the whole.
    ``f`` must treat both dims slice by slice and give results that carry
    them in the same places. With no mesh, or no free axis, ``f`` runs as
    is."""
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    free = _free_axes(mesh)
    if not free:
        return f(*args)
    rows = [a for a in free if a != MODEL_AXIS]
    split = [None] * (1 if model_dim is None else model_dim + 1)
    if rows and all(x.shape[0] % math.prod(mesh.shape[a] for a in rows) == 0
                    for x in args):
        split[0] = _entry(rows)
    if (model_dim is not None and MODEL_AXIS in free
            and all(x.shape[model_dim] % mesh.shape[MODEL_AXIS] == 0
                    for x in args)):
        split[model_dim] = MODEL_AXIS
    spec = P(*split)
    return jax.shard_map(f, mesh=mesh, in_specs=(spec,) * len(args),
                         out_specs=spec, axis_names=set(free))(*args)
