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
