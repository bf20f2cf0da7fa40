"""Gossip / consensus mixing backends (paper eq. 3, first term).

The consensus step for the estimate matrix W (columns = worker replicas) is
``W ← W·A``.  In this framework every parameter leaf carries a leading worker
dimension of size M, so mixing leaf ``x`` of shape (M, ...) is
``x ← einsum('im,i...->m...', A, x)``.

Backends (selected via :class:`GossipSpec`):

* ``einsum``     — dense contraction with A. Correct for any A; lowers to an
                   all-gather over the worker axis (the *naive baseline* whose
                   collective cost we hillclimb away in EXPERIMENTS.md §Perf).
* ``ppermute``   — Birkhoff-decomposes A into weighted permutations and runs
                   one ``jax.lax.ppermute`` per non-identity permutation inside
                   a *partial-manual* ``shard_map`` over the worker axes; the
                   model axes stay automatic. Collective bytes = degree ×
                   bytes(params)/M per device, all single-hop on a ring — the
                   TPU-native rendering of the paper's sparse topology.
* ``allreduce``  — clique fast path: ``pmean`` over the worker axes (this is
                   the PS / ring-allreduce baseline the paper compares with).
* ``fused``      — the flat-buffer gossip bus (`repro.core.bus`): the whole
                   parameter pytree is packed into one contiguous buffer, the
                   consensus runs as ONE bulk collective per non-identity
                   Birkhoff permutation (vs leaves × perms for ``ppermute``),
                   and the mix (+ optimizer update, in the train step) is a
                   single fused Pallas VMEM pass. See EXPERIMENTS.md §Perf for
                   the collective-count / HBM-traffic model.

All backends are numerically interchangeable (tests assert allclose vs the
dense oracle).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.topology import Topology

__all__ = ["GossipSpec", "mix_pytree", "mix_reference", "make_mixer",
           "hierarchical_mix", "hierarchical_mix_compressed",
           "split_hierarchical",
           "survivor_mix", "survivor_hierarchical_mix"]

PyTree = Any


@dataclasses.dataclass(frozen=True)
class GossipSpec:
    """Static description of how the consensus step executes.

    Attributes:
      topology: the Topology (consensus matrix A, M workers).
      backend: 'einsum' | 'ppermute' | 'allreduce' | 'fused' | 'auto'.
      worker_axes: mesh axis name(s) the worker dimension is sharded over,
        e.g. ('data',) or ('pod', 'data') for multi-pod.
      model_axis: intra-replica sharding axis (WorkerMesh.model_axis) or
        None. When set, the fused bus gossips *per model shard*: each device
        packs exactly its 1/k of the replica by flat-buffer rows (layout v2 —
        tensor-sharded leaves as local shards, indivisible leaves row-split)
        and the bulk ppermutes move 1/k the bytes with zero replicated-leaf
        traffic — gossip composes with tensor/FSDP-sharded replicas.
      period: gossip every `period` optimizer steps (1 = paper's synchronous
        DSM; >1 = local-SGD-style beyond-paper variant).
      time_varying: None (static topology) or 'one_peer_exp' — beyond-paper:
        the step-k consensus matrix pairs node i with i ± 2^(k mod log2 M)
        (SGP-style). Degree-1 communication per step, exact consensus every
        log2(M) rounds — strictly cheaper than the paper's static ring with
        faster mixing.
      hierarchical: execute a kronecker/`hier` topology as its TWO factored
        stages (intra-pod then cross-pod — :func:`split_hierarchical` /
        :func:`hierarchical_mix`) instead of one mix with the product
        matrix. Mathematically identical consensus matrix, but the lowered
        collectives factor too: the intra stage's permutations ride only
        ICI (pod-local), the inter stage's ride only the pod (DCI) axis —
        the property the dryrun `--hier-smoke` lane HLO-asserts.
    """

    topology: Topology
    backend: str = "auto"
    worker_axes: tuple[str, ...] = ("data",)
    model_axis: str | None = None
    period: int = 1
    time_varying: str | None = None
    hierarchical: bool = False

    @classmethod
    def for_mesh(cls, topology: Topology, wmesh, **kw) -> "GossipSpec":
        """Spec bound to a WorkerMesh: worker axes + model axis follow the
        mesh factorization (model_axis only when the shard factor k > 1)."""
        from repro.launch.mesh import WorkerMesh

        wm = WorkerMesh.ensure(wmesh)
        return cls(topology=topology, worker_axes=wm.worker_axes,
                   model_axis=wm.model_axis if wm.model_factor > 1 else None,
                   **kw)

    def resolved_backend(self) -> str:
        if self.backend != "auto":
            return self.backend
        t = self.topology
        if t.circulant_offsets is not None and len(t.circulant_offsets) == t.M:
            return "allreduce"  # clique
        return "ppermute"

    @functools.cached_property
    def permutations(self) -> list[tuple[float, np.ndarray]]:
        return self.topology.permutations()


# ---------------------------------------------------------------------------
# Reference (oracle) mixing — dense matmul with A, used in tests & simulator
# ---------------------------------------------------------------------------


def mix_reference(x: jax.Array, A: jax.Array | np.ndarray) -> jax.Array:
    """Dense W·A for one leaf with leading worker dim: x[m] ← Σ_i A[i,m] x[i]."""
    A = jnp.asarray(A, dtype=x.dtype)
    return jnp.einsum("im,i...->m...", A, x)


def mix_pytree_reference(params: PyTree, A) -> PyTree:
    return jax.tree.map(lambda x: mix_reference(x, A), params)


# ---------------------------------------------------------------------------
# Distributed mixing
# ---------------------------------------------------------------------------


def _einsum_mix(params: PyTree, spec: GossipSpec) -> PyTree:
    A = spec.topology.A
    return jax.tree.map(lambda x: mix_reference(x, A), params)


def _allreduce_leaf(x: jax.Array, axes: tuple[str, ...]) -> jax.Array:
    # inside shard_map, per-shard leading dim is 1 (one replica per worker)
    return jax.lax.pmean(x, axes)


def _ppermute_leaf(x: jax.Array, spec: GossipSpec) -> jax.Array:
    """Mix one leaf inside shard_map: x has shape (1, ...) per worker shard."""
    M = spec.topology.M
    axes = spec.worker_axes if len(spec.worker_axes) > 1 else spec.worker_axes[0]
    acc = None
    for w, perm in spec.permutations:
        is_identity = bool(np.all(perm == np.arange(M)))
        if is_identity:
            contrib = x * x.dtype.type(w)
        else:
            # perm[j] = source for destination j  ⇒ ppermute pairs (src, dst)
            pairs = [(int(perm[j]), j) for j in range(M)]
            contrib = jax.lax.ppermute(x, axes, pairs) * x.dtype.type(w)
        acc = contrib if acc is None else acc + contrib
    return acc


def _shard_map_mix(params: PyTree, spec: GossipSpec, mesh, leaf_fn,
                   param_specs: PyTree | None = None) -> PyTree:
    """Run leaf_fn per worker shard with the worker axes manual, rest auto.

    ``param_specs`` (per-leaf PartitionSpecs incl. the leading worker entry
    and any model-axis sharding) keeps tensor-sharded replicas *sharded*
    inside the body: each device mixes only its local model shard — without
    it every leaf would be gathered to P(worker_axes) (full replica per
    device) first.
    """
    specs = param_specs
    manual = set(spec.worker_axes)
    if specs is None:
        specs = jax.tree.map(lambda _: P(spec.worker_axes), params)
    elif spec.model_axis:
        manual = manual | {spec.model_axis}

    def f(p):
        return jax.tree.map(leaf_fn, p)

    return jax.shard_map(
        f,
        mesh=mesh,
        in_specs=(specs,),
        out_specs=specs,
        axis_names=manual,
    )(params)


def mix_pytree(params: PyTree, spec: GossipSpec, mesh=None, *,
               param_specs: PyTree | None = None) -> PyTree:
    """Consensus step over the parameter pytree (leaves have leading M dim)."""
    if spec.hierarchical:
        intra, inter = split_hierarchical(
            dataclasses.replace(spec, hierarchical=False))
        return mix_pytree(mix_pytree(params, intra, mesh,
                                     param_specs=param_specs),
                          inter, mesh, param_specs=param_specs)
    backend = spec.resolved_backend()
    if backend not in ("einsum", "fused", "allreduce", "ppermute"):
        raise ValueError(f"unknown gossip backend {backend!r}")
    if backend == "einsum":
        with jax.named_scope("mix"):
            return _einsum_mix(params, spec)
    if backend == "fused":
        from repro.core import bus  # local import: bus pulls in Pallas

        # mesh=None falls back to the bus's single-process gather emulation
        # (numerically identical to the sharded path, same fused kernel).
        return bus.mix_bus(params, spec, mesh, param_specs=param_specs)
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty:
            raise ValueError(
                f"gossip backend {backend!r} runs collectives over the worker "
                f"axes {spec.worker_axes}, and no mesh is set: pass mesh= or "
                "run under jax.set_mesh (backend='einsum' runs meshless)")
    leaf_fn = (lambda x: _allreduce_leaf(x, spec.worker_axes)) \
        if backend == "allreduce" else (lambda x: _ppermute_leaf(x, spec))
    with jax.named_scope("mix"):     # the per-leaf path has no pack/unpack
        return _shard_map_mix(params, spec, mesh, leaf_fn, param_specs)


def make_mixer(spec: GossipSpec, mesh=None):
    """Returns params -> mixed_params closure for the given spec."""

    def mixer(params: PyTree) -> PyTree:
        return mix_pytree(params, spec, mesh)

    return mixer


def mix_pytree_time_varying(params: PyTree, spec: GossipSpec, step: jax.Array,
                            mesh=None, *,
                            param_specs: PyTree | None = None) -> PyTree:
    """Step-dependent consensus (spec.time_varying = 'one_peer_exp').

    lax.switch over the log2(M) one-peer-exponential rounds; each branch is
    the normal (einsum/ppermute) mix for that round's pairwise topology.
    """
    from repro.core.topology import one_peer_exponential

    M = spec.topology.M
    tau = int(np.log2(M))
    assert 1 << tau == M, "one_peer_exp needs M a power of two"
    branches = []
    for k in range(tau):
        sub = dataclasses.replace(
            spec, topology=one_peer_exponential(M, k), time_varying=None)
        branches.append(lambda p, s=sub: mix_pytree(p, s, mesh,
                                                    param_specs=param_specs))
    return jax.lax.switch(step % tau, branches, params)


# ---------------------------------------------------------------------------
# Hierarchical multi-pod mixing (beyond-paper §Perf optimization)
# ---------------------------------------------------------------------------


def split_hierarchical(spec: GossipSpec) -> tuple[GossipSpec, GossipSpec]:
    """Factor a spec on a kronecker/`hier` topology into its two stages.

    Returns ``(intra, inter)`` specs on the same M workers —
    ``intra.topology.A = I ⊗ A_inner`` (pod-local, every edge ICI) and
    ``inter.topology.A = A_outer ⊗ I`` (cross-pod, every edge DCI) — such
    that :func:`hierarchical_mix` with them equals one mix with the original
    Kronecker matrix. These are also exactly the two stages the simulator's
    `hier` protocol (``repro.sim.protocols.HierGossip``) overlaps: the intra
    stage is a local barrier on fast ICI links, the inter stage rides DCI
    messages that stay in flight while the pod keeps mixing."""
    from repro.core.topology import split_kronecker

    intra_t, inter_t = split_kronecker(spec.topology)
    return (dataclasses.replace(spec, topology=intra_t),
            dataclasses.replace(spec, topology=inter_t))


def hierarchical_mix(params: PyTree, intra: GossipSpec, inter: GossipSpec, mesh=None) -> PyTree:
    """Two-level gossip: dense/cheap mixing inside a pod (fast ICI), sparse
    mixing across pods (slow DCI). Equivalent consensus matrix is the
    Kronecker product A_inter ⊗ A_intra — still doubly stochastic & normal.
    :func:`split_hierarchical` factors a kronecker-topology spec into the
    two stage specs; the wall-clock behaviour of overlapping them (intra
    barrier + in-flight DCI) is simulated by the `hier` protocol in
    ``repro.sim.protocols``.
    """
    return mix_pytree(mix_pytree(params, intra, mesh), inter, mesh)


def hierarchical_mix_compressed(params: PyTree, intra: GossipSpec,
                                inter: GossipSpec, mesh=None, *,
                                dci_dtype: str | None = None,
                                residual: list | None = None
                                ) -> tuple[PyTree, list | None]:
    """Two-level gossip with a lossy cross-pod (DCI) stage.

    The intra-pod stage keeps the exact fused path (fast ICI links don't
    need compression); the inter-pod stage — whose every edge is a slow DCI
    link — rides the compressed bus: bf16/int8 quantize on pack, dequantize
    plus error-feedback residual accumulation on mix
    (:func:`repro.core.bus.mix_bus_compressed`). Returns
    ``(mixed_params, residual)``; thread ``residual`` across rounds.
    ``dci_dtype=None`` is bit-identical to :func:`hierarchical_mix`.
    """
    if dci_dtype is None:
        return hierarchical_mix(params, intra, inter, mesh), residual
    from repro.core import bus  # local import: bus pulls in Pallas

    mixed = mix_pytree(params, intra, mesh)
    return bus.mix_bus_compressed(mixed, inter, mesh, wire_dtype=dci_dtype,
                                  residual=residual)


# ---------------------------------------------------------------------------
# Survivor-renormalized mixing (fault tolerance — mix over a partial fleet)
# ---------------------------------------------------------------------------


def survivor_mix(params: PyTree, topology: Topology, alive,
                 mode: str = "reabsorb") -> PyTree:
    """Consensus step over the survivors only (dense path).

    ``alive`` is a boolean live-mask over the M workers; the consensus
    matrix is repaired with :func:`~repro.core.topology.survivor_matrix`
    (dead rows/columns isolated, surviving columns re-stochasticized), so
    dead workers' estimates get zero weight and dead slices pass through
    untouched. With a full live-mask the repaired matrix IS ``topology.A``
    (bit-identical), so the result bit-matches the unmasked einsum mix."""
    from repro.core.topology import survivor_matrix

    A = survivor_matrix(topology.A, np.asarray(alive, dtype=bool), mode)
    return mix_pytree_reference(params, A)


def survivor_hierarchical_mix(params: PyTree, topology: Topology, alive,
                              mode: str = "reabsorb") -> PyTree:
    """Two-stage hierarchical mix with churn re-planned stages (dense path).

    The kronecker topology's intra/inter stages are repaired with
    :func:`~repro.core.topology.repair_hier_stages` — whole-pod drops
    contract the outer graph (surviving pods bridged and re-weighted) —
    then applied back-to-back. Full live-mask ⇒ bit-matches
    :func:`hierarchical_mix` on the einsum backend."""
    from repro.core.topology import repair_hier_stages

    intra_A, inter_A = repair_hier_stages(
        topology, np.asarray(alive, dtype=bool), mode)
    return mix_pytree_reference(mix_pytree_reference(params, intra_A), inter_A)
