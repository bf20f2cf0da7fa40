"""Decentralized (consensus-based) training step — the paper's eq. (3).

    w_j(k+1) = Σ_{i∈N_j∪{j}} A_{i,j} w_i(k)  −  η(k) g_j(w_j(k))

Implementation notes
--------------------
* gossip mode: every parameter leaf carries a leading worker dim of size M,
  sharded over the mesh worker axes. The per-worker gradient is a `vmap`
  (workers are data-parallel replicas with *different* params), the optimizer
  update is elementwise, and the consensus mix is the only cross-worker
  communication (see `repro.core.gossip`). Momentum is applied to the local
  subgradients as in the paper's CIFAR experiments.
* the step's layers are named in the compiled program's op metadata by
  plain ``jax.named_scope`` boundaries: ``model`` (forward, and backward
  under ``transpose(``), ``optimizer``, ``stats`` and ``gossip``. A scope
  changes no instruction, so they are always on; a profile of the step is
  attributed to them through the executable's ``as_text()``.
* allreduce mode: the centralized baseline the paper compares against
  (parameter server / ring all-reduce ≡ clique topology, A = 11ᵀ/M):
  params are replicated over the worker axes, XLA inserts the all-reduce.

Replicas that don't fit one device are handled *inside* gossip mode, not by
a separate mode: the WorkerMesh (launch/mesh.py) factors the device mesh
into worker axes × a model axis, ``param_specs`` carries each leaf's
tensor/FSDP sharding over 'model', and the gossip backends mix per model
shard (per-device collective bytes ∝ 1/k). The old ``fsdp`` fallback mode —
which turned the paper's technique OFF for nemotron-scale archs — is
retired; requesting it raises with a pointer here.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import gossip as gossip_lib
from repro.core.gossip import GossipSpec
from repro.optim import Optimizer

PyTree = Any


class TrainState(NamedTuple):
    step: jax.Array
    params: PyTree
    opt_state: PyTree


class StepMetrics(NamedTuple):
    loss: jax.Array            # mean loss over workers
    grad_energy: jax.Array     # Ê  = Σ_j ||g_j||²            (paper A5, E)
    grad_spread: jax.Array     # Ê_sp = Σ_j ||g_j - ḡ||²      (paper E_sp)
    mean_grad_norm: jax.Array  # √M·||ḡ||₂ — single-sample proxy for H
    param_spread: jax.Array    # ||ΔW||_F² = Σ_j ||w_j - w̄||² (consensus error)


def _raw_mesh(mesh):
    """Accept a WorkerMesh (launch/mesh.py) or a raw jax mesh everywhere."""
    from repro.launch.mesh import WorkerMesh  # local: keep core → launch lazy

    return WorkerMesh.raw(mesh)


def init_state(params: PyTree, optimizer: Optimizer) -> TrainState:
    return TrainState(jnp.zeros((), jnp.int32), params, optimizer.init(params))


def replicate_for_workers(params: PyTree, M: int) -> PyTree:
    """Give every leaf a leading worker dim (same init ⇒ R_sp = 0, paper §3)."""
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (M,) + x.shape), params)


def _tree_sq_norm(t: PyTree) -> jax.Array:
    leaves = jax.tree.leaves(t)
    return sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)


def gradient_stats(grads_M: PyTree) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(E, E_sp, √M||ḡ||) from per-worker grads (leading M dim)."""
    E = _tree_sq_norm(grads_M)
    mean_g = jax.tree.map(lambda g: g.mean(0, keepdims=True), grads_M)
    delta = jax.tree.map(lambda g, m: g - m, grads_M, mean_g)
    E_sp = _tree_sq_norm(delta)
    M = jax.tree.leaves(grads_M)[0].shape[0]
    H_proxy = jnp.sqrt(M * _tree_sq_norm(mean_g) / 1.0)
    return E, E_sp, H_proxy


def param_spread(params_M: PyTree) -> jax.Array:
    mean_p = jax.tree.map(lambda p: p.mean(0, keepdims=True), params_M)
    return _tree_sq_norm(jax.tree.map(lambda p, m: p - m, params_M, mean_p))


def _microbatched(value_and_grad_fn, microbatch: int, batch_axis: int):
    """Gradient accumulation: split the batch axis into `microbatch` chunks,
    scan, accumulate grads in fp32.  Cuts activation memory ~1/microbatch
    (the dominant per-device HBM term found by the dry-run memory analysis)."""

    def run(params, batch):
        def split(x):
            b = x.shape[batch_axis]
            assert b % microbatch == 0, (b, microbatch)
            shape = (x.shape[:batch_axis] + (microbatch, b // microbatch)
                     + x.shape[batch_axis + 1:])
            return jnp.moveaxis(x.reshape(shape), batch_axis, 0)

        mbs = jax.tree.map(split, batch)

        def body(carry, mb):
            acc_l, acc_g = carry
            l, g = value_and_grad_fn(params, mb)
            acc_g = jax.tree.map(lambda a, x: a + x.astype(jnp.float32), acc_g, g)
            return (acc_l + l, acc_g), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        l0 = jnp.zeros(jax.eval_shape(lambda b: value_and_grad_fn(params, b)[0],
                                      jax.tree.map(lambda x: x[0], mbs)).shape,
                       jnp.float32)
        (loss, grads), _ = jax.lax.scan(body, (l0, zeros), mbs)
        inv = 1.0 / microbatch
        return loss * inv, jax.tree.map(lambda g: g * inv, grads)

    return run


def make_train_step(
    loss_fn: Callable[[PyTree, PyTree], jax.Array],
    optimizer: Optimizer,
    gossip: GossipSpec | None = None,
    mode: str = "gossip",
    mesh=None,
    compute_stats: bool = True,
    mix_first: bool = True,
    microbatch: int = 1,
    param_specs: Any = None,
):
    """Build the jit-able train step.

    Args:
      loss_fn: (params, batch) -> scalar loss for ONE worker (no leading M).
      optimizer: repro.optim Optimizer.
      gossip: GossipSpec (required for mode='gossip').
      mode: 'gossip' | 'allreduce'.
      mix_first: paper's eq. (3) mixes the *current* params and subtracts the
        gradient taken at the current local params (True). False gives the
        'adapt-then-combine' DSGD variant (Lian et al. 2017) — mix(w - η g).
      microbatch: gradient-accumulation factor over the per-worker batch.
      param_specs: per-leaf PartitionSpecs of the (worker-stacked) params —
        ``shardings.param_pspecs`` output. Lets the gossip backends mix
        model-sharded replicas shard-locally (WorkerMesh composition);
        without it each worker's replica must fit one device group.
    """
    mesh = _raw_mesh(mesh)

    if mode == "gossip":
        if gossip is None:
            raise ValueError("gossip mode requires a GossipSpec")
        M = gossip.topology.M
        # Fused bus path: mix + update land in ONE Pallas VMEM pass over the
        # flat parameter buffer (mix_first only — adapt-then-combine needs
        # the update applied before the mix, so it stays on the generic path;
        # hierarchical specs run as TWO staged mixes, so the single-pass
        # fusion doesn't apply either).
        fuse_update = (gossip.resolved_backend() == "fused" and mix_first
                       and not gossip.hierarchical)

        def step(state: TrainState, batch: PyTree) -> tuple[TrainState, StepMetrics]:
            # batch leaves: (M, per_worker_batch, ...)
            vg = jax.vmap(jax.value_and_grad(loss_fn))
            with jax.named_scope("model"):
                if microbatch > 1:
                    losses, grads = _microbatched(vg, microbatch, batch_axis=1)(
                        state.params, batch)
                else:
                    losses, grads = vg(state.params, batch)
            with jax.named_scope("optimizer"):
                updates, opt_state = optimizer.update(
                    grads, state.opt_state, state.params, state.step
                )

            def do_mix(p):
                if gossip.time_varying:
                    return gossip_lib.mix_pytree_time_varying(
                        p, gossip, state.step, mesh, param_specs=param_specs)
                return gossip_lib.mix_pytree(p, gossip, mesh,
                                             param_specs=param_specs)

            def apply_update(p):
                return jax.tree.map(lambda m, u: m + u.astype(m.dtype), p, updates)

            if fuse_update:
                from repro.core import bus

                def do_mix_update(p):
                    # updates already carry −lr ⇒ eta = −1 gives mix(p) + u
                    if gossip.time_varying:
                        return bus.mix_and_update_time_varying(
                            p, gossip, updates, state.step, mesh, eta=-1.0,
                            param_specs=param_specs)
                    return bus.mix_bus(p, gossip, mesh, updates=updates,
                                       eta=-1.0, param_specs=param_specs)

                with jax.named_scope("gossip"):
                    if gossip.period > 1:
                        new_params = jax.lax.cond(
                            state.step % gossip.period == 0,
                            do_mix_update, apply_update, state.params)
                    else:
                        new_params = do_mix_update(state.params)
            elif mix_first:
                with jax.named_scope("gossip"):
                    if gossip.period > 1:
                        mixed = jax.lax.cond(
                            state.step % gossip.period == 0, do_mix,
                            lambda p: p, state.params)
                    else:
                        mixed = do_mix(state.params)
                with jax.named_scope("optimizer"):
                    new_params = apply_update(mixed)
            else:
                with jax.named_scope("optimizer"):
                    stepped = apply_update(state.params)
                with jax.named_scope("gossip"):
                    new_params = gossip_lib.mix_pytree(
                        stepped, gossip, mesh, param_specs=param_specs) \
                        if gossip.period == 1 else jax.lax.cond(
                            state.step % gossip.period == 0, do_mix,
                            lambda p: p, stepped)

            if compute_stats:
                with jax.named_scope("stats"):
                    E, E_sp, H = gradient_stats(grads)
                    spread = param_spread(new_params)
            else:
                E = E_sp = H = spread = jnp.zeros((), jnp.float32)
            metrics = StepMetrics(losses.mean(), E, E_sp, H, spread)
            return TrainState(state.step + 1, new_params, opt_state), metrics

        return step

    if mode == "fsdp":
        raise ValueError(
            "the 'fsdp' train mode is retired: shard the replica over the "
            "WorkerMesh model axis instead (mode='gossip' with param_specs "
            "from shardings.param_pspecs — see launch/mesh.WorkerMesh)")

    if mode == "allreduce":
        # Centralized equivalent: single param copy; batch (B, ...) sharded
        # over the worker axes; XLA all-reduces the gradient.
        def step(state: TrainState, batch: PyTree) -> tuple[TrainState, StepMetrics]:
            vg = jax.value_and_grad(loss_fn)
            with jax.named_scope("model"):
                if microbatch > 1:
                    loss, grads = _microbatched(vg, microbatch, batch_axis=0)(
                        state.params, batch)
                else:
                    loss, grads = vg(state.params, batch)
            with jax.named_scope("optimizer"):
                updates, opt_state = optimizer.update(
                    grads, state.opt_state, state.params, state.step
                )
                new_params = jax.tree.map(
                    lambda p, u: p + u.astype(p.dtype), state.params, updates
                )
            z = jnp.zeros((), jnp.float32)
            with jax.named_scope("stats"):
                gn = _tree_sq_norm(grads)
            metrics = StepMetrics(loss, gn, z, jnp.sqrt(gn), z)
            return TrainState(state.step + 1, new_params, opt_state), metrics

        return step

    raise ValueError(f"unknown mode {mode!r}")
