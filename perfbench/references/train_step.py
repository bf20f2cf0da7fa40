"""Plain float32 reference of the timed train step, for any architecture
whose reference module gives ``row_loss``.

Gossip (paper eq. 3, mix first): every worker j takes the gradient of its
own rows at its own parameters, its momentum becomes ``mu * m_j + g_j``,
and its new parameters are ``sum_i A[i, j] w_i - lr * m_j``. Everything is
computed in float32; the parameters are then stored in the configuration's
``param_dtype`` (rounded to nearest), as the configuration states they are
kept. Nothing here imports the system under test; the mixing matrix is
built from the traffic's topology name in :mod:`references.topologies`.

The readings are the ones ``correct`` compares: the mean loss of each of
the first steps, the norm of each (worker, leaf) of the momentum after
step 1 (which is the first gradient), and the norm of each (worker, leaf)
of the change of the parameters over all the steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from references.common import HIGHEST
from references.topologies import mixing_matrix

FAULTS = ("drop_half", "no_mix", "no_momentum")


def leaf_norms(tree):
    """(workers, leaves) float32 norms of a worker-stacked tree."""
    out = []
    for x in jax.tree.leaves(tree):
        x = x.astype(jnp.float32)
        out.append(jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)), 1)))
    return jnp.stack(out, axis=1)


def _worker_loss(arch, m, prec, drop_half):
    def loss(params, rows):
        weights = None
        if drop_half:       # half of every row's positions left out
            L = rows.shape[-1] - 1
            weights = (jnp.arange(L) < L // 2).astype(jnp.float32)
        f = jax.checkpoint(lambda p, r: arch.row_loss(p, m, r, prec, weights))
        return sum(f(params, rows[i]) for i in range(rows.shape[0])) \
            / rows.shape[0]
    return loss


def reference_readings(arch, m: dict, traffic: dict, replica, batches,
                       *, prec: str = "f32",
                       fault: str | None = None) -> dict:
    """Run ``len(batches)`` reference steps, every worker starting from
    ``replica`` (one replica's leaves, as stored).

    ``batches``: host int arrays of (workers, rows, L + 1). ``fault``
    breaks the step on purpose, for the tests and the chip readings of the
    limits. The parameters stay on the device, held in their stored dtype
    (a real bfloat16 buffer: XLA may skip a rounding that is widened again
    inside the same program), and the momentum on the host, so that a
    replica set as large as the program's fits the chip beside one
    worker's gradient.
    """
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    lr, mu = traffic["optimizer"]["lr"], traffic["optimizer"]["mu"]
    if fault == "no_momentum":
        mu = 0.0
    M = traffic["workers"]
    A = np.eye(M) if fault == "no_mix" else mixing_matrix(
        traffic["topology"], M)
    store = jnp.dtype(m["param_dtype"])
    A = jnp.asarray(A, jnp.float32)
    loss = _worker_loss(arch, m, prec, fault == "drop_half")
    vg = jax.jit(jax.value_and_grad(loss))
    take = jax.jit(lambda t, j: jax.tree.map(
        lambda x: x[j].astype(jnp.float32), t))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def mix_leaf(w, u):
        new = jnp.einsum("ij,i...->j...", A, w.astype(jnp.float32),
                         precision=HIGHEST) - lr * u
        return new.astype(store)

    P = jax.jit(lambda r: jax.tree.map(
        lambda x: jnp.broadcast_to(x.astype(store), (M,) + x.shape), r))(
            replica)
    leaves, tdef = jax.tree.flatten(P)
    mom = [np.zeros(x.shape, np.float32) for x in leaves]
    del leaves
    losses, grad = [], None
    for batch in batches:
        step_losses = []
        for j in range(M):
            lj, gj = vg(take(P, j), jnp.asarray(batch[j]))
            for u, g in zip(mom, jax.tree.leaves(gj)):
                u[j] *= mu
                u[j] += np.asarray(g)
            step_losses.append(float(lj))
            del gj
        losses.append(float(np.mean(step_losses)))
        if grad is None:
            grad = np.stack([np.linalg.norm(u.reshape(M, -1), axis=1)
                             for u in mom], axis=1)
        leaves = jax.tree.leaves(P)
        del P
        P = tdef.unflatten([mix_leaf(w, jnp.asarray(u))
                            for w, u in zip(leaves, mom)])
        del leaves
    del mom
    change = np.asarray(jax.jit(lambda a, r: leaf_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, r)))(
            P, replica))
    return {"losses": np.asarray(losses), "grad": grad, "change": change}
