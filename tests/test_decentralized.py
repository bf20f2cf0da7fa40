"""DSM train step (paper eq. 3): convergence, equivalences, gossip math."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import topology as T
from repro.core.decentralized import (
    gradient_stats,
    init_state,
    make_train_step,
    param_spread,
    replicate_for_workers,
)
from repro.core.gossip import GossipSpec, mix_pytree, mix_pytree_reference
from repro.optim import adam, momentum_sgd, sgd


def quad_loss(params, batch):
    return jnp.sum((params["x"] - batch) ** 2)


def _run(topo, steps=300, lr=0.05, mode="gossip", backend="einsum", targets=None,
         optimizer=None, **kw):
    M = topo.M
    if targets is None:
        targets = jnp.arange(M * 2, dtype=jnp.float32).reshape(M, 2)
    opt = optimizer or sgd(lr)
    spec = GossipSpec(topology=topo, backend=backend)
    step = make_train_step(quad_loss, opt, gossip=spec, mode=mode, **kw)
    params0 = replicate_for_workers({"x": jnp.zeros(2)}, M)
    state = init_state(params0, opt)
    jstep = jax.jit(step)
    for _ in range(steps):
        state, m = jstep(state, targets)
    return state, m, targets


def test_dsm_converges_to_consensus_mean():
    topo = T.undirected_ring(6)
    state, m, targets = _run(topo, steps=800, lr=0.02)
    mean = targets.mean(0)
    # every worker near the global optimum; residual spread ∝ η·E_sp (paper §3)
    assert np.allclose(np.asarray(state.params["x"]), mean, atol=0.5)
    state_lo, _, _ = _run(topo, steps=1600, lr=0.01)
    spread_hi = float(param_spread(state.params))
    spread_lo = float(param_spread(state_lo.params))
    assert spread_lo < spread_hi  # smaller η ⇒ tighter consensus


def test_clique_gossip_equals_centralized_sgd():
    """A = 11ᵀ/M with identical data ⇒ DSM ≡ centralized SGD (paper §2)."""
    M = 4
    topo = T.clique(M)
    target = jnp.full((M, 2), 3.0)  # identical local data
    state, _, _ = _run(topo, steps=50, targets=target)
    # centralized: w_{k+1} = w - lr*2*(w-3)
    w = np.zeros(2)
    for _ in range(50):
        w = w - 0.05 * 2 * (w - 3.0)
    assert np.allclose(np.asarray(state.params["x"]), w, atol=1e-4)
    assert float(param_spread(state.params)) < 1e-10  # replicas identical


def test_momentum_matches_paper_form():
    topo = T.clique(2)
    state, _, _ = _run(topo, steps=30, optimizer=momentum_sgd(0.02, 0.9),
                       targets=jnp.full((2, 2), 1.0))
    # manual: u = 0.9u + g; w = mean-mix(w) - lr*u (identical workers ⇒ mix = id)
    w, u = np.zeros(2), np.zeros(2)
    for _ in range(30):
        g = 2 * (w - 1.0)
        u = 0.9 * u + g
        w = w - 0.02 * u
    assert np.allclose(np.asarray(state.params["x"][0]), w, atol=1e-4)


def test_adam_runs_and_converges():
    topo = T.undirected_ring(4)
    state, m, targets = _run(topo, steps=1500, optimizer=adam(0.03))
    assert np.allclose(np.asarray(state.params["x"]).mean(0),
                       np.asarray(targets.mean(0)), atol=1.0)
    assert np.isfinite(float(m.loss))


def test_gossip_period_local_sgd():
    """period > 1 (local SGD variant) still converges to consensus region."""
    topo = T.undirected_ring(4)
    spec = GossipSpec(topology=topo, backend="einsum", period=4)
    opt = sgd(0.05)
    step = make_train_step(quad_loss, opt, gossip=spec, mode="gossip")
    targets = jnp.arange(8, dtype=jnp.float32).reshape(4, 2)
    state = init_state(replicate_for_workers({"x": jnp.zeros(2)}, 4), opt)
    jstep = jax.jit(step)
    for _ in range(400):
        state, m = jstep(state, targets)
    assert np.allclose(np.asarray(state.params["x"]).mean(0),
                       np.asarray(targets.mean(0)), atol=0.7)


def test_mix_first_vs_adapt_then_combine():
    """Both DSM orderings converge; they differ transiently."""
    topo = T.undirected_ring(4)
    targets = jnp.arange(8, dtype=jnp.float32).reshape(4, 2)
    s1, _, _ = _run(topo, steps=200, targets=targets, mix_first=True)
    s2, _, _ = _run(topo, steps=200, targets=targets, mix_first=False)
    assert np.allclose(np.asarray(s1.params["x"]).mean(0),
                       np.asarray(s2.params["x"]).mean(0), atol=0.3)


def test_microbatched_step_matches_full_batch():
    """Gradient accumulation must reproduce the full-batch gradient step."""
    topo = T.undirected_ring(4)
    opt = sgd(0.1)
    spec = GossipSpec(topology=topo, backend="einsum")

    def loss(params, batch):
        return jnp.mean((params["x"][None, :] - batch) ** 2)

    batch = jnp.arange(4 * 8 * 2, dtype=jnp.float32).reshape(4, 8, 2)
    p0 = replicate_for_workers({"x": jnp.zeros(2)}, 4)
    s_full = init_state(p0, opt)
    s_mb = init_state(p0, opt)
    step_full = jax.jit(make_train_step(loss, opt, gossip=spec, mode="gossip"))
    step_mb = jax.jit(make_train_step(loss, opt, gossip=spec, mode="gossip",
                                      microbatch=4))
    s_full, m_full = step_full(s_full, batch)
    s_mb, m_mb = step_mb(s_mb, batch)
    assert np.allclose(np.asarray(s_full.params["x"]),
                       np.asarray(s_mb.params["x"]), atol=1e-5)
    assert np.isclose(float(m_full.loss), float(m_mb.loss), atol=1e-5)


def test_gradient_stats_match_definitions():
    grads = {"a": jnp.asarray([[1.0, 0.0], [0.0, 1.0], [1.0, 1.0], [0.0, 0.0]])}
    E, E_sp, H = gradient_stats(grads)
    G = np.asarray(grads["a"]).T  # (n, M)
    assert np.isclose(float(E), np.linalg.norm(G, "fro") ** 2)
    D = G - G.mean(1, keepdims=True)
    assert np.isclose(float(E_sp), np.linalg.norm(D, "fro") ** 2, atol=1e-6)
    assert np.isclose(float(H), np.sqrt(4) * np.linalg.norm(G.mean(1)), atol=1e-6)


def test_gossip_preserves_mean_property():
    """Doubly-stochastic mixing preserves the worker mean (any topology)."""
    for topo in (T.undirected_ring(6), T.expander(8, 4, n_candidates=3),
                 T.directed_ring_lattice(6, 2)):
        x = {"w": jnp.arange(topo.M * 3, dtype=jnp.float32).reshape(topo.M, 3)}
        mixed = mix_pytree_reference(x, topo.A)
        assert np.allclose(np.asarray(mixed["w"]).mean(0),
                           np.asarray(x["w"]).mean(0), atol=1e-5)


def test_pure_consensus_converges_at_lambda2_rate():
    """W A^k → mean at rate |λ2|^k (paper eq. 5 with zero gradients)."""
    topo = T.undirected_ring(8)
    rng = np.random.default_rng(0)
    x = {"w": jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)}
    spread0 = float(param_spread(x))
    cur = x
    K = 25
    for _ in range(K):
        cur = mix_pytree_reference(cur, topo.A)
    spread = float(param_spread(cur))
    rate = (spread / spread0) ** (1 / (2 * K))   # spread is squared norm
    assert rate <= topo.lambda2 + 0.02


def test_time_varying_one_peer_gossip():
    """Beyond-paper: one-peer exponential time-varying gossip (degree 1 per
    step) converges — and pure consensus is EXACT after log2(M) rounds."""
    from repro.core.gossip import mix_pytree_time_varying

    M = 8
    topo = T.undirected_ring(M)  # placeholder; matrices come from the rounds
    spec = GossipSpec(topology=topo, backend="einsum",
                      time_varying="one_peer_exp")
    x = {"w": jnp.arange(M * 2, dtype=jnp.float32).reshape(M, 2)}
    cur = x
    for k in range(3):  # log2(8) rounds
        cur = mix_pytree_time_varying(cur, spec, jnp.asarray(k), None)
    mean = np.asarray(x["w"]).mean(0)
    assert np.allclose(np.asarray(cur["w"]), mean, atol=1e-5)

    # full DSM with time-varying gossip converges to consensus optimum
    targets = jnp.arange(M * 2, dtype=jnp.float32).reshape(M, 2)
    opt = sgd(0.05)
    step = make_train_step(quad_loss, opt, gossip=spec, mode="gossip")
    state = init_state(replicate_for_workers({"x": jnp.zeros(2)}, M), opt)
    jstep = jax.jit(step)
    for _ in range(400):
        state, m = jstep(state, targets)
    assert np.allclose(np.asarray(state.params["x"]).mean(0),
                       np.asarray(targets.mean(0)), atol=0.5)
    # degree-1 mixing per step => larger residual spread than the static ring
    assert float(m.param_spread) < 15.0


@pytest.mark.parametrize("backend", ["ppermute", "allreduce"])
def test_collective_backend_without_mesh_raises(backend):
    """A collective backend never falls back to the dense mix in silence."""
    spec = GossipSpec(topology=T.undirected_ring(4), backend=backend)
    with pytest.raises(ValueError, match="no mesh is set"):
        mix_pytree({"w": jnp.ones((4, 3))}, spec)
