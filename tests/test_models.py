"""Per-architecture smoke tests (assignment requirement): reduced variant of
each family, one forward/train step on CPU, output shapes + no NaNs."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_config
from repro.core import topology as T
from repro.core.decentralized import init_state, make_train_step, replicate_for_workers
from repro.core.gossip import GossipSpec
from repro.models import model as M
from repro.optim import momentum_sgd

KEY = jax.random.PRNGKey(0)

# Full per-arch sweeps are heavy on CPU (~4 min): plain `pytest -q` smokes a
# dense and a MoE representative; `pytest -m slow` sweeps every family.
FAST_ARCHS = {"granite-3-2b", "mixtral-8x7b"}
ARCH_SWEEP = [a if a in FAST_ARCHS else pytest.param(a, marks=pytest.mark.slow)
              for a in ARCH_NAMES]


def _batch(cfg, B=2, L=32):
    b = {"tokens": jax.random.randint(KEY, (B, L + 1), 0, cfg.vocab_size)}
    if cfg.encoder_layers:
        b["enc_embeds"] = jax.random.normal(KEY, (B, cfg.encoder_seq, cfg.d_model))
    return b


@pytest.mark.parametrize("arch", ARCH_SWEEP)
def test_smoke_forward_and_loss(arch):
    cfg = get_config(arch, reduced=True)
    params = M.init(KEY, cfg)
    batch = _batch(cfg)
    h, _, aux = M.forward(params, cfg, batch["tokens"][:, :-1],
                          memory=M.encode(params, cfg, batch["enc_embeds"])
                          if cfg.encoder_layers else None)
    assert h.shape == (2, 32, cfg.d_model)
    assert not bool(jnp.any(jnp.isnan(h)))
    loss = jax.jit(lambda p, b: M.loss_fn(p, cfg, b))(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss))
    # loss near ln(V) at init (uniform predictions)
    assert abs(float(loss) - np.log(cfg.vocab_size)) < 1.5


@pytest.mark.parametrize("arch", ARCH_SWEEP)
def test_smoke_one_train_step(arch):
    """One decentralized train step on a 2-worker ring (einsum backend, CPU)."""
    cfg = get_config(arch, reduced=True)
    Mw = 2
    params = replicate_for_workers(M.init(KEY, cfg), Mw)
    opt = momentum_sgd(1e-2, 0.9)
    spec = GossipSpec(topology=T.undirected_ring(Mw) if Mw > 2 else
                      T.clique(Mw), backend="einsum")
    loss_fn = lambda p, b: M.loss_fn(p, cfg, b)
    step = jax.jit(make_train_step(loss_fn, opt, gossip=spec, mode="gossip"))
    batch = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (Mw,) + x.shape), _batch(cfg))
    state = init_state(params, opt)
    state, metrics = step(state, batch)
    assert int(state.step) == 1
    assert np.isfinite(float(metrics.loss))
    assert float(metrics.grad_energy) > 0
    for leaf in jax.tree.leaves(state.params):
        assert not bool(jnp.any(jnp.isnan(leaf)))


@pytest.mark.parametrize("arch", ARCH_SWEEP)
def test_smoke_decode_consistency(arch):
    """prefill + 1 decode step ≡ uncached forward (per-arch, reduced).

    MoE archs included: the expert layer is dropless, so a token's output
    does not depend on the rest of the batch."""
    cfg = get_config(arch, reduced=True)
    params = M.init(KEY, cfg)
    B, Lp = 2, 16
    toks = jax.random.randint(KEY, (B, Lp + 1), 0, cfg.vocab_size)
    enc = (jax.random.normal(KEY, (B, cfg.encoder_seq, cfg.d_model))
           if cfg.encoder_layers else None)
    memory = M.encode(params, cfg, enc) if cfg.encoder_layers else None
    h, _, _ = M.forward(params, cfg, toks, memory=memory)
    want = M.logits_from_hidden(params, cfg, h[:, -1:])
    _, caches, ckvs, mem = M.prefill(params, cfg, toks[:, :Lp], max_len=Lp + 4,
                                     enc_embeds=enc)
    got, _ = M.decode_step(params, cfg, caches, toks[:, Lp:Lp + 1],
                           memory=mem, cross_kvs=ckvs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-3, rtol=2e-2)


def test_scan_equals_unrolled():
    """scan-over-layers must be numerically identical to the python loop."""
    cfg_u = get_config("granite-3-2b", reduced=True)
    cfg_s = dataclasses.replace(cfg_u, scan_layers=True)
    # same params: init from unrolled defs, stack manually for the scanned form
    params_u = M.init(KEY, cfg_u)
    layers = params_u["segments"][0]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    params_s = dict(params_u)
    params_s["segments"] = [stacked]
    toks = jax.random.randint(KEY, (2, 17), 0, cfg_u.vocab_size)
    l_u = M.loss_fn(params_u, cfg_u, {"tokens": toks})
    l_s = M.loss_fn(params_s, cfg_s, {"tokens": toks})
    assert np.isclose(float(l_u), float(l_s), atol=1e-5)


@pytest.mark.slow
def test_remat_does_not_change_loss():
    cfg = get_config("gemma-2b", reduced=True)
    cfg_r = dataclasses.replace(cfg, remat=True)
    params = M.init(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 17), 0, cfg.vocab_size)
    l0 = M.loss_fn(params, cfg, {"tokens": toks})
    l1 = M.loss_fn(params, cfg_r, {"tokens": toks})
    g0 = jax.grad(lambda p: M.loss_fn(p, cfg, {"tokens": toks}))(params)
    g1 = jax.grad(lambda p: M.loss_fn(p, cfg_r, {"tokens": toks}))(params)
    assert np.isclose(float(l0), float(l1), atol=1e-6)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_moe_router_balance_loss_positive():
    cfg = get_config("mixtral-8x7b", reduced=True)
    params = M.init(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 17), 0, cfg.vocab_size)
    _, _, aux = M.forward(params, cfg, toks[:, :-1])
    assert float(aux) > 0


def test_param_count_sane():
    """Full configs: n_params() within 25% of the nominal model size."""
    expect = {
        "granite-3-2b": 2.5e9, "deepseek-7b": 7e9, "gemma-2b": 2.5e9,
        "mamba2-2.7b": 2.7e9, "mixtral-8x7b": 47e9, "chameleon-34b": 34e9,
        "nemotron-4-340b": 340e9, "deepseek-v2-lite-16b": 16e9,
        "recurrentgemma-2b": 2.7e9,
    }
    for arch, n in expect.items():
        got = get_config(arch).n_params()
        assert 0.6 * n < got < 1.5 * n, (arch, got, n)


def test_chunked_ce_matches_dense():
    cfg = get_config("granite-3-2b", reduced=True)
    params = M.init(KEY, cfg)
    h = jax.random.normal(KEY, (2, 32, cfg.d_model))
    labels = jax.random.randint(KEY, (2, 32), 0, cfg.vocab_size)
    chunked = M.cross_entropy_chunked(params, cfg, h, labels, n_chunks=8)
    logits = M.logits_from_hidden(params, cfg, h)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    dense = jnp.mean(logz - gold)
    assert np.isclose(float(chunked), float(dense), rtol=1e-6)
