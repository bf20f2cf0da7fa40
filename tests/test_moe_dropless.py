"""The dropless expert layer over a chip's share of the experts, and
DeepSeek-V2-Lite's published mathematics (MLA with YaRN, the sequence-wise
balance loss), against the benchmark's plain float32 reference.

All on the CPU at toy widths, in float32 (the reference's matmuls are at
``HIGHEST``; the CPU's float32 matmuls are exact float32 too), so the two
differ only by the order of float32 sums.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import model as M

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench"))
from references import deepseek_v2_lite as R  # noqa: E402

KEY = jax.random.PRNGKey(0)

# DeepSeek-V2-Lite's layer at toy widths: 1 dense + 1 expert layer, the
# router over 8 experts of which this chip holds 4 (offset 2), top-3,
# 2 shared experts, YaRN x40 and the sequence-wise loss as published.
TOY = ModelConfig(
    name="dsv2lite-toy", arch_type="moe", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=4, head_dim=16, d_ff=96, vocab_size=128, mlp_type="swiglu",
    attention_type="mla", kv_lora_rank=32, qk_rope_dim=8, qk_nope_dim=16,
    v_head_dim=16, first_dense_layers=1, n_experts=4, router_experts=8,
    expert_offset=2, n_shared_experts=2, top_k=3, d_ff_expert=24,
    norm_topk_prob=False, moe_aux="seq", router_aux_coef=0.001,
    rope_theta=10000.0, rope_scaling_factor=40.0, rope_original_max_pos=4096,
    yarn_mscale=0.707, yarn_mscale_all_dim=0.707, scan_layers=False,
    remat=False)


def _m(cfg: ModelConfig) -> dict:
    return dataclasses.asdict(cfg)


def _init(cfg, key=KEY):
    """Seeded weights the size the reference initialises them (the
    program's own init scales the expert weights for a deep model)."""
    shapes = jax.eval_shape(lambda: M.init(key, cfg))
    flat, tdef = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = [R.init_leaf(tuple(str(k.key) for k in p if hasattr(k, "key")),
                          s.shape, jax.random.fold_in(key, i), _m(cfg))
              for i, (p, s) in enumerate(flat)]
    return tdef.unflatten(leaves)


def test_loss_and_gradients_match_the_reference():
    """Loss and every leaf's gradient of 2 rows of 32 tokens. Tolerances:
    the loss to 2e-6 relative and each gradient leaf to 1e-4 of its norm,
    what float32 sums in another order give over 2 layers (the program
    runs blockless attention, chunked cross-entropy and sorted expert
    groups; the reference dense per-row attention and every held expert
    on every token)."""
    cfg = TOY
    params = _init(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                              cfg.vocab_size)
    lp, gp = jax.value_and_grad(
        lambda p: M.loss_fn(p, cfg, {"tokens": toks}))(params)
    m = _m(cfg)
    with jax.default_matmul_precision("highest"):
        lr, gr = jax.value_and_grad(lambda p: sum(
            R.row_loss(p, m, toks[i], "f32") for i in range(2)) / 2)(params)
    np.testing.assert_allclose(float(lp), float(lr), rtol=2e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(gp),
                            jax.tree.leaves(gr)):
        a, b = np.asarray(a), np.asarray(b)
        gap = np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)
        assert gap < 1e-4, (jax.tree_util.keystr(path), gap)


def _layer(cfg):
    """One expert layer's params (the reference's init) and a (2, 16, D)
    input."""
    p = _init(cfg, jax.random.PRNGKey(3))["segments"][1][0]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, cfg.d_model))
    return p, x


def _share(p, lo, n):
    return {k: (v[lo:lo + n] if k in ("w_gate", "w_up", "w_down") else v)
            for k, v in p.items()}


@pytest.mark.parametrize("norm_topk", [False, True])
def test_shares_add_up_to_the_uncut_layer(norm_topk):
    """16 experts over 4 shares of 4: the routed parts every share
    computes, plus the shared experts counted once, give the layer whose
    chip holds all 16, and the balance loss is the same on every share."""
    full = dataclasses.replace(TOY, n_experts=16, router_experts=16,
                               expert_offset=0, top_k=6,
                               norm_topk_prob=norm_topk)
    p, x = _layer(full)
    whole, aux = L.moe_apply(p, full, x)
    shared = L._shared_expert(p, full, x)
    routed = 0
    for lo in range(0, 16, 4):
        cfg = dataclasses.replace(full, n_experts=4, expert_offset=lo)
        y, a = L.moe_apply(_share(p, lo, 4), cfg, x)
        routed = routed + (y - shared)
        np.testing.assert_allclose(float(a), float(aux), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(routed + shared),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)


def _per_token(p, cfg, x):
    """The layer one token at a time, with every held expert computed
    densely (no sorting, no groups)."""
    xf = np.asarray(x, np.float64).reshape(-1, cfg.d_model)
    logits = xf @ np.asarray(p["router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros_like(xf)
    g, u, d = (np.asarray(p[k], np.float64) for k in ("w_gate", "w_up",
                                                      "w_down"))
    for t in range(xf.shape[0]):
        top = np.argsort(-probs[t], kind="stable")[:cfg.top_k]
        w = probs[t, top]
        if cfg.norm_topk_prob:
            w = w / w.sum()
        for e, we in zip(top, w):
            e = e - cfg.expert_offset
            if 0 <= e < cfg.n_experts:
                h = xf[t] @ g[e]
                out[t] += we * ((h / (1 + np.exp(-h))) * (xf[t] @ u[e])) @ d[e]
    return out.reshape(x.shape)


@pytest.mark.parametrize("biased", [False, True])
def test_no_token_is_dropped_and_none_depends_on_the_batch(biased):
    """Each row alone gives what it gives in the batch, and the layer
    equals the per-token computation, also where the router sends every
    token to one held expert (a capacity-sized buffer would drop most)."""
    cfg = dataclasses.replace(TOY, n_shared_experts=0)
    p, x = _layer(cfg)
    if biased:       # expert 3 (held: offset 2) wins for every token
        p = dict(p, router=p["router"].at[:, 3].set(1.0))
        x = jnp.abs(x)
    y, _ = L.moe_apply(p, cfg, x)
    for b in range(x.shape[0]):
        yb, _ = L.moe_apply(p, cfg, x[b:b + 1])
        np.testing.assert_array_equal(np.asarray(yb[0]), np.asarray(y[b]))
    np.testing.assert_allclose(np.asarray(y), _per_token(p, cfg, x),
                               rtol=1e-4, atol=1e-5)


def test_yarn_frequencies_and_softmax_scale_are_the_published_ones():
    """DeepSeek-V2-Lite's YaRN (factor 40, 4096 original positions, betas
    32 and 1): frequencies 0..10 as plain rope, 23..31 divided by 40, and
    the softmax scale 192^-1/2 * (0.1 * 0.707 * ln 40 + 1)^2."""
    cfg = get_config("deepseek-v2-lite-16b")
    inv = L.rope_inv_freq(cfg, 64)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-12)
    assert np.all(inv[11:23] < plain[11:23])
    assert np.all(inv[11:23] > plain[11:23] / 40)
    assert A.mla_softmax_scale(cfg) == pytest.approx(0.1147214, abs=5e-8)
    np.testing.assert_allclose(R.yarn_inv_freq(_m(cfg)), inv, rtol=1e-12)
    assert R.softmax_scale(_m(cfg)) == pytest.approx(A.mla_softmax_scale(cfg))


def test_grouped_matmul_under_vmap_runs_per_worker():
    """Under the train step's vmap over workers the grouped matmul and its
    gradient give, for each worker, what they give for that worker alone."""
    k = jax.random.split(KEY, 4)
    x = jax.random.normal(k[0], (3, 12, 8))
    w = jax.random.normal(k[1], (3, 4, 8, 5))
    gs = jnp.array([[3, 0, 4, 2], [0, 0, 0, 12], [1, 1, 1, 1]], jnp.int32)
    f = lambda x, w, g: jnp.sum(jnp.sin(L.grouped_matmul(x, w, g)))
    val, grads = jax.vmap(jax.value_and_grad(f, (0, 1)))(x, w, gs)
    for i in range(3):
        v, g = jax.value_and_grad(f, (0, 1))(x[i], w[i], gs[i])
        np.testing.assert_allclose(float(val[i]), float(v), rtol=1e-6)
        for a, b in zip(grads, g):
            np.testing.assert_allclose(np.asarray(a[i]), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
    # rows past the groups come out 0, with a zero gradient
    y = L.grouped_matmul(x[2], w[2], gs[2])
    assert not np.any(np.asarray(y[4:]))


def test_train_emits_expert_load_only_with_a_sink():
    """``train(routing=...)`` emits the held pairs and the load imbalance
    at log windows with a recording sink, and leaves the run bit-identical
    with the null sink."""
    from repro import telemetry
    from repro.core import topology as T
    from repro.core.decentralized import replicate_for_workers
    from repro.core.gossip import GossipSpec
    from repro.optim import momentum_sgd
    from repro.train.loop import train

    cfg = dataclasses.replace(TOY, n_experts=8, expert_offset=0)
    W = 2
    p0 = replicate_for_workers(_init(cfg), W)
    toks = jax.random.randint(jax.random.PRNGKey(5), (4, W, 2, 17), 0,
                              cfg.vocab_size)
    loss = lambda p, b: M.loss_fn(p, cfg, b)
    routing = lambda p, b: M.routing_counts(p, cfg, b["tokens"][:, :-1])
    counts = np.asarray(routing(_init(cfg), {"tokens": toks[0, 0]}))
    assert counts.shape == (1, 8) and counts.sum() == 2 * 16 * cfg.top_k
    kw = dict(steps=4, gossip=GossipSpec(topology=T.make("ring", W),
                                         backend="einsum"),
              log_every=2, verbose=False)
    s1, h1 = train(loss, p0, momentum_sgd(1e-2, 0.9),
                   ({"tokens": t} for t in toks), **kw)
    s2, h2 = train(loss, p0, momentum_sgd(1e-2, 0.9),
                   ({"tokens": t} for t in toks), routing=routing, **kw)
    with telemetry.run() as tel:
        train(loss, p0, momentum_sgd(1e-2, 0.9),
              ({"tokens": t} for t in toks), routing=routing, **kw)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert h1.loss == h2.loss
    # windows end at steps 0, 2 and 3: every pair of worker 0's 2 x 16
    # tokens is held here
    assert tel.counters["moe.held_pairs"] == 3 * 2 * 16 * cfg.top_k
    loads = [g["value"] for g in tel.gauges
             if g["name"] == "moe.load_max_over_mean"]
    assert len(loads) == 3 and all(v >= 1.0 for v in loads)


def test_rows_past_the_groups_never_reach_a_token(monkeypatch):
    """The TPU's ragged dot leaves the rows past the groups unwritten, in
    its result and in the input's gradient: filled with NaN there, the
    layer and its gradients still equal the clean ones."""
    orig = jax.lax.ragged_dot

    def tail_nan(y, gs):
        rows = jnp.arange(y.shape[0])[:, None]
        return jnp.where(rows < jnp.sum(gs), y, jnp.nan)

    @jax.custom_vjp
    def unwritten(x, w, gs):
        return tail_nan(orig(x, w, gs), gs)

    def fwd(x, w, gs):
        return unwritten(x, w, gs), (x, w, gs)

    def bwd(res, g):
        x, w, gs = res
        _, vjp = jax.vjp(lambda a, b: orig(a, b, gs), x, w)
        dx, dw = vjp(g)
        return tail_nan(dx, gs), dw, None

    unwritten.defvjp(fwd, bwd)
    cfg = TOY
    p, x = _layer(cfg)
    f = jax.value_and_grad(lambda p, x: jnp.sum(
        jnp.sin(L.moe_apply(p, cfg, x)[0])), (0, 1))
    want = f(p, x)
    monkeypatch.setattr(jax.lax, "ragged_dot", unwritten)
    got = f(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-7)
