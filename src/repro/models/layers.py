"""Shared layers: norms, rotary embeddings (plain and YaRN), MLP variants,
Mixture-of-Experts (dropless, over the experts a chip holds).

All modules expose ``<name>_defs(cfg, ...)`` returning a ParamDef pytree and
``<name>_apply(params, cfg, x, ...)``.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.launch.mesh import batch_all, per_worker
from repro.models.params import ParamDef

PyTree = Any


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_defs(dim: int, axis: str = "embed") -> PyTree:
    return {"scale": ParamDef((dim,), (axis,), init="ones")}


def rmsnorm_apply(params: PyTree, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    normed = x32 * jax.lax.rsqrt(var + eps)
    return (normed * params["scale"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0,
         inv_freq: np.ndarray | None = None) -> jax.Array:
    """Apply rotary embedding.  x: (..., L, H, hd); positions: (..., L).

    ``inv_freq`` (hd / 2,) replaces the plain frequencies theta^(-2i/hd)
    (YaRN: :func:`rope_inv_freq`)."""
    hd = x.shape[-1]
    if inv_freq is None:
        freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    else:
        freqs = np.asarray(inv_freq, np.float32)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., L, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_inv_freq(cfg: ModelConfig, dim: int) -> np.ndarray | None:
    """YaRN frequencies of a ``dim``-wide rotary part (DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding``), or None for plain rope:

        f_extra,i = theta^(-2i/dim),  f_inter,i = f_extra,i / factor,
        d(r) = dim ln(L_orig / (2 pi r)) / (2 ln theta),
        low = floor(d(beta_fast)),  high = ceil(d(beta_slow)),
        ramp_i = clip((i - low) / (high - low), 0, 1),
        inv_freq_i = f_inter,i ramp_i + f_extra,i (1 - ramp_i).

    cos and sin carry mscale(yarn_mscale) / mscale(yarn_mscale_all_dim),
    which is 1 where the two are equal (DeepSeek-V2), the only case taken.
    """
    factor = cfg.rope_scaling_factor
    if factor <= 1:
        return None
    if cfg.yarn_mscale != cfg.yarn_mscale_all_dim:
        raise NotImplementedError("YaRN with mscale != mscale_all_dim")
    base = cfg.rope_theta
    f_extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def d(r):
        return (dim * math.log(cfg.rope_original_max_pos / (2 * math.pi * r))
                / (2 * math.log(base)))

    low = max(math.floor(d(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(d(cfg.yarn_beta_slow)), dim - 1)
    if high == low:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return f_extra / factor * ramp + f_extra * (1.0 - ramp)


# ---------------------------------------------------------------------------
# MLPs (SwiGLU / GeGLU / squared-ReLU)
# ---------------------------------------------------------------------------


def mlp_defs(cfg: ModelConfig, d_ff: int | None = None) -> PyTree:
    D = cfg.d_model
    F = d_ff if d_ff is not None else cfg.d_ff
    if cfg.mlp_type in ("swiglu", "geglu"):
        return {
            "w_gate": ParamDef((D, F), ("embed", "ff")),
            "w_up": ParamDef((D, F), ("embed", "ff")),
            "w_down": ParamDef((F, D), ("ff", "embed")),
        }
    if cfg.mlp_type in ("relu2", "gelu"):  # nemotron squared-ReLU / plain GELU
        return {
            "w_up": ParamDef((D, F), ("embed", "ff")),
            "w_down": ParamDef((F, D), ("ff", "embed")),
        }
    raise ValueError(cfg.mlp_type)


def mlp_apply(params: PyTree, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.mlp_type == "swiglu":
        h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    elif cfg.mlp_type == "geglu":
        h = jax.nn.gelu(x @ params["w_gate"], approximate=True) * (x @ params["w_up"])
    elif cfg.mlp_type == "relu2":
        h = jnp.square(jax.nn.relu(x @ params["w_up"]))
    elif cfg.mlp_type == "gelu":
        h = jax.nn.gelu(x @ params["w_up"], approximate=True)
    else:
        raise ValueError(cfg.mlp_type)
    return h @ params["w_down"]


# ---------------------------------------------------------------------------
# Mixture of Experts: dropless, over the experts this chip holds
# ---------------------------------------------------------------------------


def moe_defs(cfg: ModelConfig) -> PyTree:
    D, E, Fe = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    gate_mats = 3 if cfg.mlp_type in ("swiglu", "geglu") else 2
    defs: PyTree = {
        "router": ParamDef((D, cfg.n_router_experts), ("embed", "experts"),
                           scale=0.02),
        "w_gate": ParamDef((E, D, Fe), ("experts", "embed", "expert_ff")),
        "w_up": ParamDef((E, D, Fe), ("experts", "embed", "expert_ff")),
        "w_down": ParamDef((E, Fe, D), ("experts", "expert_ff", "embed")),
    }
    if gate_mats == 2:
        defs.pop("w_gate")
    if cfg.n_shared_experts:
        Fs = cfg.d_ff_expert * cfg.n_shared_experts
        defs["shared"] = {
            "w_gate": ParamDef((D, Fs), ("embed", "ff")),
            "w_up": ParamDef((D, Fs), ("embed", "ff")),
            "w_down": ParamDef((Fs, D), ("ff", "embed")),
        }
    return defs


def moe_apply(
    params: PyTree, cfg: ModelConfig, x: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Top-k routed experts, dropless; returns (out, aux_loss).

    x: (B, L, D). The router scores all ``n_router_experts`` in float32 and
    keeps the top k (softmax; the weights renormalised to sum 1 only with
    ``norm_topk_prob``). This chip holds experts [expert_offset,
    expert_offset + n_experts) and computes the routed part only for the
    (token, expert) pairs whose expert it holds:

        y = sum_{k: e_k held} w_k FFN_{e_k}(x) + SharedFFN(x).

    The pairs are sorted by expert into a buffer of N * min(K, n_experts)
    rows (N = B L), a static bound no token can exceed, so no pair is ever
    dropped and a token's output does not depend on the rest of the batch;
    the expert FFNs run as grouped matmuls over the held groups
    (:func:`grouped_matmul`), and the results are weighted and added back
    to their tokens. Nothing stands in for the experts held elsewhere.
    """
    B, L, D = x.shape
    xf = x.reshape(B * L, D)
    with jax.named_scope("router"):
        topw, topi, aux = _route(params["router"], cfg, xf, B)
    with jax.named_scope("permute"):
        order, group_sizes = _permute(cfg, topi)
        tok = order // cfg.top_k
        xs = xf[tok]
    with jax.named_scope("experts"):
        ys = _expert_ffn(params, cfg, xs, group_sizes)
    with jax.named_scope("unpermute"):
        valid = jnp.arange(order.shape[0]) < jnp.sum(group_sizes)
        w = jnp.where(valid, topw.reshape(-1)[order], 0.0)
        y = jnp.zeros((B * L, D), jnp.float32).at[tok].add(
            ys.astype(jnp.float32) * w[:, None])
        out = y.astype(x.dtype).reshape(B, L, D)
    if cfg.n_shared_experts:
        with jax.named_scope("shared"):
            out = out + _shared_expert(params, cfg, x)
    return out, aux


def _route(router: jax.Array, cfg: ModelConfig, xf: jax.Array, n_seq: int):
    """(top-k weights, top-k expert ids, balance loss) of (N, D) tokens
    that are ``n_seq`` sequences of equal length.

    ``moe_aux='seq'`` (DeepSeek-V2): per sequence f_e = count_e E / (L K)
    and P_e = mean_t prob_e, loss coef * mean_seq sum_e f_e P_e.
    ``'global'`` (Switch): coef * E * sum_e mean_t prob_e * count_e / (N K).
    """
    E, K = cfg.n_router_experts, cfg.top_k
    N = xf.shape[0]
    logits = jnp.dot(xf.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, K)
    if cfg.norm_topk_prob:
        topw = topw / jnp.clip(topw.sum(-1, keepdims=True), 1e-9)
    counts = jnp.sum(jax.nn.one_hot(topi, E, dtype=jnp.float32), axis=1)
    if cfg.moe_aux == "seq":
        L = N // n_seq
        f = counts.reshape(n_seq, L, E).sum(1) * (E / (L * K))
        p = probs.reshape(n_seq, L, E).mean(1)
        aux = cfg.router_aux_coef * jnp.mean(jnp.sum(f * p, -1))
    elif cfg.moe_aux == "global":
        aux = cfg.router_aux_coef * E * jnp.sum(
            probs.mean(0) * counts.sum(0) / (N * K))
    else:
        raise ValueError(cfg.moe_aux)
    return topw, topi, aux


def _permute(cfg: ModelConfig, topi: jax.Array):
    """(order, group_sizes): ``order`` lists the flat (token, k) pair
    indices of the held pairs sorted by expert, then as many others as the
    static bound N * min(K, n_experts) leaves room for; ``group_sizes``
    (n_experts,) counts the held pairs of each expert."""
    N, K = topi.shape
    E = cfg.n_experts
    local = topi.reshape(-1) - cfg.expert_offset
    key = jnp.where((local >= 0) & (local < E), local, E)
    order = jnp.argsort(key, stable=True)[:N * min(K, E)]
    group_sizes = jnp.zeros((E,), jnp.int32).at[key].add(1, mode="drop")
    return order, group_sizes


def _expert_ffn(params: PyTree, cfg: ModelConfig, xs: jax.Array,
                group_sizes: jax.Array) -> jax.Array:
    """(P, D) rows sorted by expert -> (P, D) through each row's expert."""
    if "w_gate" in params:
        act = jax.nn.silu if cfg.mlp_type == "swiglu" else (
            lambda v: jax.nn.gelu(v, approximate=True))
        h = act(grouped_matmul(xs, params["w_gate"], group_sizes)) \
            * grouped_matmul(xs, params["w_up"], group_sizes)
    else:
        h = jnp.square(jax.nn.relu(
            grouped_matmul(xs, params["w_up"], group_sizes)))
    return grouped_matmul(h, params["w_down"], group_sizes)


def _shared_expert(params, cfg: ModelConfig, x):
    sh = params["shared"]
    h = jax.nn.silu(x @ sh["w_gate"]) * (x @ sh["w_up"])
    return h @ sh["w_down"]


# ---------------------------------------------------------------------------
# Grouped matmul (the experts' FFN over rows sorted by expert)
# ---------------------------------------------------------------------------


def grouped_matmul(x: jax.Array, w: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """x (P, K) rows sorted by group, w (G, K, N), group_sizes (G,) int32
    -> (P, N): each row times the weight of the group it falls in; rows
    past sum(group_sizes) come out 0, in the forward and in x's gradient
    (:func:`_zero_tail`).

    ``lax.ragged_dot``, whose TPU form takes no batch dimension: under a
    ``vmap`` (the train step's over workers) the calls run per worker, in a
    ``shard_map`` over the mesh's worker axes where the workers are spread
    over them, else one after another
    (:func:`repro.launch.mesh.per_worker`). The gradient is ragged_dot's
    own, per worker the same way.
    """
    return _gmm(x, w, group_sizes)


@jax.custom_vjp
def _gmm(x, w, group_sizes):
    return _ragged(x, w, group_sizes)


def _gmm_fwd(x, w, group_sizes):
    return _ragged(x, w, group_sizes), (x, w, group_sizes)


def _gmm_bwd(res, g):
    x, w, group_sizes = res
    dx, dw = _ragged_vjp(x, w, group_sizes, g)
    return dx, dw, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def _zero_tail(y, group_sizes):
    """Rows of ``y`` past sum(group_sizes) set to 0: the TPU's ragged dot
    leaves them unwritten, so they hold whatever the buffer held."""
    rows = jnp.arange(y.shape[0])[:, None]
    return jnp.where(rows < jnp.sum(group_sizes), y, jnp.zeros((), y.dtype))


def _ragged_dot(x, w, group_sizes):
    return _zero_tail(jax.lax.ragged_dot(x, w, group_sizes), group_sizes)


def _ragged_dot_vjp(x, w, group_sizes, g):
    _, vjp = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, group_sizes),
                     x, w)
    dx, dw = vjp(g)
    return _zero_tail(dx, group_sizes), dw


@jax.custom_batching.custom_vmap
def _ragged(x, w, group_sizes):
    return _ragged_dot(x, w, group_sizes)


@_ragged.def_vmap
def _ragged_batched(axis_size, in_batched, x, w, group_sizes):
    args = batch_all(axis_size, in_batched, (x, w, group_sizes))
    return per_worker(_ragged_dot, args), True


@jax.custom_batching.custom_vmap
def _ragged_vjp(x, w, group_sizes, g):
    return _ragged_dot_vjp(x, w, group_sizes, g)


@_ragged_vjp.def_vmap
def _ragged_vjp_batched(axis_size, in_batched, x, w, group_sizes, g):
    args = batch_all(axis_size, in_batched, (x, w, group_sizes, g))
    return per_worker(_ragged_dot_vjp, args), (True, True)
