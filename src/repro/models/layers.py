"""Shared layers: norms, rotary embeddings, MLP variants, Mixture-of-Experts.

All modules expose ``<name>_defs(cfg, ...)`` returning a ParamDef pytree and
``<name>_apply(params, cfg, x, ...)``.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
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


def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """Apply rotary embedding.  x: (..., L, H, hd); positions: (..., L)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., L, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


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
# Mixture of Experts (capacity-based scatter dispatch, shared + routed)
# ---------------------------------------------------------------------------


def moe_defs(cfg: ModelConfig) -> PyTree:
    D, E, Fe = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    gate_mats = 3 if cfg.mlp_type in ("swiglu", "geglu") else 2
    defs: PyTree = {
        "router": ParamDef((D, E), ("embed", "experts"), scale=0.02),
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


def _cap_shard(buf: jax.Array) -> jax.Array:
    """Pin the capacity dim of the (E, C, D) expert buffer to 'model' —
    with replicated expert weights the FFN becomes fully local (no TP psum
    on the 2.5x-expanded buffer). §Perf hillclimb B."""
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    if "model" not in mesh.axis_names:
        return buf
    if buf.shape[-2] % mesh.shape["model"]:
        return buf
    return jax.lax.with_sharding_constraint(buf, P(None, "model", None))


def _expert_ffn(params: PyTree, cfg: ModelConfig, xe: jax.Array) -> jax.Array:
    """xe: (E, C, D) -> (E, C, D), batched over experts."""
    if "w_gate" in params:
        act = jax.nn.silu if cfg.mlp_type == "swiglu" else (
            lambda v: jax.nn.gelu(v, approximate=True))
        h = act(jnp.einsum("ecd,edf->ecf", xe, params["w_gate"])) * jnp.einsum(
            "ecd,edf->ecf", xe, params["w_up"])
    else:
        h = jnp.square(jax.nn.relu(jnp.einsum("ecd,edf->ecf", xe, params["w_up"])))
    return jnp.einsum("ecf,efd->ecd", h, params["w_down"])


def moe_apply(
    params: PyTree, cfg: ModelConfig, x: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Top-k routed experts with capacity; returns (out, aux_loss).

    x: (B, L, D).  Dispatch: tokens are scattered into per-expert capacity
    buffers (E, C, D) (overflow drops), expert FFNs run batched, outputs are
    gathered back weighted by the router probabilities.  Sharding the expert
    dim over "model" yields expert parallelism (the scatter/gather lower to
    all-to-all on the mesh); when E doesn't divide the mesh axis the ff dim
    is sharded instead (tensor parallel experts) — see params.resolve_spec.

    moe_dispatch='per_sequence' dispatches within each sequence independently
    (capacity per sequence): scatter/gather indices never cross the batch dim,
    so a batch-sharded mesh never all-gathers the token buffers — the fix for
    the collective-bound MoE prefill found in EXPERIMENTS.md §Perf.
    """
    dispatch = getattr(cfg, "moe_dispatch", "global")
    if dispatch == "per_sequence_smap":
        # Partial-manual shard_map over the batch axes: dispatch gathers are
        # device-local by construction (XLA SPMD replicates batched gathers
        # otherwise — §Perf hillclimb B it3). Expert weights stay 'model'-auto.
        from jax.sharding import PartitionSpec as P

        mesh = jax.sharding.get_abstract_mesh()
        wa = tuple(a for a in mesh.axis_names if a != "model")
        n_shards = 1
        for a in wa:
            n_shards *= mesh.shape[a]
        if wa and x.shape[0] % n_shards == 0 and n_shards > 1:
            spec = P(wa[0] if len(wa) == 1 else wa, None, None)

            def f(xb):
                y, aux = jax.vmap(lambda s: _moe_tokens(params, cfg, s))(xb)
                return y, jax.lax.pmean(aux.mean(), wa)

            y, aux = jax.shard_map(f, mesh=mesh, in_specs=(spec,),
                                      out_specs=(spec, P()),
                                      axis_names=set(wa))(x)
            if cfg.n_shared_experts:
                y = y + _shared_expert(params, cfg, x)
            return y, aux
        dispatch = "per_sequence"  # fallback: no mesh / indivisible batch
    if dispatch == "per_sequence":
        y, aux = jax.vmap(lambda xb: _moe_tokens(params, cfg, xb))(x)
        out = y
        if cfg.n_shared_experts:
            out = out + _shared_expert(params, cfg, x)
        return out, aux.mean()
    B, L, D = x.shape
    out, aux = _moe_tokens(params, cfg, x.reshape(B * L, D))
    out = out.reshape(B, L, D)
    if cfg.n_shared_experts:
        out = out + _shared_expert(params, cfg, x)
    return out, aux


def _shared_expert(params, cfg: ModelConfig, x):
    sh = params["shared"]
    h = jax.nn.silu(x @ sh["w_gate"]) * (x @ sh["w_up"])
    return h @ sh["w_down"]


def _moe_tokens(params: PyTree, cfg: ModelConfig, xf: jax.Array):
    """Routed-expert compute over a flat token matrix xf: (N, D)."""
    N, D = xf.shape
    E, K = cfg.n_experts, cfg.top_k
    logits = (xf @ params["router"]).astype(jnp.float32)  # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, K)                  # (N, K)
    topw = topw / jnp.clip(topw.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch-style)
    me = probs.mean(0)                                    # mean prob per expert
    ce = jnp.zeros((E,), jnp.float32).at[topi.reshape(-1)].add(1.0) / (N * K)
    aux = E * jnp.sum(me * ce) * cfg.router_aux_coef

    capacity = int(np.ceil(N * K / E * cfg.capacity_factor))
    # position of each (token, k) within its expert queue
    onehot = jax.nn.one_hot(topi, E, dtype=jnp.int32)     # (N, K, E)
    flat_oh = onehot.reshape(N * K, E)
    pos_in_e = (jnp.cumsum(flat_oh, axis=0) - flat_oh)    # (N*K, E)
    pos = (pos_in_e * flat_oh).sum(-1).reshape(N, K)      # (N, K)
    keep = pos < capacity
    slot = jnp.where(keep, topi * capacity + pos, E * capacity)  # overflow bin

    # Scatter only token INDICES into the slot table (D-free, int32 — tiny),
    # then fetch values with a gather: batched value-scatters force XLA SPMD
    # to all-gather the (E·C, D) buffer over the batch axis; batched gathers
    # partition cleanly (EXPERIMENTS.md §Perf hillclimb B).
    inv = jnp.full((E * capacity + 1,), N, jnp.int32)
    for k in range(K):
        inv = inv.at[slot[:, k]].set(jnp.arange(N, dtype=jnp.int32))
    xf_pad = jnp.concatenate([xf, jnp.zeros((1, D), xf.dtype)], axis=0)
    buf = xf_pad[inv[:-1]].reshape(E, capacity, D)
    if cfg.moe_shard == "capacity":
        buf = _cap_shard(buf)
    out_e = _expert_ffn(params, cfg, buf)
    out_flat = jnp.concatenate(
        [out_e.reshape(E * capacity, D), jnp.zeros((1, D), xf.dtype)], axis=0
    )
    y = jnp.zeros((N, D), xf.dtype)
    for k in range(K):
        y = y + out_flat[slot[:, k]] * (topw[:, k] * keep[:, k].astype(jnp.float32))[:, None].astype(xf.dtype)
    return y, aux
