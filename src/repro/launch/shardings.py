"""Sharding rules: params, batches, and KV caches → PartitionSpecs.

Every function here consumes a :class:`~repro.launch.mesh.WorkerMesh` (raw
meshes are accepted and factored on entry): worker axes host the gossip
workers, the model axis shards each worker's replica.

Param-spec modes:
  gossip    — every param leaf gets a leading worker dim sharded over the
              worker axes; within a worker the model axis shards heads/ff/
              vocab (tensor/FSDP-sharded replicas — shard factor k). These
              specs double as the bus's ``param_specs``: gossip mixes per
              model shard, so the technique stays ON when a replica no
              longer fits one device. Leaves whose logical axes do NOT
              divide by k (MQA/GQA kv heads, small norms/biases) fall back
              to replicated *storage* here — but they no longer replicate on
              the gossip bus: layout v2 row-splits every such leaf over the
              model axis by flat-buffer rows (:func:`bus_row_split_flags`),
              so the old replicated-leaf carve-out costs zero inter-worker
              bytes.
  allreduce — params replicated over worker axes (centralized baseline).
  fsdp      — serving-side layout for huge checkpoints: no worker dim, the
              `embed` (d_model) logical axis additionally sharded over the
              worker axes. No longer a *training* mode (the retired
              technique-off fallback) — decode/prefill of nemotron-scale
              archs still uses it to spread one replica over the whole mesh.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.launch.mesh import WorkerMesh
from repro.models import model as M
from repro.models.params import DEFAULT_RULES, tree_specs

PyTree = Any


def param_pspecs(cfg: ModelConfig, mesh, mode: str | None = None,
                 worker_internal: str = "tp") -> PyTree:
    """worker_internal (gossip mode only):
      'tp' — each worker tensor-parallelizes its replica over 'model' (default);
      'dp' — each worker REPLICATES its params over 'model' and splits its
             local batch instead (§Perf hillclimb: removes per-layer TP
             activation all-reduces; one gradient psum per step remains).
    """
    wm = WorkerMesh.ensure(mesh)
    mode = mode or cfg.dp_mode
    defs = M.model_defs(cfg)
    if mode == "gossip":
        if worker_internal == "dp":
            rules = {k: None for k in DEFAULT_RULES}
            return tree_specs(defs, rules=rules, mesh=wm.mesh,
                              prefix_axes=(wm.wa,))
        # 'tp' and 'fsdp' share param storage sharding (heads/ff/vocab over
        # 'model'); they differ in the batch spec — with the batch split over
        # 'model' too, XLA gathers the (smaller) weights per layer instead of
        # all-reducing activations: FSDP-within-worker (§Perf hillclimb A).
        return tree_specs(defs, mesh=wm.mesh, prefix_axes=(wm.wa,))
    if mode == "allreduce":
        return tree_specs(defs, mesh=wm.mesh)
    if mode == "fsdp":
        rules = dict(DEFAULT_RULES)
        rules["embed"] = wm.wa              # shard d_model over worker axes
        return tree_specs(defs, rules=rules, mesh=wm.mesh)
    raise ValueError(mode)


def bus_row_split_flags(param_specs: PyTree, mesh) -> PyTree:
    """Which leaves the gossip bus row-splits over the model axis.

    Returns a bool pytree mirroring ``param_specs``: True for leaves whose
    spec does NOT shard over the WorkerMesh's model axis — exactly the
    leaves the pre-v2 bus shipped fully replicated through every bulk
    ppermute, and that layout v2 instead assigns a 1/k row range of the flat
    buffer (`repro.core.bus.plan_layout` pass 2). Diagnostic/benchmark
    helper; the bus derives the same flags internally from ``param_specs``.
    """
    from jax.sharding import PartitionSpec as P

    from repro.core.bus import sharded_leaf_flags

    wm = WorkerMesh.ensure(mesh)
    ma = wm.model_axis if wm is not None and wm.model_factor > 1 else None
    is_p = lambda s: s is None or isinstance(s, P)
    leaves, treedef = jax.tree_util.tree_flatten(param_specs, is_leaf=is_p)
    if ma is None:  # k == 1: every leaf packs whole — nothing row-splits
        return jax.tree_util.tree_unflatten(treedef, [False] * len(leaves))
    flags = sharded_leaf_flags(leaves, ma)
    return jax.tree_util.tree_unflatten(treedef, [not f for f in flags])


def state_pspecs(cfg: ModelConfig, mesh, opt_state_like: PyTree,
                 params_spec: PyTree) -> PyTree:
    """TrainState(step, params, opt_state) specs; momentum mirrors params."""
    from repro.core.decentralized import TrainState

    # momentum_sgd state mirrors params; adam state is {"m":..., "v":...}
    if isinstance(opt_state_like, dict) and set(opt_state_like) == {"m", "v"}:
        opt_spec_tree = {"m": params_spec, "v": params_spec}
    elif opt_state_like == ():
        opt_spec_tree = ()
    else:
        opt_spec_tree = params_spec
    return TrainState(P(), params_spec, opt_spec_tree)


def batch_pspecs(cfg: ModelConfig, mesh, kind: str, mode: str,
                 worker_internal: str = "tp") -> PyTree:
    wa = WorkerMesh.ensure(mesh).wa
    specs = {}
    if mode == "gossip" and kind == "train":
        # worker_internal 'dp'/'fsdp': split the per-worker batch over 'model'
        b_ax = "model" if worker_internal in ("dp", "fsdp") else None
        specs["tokens"] = P(wa, b_ax, None)      # (M, b, L)
        specs["labels"] = P(wa, b_ax, None)
        if cfg.encoder_layers:
            specs["enc_embeds"] = P(wa, b_ax, None, None)
    else:
        specs["tokens"] = P(wa, None)            # (B, L)
        if kind == "train":
            specs["labels"] = P(wa, None)
        if cfg.encoder_layers:
            specs["enc_embeds"] = P(wa, None, None)
    return specs


def _div(n: int, mesh, axis) -> Any:
    """axis if n divides the mesh axis size (tuple axes = product)."""
    shape = WorkerMesh.ensure(mesh).shape
    names = axis if isinstance(axis, tuple) else (axis,)
    total = int(np.prod([shape[a] for a in names]))
    return axis if (total > 1 and n % total == 0) else None


def cache_pspecs(cfg: ModelConfig, mesh, batch: int) -> PyTree:
    """Specs mirroring model.init_cache structure (incl. scan-stacked dims)."""
    from repro.models.attention import KVCache, MLACache
    from repro.models.rglru import RGLRUCache
    from repro.models.ssm import MambaCache

    wm = WorkerMesh.ensure(mesh)
    mesh, wa = wm, wm.wa
    b_ax = _div(batch, mesh, wa)

    def kv_spec():
        # prefer sharding kv heads over 'model'; if indivisible (GQA kv=8 on a
        # 16-way model axis) shard the SEQUENCE dim instead — attention then
        # reduces over the sharded kv length (sequence-sharded KV cache)
        h_ax = _div(cfg.n_kv_heads, mesh, "model")
        s_ax = "model" if h_ax is None else None
        return KVCache(P(b_ax, s_ax, h_ax, None), P(b_ax, s_ax, h_ax, None), P())

    def mla_spec():
        # compressed cache has no head dim: shard the sequence dim
        return MLACache(P(b_ax, "model", None), P(b_ax, "model", None), P())

    def mamba_spec():
        conv_dim = cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state
        return MambaCache(
            P(b_ax, None, _div(conv_dim, mesh, "model")),
            P(b_ax, _div(cfg.ssm_nheads, mesh, "model"), None, None), P())

    def rglru_spec():
        W = cfg.lru_width or cfg.d_model
        w_ax = _div(W, mesh, "model")
        return RGLRUCache(P(b_ax, None, w_ax), P(b_ax, w_ax), P())

    def one(kind: str):
        if kind in ("attn", "local"):
            return mla_spec() if cfg.attention_type == "mla" else kv_spec()
        if kind == "ssm":
            return mamba_spec()
        if kind == "rglru":
            return rglru_spec()
        raise ValueError(kind)

    segs = M.plan_segments(cfg)
    out = []
    for seg in segs:
        spec = one(seg.kind)
        if seg.scanned:
            spec = jax.tree.map(lambda p: P(None, *p), spec,
                                is_leaf=lambda x: isinstance(x, P))
        else:
            spec = [one(seg.kind) for _ in range(seg.length)]
        out.append(spec)
    return out


def cross_kv_pspecs(cfg: ModelConfig, mesh, batch: int) -> PyTree:
    wa = WorkerMesh.ensure(mesh).wa
    b_ax = _div(batch, mesh, wa)
    h_ax = _div(cfg.n_kv_heads, mesh, "model")
    segs = M.plan_segments(cfg)
    out = []
    for seg in segs:
        pair = (P(b_ax, None, h_ax, None), P(b_ax, None, h_ax, None))
        if seg.scanned:
            pair = jax.tree.map(lambda p: P(None, *p), pair,
                                is_leaf=lambda x: isinstance(x, P))
            out.append(pair)
        else:
            out.append([pair for _ in range(seg.length)])
    return out
