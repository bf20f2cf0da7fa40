"""Model assembly: decoder-only and encoder-decoder transformers, SSM and
hybrid stacks, built from per-layer modules with scan-over-layers.

Public API (all pure functions over a params pytree):
  model_defs(cfg)                      -> ParamDef pytree
  init(key, cfg)                       -> params
  loss_fn(params, cfg, batch)          -> scalar  (next-token CE [+ MoE aux])
  prefill(params, cfg, tokens, ...)    -> (last logits, caches, cross_kvs, memory)
  decode_step(params, cfg, caches, tok)-> (logits, caches)

Layers with identical (kind, moe) signature are grouped into segments; a
segment is executed with ``lax.scan`` over stacked params (+ optional remat),
keeping the HLO size independent of depth — required for the 96-layer
nemotron dry-run at 512 devices.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.flash_attention import ops as flash_ops
from repro.models import attention as attn_lib
from repro.models import layers as L
from repro.models import rglru as rglru_lib
from repro.models import ssm as ssm_lib
from repro.models.params import ParamDef, init_tree

PyTree = Any
# remat keeps only the flash kernel's output and logsumexp (where a layer
# ran it): the backward then runs no second forward kernel
_remat = functools.partial(
    jax.checkpoint,
    policy=jax.checkpoint_policies.save_only_these_names(
        flash_ops.RESIDUAL_NAME))


# ---------------------------------------------------------------------------
# Segments
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Segment:
    kind: str          # attn | local | ssm | rglru
    moe: bool
    length: int
    scanned: bool


def plan_segments(cfg: ModelConfig) -> list[Segment]:
    kinds = cfg.layer_kinds
    moes = cfg.moe_layer_flags
    segs: list[Segment] = []
    i = 0
    while i < cfg.n_layers:
        j = i
        while j < cfg.n_layers and kinds[j] == kinds[i] and moes[j] == moes[i]:
            j += 1
        n = j - i
        segs.append(Segment(kinds[i], moes[i], n, scanned=cfg.scan_layers and n > 1))
        i = j
    return segs


def _self_window(cfg: ModelConfig, kind: str) -> int | None:
    if kind == "local":
        return cfg.window
    if cfg.window and cfg.arch_type != "hybrid":
        return cfg.window  # e.g. mixtral: SWA on every layer
    return None


# ---------------------------------------------------------------------------
# Param defs
# ---------------------------------------------------------------------------


def _block_defs(cfg: ModelConfig, kind: str, moe: bool, cross: bool) -> PyTree:
    d: PyTree = {"norm1": L.rmsnorm_defs(cfg.d_model)}
    if kind in ("attn", "local"):
        d["mix"] = attn_lib.mla_defs(cfg) if cfg.attention_type == "mla" \
            else attn_lib.gqa_defs(cfg)
    elif kind == "ssm":
        d["mix"] = ssm_lib.mamba2_defs(cfg)
    elif kind == "rglru":
        d["mix"] = rglru_lib.rglru_defs(cfg)
    else:
        raise ValueError(kind)
    if cross:
        d["norm_cross"] = L.rmsnorm_defs(cfg.d_model)
        d["cross"] = attn_lib.gqa_defs(cfg)
    if kind != "ssm":  # mamba2 stacks have no MLP (d_ff = 0)
        d["norm2"] = L.rmsnorm_defs(cfg.d_model)
        d["mlp"] = L.moe_defs(cfg) if moe else L.mlp_defs(cfg)
    return d


def _stack_defs(defs: PyTree, n: int) -> PyTree:
    return jax.tree.map(
        lambda p: ParamDef((n,) + p.shape, ("layers",) + p.axes, p.init, p.scale),
        defs,
        is_leaf=lambda x: isinstance(x, ParamDef),
    )


def _encoder_block_defs(cfg: ModelConfig) -> PyTree:
    return {
        "norm1": L.rmsnorm_defs(cfg.d_model),
        "mix": attn_lib.gqa_defs(cfg),
        "norm2": L.rmsnorm_defs(cfg.d_model),
        "mlp": L.mlp_defs(cfg),
    }


def model_defs(cfg: ModelConfig) -> PyTree:
    cross = cfg.encoder_layers > 0
    segs = plan_segments(cfg)
    layer_defs = []
    for s in segs:
        bd = _block_defs(cfg, s.kind, s.moe, cross)
        layer_defs.append(
            _stack_defs(bd, s.length) if s.scanned
            else [_block_defs(cfg, s.kind, s.moe, cross) for _ in range(s.length)])
    d: PyTree = {
        # 'embed_table' logical axis: the table's d_model dim is never sharded
        # (fsdp sharding it forces involuntary remat on the token gather)
        "embed": ParamDef((cfg.vocab_size, cfg.d_model), ("vocab", "embed_table"), scale=0.02),
        "segments": layer_defs,
        "out_norm": L.rmsnorm_defs(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        d["lm_head"] = ParamDef((cfg.d_model, cfg.vocab_size), ("embed", "vocab"), scale=0.02)
    if cfg.encoder_layers:
        enc = _encoder_block_defs(cfg)
        d["encoder"] = {
            "layers": _stack_defs(enc, cfg.encoder_layers)
                      if cfg.scan_layers and cfg.encoder_layers > 1
                      else [_encoder_block_defs(cfg) for _ in range(cfg.encoder_layers)],
            "out_norm": L.rmsnorm_defs(cfg.d_model),
        }
    return d


def init(key: jax.Array, cfg: ModelConfig) -> PyTree:
    return init_tree(key, model_defs(cfg), jnp.dtype(cfg.param_dtype))


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _block_apply(bp: PyTree, cfg: ModelConfig, kind: str, moe: bool, x,
                 q_base, cache, memory, cross_kv, lengths=None,
                 prompt_len=None):
    """One residual block. cache / cross_kv may be None (training).

    lengths (B,) + prompt_len mark right-padded ragged prompts: attention
    masks the pad keys and offsets per-row rope positions. Recurrent kinds
    (ssm/rglru) carry pad tokens through their state, so ragged batches are
    rejected — equal-length batching (WaveBatcher) remains their path.
    """
    aux = jnp.zeros((), jnp.float32)
    h = L.rmsnorm_apply(bp["norm1"], x, cfg.norm_eps)
    window = _self_window(cfg, kind)
    parallel = cfg.parallel_block and "mlp" in bp and "cross" not in bp \
        and kind in ("attn", "local")
    if kind in ("attn", "local"):
        with jax.named_scope("attention"):
            if cfg.attention_type == "mla":
                mixed, new_c = attn_lib.mla_apply(
                    bp["mix"], cfg, h, q_base=q_base, cache=cache,
                    lengths=lengths, prompt_len=prompt_len)
            else:
                mixed, new_c = attn_lib.gqa_apply(
                    bp["mix"], cfg, h, q_base=q_base, causal=True,
                    window=window, cache=cache, lengths=lengths,
                    prompt_len=prompt_len)
    elif kind == "ssm":
        if lengths is not None:
            raise NotImplementedError(
                "ragged prompts pollute mamba2 recurrent state; batch "
                "equal-length prompts instead")
        with jax.named_scope("ssd"):
            mixed, new_c = ssm_lib.mamba2_apply(bp["mix"], cfg, h,
                                                cache=cache)
    elif kind == "rglru":
        if lengths is not None:
            raise NotImplementedError(
                "ragged prompts pollute rglru recurrent state; batch "
                "equal-length prompts instead")
        mixed, new_c = rglru_lib.rglru_apply(bp["mix"], cfg, h, cache=cache)
    else:
        raise ValueError(kind)

    if parallel:
        # PaLM-style parallel block: attn and MLP read the same residual input
        # and their (row-parallel) outputs sum before the single TP all-reduce.
        h2 = L.rmsnorm_apply(bp["norm2"], x, cfg.norm_eps)
        y, aux = _mlp(bp["mlp"], cfg, h2, moe)
        return x + mixed + y, new_c, aux

    x = x + mixed

    if "cross" in bp and memory is not None:
        hc = L.rmsnorm_apply(bp["norm_cross"], x, cfg.norm_eps)
        if cross_kv is not None:
            ck, cv = cross_kv
            q = jnp.einsum("bld,dhk->blhk", hc, bp["cross"]["wq"])
            o = attn_lib.dense_attention(
                q, ck, cv, jnp.arange(hc.shape[1]), jnp.arange(ck.shape[1]),
                causal=False)
            cmix = jnp.einsum("blhk,hkd->bld", o, bp["cross"]["wo"])
        else:
            cmix, _ = attn_lib.gqa_apply(bp["cross"], cfg, hc, causal=False,
                                         memory=memory)
        x = x + cmix

    if "mlp" in bp:
        h2 = L.rmsnorm_apply(bp["norm2"], x, cfg.norm_eps)
        y, aux = _mlp(bp["mlp"], cfg, h2, moe)
        x = x + y
    return x, new_c, aux


def _mlp(mp: PyTree, cfg: ModelConfig, h, moe: bool):
    """(output, moe aux loss) of the block's feed-forward."""
    with jax.named_scope("mlp"):
        if moe:
            return L.moe_apply(mp, cfg, h)
        return L.mlp_apply(mp, cfg, h), jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _embed(params, cfg: ModelConfig, tokens):
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(jnp.dtype(cfg.compute_dtype))
        if cfg.emb_scale:
            x = x * float(np.sqrt(cfg.d_model))  # weak-typed: keeps dtype
        return x


def encode(params, cfg: ModelConfig, enc_embeds: jax.Array) -> jax.Array:
    """Encoder over precomputed frontend embeddings (audio stub input)."""
    x = enc_embeds.astype(jnp.dtype(cfg.compute_dtype))
    enc = params["encoder"]

    def body(x, bp):
        h = L.rmsnorm_apply(bp["norm1"], x, cfg.norm_eps)
        mixed, _ = attn_lib.gqa_apply(bp["mix"], cfg, h, causal=False)
        x = x + mixed
        h2 = L.rmsnorm_apply(bp["norm2"], x, cfg.norm_eps)
        return x + L.mlp_apply(bp["mlp"], cfg, h2), None

    if isinstance(enc["layers"], list):
        for bp in enc["layers"]:
            x, _ = body(x, bp)
    else:
        fn = _remat(body) if cfg.remat else body
        x, _ = jax.lax.scan(fn, x, enc["layers"])
    return L.rmsnorm_apply(enc["out_norm"], x, cfg.norm_eps)


def _act_shard(x, cfg: ModelConfig):
    """Optional activation sharding pin (cfg.shard_activations; §Perf lever).

    'model' / True — shard d_model over 'model' (sequence-parallel-style);
    'batch'        — pin the batch dim over the worker axes (canonical FSDP:
                     stops XLA from re-sharding activations inside the layer
                     scan and forces per-layer weight gathering instead).
    Never used under the gossip vmap.
    """
    mode = cfg.shard_activations
    if not mode:
        return x
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "model" not in mesh.axis_names:
        return x
    if mode == "batch":
        wa = tuple(a for a in mesh.axis_names if a != "model")
        n = 1
        for a in wa:
            n *= mesh.shape[a]
        if not wa or x.shape[0] % n:
            return x
        return jax.lax.with_sharding_constraint(
            x, P(wa[0] if len(wa) == 1 else wa,
                 *([P.UNCONSTRAINED] * (x.ndim - 1))))
    if x.shape[-1] % mesh.shape["model"]:
        return x
    return jax.lax.with_sharding_constraint(
        x, P(*([P.UNCONSTRAINED] * (x.ndim - 1)), "model"))


def forward(params, cfg: ModelConfig, tokens, *, q_base: int = 0,
            caches: list | None = None, memory: jax.Array | None = None,
            cross_kvs: list | None = None, lengths=None,
            prompt_len: int | None = None):
    """Decoder forward. Returns (hidden, new_caches, moe_aux)."""
    x = _embed(params, cfg, tokens)
    x = _act_shard(x, cfg)
    segs = plan_segments(cfg)
    new_caches: list = []
    aux_total = jnp.zeros((), jnp.float32)
    for si, (seg, sp) in enumerate(zip(segs, params["segments"])):
        cache_s = caches[si] if caches is not None else None
        ckv_s = cross_kvs[si] if cross_kvs is not None else None
        if not seg.scanned:
            seg_new = []
            for li in range(seg.length):
                fn = functools.partial(_block_apply, cfg=cfg, kind=seg.kind, moe=seg.moe)
                if cfg.remat:
                    fn = _remat(
                        lambda bp, x, c, k, _f=fn: _f(bp, x=x, q_base=q_base,
                                                      cache=c, memory=memory,
                                                      cross_kv=k, lengths=lengths,
                                                      prompt_len=prompt_len))
                    x, nc, aux = fn(sp[li], x,
                                    cache_s[li] if cache_s is not None else None,
                                    ckv_s[li] if ckv_s is not None else None)
                else:
                    x, nc, aux = fn(sp[li], x=x, q_base=q_base,
                                    cache=cache_s[li] if cache_s is not None else None,
                                    memory=memory,
                                    cross_kv=ckv_s[li] if ckv_s is not None else None,
                                    lengths=lengths, prompt_len=prompt_len)
                aux_total = aux_total + aux
                seg_new.append(nc)
            new_caches.append(seg_new)
        else:
            has_cache = cache_s is not None
            has_ckv = ckv_s is not None

            def body(carry, inp):
                x, auxc = carry
                bp = inp[0]
                c = inp[1] if has_cache else None
                k = (inp[2] if has_cache else inp[1]) if has_ckv else None
                xo, nc, aux = _block_apply(bp, cfg, seg.kind, seg.moe, x,
                                           q_base, c, memory, k, lengths,
                                           prompt_len)
                xo = _act_shard(xo, cfg)
                return (xo, auxc + aux), nc

            xs: tuple = (sp,)
            if has_cache:
                xs = xs + (cache_s,)
            if has_ckv:
                xs = xs + (ckv_s,)
            fn = _remat(body) if cfg.remat else body
            (x, aux_total), ncs = jax.lax.scan(fn, (x, aux_total), xs)
            new_caches.append(ncs)
    h = L.rmsnorm_apply(params["out_norm"], x, cfg.norm_eps)
    return h, new_caches, aux_total


def routing_counts(params, cfg: ModelConfig, tokens) -> jax.Array:
    """(expert layers, n_experts) int32: the (token, expert) pairs each
    expert this chip holds is routed, per expert layer, for (B, L) tokens
    (the forward without its loss; ``train(routing=...)`` emits them)."""
    x = _embed(params, cfg, tokens)
    B = tokens.shape[0]
    counts = []
    for seg, sp in zip(plan_segments(cfg), params["segments"]):
        layers = [layer_params(sp, i) for i in range(seg.length)]
        for bp in layers:
            if not seg.moe:
                x, _, _ = _block_apply(bp, cfg, seg.kind, False, x, 0, None,
                                       None, None)
                continue
            h = L.rmsnorm_apply(bp["norm1"], x, cfg.norm_eps)
            mixed, _ = attn_lib.mla_apply(bp["mix"], cfg, h) \
                if cfg.attention_type == "mla" else attn_lib.gqa_apply(
                    bp["mix"], cfg, h, causal=True,
                    window=_self_window(cfg, seg.kind))
            x = x + mixed
            h = L.rmsnorm_apply(bp["norm2"], x, cfg.norm_eps)
            _, topi, _ = L._route(bp["mlp"]["router"], cfg,
                                  h.reshape(-1, cfg.d_model), B)
            counts.append(L._permute(cfg, topi)[1])
            x = x + L.moe_apply(bp["mlp"], cfg, h)[0]
    return jnp.stack(counts) if counts else jnp.zeros((0, cfg.n_experts),
                                                      jnp.int32)


def layer_params(seg, i: int):
    """Layer ``i`` of a segment: stacked leaves (scanned) or a list."""
    if isinstance(seg, (list, tuple)):
        return seg[i]
    return jax.tree.map(lambda x: x[i], seg)


def logits_from_hidden(params, cfg: ModelConfig, h: jax.Array) -> jax.Array:
    W = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("bld,dv->blv", h, W.astype(h.dtype),
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Loss (chunked over sequence: never materializes (B, L, V) logits)
# ---------------------------------------------------------------------------


def cross_entropy_chunked(params, cfg: ModelConfig, h, labels,
                          n_chunks: int = 8) -> jax.Array:
    with jax.named_scope("lm_head"):
        return _cross_entropy_chunked(params, cfg, h, labels, n_chunks)


def _cross_entropy_chunked(params, cfg, h, labels, n_chunks):
    B, Ltot, D = h.shape
    n_chunks = min(n_chunks, Ltot)
    while Ltot % n_chunks:
        n_chunks -= 1
    ck = Ltot // n_chunks
    W = params["embed"].T if cfg.tie_embeddings else params["lm_head"]

    def body(tot, i):
        hs = jax.lax.dynamic_slice_in_dim(h, i * ck, ck, 1)
        ls = jax.lax.dynamic_slice_in_dim(labels, i * ck, ck, 1)
        logits = jnp.einsum("bld,dv->blv", hs, W.astype(hs.dtype),
                            preferred_element_type=jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ls[..., None], axis=-1)[..., 0]
        return tot + jnp.sum(logz - gold), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), jnp.arange(n_chunks))
    return total / (B * Ltot)


def loss_fn(params, cfg: ModelConfig, batch: PyTree) -> jax.Array:
    """Next-token CE.

    batch: {"tokens": (B, L) [, "labels": (B, L)] [, "enc_embeds": (B, Ls, D)]}.
    With explicit labels the model runs over the full L tokens; otherwise the
    shift happens internally (tokens[:-1] -> tokens[1:]).
    """
    tokens = batch["tokens"]
    memory = encode(params, cfg, batch["enc_embeds"]) if cfg.encoder_layers else None
    labels = batch.get("labels")
    if labels is None:
        tokens, labels = tokens[:, :-1], tokens[:, 1:]
    h, _, aux = forward(params, cfg, tokens, memory=memory)
    return cross_entropy_chunked(params, cfg, h, labels) + aux


# ---------------------------------------------------------------------------
# Caches / serving
# ---------------------------------------------------------------------------


def _layer_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int, dtype):
    if kind in ("attn", "local"):
        if cfg.attention_type == "mla":
            return attn_lib.init_mla_cache(cfg, batch, max_len, dtype)
        return attn_lib.init_kv_cache(cfg, batch, max_len, _self_window(cfg, kind), dtype)
    if kind == "ssm":
        return ssm_lib.init_mamba_cache(cfg, batch, dtype)
    if kind == "rglru":
        return rglru_lib.init_rglru_cache(cfg, batch, dtype)
    raise ValueError(kind)


def init_cache(params, cfg: ModelConfig, batch: int, max_len: int):
    """Per-layer caches (stacked along the scan dim for scanned segments)."""
    dtype = jnp.dtype(cfg.compute_dtype)
    segs = plan_segments(cfg)
    caches = []
    for seg in segs:
        one = _layer_cache(cfg, seg.kind, batch, max_len, dtype)
        if seg.scanned:
            caches.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (seg.length,) + x.shape), one))
        else:
            caches.append([_layer_cache(cfg, seg.kind, batch, max_len, dtype)
                           for _ in range(seg.length)])
    return caches


def precompute_cross_kv(params, cfg: ModelConfig, memory: jax.Array):
    """Cross-attention K/V per decoder layer, computed once from the encoder
    memory (enc-dec serving)."""
    segs = plan_segments(cfg)
    out = []
    for seg, sp in zip(segs, params["segments"]):
        def kv(bp):
            k = jnp.einsum("bld,dhk->blhk", memory, bp["cross"]["wk"])
            v = jnp.einsum("bld,dhk->blhk", memory, bp["cross"]["wv"])
            return (k, v)
        if seg.scanned:
            out.append(jax.lax.map(kv, sp))
        else:
            out.append([kv(bp) for bp in sp])
    return out


def prefill(params, cfg: ModelConfig, tokens, max_len: int | None = None,
            enc_embeds=None, lengths=None):
    """Run the prompt, building caches; returns logits of the last position.

    With ``lengths`` (B,), tokens are RIGHT-padded ragged prompts: pad keys
    are masked out of attention and the returned logits are gathered at each
    row's last *real* position (column lengths[b]-1), not the pad tail.
    """
    B, Lp = tokens.shape
    max_len = max_len or Lp
    memory = encode(params, cfg, enc_embeds) if cfg.encoder_layers else None
    cross_kvs = precompute_cross_kv(params, cfg, memory) if memory is not None else None
    caches = init_cache(params, cfg, B, max_len)
    h, new_caches, _ = forward(params, cfg, tokens, caches=caches,
                               memory=memory, cross_kvs=cross_kvs,
                               lengths=lengths, prompt_len=Lp)
    if lengths is not None:
        h_last = h[jnp.arange(B), lengths - 1][:, None, :]
    else:
        h_last = h[:, -1:]
    logits = logits_from_hidden(params, cfg, h_last)
    return logits, new_caches, cross_kvs, memory


def decode_step(params, cfg: ModelConfig, caches, token, *, memory=None,
                cross_kvs=None, lengths=None, prompt_len: int | None = None):
    """One decode step. token: (B, 1) int32 → (logits (B, 1, V), new caches).

    lengths/prompt_len continue a ragged prefill: rope positions per row run
    lengths[b], lengths[b]+1, ... and the original pad columns stay masked.
    Omit both when decoding against a paged cache — per-slot positions come
    from the cache's own lengths.
    """
    h, new_caches, _ = forward(params, cfg, token, caches=caches,
                               memory=memory, cross_kvs=cross_kvs,
                               lengths=lengths, prompt_len=prompt_len)
    return logits_from_hidden(params, cfg, h), new_caches
