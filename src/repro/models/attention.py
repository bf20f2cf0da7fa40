"""Attention variants: GQA/MQA/MHA, MLA (DeepSeek-V2), sliding-window/local.

Causal self-attention over a whole number of kernel blocks runs, on TPU,
in the Pallas flash kernel of ``repro.kernels.flash_attention``, forward
and backward (training and prefill; :func:`takes_flash_kernel`). Everything
else, and every run off the TPU, takes the XLA paths here: dense scores for
short kv, and for long kv a blockwise online-softmax formulation (the flash
algorithm in pure JAX) that never materializes the quadratic score matrix.
The blockwise twin is also the kernel's numerical oracle.

KV caches:
  * full cache (B, S_max, K, hd) with insertion position,
  * ring cache (B, W, K, hd) for sliding-window archs — bounded state, enables
    the long_500k decode shape,
  * MLA compressed cache (B, S_max, kv_lora + rope_dim).
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.flash_attention import ops as flash_ops
from repro.models.layers import (rmsnorm_apply, rmsnorm_defs, rope,
                                 rope_inv_freq, yarn_mscale)
from repro.models.params import ParamDef

PyTree = Any
NEG_INF = -1e30
# float32 score blocks a blockwise attention's backward may keep, in all
REMAT_SCORES_BYTES = 1 << 30


# ---------------------------------------------------------------------------
# Core scaled-dot-product attention (dense + blockwise)
# ---------------------------------------------------------------------------


def _mask_bias(q_pos, kv_pos, *, causal: bool, window: int | None) -> jax.Array:
    """(Lq, Lkv) additive bias from absolute positions."""
    qp = q_pos[:, None]
    kp = kv_pos[None, :]
    ok = jnp.ones(qp.shape[:1] + kp.shape[1:], bool)
    if causal:
        ok &= kp <= qp
    if window is not None:
        ok &= kp > qp - window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def repeat_kv(x: jax.Array, H: int) -> jax.Array:
    """(B, L, Kh, hd) -> (B, L, H, hd).

    Explicit head repetition keeps the q-head mesh sharding intact through
    attention (a (Kh, G) reshape of a 16-way-sharded head dim silently
    degrades to replication and blows per-device score memory — found in the
    dry-run memory analysis, see EXPERIMENTS.md §Perf iteration 0).
    """
    Kh = x.shape[2]
    if Kh == H:
        return x
    return jnp.repeat(x, H // Kh, axis=2)


def dense_attention(q, k, v, q_pos, kv_pos, *, causal=True, window=None,
                    kv_valid=None, scale=None) -> jax.Array:
    """q: (B, Lq, H, hd); k/v: (B, Lkv, Kh, hd); GQA kv repeated to H heads."""
    B, Lq, H, hd = q.shape
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    k = repeat_kv(k, H)
    v = repeat_kv(v, H)
    s = jnp.einsum("bqhd,bshd->bhqs", q, k, preferred_element_type=jnp.float32)
    s = s * scale + _mask_bias(q_pos, kv_pos, causal=causal, window=window)
    if kv_valid is not None:  # (B, Lkv) bool — e.g. cache slots not yet written
        s = s + jnp.where(kv_valid, 0.0, NEG_INF)[:, None, None, :]
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bhqs,bshd->bqhd", p, v)
    return o


def blockwise_attention(q, k, v, q_base: int, *, causal=True, window=None,
                        q_chunk=1024, kv_chunk=1024, scale=None) -> jax.Array:
    """Flash-style attention; never materializes (Lq, Lkv) scores.

    Python-unrolled over q blocks; each q block scans only the kv blocks its
    mask can reach (causal / sliding window), so FLOPs match the masked
    dense computation (roofline honesty). Where the float32 score blocks a
    backward pass would keep exceed ``REMAT_SCORES_BYTES`` in all, each kv
    step is recomputed in the backward instead (only the running max, sum
    and accumulator are kept).
    """
    B, Lq, H, hd = q.shape
    Lkv = k.shape[1]
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    k = repeat_kv(k, H)
    v = repeat_kv(v, H)
    nq = max(Lq // q_chunk, 1)
    q_chunk = Lq // nq
    nkv = max(Lkv // kv_chunk, 1)
    kv_chunk = Lkv // nkv

    def reach(qb):
        """The static kv block range q block ``qb``'s mask can reach."""
        hi = nkv if not causal else min(
            (q_base + (qb + 1) * q_chunk - 1) // kv_chunk + 1, nkv)
        lo = 0
        if window is not None:
            lo = max((q_base + qb * q_chunk - window + 1) // kv_chunk, 0)
        return lo, hi

    steps = sum(hi - lo for lo, hi in map(reach, range(nq)))
    remat = B * H * q_chunk * kv_chunk * 4 * steps > REMAT_SCORES_BYTES

    outs = []
    for qb in range(nq):
        q_pos = q_base + qb * q_chunk + jnp.arange(q_chunk)
        qg = jax.lax.dynamic_slice_in_dim(q, qb * q_chunk, q_chunk, 1)
        lo, hi = reach(qb)
        m = jnp.full((B, H, q_chunk), NEG_INF, jnp.float32)
        l = jnp.zeros((B, H, q_chunk), jnp.float32)
        acc = jnp.zeros((B, H, q_chunk, v.shape[-1]), jnp.float32)

        def body(carry, kb):
            m, l, acc = carry
            ks = jax.lax.dynamic_slice_in_dim(k, kb * kv_chunk, kv_chunk, 1)
            vs = jax.lax.dynamic_slice_in_dim(v, kb * kv_chunk, kv_chunk, 1)
            kv_pos = kb * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bqhd,bshd->bhqs", qg, ks,
                           preferred_element_type=jnp.float32) * scale
            s = s + _mask_bias(q_pos, kv_pos, causal=causal, window=window)
            m_new = jnp.maximum(m, s.max(-1))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqs,bshd->bhqd", p.astype(vs.dtype), vs).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(body) if remat else body, (m, l, acc),
            jnp.arange(lo, hi), length=hi - lo)
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        outs.append(o.astype(q.dtype))
    out = jnp.concatenate(outs, axis=2) if nq > 1 else outs[0]
    # (B, H, Lq, hd_v) -> (B, Lq, H, hd_v)
    return out.transpose(0, 2, 1, 3)


def takes_flash_kernel(Lq: int, Lkv: int, q_base, *, causal: bool,
                       window: int | None, kv_valid) -> bool:
    """Whether attention runs in the Pallas flash kernel: on TPU, causal
    self-attention from position 0 with no window and no key mask, over a
    whole number of the kernel's blocks."""
    return (flash_ops.on_tpu() and causal and window is None
            and kv_valid is None and Lq == Lkv
            and isinstance(q_base, (int, np.integer)) and q_base == 0
            and flash_ops.pick_block(Lq) is not None)


def attention_any(q, k, v, q_base, *, causal=True, window=None, kv_valid=None,
                  scale=None, block_threshold=1024) -> jax.Array:
    """The flash kernel where :func:`takes_flash_kernel` holds; else dense
    for short kv, blockwise for long kv."""
    Lkv = k.shape[1]
    if takes_flash_kernel(q.shape[1], Lkv, q_base, causal=causal,
                          window=window, kv_valid=kv_valid):
        return flash_ops.attention(q, k, v, causal=True, scale=scale)
    if Lkv <= block_threshold or kv_valid is not None:
        q_pos = q_base + jnp.arange(q.shape[1])
        kv_pos = jnp.arange(Lkv)
        return dense_attention(q, k, v, q_pos, kv_pos, causal=causal,
                               window=window, kv_valid=kv_valid, scale=scale)
    return blockwise_attention(q, k, v, q_base, causal=causal, window=window,
                               scale=scale)


# ---------------------------------------------------------------------------
# GQA / MQA module
# ---------------------------------------------------------------------------


def gqa_defs(cfg: ModelConfig, window: int | None = None) -> PyTree:
    D, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    # explicit fan-in scales: 3-D projections contract over d_model (wq/wk/wv)
    # or heads*head_dim (wo); the ParamDef default (shape[-2]) would use the
    # head count as fan-in and over-scale the init ~sqrt(D/H)x.
    s_in = float(D) ** -0.5
    s_out = float(H * hd) ** -0.5
    defs = {
        "wq": ParamDef((D, H, hd), ("embed", "q_heads", None), scale=s_in),
        "wk": ParamDef((D, K, hd), ("embed", "kv_heads", None), scale=s_in),
        "wv": ParamDef((D, K, hd), ("embed", "kv_heads", None), scale=s_in),
        "wo": ParamDef((H, hd, D), ("q_heads", None, "embed"), scale=s_out),
    }
    if cfg.qk_norm:
        defs["q_norm"] = rmsnorm_defs(hd, axis=None)
        defs["k_norm"] = rmsnorm_defs(hd, axis=None)
    return defs


class KVCache(NamedTuple):
    k: jax.Array          # (B, S, Kh, hd) — S = max_len, or window (ring buffer)
    v: jax.Array
    pos: jax.Array        # () int32 — number of tokens already written


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  window: int | None, dtype) -> KVCache:
    S = min(window, max_len) if window else max_len
    shape = (batch, S, cfg.n_kv_heads, cfg.head_dim)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                   jnp.zeros((), jnp.int32))


class PagedKVCache(NamedTuple):
    """Block-table paged KV cache: decode slots admit/retire independently.

    Unlike :class:`KVCache` (one scalar insertion position shared by the
    whole batch), every slot carries its own length, so the continuous
    batcher can refill a freed slot mid-flight while the others keep
    decoding. Physical storage is a pool of fixed-size pages; slot `s`'s
    logical block `b` lives in page ``block_tables[s, b]``. Retired slots
    point their whole table row at a reserved dump page, so in-flight
    writes from inactive slots can never touch a reassigned page.

    ``lengths`` is NOT advanced by the attention module — all layers share
    one logical position per slot, so the serving engine bumps it once per
    decode step (masked by the active-slot set).
    """

    k_pages: jax.Array       # (P, page, Kh, hd)
    v_pages: jax.Array       # (P, page, Kh, hd)
    block_tables: jax.Array  # (S, NB) int32 — physical page per logical block
    lengths: jax.Array       # (S,) int32 — tokens cached per slot


class PagedMLACache(NamedTuple):
    """Paged variant of :class:`MLACache` (pages over the compressed dim)."""

    ckv_pages: jax.Array     # (P, page, kv_lora)
    kr_pages: jax.Array      # (P, page, rope_dim)
    block_tables: jax.Array  # (S, NB) int32
    lengths: jax.Array       # (S,) int32


def _paged_write(pages: jax.Array, block_tables: jax.Array,
                 lengths: jax.Array, new: jax.Array) -> jax.Array:
    """Write one new token per slot at its logical position ``lengths[s]``.

    new: (S, 1, ...) — the fresh per-slot k/v/ckv row. Distinct live slots
    own distinct pages (PagePool invariant) so the scatter has no
    collisions; retired slots all target the dump page (content unread)."""
    page = pages.shape[1]
    pid = jnp.take_along_axis(block_tables, (lengths // page)[:, None],
                              axis=1)[:, 0]
    return pages.at[pid, lengths % page].set(new[:, 0])


def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths,
                           scale=None) -> jax.Array:
    """Gather-free paged decode attention (GQA-grouped).

    Scores are computed against the ENTIRE page pool in place; the block
    table then gathers only the tiny (S, H, NB, page) score tensor, and the
    softmax probabilities scatter back into a pool-shaped buffer for the
    value contraction. Each page pool is read exactly once per step — no
    materialized per-slot context copy and no repeat_kv tiling, which
    together move ~3x the pool bytes in the gather-and-copy formulation
    (the dominant decode cost at serving batch sizes). Pages outside a
    slot's table contribute garbage scores that the validity mask zeroes,
    and masked probabilities scattering onto the shared dump page collide
    only with other exact zeros. XLA twin of a Pallas/flashinfer-style
    paged kernel, which would consume the block table directly (kernels/
    follow-up, see EXPERIMENTS.md §Serving)."""
    S, _, H, hd = q.shape
    Pn, page, Kh, _ = k_pages.shape
    NB = block_tables.shape[1]
    G = H // Kh
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    qg = q[:, 0].reshape(S, Kh, G, hd)
    s_all = jnp.einsum("skgd,cpkd->skgcp", qg, k_pages,
                       preferred_element_type=jnp.float32) * scale
    idx = block_tables[:, None, None, :, None]              # (S,1,1,NB,1)
    s = jnp.take_along_axis(s_all, idx, axis=3)             # (S,Kh,G,NB,page)
    s = s.reshape(S, Kh, G, NB * page)
    valid = jnp.arange(NB * page)[None, :] <= lengths[:, None]
    s = s + jnp.where(valid, 0.0, NEG_INF)[:, None, None, :]
    p = jax.nn.softmax(s, axis=-1).reshape(S, Kh, G, NB, page)
    p_pool = jnp.zeros((S, Kh, G, Pn, page), p.dtype)
    p_pool = p_pool.at[jnp.arange(S)[:, None], :, :, block_tables].set(
        p.transpose(0, 3, 1, 2, 4))
    o = jnp.einsum("skgcp,cpkd->skgd", p_pool.astype(v_pages.dtype), v_pages)
    return o.reshape(S, 1, H, hd)


def slot_decode_attention(q, k_ctx, v_ctx, kv_valid, scale=None) -> jax.Array:
    """One-token-per-slot decode attention with per-slot validity.

    q: (S, 1, H, hd); k_ctx/v_ctx: (S, Lkv, Kh, hd); kv_valid: (S, Lkv).
    Causality is entirely encoded in kv_valid — each slot's query is its
    newest token, so every valid key is attendable. Used by the paged
    decode path and the ragged (per-slot prompt length) dense decode."""
    H = q.shape[2]
    scale = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    k_ctx = repeat_kv(k_ctx, H)
    v_ctx = repeat_kv(v_ctx, H)
    s = jnp.einsum("bqhd,bshd->bhqs", q, k_ctx,
                   preferred_element_type=jnp.float32) * scale
    s = s + jnp.where(kv_valid, 0.0, NEG_INF)[:, None, None, :]
    p = jax.nn.softmax(s, axis=-1).astype(v_ctx.dtype)
    return jnp.einsum("bhqs,bshd->bqhd", p, v_ctx)


def _ragged_kv_valid(S: int, lengths: jax.Array, prompt_len: int,
                     pos) -> jax.Array:
    """(B, S) cache-slot validity for right-padded ragged prompts: real
    prompt columns [0, len_b), decode columns [prompt_len, pos+1)."""
    idx = jnp.arange(S)[None, :]
    return ((idx < lengths[:, None]) | (idx >= prompt_len)) & (idx < pos + 1)


def _is_ring(cache: KVCache, window: int | None) -> bool:
    """Static: the cache is a ring buffer iff it is exactly window-sized."""
    return window is not None and cache.k.shape[1] == window


def _seq_sharded_cache(cache_k: jax.Array) -> bool:
    """True when the decode cache is sequence-sharded over 'model' (KV heads
    don't divide the model axis — see launch.shardings.cache_pspecs)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "model" not in mesh.axis_names:
        return False
    msize = mesh.shape["model"]
    return (msize > 1 and cache_k.shape[2] % msize != 0
            and cache_k.shape[1] % msize == 0)


def _seq_parallel_decode_attention(q, ck, cv, qp, *, window, kv_valid, scale):
    """Decode attention with a sequence-sharded KV cache.

    The per-step q is tiny (one token) — replicate it across 'model'; scores
    stay sharded along the kv-sequence dim; softmax statistics and the output
    contraction psum across 'model'.  Collective payload per step is O(q),
    not O(cache) — without this, XLA involuntarily gathers the full ~50
    GB/device cache onto head sharding (dry-run finding, EXPERIMENTS.md
    §Perf)."""
    from jax.sharding import PartitionSpec as P

    B, L, H, hd = q.shape
    Kh = ck.shape[2]
    G = H // Kh
    S = ck.shape[1]
    UNC = P.UNCONSTRAINED
    spec_kv = P(UNC, "model", None, None)      # batch stays data-sharded
    ck = jax.lax.with_sharding_constraint(ck, spec_kv)
    cv = jax.lax.with_sharding_constraint(cv, spec_kv)
    q = jax.lax.with_sharding_constraint(q, P(UNC, UNC, None, None))
    qg = q.reshape(B, L, Kh, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, ck,
                   preferred_element_type=jnp.float32) * scale
    s = s + _mask_bias(qp, jnp.arange(S), causal=True, window=window)
    if kv_valid is not None:
        s = s + jnp.where(kv_valid, 0.0, NEG_INF)[:, None, None, None, :]
    s = jax.lax.with_sharding_constraint(s, P(UNC, None, None, None, "model"))
    p = jax.nn.softmax(s, axis=-1).astype(cv.dtype)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, cv)
    return o.reshape(B, L, H, cv.shape[-1])


def gqa_apply(params, cfg: ModelConfig, x, *, positions=None, q_base: int = 0,
              causal=True, window=None, cache=None,
              memory: jax.Array | None = None, lengths=None,
              prompt_len: int | None = None):
    """Self-attention (optionally cached decode) or cross-attention.

    memory: if given, keys/values come from memory (cross-attention, no cache
    path needed for training; decode uses precomputed memory each step).
    lengths: (B,) per-sequence true prompt lengths for RIGHT-padded ragged
    batches. In prefill (L > 1) pad keys are masked out of attention (and
    marked invalid for the cached decode that follows); in cached decode
    (L == 1, with `prompt_len` = the static padded prompt width) rope
    positions become per-slot (len_b + t) and the original pad columns stay
    masked — batched ragged greedy decode matches unbatched exactly.
    """
    B, L, D = x.shape
    paged = isinstance(cache, PagedKVCache)
    q = jnp.einsum("bld,dhk->blhk", x, params["wq"])
    kv_src = memory if memory is not None else x
    k = jnp.einsum("bld,dhk->blhk", kv_src, params["wk"])
    v = jnp.einsum("bld,dhk->blhk", kv_src, params["wv"])
    if cfg.qk_norm:
        q = rmsnorm_apply(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm_apply(params["k_norm"], k, cfg.norm_eps)
    if memory is None:  # rope only for self-attention
        if positions is not None:
            q_pos = positions
        elif paged:
            q_pos = cache.lengths[:, None]  # (S, 1) per-slot positions
        elif cache is not None and lengths is not None and L == 1:
            # ragged decode: token t of sequence b sits at column
            # prompt_len + t but its logical position is len_b + t
            q_pos = (cache.pos - (prompt_len - lengths))[:, None]
        elif cache is not None:
            q_pos = cache.pos + jnp.arange(L)
        else:
            q_pos = q_base + jnp.arange(L)
        q = rope(q, q_pos, cfg.rope_theta)
        k = rope(k, q_pos, cfg.rope_theta)

    if paged:
        # paged decode: write the new token at each slot's own length, then
        # attend over the slot's block-table context with per-slot validity
        assert L == 1, "paged KV cache is decode-only (prefill scatters in)"
        kp = _paged_write(cache.k_pages, cache.block_tables, cache.lengths, k)
        vp = _paged_write(cache.v_pages, cache.block_tables, cache.lengths, v)
        o = paged_decode_attention(q, kp, vp, cache.block_tables,
                                   cache.lengths)
        new_cache = PagedKVCache(kp, vp, cache.block_tables, cache.lengths)
        return jnp.einsum("blhk,hkd->bld", o, params["wo"]), new_cache

    new_cache = None
    if cache is not None and L > 1:
        # prefill: cache assumed empty (pos = 0); attention over fresh k/v via
        # the blockwise path (no quadratic score materialization at 32k),
        # then write the prompt's k/v into the cache. Right-padded ragged
        # prompts mask their pad keys so they never leak into attention.
        kv_valid = None
        if lengths is not None:
            kv_valid = jnp.arange(L)[None, :] < lengths[:, None]
        o = attention_any(q, k, v, 0, causal=causal, window=window,
                          kv_valid=kv_valid)
        if _is_ring(cache, window):
            W = cache.k.shape[1]
            if L >= W:
                # last W positions, rolled so position p sits at slot p % W
                ck = jnp.roll(k[:, -W:], L % W, axis=1)
                cv = jnp.roll(v[:, -W:], L % W, axis=1)
            else:
                ck = jax.lax.dynamic_update_slice_in_dim(cache.k, k, 0, 1)
                cv = jax.lax.dynamic_update_slice_in_dim(cache.v, v, 0, 1)
            new_cache = KVCache(ck, cv, cache.pos + L)
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(cache.k, k, 0, 1)
            cv = jax.lax.dynamic_update_slice_in_dim(cache.v, v, 0, 1)
            new_cache = KVCache(ck, cv, cache.pos + L)
        out = jnp.einsum("blhk,hkd->bld", o, params["wo"])
        return out, new_cache

    if cache is not None:
        if _is_ring(cache, window):
            if lengths is not None:
                raise NotImplementedError(
                    "ragged prompt lengths with a sliding-window ring cache: "
                    "batch equal-length prompts instead (WaveBatcher only "
                    "passes lengths when a wave is actually ragged)")
            W = cache.k.shape[1]
            slot = cache.pos % W
            ck = jax.lax.dynamic_update_slice_in_dim(cache.k, k, slot, 1)
            cv = jax.lax.dynamic_update_slice_in_dim(cache.v, v, slot, 1)
            new_cache = KVCache(ck, cv, cache.pos + L)
            idx = jnp.arange(W)
            slot_pos = jnp.where(idx <= slot, cache.pos - slot + idx,
                                 cache.pos - slot - W + idx)  # absolute pos per slot
            valid = (slot_pos >= 0) & (slot_pos > cache.pos - (window or W))
            qp = (positions if positions is not None else cache.pos + jnp.arange(L))
            H = q.shape[2]
            s = jnp.einsum("bqhd,bshd->bhqs", q, repeat_kv(ck, H),
                           preferred_element_type=jnp.float32) / np.sqrt(q.shape[-1])
            ok = (slot_pos[None, :] <= qp[:, None]) & valid[None, :]
            s = s + jnp.where(ok, 0.0, NEG_INF)[None, None]
            p = jax.nn.softmax(s, axis=-1).astype(cv.dtype)
            o = jnp.einsum("bhqs,bshd->bqhd", p, repeat_kv(cv, H))
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(cache.k, k, cache.pos, 1)
            cv = jax.lax.dynamic_update_slice_in_dim(cache.v, v, cache.pos, 1)
            new_cache = KVCache(ck, cv, cache.pos + L)
            S = ck.shape[1]
            if lengths is not None:
                # ragged decode: original pad columns [len_b, prompt_len)
                # stay masked; q positions were set per-slot above
                kv_valid = _ragged_kv_valid(S, lengths, prompt_len, cache.pos)
                o = slot_decode_attention(q, ck, cv, kv_valid)
                out = jnp.einsum("blhk,hkd->bld", o, params["wo"])
                return out, new_cache
            kv_valid = jnp.arange(S)[None, :] < (cache.pos + L)
            kv_valid = jnp.broadcast_to(kv_valid, (B, S))
            qp = cache.pos + jnp.arange(L)
            if _seq_sharded_cache(ck):
                o = _seq_parallel_decode_attention(
                    q, ck, cv, qp, window=window, kv_valid=kv_valid,
                    scale=1.0 / np.sqrt(q.shape[-1]))
            else:
                o = dense_attention(q, ck, cv, qp, jnp.arange(S), causal=True,
                                    window=window, kv_valid=kv_valid)
        out = jnp.einsum("blhk,hkd->bld", o, params["wo"])
        return out, new_cache

    o = attention_any(q, k, v, q_base, causal=causal and memory is None,
                      window=window)
    return jnp.einsum("blhk,hkd->bld", o, params["wo"]), None


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------


def mla_defs(cfg: ModelConfig) -> PyTree:
    D, H = cfg.d_model, cfg.n_heads
    r, dn, dr, dv = cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    s_d = float(D) ** -0.5
    s_r = float(r) ** -0.5
    return {
        "wq": ParamDef((D, H, dn + dr), ("embed", "q_heads", None), scale=s_d),
        "w_dkv": ParamDef((D, r + dr), ("embed", "kv_lora")),
        "kv_norm": rmsnorm_defs(r, axis="kv_lora"),
        "w_uk": ParamDef((r, H, dn), ("kv_lora", "q_heads", None), scale=s_r),
        "w_uv": ParamDef((r, H, dv), ("kv_lora", "q_heads", None), scale=s_r),
        "wo": ParamDef((H, dv, D), ("q_heads", None, "embed"),
                       scale=float(H * dv) ** -0.5),
    }


class MLACache(NamedTuple):
    ckv: jax.Array   # (B, S, kv_lora)
    krope: jax.Array  # (B, S, rope_dim)
    pos: jax.Array


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> MLACache:
    return MLACache(
        jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
        jnp.zeros((), jnp.int32),
    )


def _mla_absorbed_scores(params, q_nope, q_rope, ckv_all, kr_all, scale):
    """Absorbed-form decode scores in compressed space: (B, H, L, S)."""
    q_abs = jnp.einsum("blhk,rhk->blhr", q_nope, params["w_uk"])
    s = (jnp.einsum("blhr,bsr->bhls", q_abs, ckv_all, preferred_element_type=jnp.float32)
         + jnp.einsum("blhk,bsk->bhls", q_rope, kr_all, preferred_element_type=jnp.float32))
    return s * scale


def _mla_absorbed_out(params, p, ckv_all):
    o_c = jnp.einsum("bhls,bsr->blhr", p.astype(ckv_all.dtype), ckv_all)
    o = jnp.einsum("blhr,rhk->blhk", o_c, params["w_uv"])        # absorb W_uv
    return jnp.einsum("blhk,hkd->bld", o, params["wo"])


def _mla_paged_attention(params, q_nope, q_rope, ckv_pages, kr_pages,
                         block_tables, lengths, scale):
    """Gather-free absorbed MLA decode over the page pools — same pool-
    in-place score / tiny-score-gather / probability-scatter structure as
    :func:`paged_decode_attention`, in compressed (kv_lora) space."""
    S, _, H, _ = q_nope.shape
    Pn, page, r = ckv_pages.shape
    NB = block_tables.shape[1]
    q_abs = jnp.einsum("blhk,rhk->blhr", q_nope, params["w_uk"])[:, 0]
    s_all = (jnp.einsum("shr,cpr->shcp", q_abs, ckv_pages,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("shk,cpk->shcp", q_rope[:, 0], kr_pages,
                          preferred_element_type=jnp.float32)) * scale
    idx = block_tables[:, None, :, None]                    # (S,1,NB,1)
    s = jnp.take_along_axis(s_all, idx, axis=2).reshape(S, H, NB * page)
    valid = jnp.arange(NB * page)[None, :] <= lengths[:, None]
    s = s + jnp.where(valid, 0.0, NEG_INF)[:, None, :]
    p = jax.nn.softmax(s, axis=-1).reshape(S, H, NB, page)
    p_pool = jnp.zeros((S, H, Pn, page), p.dtype)
    p_pool = p_pool.at[jnp.arange(S)[:, None], :, block_tables].set(
        p.transpose(0, 2, 1, 3))
    o_c = jnp.einsum("shcp,cpr->shr", p_pool.astype(ckv_pages.dtype),
                     ckv_pages)
    o = jnp.einsum("shr,rhk->shk", o_c, params["w_uv"])
    return jnp.einsum("shk,hkd->sd", o, params["wo"])[:, None]


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """(qk_nope + qk_rope)^-1/2, times mscale(yarn_mscale_all_dim)^2 under
    YaRN (DeepSeek-V2: 192^-1/2 * 1.260804^2 = 0.1147214)."""
    scale = 1.0 / np.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    if cfg.rope_scaling_factor > 1 and cfg.yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.rope_scaling_factor,
                             cfg.yarn_mscale_all_dim) ** 2
    return scale


def mla_apply(params, cfg: ModelConfig, x, *, q_base: int = 0,
              cache=None, lengths=None, prompt_len: int | None = None):
    """DeepSeek-V2 multi-head latent attention (no q-LoRA).

    q = x W_q (H, nope + rope) split into q_nope, q_pe; x W_dkv gives
    r + rope: c = RMSNorm(first r), k_pe the last rope dims, one for all
    heads; k_nope = c W_uk, v = c W_uv; RoPE (YaRN frequencies where the
    config scales them, :func:`rope_inv_freq`) on q_pe and k_pe; softmax
    at :func:`mla_softmax_scale`; out o W_o. Training and prefill use this
    expanded form, cached decode the absorbed one in compressed space.
    """
    B, L, D = x.shape
    H = cfg.n_heads
    r, dn, dr, dv = cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    scale = mla_softmax_scale(cfg)
    pe = functools.partial(rope, theta=cfg.rope_theta,
                           inv_freq=rope_inv_freq(cfg, dr))

    q = jnp.einsum("bld,dhk->blhk", x, params["wq"])           # (B,L,H,dn+dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    dkv = x @ params["w_dkv"]                                    # (B,L,r+dr)
    ckv = rmsnorm_apply(params["kv_norm"], dkv[..., :r], cfg.norm_eps)
    k_rope_in = dkv[..., r:][:, :, None, :]                      # (B,L,1,dr)

    if isinstance(cache, PagedMLACache):
        # paged decode — absorbed form over the slot's block-table context
        assert L == 1, "paged MLA cache is decode-only (prefill scatters in)"
        qp = cache.lengths[:, None]                              # (S, 1)
        q_rope = pe(q_rope, qp)
        k_rope_new = pe(k_rope_in, qp)[:, :, 0]
        cp = _paged_write(cache.ckv_pages, cache.block_tables, cache.lengths, ckv)
        kp = _paged_write(cache.kr_pages, cache.block_tables, cache.lengths,
                          k_rope_new)
        out = _mla_paged_attention(params, q_nope, q_rope, cp, kp,
                                   cache.block_tables, cache.lengths, scale)
        new_cache = PagedMLACache(cp, kp, cache.block_tables, cache.lengths)
        return out, new_cache

    if cache is None or L > 1:
        # training forward, or prefill (cache assumed empty): expanded form
        q_pos = q_base + jnp.arange(L)
        q_rope = pe(q_rope, q_pos)
        k_rope = pe(k_rope_in, q_pos)[:, :, 0]  # (B,L,dr)
        k_nope = jnp.einsum("blr,rhk->blhk", ckv, params["w_uk"])
        v = jnp.einsum("blr,rhk->blhk", ckv, params["w_uv"])
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rope[:, :, None, :], (B, L, H, dr))], axis=-1)
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)
        kv_valid = None
        if lengths is not None:  # ragged right-padded prefill: mask pad keys
            kv_valid = jnp.arange(L)[None, :] < lengths[:, None]
        o = attention_any(qq, k, v, q_base, causal=True, scale=scale,
                          kv_valid=kv_valid)
        new_cache = None
        if cache is not None:
            new_cache = MLACache(
                jax.lax.dynamic_update_slice_in_dim(cache.ckv, ckv, 0, 1),
                jax.lax.dynamic_update_slice_in_dim(cache.krope, k_rope, 0, 1),
                cache.pos + L)
        return jnp.einsum("blhk,hkd->bld", o, params["wo"]), new_cache

    # cached decode — absorbed form: score in compressed space
    if lengths is not None:
        qp = (cache.pos - (prompt_len - lengths))[:, None]       # (B, 1)
    else:
        qp = cache.pos + jnp.arange(L)
    q_rope = pe(q_rope, qp)
    k_rope_new = pe(k_rope_in, qp)[:, :, 0]
    ckv_all = jax.lax.dynamic_update_slice_in_dim(cache.ckv, ckv, cache.pos, 1)
    kr_all = jax.lax.dynamic_update_slice_in_dim(cache.krope, k_rope_new, cache.pos, 1)
    new_cache = MLACache(ckv_all, kr_all, cache.pos + L)
    S = ckv_all.shape[1]
    s = _mla_absorbed_scores(params, q_nope, q_rope, ckv_all, kr_all, scale)
    if lengths is not None:
        # ragged decode: original pad columns [len_b, prompt_len) stay masked
        kv_valid = _ragged_kv_valid(S, lengths, prompt_len, cache.pos)
        s = s + jnp.where(kv_valid, 0.0, NEG_INF)[:, None, None, :]
        p = jax.nn.softmax(s, axis=-1)
        return _mla_absorbed_out(params, p, ckv_all), new_cache
    kv_valid = jnp.arange(S)[None, :] < (cache.pos + L)
    causal_ok = jnp.arange(S)[None, :] <= qp[:, None]
    s = s + jnp.where(causal_ok[None, None], 0.0, NEG_INF) \
          + jnp.where(kv_valid[:, None, None, :], 0.0, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return _mla_absorbed_out(params, p, ckv_all), new_cache
