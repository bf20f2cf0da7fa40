"""Pallas TPU flash-attention (forward) with explicit BlockSpec VMEM tiling.

Grid: (batch, q_heads, q_blocks, kv_blocks) — the kv dimension is the
innermost ("arbitrary") axis; scratch (m, l, acc) persists across it and the
output tile is written on the last kv step.  GQA is handled in the k/v
index_maps (kv head = q head // group), so kv tiles are fetched once per
group without materializing repeated heads in HBM.

Causal / sliding-window masking is applied per tile; fully-masked tiles are
skipped with ``pl.when`` (no MXU work), matching the FLOP count of the masked
computation — the same blockwise algorithm as the XLA twin in
``repro.models.attention.blockwise_attention``, which doubles as its oracle.

Block sizes default to (q=512, kv=512, hd ≤ 256): VMEM residency =
q·hd + 2·kv·hd + q·kv (scores) + accumulators ≈ 2–3 MiB in fp32 — inside the
~16 MiB/core v5e VMEM budget with double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_KV = 512


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int | None,
                  block_q: int, block_kv: int, n_kv: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    q_start = qi * block_q
    k_start = ki * block_kv

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # tile-level mask reachability (dynamic on grid indices -> pl.when)
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
    if window is not None:
        run = jnp.logical_and(run, k_start + block_kv - 1 > q_start - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)               # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)               # (bkv, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qp = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        kp = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        ok = jnp.ones((block_q, block_kv), bool)
        if causal:
            ok &= kp <= qp
        if window is not None:
            ok &= kp > qp - window
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, window: int | None = None, scale: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q, block_kv: int = DEFAULT_BLOCK_KV,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, H, Lq, hd); k/v: (B, Hkv, Lkv, hd) -> (B, H, Lq, hd)."""
    B, H, Lq, hd = q.shape
    Hkv, Lkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    block_q = min(block_q, Lq)
    block_kv = min(block_kv, Lkv)
    assert Lq % block_q == 0 and Lkv % block_kv == 0
    n_kv = Lkv // block_kv
    grid = (B, H, Lq // block_q, n_kv)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, n_kv=n_kv)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Lq, hd), q.dtype,
                                       vma=jax.typeof(q).vma),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ) if not interpret else None,
        interpret=interpret,
    )(q, k, v)
