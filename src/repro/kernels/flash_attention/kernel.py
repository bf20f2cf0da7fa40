"""Pallas TPU flash attention, forward and backward (FlashAttention-2).

Layout ``(B, heads, L, d)``: q ``(B, H, Lq, d_qk)``, k ``(B, Kh, Lkv, d_qk)``,
v ``(B, Kh, Lkv, d_v)`` with ``H % Kh == 0``; the value head dim may differ
from the qk one (MLA: 192 and 128). GQA goes through the k/v index maps
(kv head = q head // group), so no repeated head reaches HBM.

* Forward, grid ``(B, H, q blocks, kv blocks)``: the kv axis is innermost
  ("arbitrary"); the running max, sum and output accumulator (float32)
  persist in VMEM across it, and the output tile and each row's logsumexp
  are written on its last step. Score tiles never leave VMEM.
* Backward: a dQ kernel on the forward's grid, and a dK/dV kernel on
  ``(B, Kh, kv blocks, group, q blocks)`` that sums a kv block's gradient
  over its group's q heads and the q blocks. Each recomputes its score tile
  from q, k and the saved logsumexp; the row term ``rowsum(dO * O)`` comes
  in computed once.

Precision: the MXU operands stay in the inputs' dtype with float32
accumulation, and the softmax scale multiplies the float32 scores (q is
not pre-scaled, which would round once more where the scale is no power of
two); dQ and dK take the scale on their float32 accumulators. The float32
score gradient dS reaches the dQ and dK matmuls whole: with bfloat16
inputs it goes in as two bfloat16 parts, its rounding and what the
rounding left (:func:`_ds_dot`), as the XLA twin's float32 autodiff would
keep it.

Masking is causal and/or a sliding window in the forward, causal in the
backward. A tile the mask covers wholly is skipped with ``pl.when``, and
its index maps repeat a block already fetched, so it moves no bytes
either; only tiles the mask cuts compute it.

The logsumexp is stored ``(B, H, 8, Lq)``, each row's value on 8 sublanes:
the forward writes a transposed ``(8, block_q)`` tile, the dK/dV kernel reads
a ``(1, block_q)`` row of it and the dQ kernel a column.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
SUBLANES = 8
LANES = 128
VMEM_LIMIT_BYTES = 64 << 20
_NT = (((1,), (1,)), ((), ()))   # a @ b.T


# ---------------------------------------------------------------------------
# Which tiles the mask reaches
# ---------------------------------------------------------------------------


def _kv_span(qi, bq, bk, nk, causal, window):
    """First and last kv block that q block ``qi`` can see."""
    hi = jnp.minimum((qi * bq + bq - 1) // bk, nk - 1) if causal else nk - 1
    lo = 0 if window is None else jnp.maximum((qi * bq - window + 1) // bk, 0)
    return lo, hi


def _first_q(ki, bq, bk):
    """The first q block that can see kv block ``ki`` under the causal
    mask (the last is the last q block)."""
    return (ki * bk) // bq


def _on_tiles(q0, k0, bq, bk, causal, window, step):
    """``step(masked)`` on the tile at rows ``q0``, keys ``k0`` if the mask
    leaves any of it: unmasked where it cuts none, skipped where it takes
    it all. The differences k - q over the tile run from ``d_lo`` to
    ``d_hi``; causal keeps d <= 0, the window d > -window."""
    if not causal and window is None:
        step(False)
        return
    d_lo, d_hi = k0 - (q0 + bq - 1), k0 + bk - 1 - q0
    reach, whole = [], []
    if causal:
        reach.append(d_lo <= 0)
        whole.append(d_hi <= 0)
    if window is not None:
        reach.append(d_hi > -window)
        whole.append(d_lo > -window)
    reach = functools.reduce(jnp.logical_and, reach)
    whole = functools.reduce(jnp.logical_and, whole)
    pl.when(jnp.logical_and(reach, whole))(lambda: step(False))
    pl.when(jnp.logical_and(reach, jnp.logical_not(whole)))(lambda: step(True))


def _mask(s, q0, k0, causal, window, *, keys_on_rows=False):
    """Scores ``s`` with the masked entries at NEG_INF; ``s`` is (q, k), or
    (k, q) with ``keys_on_rows``."""
    r = lax.broadcasted_iota(jnp.int32, s.shape, 0)
    c = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    d = (k0 - q0) + (r - c if keys_on_rows else c - r)      # k - q
    ok = None
    if causal:
        ok = d <= 0
    if window is not None:
        w = d > -window
        ok = w if ok is None else jnp.logical_and(ok, w)
    return jnp.where(ok, s, NEG_INF)


def _scores(a, b, scale):
    return lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32) * scale


def _ds_dot(ds, x):
    """``ds @ x`` with float32 accumulation, for the float32 score gradient
    ``ds`` and an input ``x``. Where ``x`` is narrower, ``ds`` goes in as
    two parts of ``x``'s dtype, its rounding and the rest, so that the
    product keeps ds to about twice that dtype's mantissa; ``x`` itself is
    exact in it."""
    hi = ds.astype(x.dtype)
    out = lax.dot(hi, x, preferred_element_type=jnp.float32)
    if x.dtype == ds.dtype:
        return out
    lo = (ds - hi.astype(ds.dtype)).astype(x.dtype)
    return out + lax.dot(lo, x, preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, window, bq, bk, nk):
    qi, ki = pl.program_id(2), pl.program_id(3)
    q0, k0 = qi * bq, ki * bk

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked):
        v = v_ref[...]
        s = _scores(q_ref[...], k_ref[...], scale)              # (bq, bk)
        if masked:
            s = _mask(s, q0, k0, causal, window)
        m_prev = m_scr[...]                                      # (bq, 128)
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_scr[...] = corr * l_scr[...] + p.sum(-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr[:, :1] + lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    _on_tiles(q0, k0, bq, bk, causal, window, step)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / l[:, :1]).astype(o_ref.dtype)
        lse_ref[...] = (m_scr[...] + jnp.log(l)).T[:SUBLANES]   # (8, bq)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, acc_scr,
               *, scale, bq, bk, nk):
    qi, ki = pl.program_id(2), pl.program_id(3)
    q0, k0 = qi * bq, ki * bk

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked):
        k = k_ref[...]
        s = _scores(q_ref[...], k, scale)                        # (bq, bk)
        if masked:
            s = _mask(s, q0, k0, True, None)
        p = jnp.exp(s - lse_ref[0][:, None])
        dp = lax.dot_general(do_ref[...], v_ref[...], _NT,
                             preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[0][:, None])
        acc_scr[...] += _ds_dot(ds, k)

    _on_tiles(q0, k0, bq, bk, True, None, step)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[...] = (acc_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, scale, bq, bk, nq, group):
    ki, g, qi = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    q0, k0 = qi * bq, ki * bk

    @pl.when(jnp.logical_and(g == 0, qi == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def step(masked):
        q, do = q_ref[...], do_ref[...]
        st = _scores(k_ref[...], q, scale)                       # (bk, bq)
        if masked:
            st = _mask(st, q0, k0, True, None, keys_on_rows=True)
        pt = jnp.exp(st - lse_ref[:1, :])
        dv_scr[...] += lax.dot(pt.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        dpt = lax.dot_general(v_ref[...], do, _NT,
                              preferred_element_type=jnp.float32)
        dst = pt * (dpt - di_ref[:1, :])
        dk_scr[...] += _ds_dot(dst, q)

    _on_tiles(q0, k0, bq, bk, True, None, step)

    @pl.when(jnp.logical_and(g == group - 1, qi == nq - 1))
    def _finish():
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_calls
# ---------------------------------------------------------------------------


def _out(shape, dtype, like):
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _params(interpret, semantics):
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _check(q, k, v, block_q, block_kv):
    B, H, Lq, _ = q.shape
    Kh, Lkv = k.shape[1], k.shape[2]
    assert H % Kh == 0 and v.shape[:3] == k.shape[:3], (q.shape, k.shape, v.shape)
    assert Lq % block_q == 0 and Lkv % block_kv == 0, (Lq, Lkv, block_q, block_kv)
    return B, H, Kh, Lq, Lkv


def flash_forward(q, k, v, *, scale: float, causal: bool = True,
                  window: int | None = None, block_q: int, block_kv: int,
                  interpret: bool = False):
    """-> o ``(B, H, Lq, d_v)`` in q's dtype, and the rows' float32
    logsumexp ``(B, H, 8, Lq)`` (8 equal sublanes)."""
    B, H, Kh, Lq, Lkv = _check(q, k, v, block_q, block_kv)
    G, dqk, dv = H // Kh, q.shape[-1], v.shape[-1]
    bq, bk = block_q, block_kv
    nq, nk = Lq // bq, Lkv // bk

    def kv_map(b, h, qi, ki):
        lo, hi = _kv_span(qi, bq, bk, nk, causal, window)
        return b, h // G, jnp.clip(ki, lo, hi), 0

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               window=window, bq=bq, bk=bk, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((None, None, bq, dqk), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((None, None, bk, dqk), kv_map),
            pl.BlockSpec((None, None, bk, dv), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((None, None, bq, dv), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((None, None, SUBLANES, bq),
                         lambda b, h, qi, ki: (b, h, 0, qi)),
        ],
        out_shape=[_out((B, H, Lq, dv), q.dtype, q),
                   _out((B, H, SUBLANES, Lq), jnp.float32, q)],
        scratch_shapes=[pltpu.VMEM((bq, LANES), jnp.float32),
                        pltpu.VMEM((bq, LANES), jnp.float32),
                        pltpu.VMEM((bq, dv), jnp.float32)],
        compiler_params=_params(interpret, ("parallel", "parallel",
                                            "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)


def flash_backward(q, k, v, do, lse, di, *, scale: float, block_q: int,
                   block_kv: int, interpret: bool = False):
    """-> (dq, dk, dv) of causal attention, in the inputs' dtypes, from the
    forward's ``lse`` and the row term ``di = rowsum(dO * O)``, both
    ``(B, H, 8, Lq)`` float32."""
    B, H, Kh, Lq, Lkv = _check(q, k, v, block_q, block_kv)
    G, dqk, dv = H // Kh, q.shape[-1], v.shape[-1]
    bq, bk = block_q, block_kv
    nq, nk = Lq // bq, Lkv // bk
    kw = dict(scale=scale, bq=bq, bk=bk)

    # dQ: the forward's grid, kv innermost
    def kv_map(b, h, qi, ki):
        lo, hi = _kv_span(qi, bq, bk, nk, True, None)
        return b, h // G, jnp.clip(ki, lo, hi), 0

    q_rows = lambda b, h, qi, ki: (b, h, qi, 0)
    q_lanes = lambda b, h, qi, ki: (b, h, 0, qi)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, nk=nk, **kw),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((None, None, bq, dqk), q_rows),
            pl.BlockSpec((None, None, bk, dqk), kv_map),
            pl.BlockSpec((None, None, bk, dv), kv_map),
            pl.BlockSpec((None, None, bq, dv), q_rows),
            pl.BlockSpec((None, None, SUBLANES, bq), q_lanes),
            pl.BlockSpec((None, None, SUBLANES, bq), q_lanes),
        ],
        out_specs=pl.BlockSpec((None, None, bq, dqk), q_rows),
        out_shape=_out(q.shape, q.dtype, q),
        scratch_shapes=[pltpu.VMEM((bq, dqk), jnp.float32)],
        compiler_params=_params(interpret, ("parallel", "parallel",
                                            "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_dq",
    )(q, k, v, do, lse, di)

    # dK/dV: per kv block, over the group's q heads and the q blocks
    def q_block(ki, qi):
        return jnp.maximum(qi, _first_q(ki, bq, bk))

    q_rows = lambda b, kh, ki, g, qi: (b, kh * G + g, q_block(ki, qi), 0)
    q_lanes = lambda b, kh, ki, g, qi: (b, kh * G + g, 0, q_block(ki, qi))
    kv_rows = lambda b, kh, ki, g, qi: (b, kh, ki, 0)
    dk, dv_ = pl.pallas_call(
        functools.partial(_dkv_kernel, nq=nq, group=G, **kw),
        grid=(B, Kh, nk, G, nq),
        in_specs=[
            pl.BlockSpec((None, None, bq, dqk), q_rows),
            pl.BlockSpec((None, None, bk, dqk), kv_rows),
            pl.BlockSpec((None, None, bk, dv), kv_rows),
            pl.BlockSpec((None, None, bq, dv), q_rows),
            pl.BlockSpec((None, None, SUBLANES, bq), q_lanes),
            pl.BlockSpec((None, None, SUBLANES, bq), q_lanes),
        ],
        out_specs=[pl.BlockSpec((None, None, bk, dqk), kv_rows),
                   pl.BlockSpec((None, None, bk, dv), kv_rows)],
        out_shape=[_out(k.shape, k.dtype, q), _out(v.shape, v.dtype, q)],
        scratch_shapes=[pltpu.VMEM((bk, dqk), jnp.float32),
                        pltpu.VMEM((bk, dv), jnp.float32)],
        compiler_params=_params(interpret, ("parallel", "parallel", "parallel",
                                            "arbitrary", "arbitrary")),
        interpret=interpret,
        name="flash_attention_dkv",
    )(q, k, v, do, lse, di)
    return dq, dk, dv_
