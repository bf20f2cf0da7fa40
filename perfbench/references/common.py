"""Pieces shared by the plain references: matmuls at a stated precision,
RMSNorm, rotary embeddings and the next-token cross-entropy.

Nothing here imports the system under test. Everything computes in float32;
``prec`` only changes what the matmul operands are rounded to first:

* ``"f32"``: float32 operands, ``Precision.HIGHEST`` (six bf16 passes on a
  TPU, so a float32 matmul really is float32).
* ``"fp8"``: the control. Both operands, in the forward and in the backward
  pass, are rounded to float8_e4m3fn under a per-tensor absmax scale and
  multiplied with float32 accumulation: what an fp8 training path computes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = float(jnp.finfo(F8).max)


def quantize_fp8(x):
    """Round ``x`` to float8_e4m3fn under a per-tensor absmax scale."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(F8).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum_fp8(eq, a, b):
    return jnp.einsum(eq, quantize_fp8(a), quantize_fp8(b), precision=HIGHEST)


def _einsum_fp8_fwd(eq, a, b):
    qa, qb = quantize_fp8(a), quantize_fp8(b)
    return jnp.einsum(eq, qa, qb, precision=HIGHEST), (qa, qb)


def _einsum_fp8_bwd(eq, res, g):
    qa, qb = res
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(eq, x, y, precision=HIGHEST),
                     qa, qb)
    return vjp(quantize_fp8(g))


_einsum_fp8.defvjp(_einsum_fp8_fwd, _einsum_fp8_bwd)


def mm(eq: str, a, b, prec: str):
    """``einsum(eq, a, b)`` in float32, operands rounded as ``prec`` says."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if prec == "f32":
        return jnp.einsum(eq, a, b, precision=HIGHEST)
    if prec == "fp8":
        return _einsum_fp8(eq, a, b)
    raise ValueError(f"unknown precision {prec!r}")


def rmsnorm(x, scale, eps: float):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rope(x, theta: float):
    """Rotary embedding over (L, H, hd), rotate-half convention
    (GPT-NeoX / Llama): the first and second halves of hd are the pairs."""
    L, _, hd = x.shape
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(L, dtype=np.float64)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def next_token_loss(logits, labels, weights=None):
    """Mean cross-entropy of (L, V) logits against (L,) labels; ``weights``
    (L,) selects the positions that count (the mean is over those)."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    nll = logz - gold
    if weights is None:
        return jnp.mean(nll)
    return jnp.sum(nll * weights) / jnp.sum(weights)


def layer_params(seg, i: int):
    """Layer ``i`` of a segment: stacked leaves (scanned) or a list."""
    if isinstance(seg, (list, tuple)):
        return seg[i]
    return jax.tree.map(lambda x: x[i], seg)


def n_layers_of(seg) -> int:
    if isinstance(seg, (list, tuple)):
        return len(seg)
    return jax.tree.leaves(seg)[0].shape[0]
