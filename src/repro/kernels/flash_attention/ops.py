"""jit'd wrapper for the flash-attention kernel with (B, L, H, hd) layout
(matching repro.models.attention) and automatic padding to block multiples."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention


@functools.partial(jax.jit, static_argnames=("causal", "window", "interpret",
                                             "block_q", "block_kv"))
def attention(q, k, v, *, causal: bool = True, window: int | None = None,
              interpret: bool | None = None, block_q: int = 512,
              block_kv: int = 512):
    """q: (B, Lq, H, hd); k/v: (B, Lkv, Hkv, hd) -> (B, Lq, H, hd).

    ``interpret=None`` compiles the kernel on TPU and interprets it
    elsewhere.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = flash_attention(qt, kt, vt, causal=causal, window=window,
                        block_q=min(block_q, q.shape[1]),
                        block_kv=min(block_kv, k.shape[1]),
                        interpret=interpret)
    return o.transpose(0, 2, 1, 3)
