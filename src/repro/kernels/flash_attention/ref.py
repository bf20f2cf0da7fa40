"""Pure-jnp oracle for the flash-attention Pallas kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def attention_reference(q, k, v, *, causal: bool = True,
                        window: int | None = None,
                        scale: float | None = None) -> jax.Array:
    """q: (B, H, Lq, hd); k: (B, Hkv, Lkv, hd); v: (B, Hkv, Lkv, hd_v) with
    H % Hkv == 0."""
    B, H, Lq, hd = q.shape
    Hkv, Lkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    qg = q.reshape(B, Hkv, G, Lq, hd)
    s = jnp.einsum("bkgqh,bksh->bkgqs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qi = jnp.arange(Lq)[:, None]
    ki = jnp.arange(Lkv)[None, :]
    ok = jnp.ones((Lq, Lkv), bool)
    if causal:
        ok &= ki <= qi
    if window is not None:
        ok &= ki > qi - window
    s = jnp.where(ok, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bksh->bkgqh", p, v.astype(jnp.float32))
    return o.reshape(B, H, Lq, v.shape[-1]).astype(q.dtype)
