"""Plain float32 reference of a dense decoder with grouped-query attention,
as granite-3.0-2b is built: pre-norm RMSNorm blocks, GQA with rotary
embeddings (rotate-half), SwiGLU MLP, final RMSNorm, tied embeddings, and
next-token cross-entropy.

It reads parameters in the layout the system under test stores them
(``embed``, ``segments``, ``out_norm``; one segment of attention blocks,
stacked over layers or listed), since that is the checkpoint format the
benchmark hands to both. It imports nothing of the system under test.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from references.common import (layer_params, mm, n_layers_of, next_token_loss,
                               rmsnorm, rope)


def init_leaf(path: tuple[str, ...], shape, key, m: dict):
    """The benchmark's own seeded initialisation, float32, by leaf name."""
    name = path[-1]
    D, H, hd, F = m["d_model"], m["n_heads"], m["head_dim"], m["d_ff"]
    normal = lambda std: std * jax.random.normal(key, shape, jnp.float32)
    if name == "scale":                       # norm gains: 1, a little apart
        return 1.0 + normal(0.05)
    if name in ("embed", "lm_head"):
        return normal(0.02)
    if name in ("wq", "wk", "wv", "w_gate", "w_up"):
        return normal(D ** -0.5)
    if name == "wo":
        return normal((H * hd) ** -0.5)
    if name == "w_down":
        return normal(F ** -0.5)
    raise KeyError(f"no initialisation rule for leaf {'/'.join(path)}")


def _attention(bp, x, m, prec):
    H, K, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    L = x.shape[0]
    q = mm("ld,dhk->lhk", x, bp["wq"], prec)
    k = mm("ld,dhk->lhk", x, bp["wk"], prec)
    v = mm("ld,dhk->lhk", x, bp["wv"], prec)
    q, k = rope(q, m["rope_theta"]), rope(k, m["rope_theta"])
    k = jnp.repeat(k, H // K, axis=1)
    v = jnp.repeat(v, H // K, axis=1)
    s = mm("qhd,khd->hqk", q, k, prec) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((L, L), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm("hqk,khd->qhd", p, v, prec)
    return mm("qhd,hdm->qm", o, bp["wo"], prec)


def _mlp(bp, x, prec):
    g = mm("ld,df->lf", x, bp["w_gate"], prec)
    u = mm("ld,df->lf", x, bp["w_up"], prec)
    return mm("lf,fd->ld", jax.nn.silu(g) * u, bp["w_down"], prec)


def row_loss(params, m: dict, tokens, prec: str, weights=None):
    """Cross-entropy of one (L + 1,) token row, predicting tokens[1:]."""
    eps = m["norm_eps"]
    x = params["embed"].astype(jnp.float32)[tokens[:-1]]
    seg = params["segments"][0]
    for i in range(n_layers_of(seg)):
        bp = layer_params(seg, i)

        def block(x, bp):
            x = x + _attention(bp["mix"], rmsnorm(x, bp["norm1"]["scale"], eps),
                               m, prec)
            return x + _mlp(bp["mlp"], rmsnorm(x, bp["norm2"]["scale"], eps),
                            prec)

        x = jax.checkpoint(block)(x, bp)
    h = rmsnorm(x, params["out_norm"]["scale"], eps)
    w_out = params["embed"].T if m["tie_embeddings"] else params["lm_head"]
    logits = mm("ld,dv->lv", h, w_out, prec)
    return next_token_loss(logits, tokens[1:], weights)


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Forward and backward operations one trained token requires:
    6 x the matmul parameters (attention and MLP projections and the
    output projection over the vocabulary), plus causal attention,
    QK^T and PV over an average context of (T + 1) / 2, times 3 for
    forward and backward. Recomputation is not counted."""
    D, H, K, hd, F, V = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                         m["head_dim"], m["d_ff"], m["vocab_size"])
    per_layer = D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F
    n_matmul = m["n_layers"] * per_layer + V * D
    attn = m["n_layers"] * 6 * H * hd * (seq_len + 1)
    return 6.0 * n_matmul + attn
