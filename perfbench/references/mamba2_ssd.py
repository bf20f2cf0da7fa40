"""Plain float32 reference of Mamba-2 (arXiv:2405.21060), attention-free.

Each block is ``x + mixer(RMSNorm(x))``; the mixer projects to
(z, xBC, dt), runs a causal depthwise conv of width ``ssm_conv`` and SiLU
over xBC, splits it into x, B, C, and runs the selective state-space
recurrence token by token,

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t B_t^T,   y_t = S_t C_t + D x_t,

with A = -exp(A_log) and dt = softplus(dt_raw + dt_bias), then the gated
norm RMSNorm(y * silu(z)) and the output projection. The recurrence is the
state-space definition itself, not the chunked dual form the system under
test uses. Tied embeddings and next-token cross-entropy as in the dense
reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from references.common import (HIGHEST, layer_params, mm, n_layers_of,
                               next_token_loss, rmsnorm)

SCAN_BLOCK = 64          # tokens per checkpointed block of the recurrence


def init_leaf(path: tuple[str, ...], shape, key, m: dict):
    """The benchmark's own seeded initialisation, float32, by leaf name;
    the SSM parameters follow the Mamba-2 paper's initialisation."""
    name = path[-1]
    D = m["d_model"]
    di = m["ssm_expand"] * D
    normal = lambda std: std * jax.random.normal(key, shape, jnp.float32)
    uniform = lambda lo, hi: jax.random.uniform(key, shape, jnp.float32, lo, hi)
    if name in ("scale", "D"):
        return 1.0 + normal(0.05)
    if name in ("embed", "lm_head"):
        return normal(0.02)
    if name == "in_proj":
        return normal(D ** -0.5)
    if name == "out_proj":
        return normal(di ** -0.5)
    if name == "conv_w":
        return normal(m["ssm_conv"] ** -0.5)
    if name == "conv_b":
        return normal(0.02)
    if name == "A_log":                        # A ~ U[1, 16]
        return jnp.log(uniform(1.0, 16.0))
    if name == "dt_bias":                      # dt ~ logU[1e-3, 1e-1]
        dt = jnp.exp(uniform(jnp.log(1e-3), jnp.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))   # softplus^-1
    raise KeyError(f"no initialisation rule for leaf {'/'.join(path)}")


def _ssm_scan(x, dt, A, B, C):
    """x (L, H, P), dt (L, H), A (H,), B and C (L, H, N) -> y (L, H, P)."""
    L, H, P = x.shape
    N = B.shape[-1]

    def step(S, inp):
        xt, dtt, Bt, Ct = inp
        S = (jnp.exp(dtt * A)[:, None, None] * S
             + (dtt[:, None] * xt)[:, :, None] * Bt[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, Ct, precision=HIGHEST)

    def block(S, inp):
        return jax.lax.scan(step, S, inp)

    nb = L // SCAN_BLOCK
    blocks = jax.tree.map(
        lambda a: a.reshape((nb, SCAN_BLOCK) + a.shape[1:]), (x, dt, B, C))
    _, y = jax.lax.scan(jax.checkpoint(block),
                        jnp.zeros((H, P, N), jnp.float32), blocks)
    return y.reshape(L, H, P)


def _mixer(bp, x, m, prec):
    L = x.shape[0]
    D, N, G, P, W = (m["d_model"], m["ssm_state"], m["ssm_ngroups"],
                     m["ssm_headdim"], m["ssm_conv"])
    di = m["ssm_expand"] * D
    H = di // P
    zxbcdt = mm("ld,de->le", x, bp["in_proj"], prec)
    z, xbc, dt_raw = (zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * G * N],
                      zxbcdt[:, 2 * di + 2 * G * N:])
    w = bp["conv_w"].astype(jnp.float32)
    xpad = jnp.concatenate([jnp.zeros((W - 1, xbc.shape[1])), xbc], 0)
    conv = sum(xpad[i:i + L] * w[i] for i in range(W)) + bp["conv_b"]
    conv = jax.nn.silu(conv)
    xs, Bg, Cg = conv[:, :di], conv[:, di:di + G * N], conv[:, di + G * N:]
    dt = jax.nn.softplus(dt_raw + bp["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(bp["A_log"].astype(jnp.float32))
    heads = lambda g: jnp.repeat(g.reshape(L, G, N), H // G, axis=1)
    xh = xs.reshape(L, H, P)
    y = _ssm_scan(xh, dt, A, heads(Bg), heads(Cg))
    y = y + xh * bp["D"].astype(jnp.float32)[None, :, None]
    y = rmsnorm(y.reshape(L, di) * jax.nn.silu(z), bp["norm"]["scale"],
                m["norm_eps"])
    return mm("le,ed->ld", y, bp["out_proj"], prec)


def row_loss(params, m: dict, tokens, prec: str, weights=None):
    """Cross-entropy of one (L + 1,) token row, predicting tokens[1:]."""
    eps = m["norm_eps"]
    x = params["embed"].astype(jnp.float32)[tokens[:-1]]
    seg = params["segments"][0]
    for i in range(n_layers_of(seg)):
        def block(x, bp):
            return x + _mixer(bp["mix"], rmsnorm(x, bp["norm1"]["scale"], eps),
                              m, prec)

        x = jax.checkpoint(block)(x, layer_params(seg, i))
    h = rmsnorm(x, params["out_norm"]["scale"], eps)
    w_out = params["embed"].T if m["tie_embeddings"] else params["lm_head"]
    logits = mm("ld,dv->lv", h, w_out, prec)
    return next_token_loss(logits, tokens[1:], weights)


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Forward and backward operations one trained token requires:
    6 x the matmul parameters (in and out projections, output projection
    over the vocabulary), plus 3 x the forward work of the conv and of the
    SSD in its chunked dual form at chunk Q: the C.B scores over half a
    chunk on average, G*N*(Q+1); their weighted sum over the inputs,
    di*(Q+1); the chunk states and their read-out, 4*di*N. Recomputation
    is not counted; ``seq_len`` does not enter (the cost is linear)."""
    D, N, G, V = m["d_model"], m["ssm_state"], m["ssm_ngroups"], m["vocab_size"]
    di = m["ssm_expand"] * D
    H = di // m["ssm_headdim"]
    Q = m["ssm_chunk"]
    conv_dim = di + 2 * G * N
    per_layer = D * (2 * di + 2 * G * N + H) + di * D
    n_matmul = m["n_layers"] * per_layer + V * D
    ssd = G * N * (Q + 1) + di * (Q + 1) + 4 * di * N
    conv = 2 * m["ssm_conv"] * conv_dim
    return 6.0 * n_matmul + 3.0 * m["n_layers"] * (ssd + conv)
