"""Plain float32 reference of DeepSeek-V2-Lite (arXiv:2405.04434; the
published ``modeling_deepseek.py`` for V2), as one chip of an
expert-parallel worker holds it.

Pre-norm RMSNorm blocks, untied embeddings, next-token cross-entropy.

* MLA, expanded form: q = x W_q is (H, 192), split into q_nope (128) and
  q_pe (64); x W_dkv gives 512 + 64, c = RMSNorm(first 512) and k_pe is the
  last 64, shared by every head; k_nope = c W_uk, v = c W_uv; RoPE on q_pe
  and k_pe with the YaRN frequencies; dense causal softmax attention at
  scale 192^-1/2 m^2, m = 0.1 mscale_all_dim ln(factor) + 1; out o W_o.
* YaRN (dim 64, base theta): f_extra,i = theta^(-2i/64), f_inter,i =
  f_extra,i / factor; d(r) = 64 ln(L_orig / (2 pi r)) / (2 ln theta);
  low = floor(d(beta_fast)), high = ceil(d(beta_slow)); ramp_i =
  clip((i - low) / (high - low), 0, 1); inv_freq_i = f_inter,i ramp_i +
  f_extra,i (1 - ramp_i). cos and sin carry no extra factor
  (mscale / mscale_all_dim = 1).
* Layer ``first_dense_layers`` and below: a dense SwiGLU. Above: a router
  of float32 logits over all ``router_experts``, softmax, the top
  ``top_k`` by greedy selection, weights the top-k probabilities (not
  renormalised unless ``norm_topk_prob``). Of the experts this chip holds,
  ``[expert_offset, expert_offset + n_experts)``, every one runs on every
  token, times its gate weight (0 where it is not among the token's top-k);
  the shared experts (one SwiGLU of width n_shared * d_ff_expert) run on
  every token. y = routed + shared.
* Sequence-wise balance loss: per row, f_e = count_e E / (L K) and P_e =
  the mean over tokens of prob_e; alpha sum_e f_e P_e over all
  ``router_experts``, summed over expert layers and added to the row's
  loss.

It reads parameters in the layout the system under test stores them and
imports nothing of it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from references.common import mm, next_token_loss, rmsnorm

HEAD_GROUP = 4      # heads per checkpointed piece of the dense attention


def init_leaf(path: tuple[str, ...], shape, key, m: dict):
    """The benchmark's own seeded initialisation, float32, by leaf name:
    normal with the fan-in scale of each projection."""
    name = path[-1]
    normal = lambda std: std * jax.random.normal(key, shape, jnp.float32)
    if name == "scale":                       # norm gains: 1, a little apart
        return 1.0 + normal(0.05)
    if name in ("embed", "lm_head"):
        return normal(0.02)
    if name in ("wq", "w_dkv", "router", "w_gate", "w_up"):
        return normal(m["d_model"] ** -0.5)
    if name in ("w_uk", "w_uv"):
        return normal(m["kv_lora_rank"] ** -0.5)
    if name == "wo":
        return normal((m["n_heads"] * m["v_head_dim"]) ** -0.5)
    if name == "w_down":
        return normal(shape[-2] ** -0.5)
    raise KeyError(f"no initialisation rule for leaf {'/'.join(path)}")


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(m: dict) -> np.ndarray:
    """(qk_rope_dim / 2,) float64 rotary frequencies, YaRN-scaled."""
    dim, base = m["qk_rope_dim"], m["rope_theta"]
    f_extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    factor = m["rope_scaling_factor"]
    if factor <= 1:
        return f_extra
    f_inter = f_extra / factor

    def d(r):
        return (dim * math.log(m["rope_original_max_pos"] / (2 * math.pi * r))
                / (2 * math.log(base)))

    low = max(math.floor(d(m["yarn_beta_fast"])), 0)
    high = min(math.ceil(d(m["yarn_beta_slow"])), dim - 1)
    if high == low:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return f_inter * ramp + f_extra * (1.0 - ramp)


def softmax_scale(m: dict) -> float:
    s = (m["qk_nope_dim"] + m["qk_rope_dim"]) ** -0.5
    if m["rope_scaling_factor"] > 1 and m["yarn_mscale_all_dim"]:
        s *= yarn_mscale(m["rope_scaling_factor"], m["yarn_mscale_all_dim"]) ** 2
    return s


def _rope(x, inv_freq):
    """Rotate-half rotary embedding of (L, H, d) at positions 0..L-1."""
    L, _, d = x.shape
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(bp, x, m, prec):
    H, r = m["n_heads"], m["kv_lora_rank"]
    dn, dr = m["qk_nope_dim"], m["qk_rope_dim"]
    L = x.shape[0]
    inv_freq = yarn_inv_freq(m)
    q = mm("ld,dhk->lhk", x, bp["wq"], prec)
    dkv = mm("ld,dr->lr", x, bp["w_dkv"], prec)
    c = rmsnorm(dkv[:, :r], bp["kv_norm"]["scale"], m["norm_eps"])
    k_pe = _rope(dkv[:, None, r:], inv_freq)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], inv_freq)], -1)
    k = jnp.concatenate([mm("lr,rhk->lhk", c, bp["w_uk"], prec),
                         jnp.broadcast_to(k_pe, (L, H, dr))], -1)
    v = mm("lr,rhk->lhk", c, bp["w_uv"], prec)
    scale = softmax_scale(m)

    def heads(qkv):
        q, k, v = qkv
        s = mm("qhd,khd->hqk", q, k, prec) * scale
        causal = jnp.arange(L)[:, None] >= jnp.arange(L)[None, :]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return mm("hqk,khd->qhd", p, v, prec)

    def groups(t):      # (L, H, d) -> (H / HEAD_GROUP, L, HEAD_GROUP, d)
        return t.reshape(L, H // HEAD_GROUP, HEAD_GROUP, -1).swapaxes(0, 1)

    o = jax.lax.map(jax.checkpoint(heads), (groups(q), groups(k), groups(v)))
    o = o.swapaxes(0, 1).reshape(L, H, -1)
    return mm("qhd,hdm->qm", o, bp["wo"], prec)


def _swiglu(p, x, prec):
    g = mm("ld,df->lf", x, p["w_gate"], prec)
    u = mm("ld,df->lf", x, p["w_up"], prec)
    return mm("lf,fd->ld", jax.nn.silu(g) * u, p["w_down"], prec)


def _moe(p, x, m, prec):
    """(routed + shared output, sequence-wise balance loss) of one row."""
    E, K = m["router_experts"], m["top_k"]
    lo, held = m["expert_offset"], m["n_experts"]
    L = x.shape[0]
    probs = jax.nn.softmax(mm("ld,de->le", x, p["router"], "f32"), axis=-1)
    topw, topi = jax.lax.top_k(probs, K)
    if m["norm_topk_prob"]:
        topw = topw / jnp.sum(topw, -1, keepdims=True)
    rows = jnp.arange(L)[:, None]
    gate = jnp.zeros((L, E), jnp.float32).at[rows, topi].set(topw)
    count = jnp.zeros((E,), jnp.float32).at[topi.reshape(-1)].add(1.0)
    aux = m["router_aux_coef"] * jnp.sum(count * E / (L * K)
                                        * jnp.mean(probs, 0))
    g = mm("ld,edf->elf", x, p["w_gate"], prec)
    u = mm("ld,edf->elf", x, p["w_up"], prec)
    y = mm("elf,efd->eld", jax.nn.silu(g) * u, p["w_down"], prec)
    routed = jnp.einsum("eld,le->ld", y, gate[:, lo:lo + held])
    return routed + _swiglu(p["shared"], x, prec), aux


def _block(x, bp, m, prec):
    """(x after one pre-norm block, its balance loss)."""
    eps = m["norm_eps"]
    x = x + _attention(bp["mix"], rmsnorm(x, bp["norm1"]["scale"], eps), m,
                       prec)
    h = rmsnorm(x, bp["norm2"]["scale"], eps)
    if "router" in bp["mlp"]:
        y, aux = _moe(bp["mlp"], h, m, prec)
        return x + y, aux
    return x + _swiglu(bp["mlp"], h, prec), jnp.zeros((), jnp.float32)


def row_loss(params, m: dict, tokens, prec: str, weights=None):
    """Cross-entropy of one (L + 1,) token row, predicting tokens[1:],
    plus the row's balance loss. A segment of stacked layers runs as a
    scan, each block recomputed in the backward."""
    block = jax.checkpoint(lambda x, bp: _block(x, bp, m, prec))
    x = params["embed"].astype(jnp.float32)[tokens[:-1]]
    aux = jnp.zeros((), jnp.float32)
    for seg in params["segments"]:
        if isinstance(seg, (list, tuple)):
            for bp in seg:
                x, a = block(x, bp)
                aux = aux + a
        else:
            x, a = jax.lax.scan(block, x, seg)
            aux = aux + jnp.sum(a)
    h = rmsnorm(x, params["out_norm"]["scale"], m["norm_eps"])
    logits = mm("ld,dv->lv", h, params["lm_head"], prec)
    return next_token_loss(logits, tokens[1:], weights) + aux


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Forward and backward operations one trained token requires on this
    chip: 6 x the active matmul parameters (attention projections, the
    router, the dense layer's MLP, the shared experts, the routed experts
    at the expected held share top_k x held / router_experts experts per
    token, and the output projection), plus causal attention, QK^T and PV
    over an average context of (T + 1) / 2, 3 x H x (192 + 128) x (T + 1)
    per layer. Recomputation is not counted."""
    D, H, V = m["d_model"], m["n_heads"], m["vocab_size"]
    r, dn, dr, dv = (m["kv_lora_rank"], m["qk_nope_dim"], m["qk_rope_dim"],
                     m["v_head_dim"])
    Fe, n_dense = m["d_ff_expert"], m["first_dense_layers"]
    attn = D * H * (dn + dr) + D * (r + dr) + r * H * (dn + dv) + H * dv * D
    share = m["top_k"] * m["n_experts"] / m["router_experts"]
    moe = (D * m["router_experts"] + 3 * D * Fe * m["n_shared_experts"]
           + 3 * D * Fe * share)
    n_matmul = (m["n_layers"] * attn + n_dense * 3 * D * m["d_ff"]
                + (m["n_layers"] - n_dense) * moe + D * V)
    attn_ops = m["n_layers"] * 3 * H * (dn + dr + dv) * (seq_len + 1)
    return 6.0 * n_matmul + attn_ops
