"""Operations and bytes of causal self-attention's flash kernels, from the
configuration's and the traffic's shapes alone (never from
``cost_analysis``).

The program runs attention in the Pallas flash kernel where it is causal
self-attention with no window over a sequence of whole 128-row blocks
(``repro.models.attention.takes_flash_kernel``): a forward kernel, and a
dQ and a dK/dV kernel in the backward. Each call holds the rows of the
workers a chip runs (folded into the kernel's batch on one chip, one
worker's rows per chip on a mesh), with the heads ``(B, heads, L, d)``.

The work is the minimum causal attention must do, with no recomputation
counted: forward QK^T and PV, backward dP, dV, dQ and dK, each over the
half of the L x L scores causality keeps, ``L^2 (3 d_qk + 3 d_v)`` FLOPs
per row, head and layer; and q, k, v, o, dO, dQ, dK and dV each moved
once.
"""
from __future__ import annotations


def _heads(m: dict) -> tuple[int, int, int, int] | None:
    """(q heads, kv heads, d_qk, d_v) of the kernel's calls; None where no
    layer runs it."""
    if not m.get("n_heads") or m.get("arch_type") == "ssm" or m.get("window"):
        return None
    H = m["n_heads"]
    if m.get("attention_type") == "mla":   # k and v expanded to every head
        return H, H, m["qk_nope_dim"] + m["qk_rope_dim"], m["v_head_dim"]
    return H, m.get("n_kv_heads") or H, m["head_dim"], m["head_dim"]


def _rows(traffic: dict, chips: int) -> int:
    return traffic["rows_per_worker"] * traffic["workers"] // chips


def flash_result_shapes(m: dict, traffic: dict, chips: int) -> set:
    """The result shapes of one chip's flash kernels, as the trace names
    a custom call (a tuple by its first element): the forward's output
    ``(B, H, L, d_v)``, dQ ``(B, H, L, d_qk)`` and dK ``(B, Kh, L, d_qk)``."""
    heads = _heads(m)
    L = traffic["seq_len"]
    if heads is None or L % 128:
        return set()
    H, Kh, dqk, dv = heads
    B = _rows(traffic, chips)
    return {(B, H, L, dv), (B, H, L, dqk), (B, Kh, L, dqk)}


def flash_work(m: dict, traffic: dict, chips: int) -> tuple[float, float] | None:
    """(FLOPs, bytes) per chip per step of causal attention, forward and
    backward, counted as the module docstring says; None where no layer
    runs the kernel."""
    if not flash_result_shapes(m, traffic, chips):
        return None
    H, Kh, dqk, dv = _heads(m)
    L, B, layers = traffic["seq_len"], _rows(traffic, chips), m["n_layers"]
    itemsize = 2 if m["compute_dtype"] == "bfloat16" else 4
    flops = B * H * layers * L * L * (3 * dqk + 3 * dv)
    q_side = H * (dqk + dv + dv + dqk)       # q, o, dO, dQ
    kv_side = 2 * Kh * (dqk + dv)            # k, v, dK, dV
    nbytes = B * layers * L * (q_side + kv_side) * itemsize
    return float(flops), float(nbytes)
