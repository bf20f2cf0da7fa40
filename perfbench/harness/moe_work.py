"""Operations and bytes of the expert layer's grouped matmuls, from shapes
and the expected held share alone (never from ``cost_analysis``).

Each worker's step routes ``tokens x top_k`` (token, expert) pairs over
all ``router_experts``; the chip holds ``n_experts`` of them, so it expects
``tokens x top_k x n_experts / router_experts`` pairs per expert layer. A
held pair runs the expert's SwiGLU: x W_gate and x W_up (D -> F_e) and
h W_down (F_e -> D). The backward takes, for each of the three, the input's
gradient (g W^T) and the weight's (x^T g). Recomputation is not counted.
"""
from __future__ import annotations


def _has_experts(m: dict) -> bool:
    return bool(m.get("n_experts")) and bool(m.get("top_k"))


def gmm_result_shapes(m: dict, traffic: dict) -> set:
    """The result shapes of one worker's grouped matmuls: the pair buffer
    of ``tokens x min(top_k, n_experts)`` rows through each projection
    (forward and the input's gradient), and each held expert's weight
    gradient."""
    if not _has_experts(m):
        return set()
    D, F, E = m["d_model"], m["d_ff_expert"], m["n_experts"]
    rows = traffic["rows_per_worker"] * traffic["seq_len"] \
        * min(m["top_k"], E)
    return {(rows, F), (rows, D), (E, D, F), (E, F, D)}


def gmm_work(m: dict, traffic: dict, chips: int) -> tuple[float, float] | None:
    """(FLOPs, bytes) per chip per step of the grouped matmuls, forward
    and backward, at the expected held share; None without experts."""
    if not _has_experts(m):
        return None
    D, F, E = m["d_model"], m["d_ff_expert"], m["n_experts"]
    itemsize = 2 if m["compute_dtype"] == "bfloat16" else 4
    tokens = traffic["rows_per_worker"] * traffic["seq_len"]
    pairs = tokens * m["top_k"] * E / (m.get("router_experts") or E)
    layers = m["n_layers"] - m.get("first_dense_layers", 0)
    per_worker_layer_flops = 3 * 3 * 2.0 * pairs * D * F   # 3 mats x fwd+2 bwd
    x, h, w = pairs * D, pairs * F, E * D * F              # elements
    fwd = 2 * (x + w + h) + (h + w + x)                    # gate, up; down
    bwd = 3 * (2 * (x + h + w))                            # per mat: dx, dw
    per_worker_layer_bytes = (fwd + bwd) * itemsize
    workers_per_chip = traffic["workers"] / chips
    n = layers * workers_per_chip
    return per_worker_layer_flops * n, per_worker_layer_bytes * n
