"""Tiny versions of the benchmark's cells, for runs on the CPU."""
from __future__ import annotations

import copy
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import spec  # noqa: E402

TINY_MODEL = {
    "granite-3-2b": dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                         head_dim=16, d_ff=128, vocab_size=256),
    "mamba2-2.7b": dict(n_layers=2, d_model=64, vocab_size=256, ssm_state=16,
                        ssm_headdim=16, ssm_chunk=16),
}


# Limits at the toy size, set like the cells' own from readings at this
# size on the CPU (5 seeds of the program, 4 of the fp8 control, every
# worker from one replica, bf16 storage): granite loss 2.9e-5 / 1.0e-4,
# gradient 3.4e-3 / 5.2e-2, change 2.3e-2 / (faults from 0.59); mamba2
# loss 3.1e-5 / 1.0e-4, gradient 9.3e-3 / 4.3e-2, change 1.7e-2 /
# (faults from 0.50). The control fails the gradient in both.
TINY_LIMITS = {
    "granite-3-2b": {"loss_gap": 1e-4, "grad_gap": 1e-2, "change_gap": 0.12},
    "mamba2-2.7b": {"loss_gap": 1e-4, "grad_gap": 2.5e-2, "change_gap": 0.12},
}


# Cells the tests run that BENCHMARK.json does not hold: the four-chip ring
# (its path is tested here on four virtual CPU devices; on the chip it is
# not measured yet, see PERF.md).
EXTRA = {"granite-ring4-4chip": ("granite-3-2b", "ring4-mesh-uniform", 4)}


def load(name: str) -> spec.Cell:
    if name not in EXTRA:
        return spec.load(name, ROOT)
    config, traffic, chips = EXTRA[name]
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", traffic + ".json")) as f:
        tr = json.load(f)
    return spec.Cell(name, chips, cfg, tr, [], [])


def tiny_cell(name: str, seq_len: int = 64) -> spec.Cell:
    """The cell ``name`` at toy widths and a short sequence."""
    cell = load(name)
    cell.config = copy.deepcopy(cell.config)
    cell.config["model"].update(TINY_MODEL[cell.config["name"]])
    cell.traffic = dict(cell.traffic, seq_len=seq_len, batch_pool=8,
                        log_every=2, trace_steps=2)
    return cell
