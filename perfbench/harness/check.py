"""The comparison that decides ``correct``.

Set-up drives the compiled step from the seed through its first
``checked_steps`` steps, on distinct rows, and reads three things from that
same state: the mean loss of each step, the norm of each (worker, leaf) of
the momentum after step 1 (momentum SGD starts from zero, so that is the
first gradient as the optimizer got it), and the norm of each
(worker, leaf) of the parameters' change over the checked steps. Once the
window has closed and the program's state is freed, the plain float32
reference follows the same steps from the same weights and rows.

Each number compared is a gap of norms, not a norm of differences:
|program - reference| over the reference's norm of that leaf or of the
median leaf, whichever is larger, taken at the worst leaf. The median is
over the leaves the reference moves: under the configuration's bfloat16
storage an update below half a unit in the last place leaves a leaf (a
norm's scale, say) exactly where it was, in the reference as in the
program, and a leaf at 0 gives no scale. Leaves whose reference gradient
is under a thousandth of the median leaf's are left out of the change
(they move by round-off alone). The limits are per cell, in
``perfbench/limits/<cell>.json``.
"""
from __future__ import annotations

import json
import os

import jax
import numpy as np

from references.train_step import leaf_norms

ROUND_OFF_SHARE = 1e-3


def program_readings(init_replica):
    """``read(step, state, batches, key) -> (state, found)``: drive the
    compiled ``step`` through ``batches`` (the window's own call and feed)
    and read the mean loss of each step, the (workers, leaves) norms of
    the momentum after the first, and those of the parameters' change
    since the replica every worker started from. That replica is made
    again by the same compiled ``init_replica`` (so bit for bit the same)
    and handed to the difference as an argument, in its stored dtype."""
    norms = jax.jit(leaf_norms)
    change_norms = jax.jit(lambda p, r: leaf_norms(
        jax.tree.map(lambda x, y: x.astype(np.float32) - y.astype(np.float32),
                     p, r)))

    def read(step, state, batches, key):
        losses = []
        for k, batch in enumerate(batches):
            state, met = step(state, batch)
            losses.append(met.loss)
            if k == 0:
                grad = np.asarray(norms(state.opt_state))
        change = np.asarray(change_norms(state.params, init_replica(key)))
        return state, {"losses": np.asarray(jax.device_get(losses),
                                            np.float64),
                       "grad": grad, "change": change}
    return read


def worst_gap(prog: np.ndarray, ref: np.ndarray, keep=None) -> float:
    """The largest |program - reference| over the larger of the
    reference's value and the median of the values the reference does not
    leave at 0, over the entries in ``keep``."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if keep is None:
        keep = np.ones(ref.shape, bool)
    moved = ref[keep & (ref > 0)]
    scale = np.median(moved) if moved.size else 0.0
    with np.errstate(divide="ignore", invalid="ignore"):
        gap = (np.abs(prog - ref) / np.maximum(ref, scale))[keep]
    return float(np.max(gap)) if gap.size else 0.0


def gaps(prog: dict, ref: dict) -> dict:
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    keep = ref["grad"] >= ROUND_OFF_SHARE * np.median(ref["grad"])
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_gap": worst_gap(prog["grad"], ref["grad"]),
        "change_gap": worst_gap(prog["change"], ref["change"], keep),
    }


def load_limits(cell: str, bench_dir: str) -> dict:
    path = os.path.join(bench_dir, "limits", cell + ".json")
    with open(path) as f:
        return json.load(f)["limits"]


def judge(found: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and {name: [value, limit]} for every number compared;
    a number that is not finite fails."""
    checks = {k: [found[k], limits[k]] for k in limits}
    ok = all(np.isfinite(v) and v <= lim for v, lim in checks.values())
    return ok, checks
