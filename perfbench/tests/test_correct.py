"""``correct`` at a size a test run holds, on the CPU: the program agrees
with the plain reference, and comes out not correct with the timed path
broken underneath or with the control in its place."""
import json
import os
import subprocess
import sys
import time

import pytest

import tiny
from harness import check
from harness.cell import run_cell

CELLS = ["granite-ring4-1chip", "mamba2-ring4-1chip", "granite-ring4-4chip"]
FAULTS = ["frozen", "drop_half", "no_mix", "no_momentum"]  # each cell's


def _run(name, fault=None):
    import jax

    cell = tiny.tiny_cell(name)
    return run_cell(cell, jax.devices()[:cell.chips], seed=2**31 + 7,
                    seconds=0.2, trace=False, t_start=time.perf_counter(),
                    fault=fault,
                    limits=tiny.TINY_LIMITS[cell.config["name"]])


def _in_child(name, fault):
    """Cells on four chips run on four virtual CPU devices, in a child."""
    code = ("import json, sys; sys.path.insert(0, %r); import test_correct;"
            "print(json.dumps(test_correct._run(%r, %r)))"
            % (os.path.dirname(os.path.abspath(__file__)), name, fault))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _result(name, fault=None):
    if tiny.load(name).chips > 1:
        return _in_child(name, fault)
    return _run(name, fault)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    r = _result(name)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("name,fault",
                         [(c, f) for c in CELLS for f in FAULTS])
def test_broken_step_is_not_correct(name, fault):
    r = _result(name, fault)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    """The reference at fp8 in the program's place fails a limit (the
    chip readings at the cells' own size are in PERF.md)."""
    import control

    cell = tiny.tiny_cell(name)
    gaps = control.readings(cell, 3, [("fp8", {"prec": "fp8"})])["fp8"]
    gaps.pop("seconds")
    ok, checks = check.judge(gaps, tiny.TINY_LIMITS[cell.config["name"]])
    assert not ok, checks
