"""The DeepSeek-V2-Lite cell at a size a test run holds, on four virtual
CPU devices in a child, as ``test_correct.py`` runs the four-chip ring: the
program agrees with the plain reference, and comes out not correct with
the timed path broken underneath (the harness's faults, and three of the
layer's own planted here), or with the control in its place."""
import copy
import dataclasses
import json
import os
import subprocess
import sys
import time

import pytest

import tiny
from harness import check, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "dsv2lite-ring4-4chip"

# Published shapes kept where they shape the layer (top-6 of a wider
# router, 2 shared experts, 1 dense layer before the expert ones, YaRN),
# the widths cut to toy size.
TINY_MODEL = dict(n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
                  head_dim=16, d_ff=128, vocab_size=256, kv_lora_rank=32,
                  qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
                  d_ff_expert=32, router_experts=16, n_experts=4, top_k=6)

# Set from CPU readings at this size (8 seeds of the sound program: loss
# up to 2.6e-5, gradient 9.9e-3, change 5.3e-2; the gradient moves most
# where routing near the top-6 boundary flips between bf16 and float32),
# with room below the faults (from loss 1.1e-4, gradient 6.4e-2 or change
# 0.50) and the control.
TINY_LIMITS = {"loss_gap": 1e-4, "grad_gap": 2.5e-2, "change_gap": 0.12}

HARNESS_FAULTS = ["frozen", "drop_half", "no_mix", "no_momentum"]
LAYER_FAULTS = ["renorm_topk", "no_mscale", "no_shared"]


def tiny_cell() -> spec.Cell:
    cell = spec.load(CELL, tiny.ROOT)
    cell.config = copy.deepcopy(cell.config)
    cell.config["model"].update(TINY_MODEL)
    cell.traffic = dict(cell.traffic, seq_len=64, batch_pool=8,
                        log_every=2, trace_steps=2)
    return cell


def plant(fault: str) -> None:
    """Break the program's expert layer or attention the way ``fault``
    says (the reference is left as it is)."""
    import numpy as np

    from repro.models import attention, layers

    if fault == "renorm_topk":          # top-k weights renormalised to 1
        route = layers._route
        layers._route = lambda r, cfg, *a: route(
            r, dataclasses.replace(cfg, norm_topk_prob=True), *a)
    elif fault == "no_mscale":          # YaRN's softmax scale left out
        attention.mla_softmax_scale = lambda cfg: 1.0 / np.sqrt(
            cfg.qk_nope_dim + cfg.qk_rope_dim)
    elif fault == "no_shared":          # the shared experts left out
        layers._shared_expert = lambda p, cfg, x: 0 * x
    else:
        raise ValueError(fault)


def _run(fault=None):
    import jax

    harness_fault = fault if fault in HARNESS_FAULTS else None
    if fault is not None and harness_fault is None:
        plant(fault)
    from harness.cell import run_cell

    cell = tiny_cell()
    return run_cell(cell, jax.devices()[:cell.chips], seed=2**31 + 11,
                    seconds=0.2, trace=False, t_start=time.perf_counter(),
                    fault=harness_fault, limits=TINY_LIMITS)


def _in_child(fault):
    code = ("import json, sys; sys.path.insert(0, %r); import test_dsv2lite;"
            "print(json.dumps(test_dsv2lite._run(%r)))" % (HERE, fault))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct():
    r = _in_child(None)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0


@pytest.mark.parametrize("fault", HARNESS_FAULTS + LAYER_FAULTS)
def test_broken_step_is_not_correct(fault):
    r = _in_child(fault)
    assert not r["correct"], r["checks"]


def test_control_is_not_correct():
    import control

    gaps = control.readings(tiny_cell(), 3, [("fp8", {"prec": "fp8"})])["fp8"]
    gaps.pop("seconds")
    ok, checks = check.judge(gaps, TINY_LIMITS)
    assert not ok, checks


def test_configuration_runs_the_published_numbers():
    """The program's and the reference's sizes (``model``) are the
    published config's, as the file states them at its top level."""
    cfg = spec.load(CELL, tiny.ROOT).config
    m, rs = cfg["model"], cfg["rope_scaling"]
    pairs = {"hidden_size": "d_model", "intermediate_size": "d_ff",
             "kv_lora_rank": "kv_lora_rank",
             "moe_intermediate_size": "d_ff_expert",
             "n_shared_experts": "n_shared_experts",
             "norm_topk_prob": "norm_topk_prob",
             "num_attention_heads": "n_heads",
             "num_experts_per_tok": "top_k",
             "num_hidden_layers": "n_layers",
             "num_key_value_heads": "n_kv_heads",
             "qk_nope_head_dim": "qk_nope_dim",
             "qk_rope_head_dim": "qk_rope_dim", "rms_norm_eps": "norm_eps",
             "rope_theta": "rope_theta", "v_head_dim": "v_head_dim",
             "vocab_size": "vocab_size", "n_routed_experts": "n_experts",
             "first_k_dense_replace": "first_dense_layers"}
    for k, v in pairs.items():
        assert cfg[k] == m[v], k
    assert m["router_experts"] == cfg["published"]["n_routed_experts"]
    assert (rs["factor"], rs["original_max_position_embeddings"],
            rs["beta_fast"], rs["beta_slow"], rs["mscale"],
            rs["mscale_all_dim"]) == (
        m["rope_scaling_factor"], m["rope_original_max_pos"],
        m["yarn_beta_fast"], m["yarn_beta_slow"], m["yarn_mscale"],
        m["yarn_mscale_all_dim"])
    assert cfg["seq_aux"] and m["moe_aux"] == "seq"
    assert cfg["tie_word_embeddings"] == m["tie_embeddings"]
    assert cfg["q_lora_rank"] is None and cfg["topk_method"] == "greedy"
    assert cfg["scoring_func"] == "softmax"
    assert cfg["routed_scaling_factor"] == 1
