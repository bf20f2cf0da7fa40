"""The grouped-matmul readers: the work counted from shapes, and the
device time found in a hand-made trace and in recorded chip traces."""
import gzip
import json
import os
import types

import pytest

from harness import moe_work, spec, trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "dsv2lite-ring4-4chip"


def _recorded(name):
    with gzip.open(os.path.join(DATA, name + ".trace.json.gz"), "rt") as f:
        return T.reduce(json.load(f))


def _ctx(cell, ops, window, steps, chips):
    lo, hi = window
    return types.SimpleNamespace(
        cell=cell, steps=steps, chips=chips,
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        ops_in_window=lambda: [o for o in ops
                               if lo <= o.start_ns and o.end_ns <= hi])


def _op(start_ms, dur_ms, name, dev=0):
    return T.Op(dev, start_ms * 1e6, dur_ms * 1e6, name, name.split(" ")[0])


def test_work_is_counted_from_shapes_by_hand():
    m = dict(d_model=4, d_ff_expert=3, n_experts=2, router_experts=8,
             top_k=4, n_layers=3, first_dense_layers=1,
             compute_dtype="bfloat16")
    tr = dict(rows_per_worker=2, seq_len=8, workers=4)
    # 16 tokens x 4 picks, 2 of 8 experts held: 16 pairs a layer
    assert moe_work.gmm_result_shapes(m, tr) == {(32, 3), (32, 4),
                                                 (2, 4, 3), (2, 3, 4)}
    flops, nbytes = moe_work.gmm_work(m, tr, chips=4)
    # 3 matmuls of 16 x 4 x 3, forward and two backward, 2 expert layers
    assert flops == 2 * 3 * 3 * 2 * 16 * 4 * 3
    x, h, w = 16 * 4, 16 * 3, 2 * 4 * 3
    fwd = (x + w + h) * 2 + (h + w + x)
    bwd = 3 * 2 * (x + h + w)
    assert nbytes == 2 * (fwd + bwd) * 2
    assert moe_work.gmm_work(dict(m, n_experts=0), tr, 4) is None


def test_readers_on_a_known_trace():
    cell = spec.load(CELL)
    P = 4 * 4096 * 6
    ops = [_op(0, 2, f"custom-call:tpu_custom_call bf16[{P},1408]"),
           _op(2, 1, f"custom-call:tpu_custom_call bf16[{P},2048]"),
           _op(3, 1, "custom-call:tpu_custom_call bf16[8,2048,1408]"),
           _op(4, 1, "custom-call:tpu_custom_call (s32[9],...)"),
           _op(5, 3, "custom-call:tpu_custom_call bf16[64,128]"),
           _op(8, 1, "fusion bf16[98304,1408]")]
    ctx = _ctx(cell, ops, (0.0, 10e6), steps=2, chips=1)
    read = spec.metric_reader
    assert read("moe_gmm.ms")(ctx) == pytest.approx(2.0)       # 4 ms / 2
    flops, nbytes = moe_work.gmm_work(cell.model, cell.traffic, 1)
    bound = max(flops / 1e12, nbytes / 1e9)
    assert read("moe_gmm_roofline")(ctx) == pytest.approx(
        100 * bound / 2e-3)
    none = _ctx(cell, ops[3:], (0.0, 10e6), steps=2, chips=1)
    assert read("moe_gmm.ms")(none) is None
    assert read("moe_gmm_roofline")(none) is None


def test_granite_has_no_grouped_matmul():
    """On the recorded granite trace (its only Pallas kernel is the
    gossip mix) and in a cell without experts the readers find nothing."""
    tr = _recorded("granite-ring4-1chip")
    ctx = _ctx(spec.load("granite-ring4-1chip"), tr.ops, tr.window, 2, 1)
    assert spec.metric_reader("moe_gmm.ms")(ctx) is None
    assert spec.metric_reader("moe_gmm_roofline")(ctx) is None
    # the DeepSeek cell's shapes do not turn up there either
    ctx.cell = spec.load(CELL)
    assert spec.metric_reader("moe_gmm.ms")(ctx) is None


def test_reader_finds_the_grouped_matmuls_of_the_compiled_step():
    """The Mosaic custom calls of the cell's step as the TPU compiler
    builds it for a described v5e 2x2 (``data/dsv2lite-ring4-4chip.
    custom-calls.txt.gz``, each instruction compacted as the trace names
    it): the reader takes the 12 ``ragged-dot-none`` kernels (gate, up and
    down forward, recomputed forward, the inputs' and the weights'
    gradients) and leaves the 3 ``ragged-dot-metadata`` ones out."""
    path = os.path.join(DATA, CELL + ".custom-calls.txt.gz")
    with gzip.open(path, "rt") as f:
        insts = [ln for ln in f.read().splitlines() if ln]
    ops = [_op(k, 1, T.stable_name(t)[0]) for k, t in enumerate(insts)]
    ragged = [t for t in insts if t.startswith("%ragged-dot-none")]
    assert len(insts) == 15 and len(ragged) == 12
    ctx = _ctx(spec.load(CELL), ops, (0.0, len(ops) * 1e6), steps=1,
               chips=1)
    assert spec.metric_reader("moe_gmm.ms")(ctx) == pytest.approx(12.0)
