"""The flash-attention readers: the work counted from shapes, and the
device time found in a hand-made trace and in the custom calls of each
attention cell's compiled step."""
import gzip
import os
import types

import pytest

from harness import attn_work, spec, trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _ctx(cell, ops, steps, chips):
    return types.SimpleNamespace(
        cell=cell, steps=steps, chips=chips,
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        ops_in_window=lambda: ops)


def _op(k, name, dur_ms=1.0):
    return T.Op(0, k * 1e6, dur_ms * 1e6, name, name.split(" ")[0])


@pytest.mark.parametrize("cell, gflop", [
    ("granite-ring4-1chip", 412.316860416),    # 4 rows x 32 heads x 2 layers
    ("granite-ring4-4chip", 103.079215104),    # 1 row a chip
    ("dsv2lite-ring4-4chip", 3092.37645312),   # 4 rows x 16 heads x 3 layers
])
def test_work_is_the_causal_minimum(cell, gflop):
    """L^2 (3 d_qk + 3 d_v) FLOPs per row, head and layer: forward QK^T
    and PV, backward dP, dV, dQ and dK over the causal half."""
    c = spec.load(cell)
    flops, _ = attn_work.flash_work(c.model, c.traffic, c.chips)
    assert flops == pytest.approx(gflop * 1e9, rel=1e-12)


def test_work_is_counted_from_shapes_by_hand():
    m = dict(n_heads=4, n_kv_heads=2, head_dim=8, n_layers=3,
             compute_dtype="bfloat16", arch_type="dense")
    tr = dict(rows_per_worker=2, seq_len=256, workers=4)
    # 4 workers' 2 rows on one chip; 2 rows a chip on four
    assert attn_work.flash_result_shapes(m, tr, 1) == {(8, 4, 256, 8),
                                                       (8, 2, 256, 8)}
    flops, nbytes = attn_work.flash_work(m, tr, 4)
    assert flops == 2 * 4 * 3 * 256 ** 2 * (3 * 8 + 3 * 8)
    # q, o, dO, dQ over 4 heads; k, v, dK, dV over 2; 2 bytes each
    assert nbytes == 2 * 3 * 256 * (4 * 8 * 4 + 2 * 8 * 4) * 2
    mla = dict(m, attention_type="mla", qk_nope_dim=16, qk_rope_dim=8,
               v_head_dim=8)
    assert attn_work.flash_result_shapes(mla, tr, 4) == {(2, 4, 256, 8),
                                                         (2, 4, 256, 24)}
    for no in (dict(m, window=64), dict(m, arch_type="ssm"),
               dict(m, n_heads=0)):
        assert attn_work.flash_work(no, tr, 4) is None
    assert attn_work.flash_work(m, dict(tr, seq_len=200), 4) is None


def test_readers_on_a_known_trace():
    cell = spec.load("granite-ring4-1chip")
    ops = [_op(0, "custom-call:tpu_custom_call (bf16[4,32,2048,64],...)", 2),
           _op(2, "custom-call:tpu_custom_call bf16[4,32,2048,64]", 3),
           _op(5, "custom-call:tpu_custom_call (bf16[4,8,2048,64],...)", 4),
           _op(9, "custom-call:tpu_custom_call bf16[2048,128]", 5),
           _op(14, "fusion bf16[4,32,2048,64]", 6),
           _op(20, "custom-call:tpu_custom_call bf16[1,32,2048,64]", 7)]
    ctx = _ctx(cell, ops, steps=3, chips=1)
    read = spec.metric_reader
    assert read("flash_attention.ms")(ctx) == pytest.approx(3.0)   # 9 / 3
    flops, nbytes = attn_work.flash_work(cell.model, cell.traffic, 1)
    assert read("flash_attention_roofline")(ctx) == pytest.approx(
        100 * max(flops / 1e12, nbytes / 1e9) / 3e-3)
    ctx = _ctx(cell, ops[3:5], steps=3, chips=1)
    assert read("flash_attention.ms")(ctx) is None
    assert read("flash_attention_roofline")(ctx) is None


def _recorded_calls(cell):
    path = os.path.join(DATA, cell + ".step-custom-calls.txt.gz")
    with gzip.open(path, "rt") as f:
        return [ln for ln in f.read().splitlines() if ln]


@pytest.mark.parametrize("cell, scanned_segments", [
    ("granite-ring4-1chip", 1), ("granite-ring4-4chip", 1),
    ("dsv2lite-ring4-4chip", 2)])
def test_readers_find_the_kernels_of_the_compiled_step(cell, scanned_segments):
    """The Pallas custom calls of the cell's step as the TPU compiler builds
    it for a described v5e (``data/<cell>.step-custom-calls.txt.gz``, each
    instruction compacted as the trace names it): the reader takes the
    three ``flash_attention`` kernels of each scanned segment of layers
    (the forward, which remat keeps, dQ and dK/dV), and leaves the gossip
    mix and the grouped matmuls out. dsv2lite's list joins the attention
    kernels of its step (compiled with the residual stream and experts
    narrowed, which leaves their shapes as the cell's) to the grouped
    matmuls recorded with ``dsv2lite-ring4-4chip.custom-calls.txt.gz``."""
    insts = _recorded_calls(cell)
    flash = [t for t in insts if t.startswith("%flash_attention")]
    assert len(flash) == 3 * scanned_segments
    assert len(insts) > len(flash)
    ops = [_op(k, T.stable_name(t)[0]) for k, t in enumerate(insts)]
    c = spec.load(cell)
    # one chip's calls, 1 ms each, in a window of one step on c.chips
    ctx = _ctx(c, ops, steps=1, chips=c.chips)
    ms = len(flash) / c.chips
    assert spec.metric_reader("flash_attention.ms")(ctx) == pytest.approx(ms)
    flops, nbytes = attn_work.flash_work(c.model, c.traffic, c.chips)
    assert spec.metric_reader("flash_attention_roofline")(ctx) == \
        pytest.approx(100 * max(flops / 1e12, nbytes / 1e9) / (ms * 1e-3))


def test_mamba2_reads_nothing():
    """No attention in mamba2: the readers return None, there on the
    recorded granite calls and on its own recorded trace."""
    mamba = spec.load("mamba2-ring4-1chip")
    insts = _recorded_calls("granite-ring4-1chip")
    ops = [_op(k, T.stable_name(t)[0]) for k, t in enumerate(insts)]
    for name in ("flash_attention.ms", "flash_attention_roofline"):
        assert spec.metric_reader(name)(_ctx(mamba, ops, 1, 1)) is None
    with gzip.open(os.path.join(DATA, "granite-ring4-1chip.trace.json.gz"),
                   "rt") as f:
        import json
        tr = T.reduce(json.load(f))
    ctx = _ctx(spec.load("granite-ring4-1chip"), tr.ops, 2, 1)
    # the recorded trace predates the kernel: the granite cell reads None
    assert spec.metric_reader("flash_attention.ms")(ctx) is None
