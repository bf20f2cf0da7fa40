"""The trace reduction: interval arithmetic, stable names, and the
per-layer readers on a hand-made trace whose answers are known."""
import types

import pytest

from harness import spec, trace as T


def test_union_subtract_clip():
    u = T.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert u == [[0, 3], [5, 9]]
    assert T.subtract([(0, 10)], u) == [(3, 5), (9, 10)]
    assert T.clip(u, 1, 6) == [(1, 3), (5, 6)]
    assert T.length(T.subtract([(0, 3)], [[0, 3]])) == 0


@pytest.mark.parametrize("hlo,want", [
    ("%fusion.731 = bf16[4,2048,49155]{1,2,0:T(8,128)(2,1)} fusion(bf16[4] "
     "%p), kind=kOutput, calls=%fused_computation.45",
     ("fusion bf16[4,2048,49155]", "fusion")),
    ("%step.1 = bf16[6947328,128]{1,0:T(8,128)(2,1)} custom-call(s32[8] %c),"
     ' custom_call_target="tpu_custom_call", operand_layout_constraints={}',
     ("custom-call:tpu_custom_call bf16[6947328,128]",
      "custom-call:tpu_custom_call")),
    ("%collective-permute-start.3 = (bf16[1736832,128]{1,0}, bf16[1736832,"
     "128]{1,0}) collective-permute-start(bf16[1736832,128]{1,0} %x)",
     ("collective-permute-start (bf16[1736832,128],...)",
      "collective-permute-start")),
    ("%all-reduce.1 = f32[]{:T(128)} all-reduce(f32[] %x), to_apply=%add",
     ("all-reduce f32[]", "all-reduce")),
])
def test_stable_names_drop_hlo_numbers(hlo, want):
    assert T.stable_name(hlo) == want
    assert T.stable_name(T.compact(hlo)) == want


def _ctx(ops, window, steps=2, chips=1):
    tr = T.Trace(ops=ops, host=[], window=window,
                 devices=sorted({o.device for o in ops}))
    lo, hi = window
    busy = [T.length(T.busy_intervals(tr, d)) for d in tr.devices]
    return types.SimpleNamespace(
        trace=tr, steps=steps, chips=chips, window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy) / len(busy) * 1e-9, tokens_per_s=1000.0,
        flops_per_token=1e9,
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        mix_work=(0.0, 2e6),
        ops_in_window=lambda: [o for o in ops
                               if lo <= o.start_ns and o.end_ns <= hi])


def _op(start_ms, dur_ms, name, dev=0):
    op = name.split(" ")[0]
    return T.Op(dev, start_ms * 1e6, dur_ms * 1e6, name, op)


def test_readers_on_a_known_trace():
    # 10 ms window: compute 0-4, kernel 4-6, permute 5-8, idle 8-10
    ops = [_op(0, 4, "dot_general"), _op(4, 2, "custom-call:tpu_custom_call bf16[64,128]"),
           _op(5, 3, "collective-permute-done bf16[64,128]")]
    ctx = _ctx(ops, (0.0, 10e6))
    read = spec.metric_reader
    assert read("idle_share")(ctx) == pytest.approx(20.0)
    assert read("gossip_mix.ms")(ctx) == pytest.approx(1.0)      # 2 ms / 2
    # 2e6 bytes at 1e9 B/s = 2 ms per step, over 1 ms measured: 200 %,
    # the reading that tells the bytes are counted too high
    assert read("gossip_mix_roofline")(ctx) == pytest.approx(200.0)
    assert read("mfu")(ctx) == pytest.approx(100.0)


def test_readers_find_nothing_without_their_ops():
    ctx = _ctx([_op(0, 4, "dot_general")], (0.0, 10e6))
    for name in ("gossip_mix.ms", "gossip_mix_roofline"):
        assert spec.metric_reader(name)(ctx) is None


def test_breakdown_names_idle_gaps_by_host_span():
    ops = [_op(0, 4, "dot_general"), _op(6, 4, "dot_general")]
    tr = T.Trace(ops, [("window", 0, 10e6), ("wait", 3e6, 4e6)], (0, 10e6),
                 [0])
    b = T.breakdown(tr)
    assert b["device_ops"] == [["dot_general", pytest.approx(0.008)]]
    assert b["idle_gaps"] == [["wait", pytest.approx(0.002)]]


def _recorded():
    import gzip
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "data",
                        "granite-ring4-1chip.trace.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_recorded_chip_trace():
    """Two steps of granite-ring4-1chip traced on a TPU v5e, names
    compacted to what the reduction reads (tests/data)."""
    raw = _recorded()
    tr = T.reduce(raw)
    lo, hi = tr.window
    assert tr.devices == [0]
    # the same numbers, counted here without the reduction's helpers
    ops = [e for p in raw if p["plane"] == "/device:TPU:0"
           for ln in p["lines"] if ln["name"] == "XLA Ops"
           for e in ln["events"]]
    kernel = [e for e in ops if 'custom_call_target="tpu_custom_call"'
              in e["name"] and lo <= e["start_ns"]
              and e["start_ns"] + e["duration_ns"] <= hi]
    assert len(kernel) == 2                 # one gossip_mix per step
    ms = sum(e["duration_ns"] for e in kernel) * 1e-6 / 2
    edges = sorted((max(e["start_ns"], lo), min(e["start_ns"]
                    + e["duration_ns"], hi)) for e in ops
                   if e["start_ns"] + e["duration_ns"] > lo
                   and e["start_ns"] < hi)
    busy, end = 0.0, lo
    for s, e in edges:
        if e > end:
            busy += e - max(s, end)
            end = e
    ctx = _ctx(tr.ops, tr.window, steps=2)
    read = spec.metric_reader
    assert read("gossip_mix.ms")(ctx) == pytest.approx(ms)
    assert 20.0 < ms < 23.0                 # 21.2 ms over 16 steps
    assert read("idle_share")(ctx) == pytest.approx(
        100 * (1 - busy / (hi - lo)))
    assert 0.0 < read("idle_share")(ctx) < 1.0
    top = T.breakdown(tr)["device_ops"]
    assert len(top) == 10 and all("." not in n.split(" ")[0] for n, _ in top)
