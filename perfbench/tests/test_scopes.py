"""Attribution of a traced window to the program's layers, on a hand-made
trace and compiled-step text whose answers are known, and on a recorded
chip trace."""
import gzip
import json
import os

import pytest

from harness import scopes as S

HLO = """\
HloModule jit_step, is_scheduled=true, entry_computation_layout={()->()}

FileNames
1 "model.py"

StackFrames
1 {file_location_id=1 parent_frame_id=1}

%fused_computation.3 (p: f32[]) -> bf16[4,8] {
  %pad.1 = bf16[32]{0} pad(f32[] %p), \
metadata={op_name="jit(step)/gossip/shard_map/pack/jit(_pad)/pad"}
  ROOT %bitcast.2 = bf16[4,8]{1,0} bitcast(bf16[32]{0} %pad.1)
}

%fused_computation.4 (p: bf16[4,8]) -> bf16[4,8] {
  ROOT %dynamic-update-slice.3 = bf16[4,8]{1,0} dynamic-update-slice(\
bf16[4,8]{1,0} %p)
}

%body.1 (p: f32[]) -> f32[] {
  %fusion.2 = bf16[4,8]{1,0} fusion(f32[] %p), kind=kLoop, calls=%f, \
metadata={op_name="jit(step)/model/vmap(transpose(jvp()))/while/body/\
closed_call/attention/dot_general" stack_frame_id=1}
}

ENTRY %main.9 (a: f32[]) -> f32[] {
  %fusion.1 = bf16[4,8]{1,0} fusion(f32[] %a), kind=kOutput, \
metadata={op_name="jit(step)/model/vmap(jvp(lm_head))/dot_general"}
  %while.3 = f32[] while(f32[] %a), body=%body.1, \
metadata={op_name="jit(step)/model/vmap(transpose(jvp()))/while"}
  %gossip_mix.4 = bf16[64,128]{1,0} custom-call(f32[] %a), \
custom_call_target="tpu_custom_call", \
metadata={op_name="jit(step)/gossip/mix/gossip_mix"}
  %copy.5 = bf16[4,8]{1,0} copy(f32[] %a), metadata={op_name="state.step"}
  %copy-start.11 = (bf16[4,8]{1,0}, u32[]) copy-start(f32[] %a)
  %copy-done.12 = bf16[4,8]{1,0} copy-done((bf16[4,8]{1,0}, u32[]) \
%copy-start.11)
  %fusion.6 = f32[] fusion(bf16[4,8]{1,0} %copy-done.12), kind=kLoop, \
metadata={op_name="jit(step)/stats/reduce_sum"}
  %fusion.7 = bf16[4,8]{1,0} fusion(f32[] %a), kind=kLoop, \
metadata={op_name="jit(step)/attention/sin"}
  %fusion.8 = bf16[4,8]{1,0} fusion(f32[] %a), kind=kLoop, \
calls=%fused_computation.3
  %fusion.9 = bf16[4,8]{1,0} fusion(bf16[4,8]{1,0} %fusion.8), kind=kLoop, \
calls=%fused_computation.4
  ROOT %fusion.10 = (f32[], bf16[4,8]) fusion(bf16[4,8]{1,0} %fusion.9), \
kind=kLoop, metadata={op_name="jit(step)/model/convert_element_type"}
}
"""

EVENT = {
    "fusion.1": "%fusion.1 = bf16[4,8]{1,0} fusion(",
    "fusion.2": "%fusion.2 = bf16[4,8]{1,0} fusion(",
    "while.3": "%while.3 = f32[] while(",
    "gossip_mix.4": "%gossip_mix.4 = bf16[64,128]{1,0} custom-call(",
    "copy.5": "%copy.5 = bf16[4,8]{1,0} copy(",
    "fusion.6": "%fusion.6 = f32[] fusion(",
    "fusion.7": "%fusion.7 = bf16[4,8]{1,0} fusion(",
    "fusion.8": "%fusion.8 = bf16[4,8]{1,0} fusion(",
    "copy-done.12": "%copy-done.12 = bf16[4,8]{1,0} copy-done(",
}
MS = 1e6


def _ev(name, start_ms, dur_ms):
    return {"name": name, "start_ns": start_ms * MS,
            "duration_ns": dur_ms * MS, "stats": {}}


def _one_step(t0):
    """One step of 10 ms: (instruction, start, duration) in ms."""
    return [("fusion.1", t0 + 0, 2), ("while.3", t0 + 2, 3),
            ("fusion.2", t0 + 2, 3), ("gossip_mix.4", t0 + 5, 1),
            ("copy.5", t0 + 6, 0.5), ("fusion.6", t0 + 6.5, 1),
            ("fusion.7", t0 + 7.5, 0.5), ("fusion.8", t0 + 8, 1.5),
            ("copy-done.12", t0 + 9.5, 0.5)]


def _raw(devices=(0,), steps=2, other_module=True):
    planes = []
    for d in devices:
        ops, mods = [], []
        for k in range(steps):
            t0 = 1 + 11 * k
            mods.append(_ev("jit_step(123)", t0, 10))
            ops += [_ev(EVENT[i], s, dur) for i, s, dur in _one_step(t0)]
        if other_module:        # a transfer program reusing a name
            t = 1 + 11 * steps
            mods.append(_ev("jit_convert_element_type(9)", t, 1))
            ops.append(_ev(EVENT["fusion.1"], t, 1))
        planes.append({"plane": f"/device:TPU:{d}", "lines": [
            {"name": "XLA Modules", "events": mods},
            {"name": "XLA Ops", "events": ops}]})
    planes.append({"plane": "/host:CPU", "lines": [{"name": "python3",
                   "events": [_ev("window", 0, 1 + 11 * steps + 2)]}]})
    return planes


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/model/vmap(jvp(lm_head))/while/body/dot_general",
     ("model_fwd", "model/lm_head")),
    ("jit(step)/model/vmap(transpose(jvp()))/while/body/closed_call/"
     "attention/transpose", ("model_bwd", "model/attention")),
    ("jit(step)/model/vmap(jvp())/jit(take_along_axis)/gather",
     ("model_fwd", "model")),
    ("jit(step)/attention/sin", ("model_fwd", "model/attention")),
    ("jit(step)/gossip/shard_map/pack/concatenate", ("gossip", "gossip/pack")),
    ("jit(step)/gossip/mix/gossip_mix/while/body/add", ("gossip", "gossip/mix")),
    ("jit(step)/optimizer/mul", ("optimizer", "optimizer")),
    ("jit(step)/stats/reduce_sum", ("stats", "stats")),
    # the first step scope wins; a jitted function's name is no scope
    ("jit(step)/gossip/optimizer/add", ("gossip", "gossip")),
    ("jit(step)/jit(model)/add", ("unscoped", "")),
    ("jit(step)/reduce_sum", ("unscoped", "")),
    ("", ("unscoped", "")),
])
def test_layer_of_an_op_name(op_name, want):
    assert S.layer_of(op_name) == want


def test_op_names_and_module_of_the_text():
    names = S.op_names(HLO)
    assert S.module_name(HLO) == "jit_step"
    assert names["fusion.2"].endswith("/attention/dot_general")
    # only an argument's path, no named neighbour: unscoped
    assert "copy.5" not in names
    # a fusion without metadata: the op nearest its root
    assert names["fusion.8"].endswith("/pack/jit(_pad)/pad")
    # a move XLA put in: the layer that consumes its result
    assert names["copy-start.11"] == names["copy-done.12"] \
        == names["fusion.6"]
    assert names["bitcast.2"] == names["pad.1"]    # from its operand
    # a fusion with no names inside: what it reads before what reads it
    assert names["fusion.9"] == names["fusion.8"]


def test_strip_metadata_leaves_the_instructions():
    bare = S.strip_metadata(HLO)
    assert "metadata=" not in bare and "StackFrames" not in bare
    assert "file_location_id" not in bare
    renamed = HLO.replace("jit(step)/stats", "jit(step)/other")
    assert renamed != HLO
    assert S.strip_metadata(renamed) == bare
    assert "%copy.5 = bf16[4,8]{1,0} copy(" in bare


def test_attribute_splits_a_known_trace_per_step():
    tr = S.attribute(S.reduce(_raw()), HLO)
    found = S.per_step(tr, steps=2, chips=1)
    assert found["model_fwd"] == pytest.approx(2.5)     # fusion.1 + hoisted
    assert found["model_bwd"] == pytest.approx(3.0)     # the loop body's op
    assert found["gossip"] == pytest.approx(2.5)        # kernel 1 + pack
    assert found["stats"] == pytest.approx(1.5)         # with its copy
    assert found["unscoped"] == pytest.approx(0.5)      # the copy
    assert found["optimizer"] is None                   # none ran
    # the while container is not counted, the other module's op neither,
    # but both are busy: 10 ms per step, plus 1 ms of transfer over 2 steps
    assert found["busy"] == pytest.approx(10.5)
    assert found["subs"]["gossip/pack"] == pytest.approx(1.5)
    assert found["subs"]["model/attention"] == pytest.approx(3.5)
    layers = sum(found[k] or 0 for k in S.LAYERS + (S.UNSCOPED,))
    assert layers == pytest.approx(10.0)
    other = [o for o in tr.ops if o.start_ns >= 23 * MS]
    assert other and all(o.scope == "" for o in other)
    assert [o.scope for o in tr.ops if o.category == "while"] == ["", ""]
    assert S.top_ops(tr, 2, 1, S.UNSCOPED) == [("copy bf16[4,8]", 0.5)]
    assert S.top_ops(tr, 2, 1, "gossip", min_ms=1.2) == [
        ("fusion bf16[4,8]", 1.5)]
    assert S.line(found).startswith("scopes: ms/step/chip model_fwd 2.500 ")


def test_attribute_divides_by_chips():
    tr = S.attribute(S.reduce(_raw(devices=(0, 1), other_module=False)), HLO)
    found = S.per_step(tr, steps=2, chips=2)
    assert found["gossip"] == pytest.approx(2.5)
    assert found["busy"] == pytest.approx(10.0)


def test_a_program_without_scopes_reads_unscoped():
    bare = S.strip_metadata(HLO)
    tr = S.attribute(S.reduce(_raw()), bare, module="jit_step")
    found = S.per_step(tr, steps=2, chips=1)
    assert all(found[k] is None for k in S.LAYERS)
    assert found["unscoped"] == pytest.approx(10.0)


FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "granite-ring4-1chip.scopes.json.gz")


def test_recorded_chip_trace_sums_to_busy():
    """The first two steps of a sixteen-step window of granite-ring4-1chip
    traced on a TPU v5e, with the compiled step's instruction -> op_name
    map of their ops as ``harness.scopes.op_names`` resolved it, as
    ``perfbench/scope_profile.py --out`` writes them (tests/data)."""
    with gzip.open(FIXTURE, "rt") as f:
        rec = json.load(f)
    text = "HloModule {},\n".format(rec["module"]) + "\n".join(
        f'  %{k} = f32[] add(), metadata={{op_name="{v}"}}'
        for k, v in rec["op_names"].items())
    tr = S.attribute(S.reduce(rec["trace"]), text)
    found = S.per_step(tr, rec["steps"], rec["chips"])
    layers = sum(found[k] or 0 for k in S.LAYERS + (S.UNSCOPED,))
    assert layers == pytest.approx(found["busy"], rel=0.01)
    assert 340 < found["busy"] < 365                # 352.1 ms per step
    assert all(found[k] for k in S.LAYERS)
    assert found["unscoped"] <= 0.1 * found["busy"]
    assert found["model_bwd"] > 2 * found["model_fwd"]
    kernel = S.top_ops(tr, rec["steps"], rec["chips"], "gossip")[0]
    assert kernel[0] == "custom-call:tpu_custom_call bf16[6947328,128]"
    assert 20.0 < kernel[1] < 23.0                  # gossip_mix.ms 21.2
    assert found["subs"]["gossip/mix"] >= kernel[1]
